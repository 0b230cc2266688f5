"""Every read of the engine's private state, in one place.

The engine's public surface (``submit``, ``admit``, ``step``, ``retire``,
``cancel``, ``stats``) drains a queue; an open loop needs four things
more, which only private state gives today:

- start a live batch when none exists (``_begin_live_batch``) and drop
  it when ``step`` finds nothing runnable (``_live = None``), as
  ``serve_continuous`` does; run the lifecycle sweep (``_reap_lifecycle``);
- see, before a step, whether it will carry a prefill chunk, fused with
  decode or alone, and which rows decode in it (``_live.slots``,
  ``_live.pos``);
- see, after a step, the tokens each request has so far
  (``_Slot.tokens``), and before it where its next prompt chunk starts
  (``_Slot.filled``);
- the live batch's table width (``_live.kv_capacity``).

A public per-request event hook and an open-loop entry in the program
would replace this file.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple


@dataclasses.dataclass
class NextStep:
    kind: str  # "chunk" (prefill chunk alone), "fused" (chunk + decode), "decode"
    chunk_uid: Optional[int]  # the row whose chunk runs
    chunk_start: int  # its first cache position
    chunk_len: int
    decode_ctx: List[int]  # per decoding row: cache positions it attends


class Adapter:
    def __init__(self, engine):
        self.engine = engine

    def live(self) -> bool:
        return self.engine._live is not None

    def begin(self) -> None:
        self.engine._begin_live_batch()

    def drop(self) -> None:
        self.engine._live = None

    def reap(self) -> None:
        self.engine._reap_lifecycle()

    def width(self) -> int:
        live = self.engine._live
        return 0 if live is None else int(live.kv_capacity)

    def next_step(self) -> Optional[NextStep]:
        """What ``step`` will run, read as ``InferenceEngine.step`` decides it.
        After the window ``check.step_kind_drift`` holds the kinds read here
        against the engine's own step counters."""
        live = self.engine._live
        pending, active = live.prefilling(), live.active()
        ctx = [int(live.pos[i]) + 1 for i in active]
        if pending:
            i = min(pending, key=lambda j: live.slots[j].req.uid)
            s = live.slots[i]
            fused = bool(active) and len(s.pending) > 1
            return NextStep("fused" if fused else "chunk", s.req.uid, s.filled,
                            len(s.pending[0]), ctx if fused else [])
        if active:
            return NextStep("decode", None, 0, 0, ctx)
        return None

    def rows(self) -> List[Tuple[int, int]]:
        """(uid, tokens so far) per occupied row."""
        live = self.engine._live
        if live is None:
            return []
        return [(s.req.uid, len(s.tokens)) for s in live.slots if s is not None]
