"""The metric arithmetic on hand-made records and a hand-made trace."""

import numpy as np
import pytest

import devtrace as tr
import serving_loop
import readings as rd
from adapter import NextStep

MODEL = {"num_layers": 2, "d_model": 64, "vocab_size": 256, "num_heads": 4,
         "num_kv_heads": 4, "head_dim": 16, "n_routed_experts": 8,
         "n_shared_experts": 0, "top_k": 2, "moe_d_ff": 32, "shared_d_ff": 32,
         "dtype": "bfloat16"}
PEAKS = {"flops_per_s": 1e9, "hbm_bytes_per_s": 1e8}


def _run(rec, window=(10.0, 20.0), due_until=20.0, slots=4):
    return rd.Run(model=MODEL, traffic={"slots": slots}, peaks=PEAKS, rec=rec,
                  window=window, due_until=due_until)


def _rec():
    rec = serving_loop.Recorder(bucket=16)
    rec.submitted(0, 10.0, np.ones(10, np.int32), 3)   # padded 16, 6 pads
    rec.submitted(1, 12.0, np.ones(20, np.int32), 2)   # padded 32
    rec.submitted(2, 19.5, np.ones(5, np.int32), 2)    # due late: no token by 20
    rec.submitted(3, 9.0, np.ones(5, np.int32), 2)     # due before the window
    # uid 0: its one chunk (10 real tokens), then two decode steps
    rec.stepped(10.1, 10.2, NextStep("chunk", 0, 0, 16, []), [(0, 1)])
    rec.stepped(10.2, 10.3, NextStep("decode", None, 0, 0, [17]), [(0, 2)])
    # uid 1: first chunk fused with uid 0's decode, last chunk alone
    rec.stepped(12.0, 12.5, NextStep("fused", 1, 0, 16, [18]), [(0, 3), (1, 0)])
    rec.stepped(12.5, 12.6, NextStep("chunk", 1, 16, 16, []), [(1, 1)])
    rec.stepped(12.6, 12.8, NextStep("decode", None, 0, 0, [33]), [(1, 2)])
    return rec


def test_latencies_count_every_request_due_in_the_window():
    run = _run(_rec())
    # uid 0: 10.2 - 10.0; uid 1: 12.6 - 12.0; uid 2 counts at its age 0.5
    assert sorted(np.round(rd.ttfts(run), 6)) == [0.2, 0.5, 0.6]
    # uid 0: 10.2, 10.3, 12.5; uid 1: 12.6, 12.8
    assert sorted(np.round(rd.tbts(run), 6)) == [0.1, 0.2, 2.2]
    assert rd.output_tokens(run) == 5
    assert rd.prompt_tokens(run) == 10 + 4 + 16  # pads of uid 1 are in its first chunk
    assert sorted(np.round(rd.queue_waits(run), 6)) == [0.0, 0.1, 0.5]


def test_step_medians_and_occupancy():
    run = _run(_rec())
    assert rd.step_ms(run, ("decode",)) == pytest.approx(150.0)
    assert rd.step_ms(run, ("chunk", "fused")) == pytest.approx(100.0)
    assert rd.occupancy(run) == pytest.approx(1 / 4)


def test_mfu_counts_real_tokens_and_sampled_heads():
    import workcount as wc

    rec = _rec()
    for s in rec.steps:
        s.traced = True
    run = _run(rec)
    run.traced = (10.0, 20.0)
    want = (wc.step_flops(MODEL, (6, 16), [], True)
            + wc.step_flops(MODEL, (0, 0), [17], False)
            + wc.step_flops(MODEL, (12, 16), [18], False)
            + wc.step_flops(MODEL, (16, 32), [], True)
            + wc.step_flops(MODEL, (0, 0), [33], False))
    assert rd.mfu(run) == pytest.approx(100 * want / (1.0 * PEAKS["flops_per_s"]))


def test_trace_shares():
    rec = _rec()
    for s in rec.steps:
        s.traced = True
    run = _run(rec)
    run.traced = (10.0, 20.0)
    run.offset = 100.0  # the trace's clock runs 100 s ahead of the harness's
    ops = [tr.Event("fusion.1", 110.1, 110.15), tr.Event("paged_attention.7", 110.22, 110.25),
           tr.Event("grouped_matmul.21", 112.0, 112.2),
           tr.Event("paged_attention.7", 112.7, 112.72)]
    run.trace = tr.Trace({0: ops}, [])
    # steps cover 0.1 + 0.1 + 0.5 + 0.1 + 0.2 = 1.0 s, ops 0.05+0.03+0.2+0.02
    assert rd.host_gap_share(run) == pytest.approx(100 * (1.0 - 0.3))
    import workcount as wc

    least = sum(wc.least_time(*wc.paged_attn_work(MODEL, c), PEAKS) for c in ([17], [33]))
    assert rd.paged_attn_roofline(run) == pytest.approx(100 * least / 0.05)
    assert rd.gmm_roofline(run) is not None
    run.trace = tr.Trace({0: ops[:1]}, [])
    assert rd.gmm_roofline(run) is None  # no kernel events: nothing to read
