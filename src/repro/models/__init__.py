"""Model zoo public API."""
from .params import (abstract_params, count_params, init_params,  # noqa: F401
                     param_pspecs, param_shapes, param_shardings)
from .transformer import (DecodeCache, decode_step, init_cache,  # noqa: F401
                          init_paged_cache, loss_and_aux, merge_cache_rows,
                          prefill, unembed)
from .io import (INPUT_SHAPES, cache_specs, input_specs,  # noqa: F401
                 make_batch, supported_shapes)
