from repro.models.model import (  # noqa: F401
    init_params, forward, loss_fn, init_cache, prefill, decode_step,
    decode_step_rows, greedy_generate,
)
