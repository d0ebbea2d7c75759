"""Whole step: model operations of every prefill and decode execution in the
traced window, over peak bf16 times the time at least one request was in
service, as the configuration's architecture module counts them
(``run.arch``; for the dense decoder, per token the layers' matrix products,
the head where logits are needed, and attention at that token's context)."""
from bench import trace as T


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    service = T.length(T.in_service(run.trace, lo, hi)) * 1e-9
    work = 0.0
    for ex in run.trace.executions:
        spec = run.by_index.get(ex.req)
        if spec is None or ex.start < lo or ex.end > hi:
            continue
        if ex.kind == "prefill":
            work += run.arch.prefill_flops(run.model, len(spec.prompt))
        elif ex.kind == "decode":
            work += run.arch.decode_flops(
                run.model, len(spec.prompt) + ex.ordinal - 1)
    if not service or not work:
        return None
    return 100.0 * work / (run.peak["bf16_flops_per_s"] * service)
