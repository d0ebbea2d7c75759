"""Model step on the device: the least time the chip could take for the
traced window's decode executions, over their device time.

The least time of one decode step at position ``pos`` is the larger of its
operations over peak bf16 and its bytes (weights, the cache read up to
``pos``, the entry written) over HBM bandwidth, as the configuration's
architecture module counts them (``run.arch``). Only executions tied to a
request (``bench/trace.py``) inside the window count.
"""
from bench import flops as F


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    need = took = 0.0
    for ex in run.trace.executions:
        spec = run.by_index.get(ex.req)
        if ex.kind != "decode" or spec is None or ex.start < lo or ex.end > hi:
            continue
        pos = len(spec.prompt) + ex.ordinal - 1
        need += F.least_seconds(run.arch.decode_flops(run.model, pos),
                                run.arch.decode_bytes(run.model, pos),
                                run.peak)
        took += (ex.end - ex.start) * 1e-9
    return 100.0 * need / took if took else None
