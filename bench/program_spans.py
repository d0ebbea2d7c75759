"""Durations of the program's own spans (``repro.runtime.spans``) in a
traced window, for the per-layer metrics that read them."""
from typing import List


def durations_ms(run, name: str) -> List[float]:
    """Milliseconds of each ``name`` span that starts inside the traced
    window; empty without a trace, or where the program writes no such
    span."""
    if run.trace is None:
        return []
    lo, hi = run.trace_window
    return [(e.end - e.start) * 1e-6 for e in run.trace.spans(name)
            if lo <= e.start <= hi]
