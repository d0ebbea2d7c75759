"""Device: share of the time at least one request was in service in which no
operation ran on the device (union of the trace's device-op intervals)."""
from bench import trace as T


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    service = T.in_service(run.trace, lo, hi)
    total = T.length(service)
    if not total:
        return None
    busy = T.length(T.intersect(T.busy(run.trace, lo, hi), service))
    return 100.0 * (1.0 - busy / total)
