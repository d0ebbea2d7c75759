"""Engine request: mean ``rows`` of the ``engine.step`` spans that start in
the traced window, the requests one decode launch stepped (1 alone, 2 where
two rows of one model shared the step). None without a trace, or where the
program writes no such span."""
import statistics


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    rows = [float(e.stats["rows"]) for e in run.trace.spans("engine.step")
            if lo <= e.start <= hi and "rows" in e.stats]
    return statistics.fmean(rows) if rows else None
