"""MoE layer: mean ``experts_touched`` of the ``engine.decode`` spans that
start in the traced window, the held experts that took at least one token,
per MoE layer and decode step of a request. None without a trace, or where
the program writes no such stat (a dense model, or a program that does not
count)."""
import statistics


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    touched = [float(e.stats["experts_touched"])
               for e in run.trace.spans("engine.decode")
               if lo <= e.start <= hi and "experts_touched" in e.stats]
    return statistics.fmean(touched) if touched else None
