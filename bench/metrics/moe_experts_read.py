"""MoE layer: mean ``experts_read`` of the ``engine.decode`` spans that
start in the traced window, the held experts whose weights a decode step
read, per MoE layer and step of a request. None without a trace, or where
the program writes no such stat (a dense model, or a program that does not
count the experts it reads)."""
import statistics


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_window
    reads = [float(e.stats["experts_read"])
             for e in run.trace.spans("engine.decode")
             if lo <= e.start <= hi and "experts_read" in e.stats]
    return statistics.fmean(reads) if reads else None
