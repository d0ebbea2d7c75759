"""Engine queue (``ServingWorkers``): mean time from when a request was due
(open loop) or sent (closed loop) until ``generate`` began, host clock."""
import statistics


def read(run):
    waits = [s.gen_start - s.due for s in run.window.requests if s.gen_start]
    return 1e3 * statistics.fmean(waits) if waits else None
