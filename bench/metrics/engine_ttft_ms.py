"""Engine request: median ``RequestStats.ttft_s`` (MRM open, parameter-tree
rebuild and prefill, until the first token is on the host)."""
import statistics


def read(run):
    xs = [s.stats.ttft_s for s in run.window.requests if s.stats is not None]
    return 1e3 * statistics.median(xs) if xs else None
