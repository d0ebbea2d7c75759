"""Engine request, closed-loop cells: median over requests of
``(total_s - ttft_s) / (out_len - 1)``."""
import statistics


def read(run):
    xs = [(s.stats.total_s - s.stats.ttft_s) / (s.out_len - 1)
          for s in run.window.requests
          if s.stats is not None and s.out_len > 1]
    return 1e3 * statistics.median(xs) if xs else None
