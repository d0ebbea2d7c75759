"""Engine queue: mean ``serving.queue`` span, from ``ServingWorkers.submit``
putting a request on the queue until a worker takes it."""
import statistics

from bench.program_spans import durations_ms


def read(run):
    xs = durations_ms(run, "serving.queue")
    return statistics.fmean(xs) if xs else None
