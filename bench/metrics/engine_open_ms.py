"""Engine request: median ``engine.open`` span, the MRM open (or cold load)
until the handle is in hand."""
import statistics

from bench.program_spans import durations_ms


def read(run):
    xs = durations_ms(run, "engine.open")
    return statistics.median(xs) if xs else None
