"""Engine request: median ``engine.prefill`` span, from building the prefill
batch until the first token is on the host."""
import statistics

from bench.program_spans import durations_ms


def read(run):
    xs = durations_ms(run, "engine.prefill")
    return statistics.median(xs) if xs else None
