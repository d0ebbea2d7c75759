"""MRM tiers: mean ``mrm.stage`` span, from making room on the device to every
staged array ready there (host- and disk-tier opens and prefetches)."""
import statistics

from bench.program_spans import durations_ms


def read(run):
    xs = durations_ms(run, "mrm.stage")
    return statistics.fmean(xs) if xs else None
