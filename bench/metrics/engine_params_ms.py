"""Engine request: median ``engine.params`` span, the parameter-tree rebuild
(``eval_shape`` template and ``flat_to_params_like``) on every request."""
import statistics

from bench.program_spans import durations_ms


def read(run):
    xs = durations_ms(run, "engine.params")
    return statistics.median(xs) if xs else None
