"""Every run sends the same schedule; its seed draws only the prompts."""
import numpy as np

from bench import mix
from bench.tests import tiny


def _closed(seed, n):
    loop = mix.ClosedLoop(tiny.CLOSED, seed, 256)
    return [loop.next(k % loop.n_clients) for k in range(n)]


def test_open_loop_schedule_is_the_same_for_every_seed():
    a = mix.open_loop(tiny.TRAFFIC, 4.0, 1, 256)
    b = mix.open_loop(tiny.TRAFFIC, 4.0, 2 ** 33 + 7, 256)
    assert len(a) == len(b) == round(tiny.TRAFFIC["arrivals"]["rate_per_s"] * 4)
    assert [(s.due, s.variant, len(s.prompt), s.out_len) for s in a] == \
           [(s.due, s.variant, len(s.prompt), s.out_len) for s in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_closed_loop_schedule_is_the_same_for_every_seed():
    a, b = _closed(1, 12), _closed(2 ** 33 + 7, 12)
    assert [(s.variant, len(s.prompt), s.out_len) for s in a] == \
           [(s.variant, len(s.prompt), s.out_len) for s in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_same_seed_same_prompts():
    a = mix.open_loop(tiny.TRAFFIC, 4.0, 5, 256)
    b = mix.open_loop(tiny.TRAFFIC, 4.0, 5, 256)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
