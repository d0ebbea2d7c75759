"""Same-model decode rows in the engine: two requests of one model decoding
at once share steps of the two-row program and make the tokens each makes
alone; a pair without a built program steps alone; nothing compiles after
every shape has been seen once; a failing shared step fails both rows."""
import glob
import sys
import threading

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import DiskStore, MRM
from repro.models import init_params
from repro.serving import InferenceEngine, publish_model

# (prompt tokens, new tokens) of the two requests: different cache lengths
SHAPES = ((12, 20), (7, 16))
ATTEMPTS = 5          # concurrent pairs tried before giving up on a join


class _Compiles:
    """Programs built while ``active``, as the benchmark's harness counts."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT and self.active:
            self.count += 1


@pytest.fixture(scope="module")
def disk(tmp_path_factory):
    d = DiskStore(str(tmp_path_factory.mktemp("rows") / "models"))
    cfg = get_config("olmo-1b").reduced().replace(n_layers=2)
    publish_model(d, cfg, init_params(cfg, jax.random.PRNGKey(0)), name="m")
    return d, cfg


def _prompts(cfg):
    rng = np.random.default_rng(5)
    return [rng.integers(0, cfg.vocab_size, (1, s), dtype=np.int32)
            for s, _ in SHAPES]


def _engine(disk):
    eng = InferenceEngine(disk, MRM(disk, device_capacity=1 << 30))
    steps = []
    inner = eng._step_rows

    def record(rows, params, req):
        steps.append(len(rows))
        return inner(rows, params, req)

    eng._step_rows = record
    return eng, steps


def _alone(eng, prompts):
    return [eng.generate("m", p, max_new_tokens=n)[0]
            for p, (_, n) in zip(prompts, SHAPES)]


def _together(eng, prompts):
    """Both requests at once, from two threads, both entering decode
    together; their outputs or errors."""
    out = [None, None]
    start, decode = threading.Barrier(2), threading.Barrier(2)
    inner = eng._decode_row

    def joined(row, req):
        decode.wait(timeout=30)
        return inner(row, req)

    eng._decode_row = joined

    def run(i):
        start.wait(timeout=30)
        try:
            out[i] = eng.generate("m", prompts[i],
                                  max_new_tokens=SHAPES[i][1])[0]
        except Exception as e:  # noqa: BLE001 — handed to the test
            out[i] = e

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    del eng._decode_row
    assert not any(t.is_alive() for t in threads)
    return out


def test_rows_of_one_model_step_together_and_match_alone(disk, tmp_path):
    d, cfg = disk
    eng, steps = _engine(d)
    prompts = _prompts(cfg)
    alone = _alone(eng, prompts)
    assert set(steps) == {1}
    # every pair of the two lengths has its program, built at first sight
    assert len(eng._row_exe) == 3

    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(ATTEMPTS):
            steps.clear()
            got = _together(eng, prompts)
            for a, b in zip(alone, got):
                np.testing.assert_array_equal(a, b)
            if 2 in steps:
                break
    finally:
        jax.profiler.stop_trace()
    assert 2 in steps
    assert sum(steps) == sum(n - 1 for _, n in SHAPES)
    assert not eng._decoding          # both rows left their group

    (f,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    rows = [int(dict(ev.stats)["rows"])
            for plane in ProfileData.from_file(f).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name == "engine.step"]
    assert 2 in rows and set(rows) <= {1, 2}


def test_nothing_compiles_once_every_shape_was_seen(disk):
    d, cfg = disk
    eng, steps = _engine(d)
    prompts = _prompts(cfg)
    alone = _alone(eng, prompts)           # the warm-up: each shape once
    compiles = _Compiles()
    compiles.active = True
    try:
        for _ in range(ATTEMPTS):
            steps.clear()
            got = _together(eng, prompts)
            if 2 in steps:
                break
    finally:
        compiles.active = False
    assert 2 in steps
    assert compiles.count == 0
    for a, b in zip(alone, got):
        np.testing.assert_array_equal(a, b)


def test_a_pair_without_a_program_steps_alone(disk):
    d, cfg = disk
    eng, steps = _engine(d)
    prompts = _prompts(cfg)
    alone = _alone(eng, prompts)
    eng._row_exe.clear()                   # as if never built
    for _ in range(2):
        steps.clear()
        got = _together(eng, prompts)
        assert set(steps) == {1}
        for a, b in zip(alone, got):
            np.testing.assert_array_equal(a, b)


def test_a_failing_step_fails_both_rows(disk):
    d, cfg = disk
    eng, steps = _engine(d)
    prompts = _prompts(cfg)
    _alone(eng, prompts)

    def broken(*args):
        raise RuntimeError("two-row step failed")

    for k in eng._row_exe:
        eng._row_exe[k] = broken
    for _ in range(ATTEMPTS):
        steps.clear()
        got = _together(eng, prompts)
        if 2 in steps:
            break
    assert 2 in steps
    for g in got:
        assert isinstance(g, RuntimeError) and "two-row" in str(g)
    assert not eng._decoding
    # the engine serves on: each request alone again
    for a, b in zip(_alone(eng, prompts), _alone(eng, prompts)):
        np.testing.assert_array_equal(a, b)


def test_many_rows_under_fast_thread_switching(disk):
    """More threads than a group holds, switching every few microseconds:
    every row makes exactly its own steps and its own tokens."""
    d, cfg = disk
    eng, steps = _engine(d)
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, cfg.vocab_size, (1, s), dtype=np.int32), n)
            for s, n in SHAPES * 3]
    alone = [eng.generate("m", p, max_new_tokens=n)[0] for p, n in reqs]
    steps.clear()
    out = [None] * len(reqs)
    start = threading.Barrier(len(reqs))

    def run(i):
        start.wait(timeout=30)
        try:
            out[i] = eng.generate("m", reqs[i][0], max_new_tokens=reqs[i][1])[0]
        except Exception as e:  # noqa: BLE001 — handed to the test
            out[i] = e

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    for a, b in zip(alone, out):
        np.testing.assert_array_equal(a, b)
    assert sum(k * steps.count(k) for k in (1, 2)) == sum(n - 1 for _, n in reqs)
    assert set(steps) <= {1, 2} and not eng._decoding
