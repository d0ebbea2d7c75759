"""Serving-path spans on the profiler's clock: request ids shared by the
spans of one request, the engine's spans in order on one thread, the
``mrm.stage`` span of a host-tier open ended by the waiter once the copies
land, and nothing of it with the profiler off."""
import glob
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import DiskStore, MRM, ModelKey
from repro.models import init_params
from repro.runtime import spans
from repro.serving import (FRAMEWORK, InferenceEngine, Request,
                           ServingWorkers, publish_model)

ENGINE = ("engine.open", "engine.params", "engine.prefill", "engine.decode")
STEP = "engine.step"                        # each decode launch, in decode
MODELS = ("tiny-a", "tiny-b", "tiny-a")     # three host-tier opens


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    disk = DiskStore(str(tmp_path_factory.mktemp("spans") / "models"))
    cfg = get_config("olmo-1b").reduced().replace(n_layers=2)
    for seed, name in enumerate(("tiny-a", "tiny-b")):
        publish_model(disk, cfg, init_params(cfg, jax.random.PRNGKey(seed)),
                      name=name)
    nbytes = disk.open(ModelKey(FRAMEWORK, "tiny-a", "1")).total_bytes
    return disk, cfg, nbytes


def _marked_put(a):
    # brackets the enqueue of each copy with a span of its own
    with jax.profiler.TraceAnnotation("test.put"):
        return jnp.asarray(a)


def _serve(disk, nbytes, tokens, wrap=None):
    """Serve MODELS one after another from the host tier, through workers,
    on a device tier too small for two models."""
    mrm = MRM(disk, device_capacity=int(1.5 * nbytes),
              host_capacity=8 * nbytes, device_put_fn=_marked_put)
    for name in ("tiny-a", "tiny-b"):
        mrm.prefetch(ModelKey(FRAMEWORK, name, "1"), tier="host").result()
    engine = InferenceEngine(disk, mrm)
    calls = []
    if wrap:
        inner = engine.generate

        def generate(name, tokens, max_new_tokens=8, version="1"):
            calls.append(name)
            return inner(name, tokens, max_new_tokens, version)

        engine.generate = generate
    workers = ServingWorkers(engine, n_workers=1, lookahead_prefetch=False)
    reqs = []
    try:
        for name in MODELS:
            r = workers.submit(Request(name, tokens, max_new=3))
            workers.drain([r], timeout=120)
            assert not isinstance(r.result, Exception), r.result
            reqs.append(r)
    finally:
        workers.stop()
    waiter = mrm._stage_waiter
    mrm.shutdown()
    return reqs, calls, waiter


def _host_spans(path):
    """(name, line, start, end, stats) of the spans under test."""
    names = {"serving.queue", "mrm.stage", "test.put", STEP, *ENGINE}
    (f,) = glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(f).planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, li, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return out


def test_spans_off_start_no_waiter_and_tokens_match_traced(store, tmp_path):
    disk, cfg, nbytes = store
    tokens = (np.arange(12, dtype=np.int32) % cfg.vocab_size)[None, :]
    assert not spans.tracing()
    plain, _, waiter = _serve(disk, nbytes, tokens)
    assert waiter is None
    assert not any(t.name == "mrm-stage-waiter" for t in threading.enumerate())
    assert [r.stats.tier_hit for r in plain] == ["host"] * 3

    jax.profiler.start_trace(str(tmp_path))
    try:
        traced, calls, waiter = _serve(disk, nbytes, tokens, wrap=True)
    finally:
        jax.profiler.stop_trace()
    assert waiter is not None and not waiter.is_alive()
    # the harness-shaped wrapper installed on the engine is what workers call
    assert calls == list(MODELS)
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.result, b.result)

    evs = _host_spans(tmp_path)
    by_req = {}
    for name, line, start, end, st in evs:
        if "req" in st:
            by_req.setdefault(int(st["req"]), {}).setdefault(
                name, []).append((line, start, end, st))
    stages = sorted((e for e in evs if e[0] == "mrm.stage"),
                    key=lambda e: e[2])
    puts = [e for e in evs if e[0] == "test.put"]
    assert len(stages) == len(MODELS)
    for r, stage in zip(traced, stages):
        got = by_req[r.id]
        assert sorted(got) == sorted(("serving.queue", STEP) + ENGINE)
        steps = got.pop(STEP)
        assert all(len(v) == 1 for v in got.values())
        seq = [got[n][0] for n in ENGINE]
        # the engine's spans run on one thread, one after another
        assert len({line for line, _, _, _ in seq}) == 1
        for (_, _, end, _), (_, start, _, _) in zip(seq, seq[1:]):
            assert end <= start
        # one step per decode launch, alone (one worker), inside the decode
        decode = got["engine.decode"][0]
        assert len(steps) == 2
        for line, start, end, st in steps:
            assert line == decode[0] and decode[1] <= start <= end <= decode[2]
            assert int(st["rows"]) == 1
        opened = seq[0]
        assert opened[3]["model"] == r.model and opened[3]["tier"] == "host"
        assert got["serving.queue"][0][2] <= opened[1]
        # one staging per host-tier open, begun inside it, with the model's
        # bytes, ended by the waiter no earlier than its last enqueue
        _, s_line, s_start, s_end, s_st = stage
        assert opened[1] <= s_start <= opened[2]
        assert s_st["model"] == r.model and int(s_st["bytes"]) == nbytes
        assert s_st["source"] == "host" and int(s_st["prefetch"]) == 0
        assert s_line != opened[0]
        own = [p for p in puts if s_start <= p[2] <= s_end]
        assert own and s_end >= max(p[3] for p in own)


def test_engine_calls_outside_workers_take_fresh_request_ids(store, tmp_path):
    disk, cfg, nbytes = store
    engine = InferenceEngine(disk, MRM(disk, device_capacity=4 * nbytes))
    tokens = np.ones((1, 8), np.int32)
    engine.generate("tiny-a", tokens, max_new_tokens=2)   # compile untraced
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.generate("tiny-a", tokens, max_new_tokens=2)
        with spans.request(10 ** 9):
            engine.generate("tiny-a", tokens, max_new_tokens=2)
        engine.generate("tiny-a", tokens, max_new_tokens=2)
    finally:
        jax.profiler.stop_trace()
    reqs = [int(st["req"]) for name, _, _, _, st in
            sorted(_host_spans(tmp_path), key=lambda e: e[2])
            if name == "engine.prefill"]
    assert len(reqs) == 3 and reqs[1] == 10 ** 9
    assert reqs[0] < reqs[2] and 10 ** 9 not in (reqs[0], reqs[2])
    assert spans.request_id() is None


def test_request_binding_nests_and_spans_are_inert_untraced():
    assert not spans.tracing()
    with spans.span("engine.open", req=1) as sp:
        sp.set_metadata(tier="device")
    with spans.request() as outer:
        with spans.request() as inner:
            assert inner == outer == spans.request_id()
        with spans.request(outer + 100) as other:
            assert spans.request_id() == other == outer + 100
        assert spans.request_id() == outer
    assert spans.request_id() is None
    with spans.request() as later:
        assert later > outer
