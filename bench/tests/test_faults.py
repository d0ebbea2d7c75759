"""The whole run but the look for a chip, at a CPU size, with the timed path
broken underneath: ``correct`` has to come out false for each fault a
serving cell can have, and true without one."""
import time

import numpy as np
import pytest

from bench import harness, run
from bench.tests import tiny

SEED = 2 ** 31 + 4242


def _run(traffic, seconds=3.0):
    cell = {"name": "tiny", "config": "tiny", "traffic": "tiny", "chips": 1}
    return run.run_cell(tiny.bench(), cell, tiny.CONFIG, traffic, SEED, seconds,
                        False, tiny.PEAK, lambda m: None, time.perf_counter())


@pytest.mark.parametrize("traffic", [tiny.TRAFFIC, tiny.CLOSED],
                         ids=["open", "closed"])
def test_sound_run_is_correct(traffic):
    res = _run(traffic)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 10 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"ttft_p50_ms", "ttft_p95_ms",
                                   "latency_p95_ms", "tokens_per_s", "setup_s"}


def test_altered_token_fails(monkeypatch):
    from repro.serving.engine import InferenceEngine

    inner = InferenceEngine._generate_batch

    def altered(self, *a, **kw):
        out, st = inner(self, *a, **kw)
        out = np.array(out)
        out[:, -1] = (out[:, -1] + 1) % tiny.CONFIG["model"]["vocab_size"]
        return out, st

    monkeypatch.setattr(InferenceEngine, "_generate_batch", altered)
    res = _run(tiny.TRAFFIC)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_other_variants_weights_fail(monkeypatch):
    from repro.serving.engine import InferenceEngine

    inner = InferenceEngine.load_model

    def swapped(self, name, version="1"):
        if name.endswith(".v0"):
            name = name[:-1] + "1"
        return inner(self, name, version)

    monkeypatch.setattr(InferenceEngine, "load_model", swapped)
    res = _run(tiny.TRAFFIC)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > res["checks"]["logit_gap"]["limit"]


def test_corrupted_host_copy_fails(monkeypatch):
    inner = harness.Cell._warm_up

    def corrupt(self):
        inner(self)
        from repro.core.cache import Tier
        key = self._key(self.names[2])
        assert self.mrm.resident(key, Tier.HOST)
        arrays = self.mrm.host.peek(key).payload.arrays
        name = max(arrays, key=lambda k: np.asarray(arrays[k]).size)
        arrays[name] = -np.asarray(arrays[name])

    monkeypatch.setattr(harness.Cell, "_warm_up", corrupt)
    res = _run(tiny.TRAFFIC)
    assert not res["correct"]
    assert res["checks"]["weights_off"]["value"] >= 1
