"""Serving engine: publish/load roundtrip, cold-vs-warm, executable cache,
decode correctness through the engine, concurrent workers."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import DiskStore, MRM
from repro.models import forward, greedy_generate, init_params
from repro.serving import (InferenceEngine, Request, ServingWorkers,
                           arch_signature, publish_model)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("serving")
    disk = DiskStore(str(tmp / "models"))
    cfg = get_config("olmo-1b").reduced().replace(n_layers=2)
    params = init_params(cfg, jax.random.PRNGKey(0))
    publish_model(disk, cfg, params, name="olmo-1b")
    # a second model with the same topology but different weights
    params2 = init_params(cfg, jax.random.PRNGKey(1))
    publish_model(disk, cfg, params2, name="olmo-1b-finetune")
    return disk, cfg, params


def test_publish_load_roundtrip(served):
    disk, cfg, params = served
    mrm = MRM(disk, device_capacity=1 << 30, host_capacity=1 << 30)
    engine = InferenceEngine(disk, mrm)
    sm, _ = engine.load_model("olmo-1b")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(sm.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert sm.cfg == cfg
    engine.release(sm)


def test_generate_matches_reference(served):
    disk, cfg, params = served
    engine = InferenceEngine(disk, MRM(disk, device_capacity=1 << 30))
    toks = np.arange(1, 17, dtype=np.int32).reshape(1, 16) % cfg.vocab_size
    out, st = engine.generate("olmo-1b", toks, max_new_tokens=4)
    ref = greedy_generate(cfg, params, {"tokens": jnp.asarray(toks)}, 4, 16 + 4)
    np.testing.assert_array_equal(out, np.asarray(ref))


def test_warm_path_faster_and_shared(served):
    disk, cfg, _ = served
    mrm = MRM(disk, device_capacity=1 << 30)
    engine = InferenceEngine(disk, mrm)
    toks = np.ones((1, 8), np.int32)
    _, cold = engine.generate("olmo-1b", toks, max_new_tokens=2)
    _, warm = engine.generate("olmo-1b", toks, max_new_tokens=2)
    assert warm.tier_hit == "device"
    assert warm.model_load_s <= cold.model_load_s
    assert mrm.stats()["disk_loads"] == 1


def test_executable_cache_shared_across_same_topology(served):
    """Two same-architecture models share one compiled program — the
    compilation analogue of weight sharing (DESIGN.md §2)."""
    disk, cfg, _ = served
    engine = InferenceEngine(disk, MRM(disk, device_capacity=1 << 30))
    toks = np.ones((1, 8), np.int32)
    engine.generate("olmo-1b", toks, max_new_tokens=2)
    misses_before = engine.exe_cache_misses
    engine.generate("olmo-1b-finetune", toks, max_new_tokens=2)
    assert engine.exe_cache_misses == misses_before  # no new compile
    assert engine.exe_cache_hits >= 2


def test_no_trims_baseline_reloads(served):
    disk, cfg, _ = served
    engine = InferenceEngine(disk, mrm=None, use_trims=False)
    toks = np.ones((1, 8), np.int32)
    _, s1 = engine.generate("olmo-1b", toks, max_new_tokens=2)
    _, s2 = engine.generate("olmo-1b", toks, max_new_tokens=2)
    assert s1.tier_hit == "none(cold)" and s2.tier_hit == "none(cold)"


def test_concurrent_workers(served):
    disk, cfg, _ = served
    engine = InferenceEngine(disk, MRM(disk, device_capacity=1 << 30))
    workers = ServingWorkers(engine, n_workers=3)
    toks = np.ones((1, 8), np.int32)
    reqs = [workers.submit(Request(model="olmo-1b", tokens=toks, max_new=2))
            for _ in range(6)]
    workers.drain(reqs, timeout=120)
    workers.stop()
    assert all(not isinstance(r.result, Exception) for r in reqs)
    assert engine.mrm.stats()["disk_loads"] == 1  # one load served them all


def test_arch_signature_stable():
    c1 = get_config("olmo-1b").reduced()
    c2 = get_config("olmo-1b").reduced()
    c3 = c1.replace(n_layers=3)
    assert arch_signature(c1) == arch_signature(c2)
    assert arch_signature(c1) != arch_signature(c3)


def test_serve_once_reduced_cold_then_device_matches_no_trims(tmp_path):
    """The launcher's serving function (the one chip_smoke.py drives at full
    width): cold disk open, then a device-tier hit, both token-identical to
    the engine without TrIMS; prefill logits agree with the first token."""
    from repro.launch.serve import serve_once

    r = serve_once("olmo-1b", reduced=True, store_dir=str(tmp_path / "m"),
                   requests=2, baseline=True, max_new=4, prompt_len=16)
    assert r["cfg"] == get_config("olmo-1b").reduced()
    assert [st.tier_hit for st in r["stats"]] == ["disk", "device"]
    base_out, base_st = r["baseline"]
    assert base_st.tier_hit == "none(cold)"
    for out in r["outputs"]:
        np.testing.assert_array_equal(out, base_out)
    assert r["prompt"].max() < r["cfg"].vocab_size
    logits = r["engine"].prefill_logits("olmo-1b", r["prompt"], 4)
    assert logits.shape == (1, r["cfg"].padded_vocab)
    np.testing.assert_array_equal(logits.argmax(-1), base_out[:, 0])
    assert r["mrm_stats"]["disk_loads"] == 1


def test_serve_once_refuses_store_with_other_config(tmp_path):
    from repro.launch.serve import serve_once

    disk = DiskStore(str(tmp_path / "m"))
    other = get_config("olmo-1b").reduced().replace(n_layers=2)
    publish_model(disk, other, init_params(other, jax.random.PRNGKey(0)),
                  name="olmo-1b")
    with pytest.raises(ValueError, match="n_layers=2"):
        serve_once("olmo-1b", reduced=True, store_dir=disk.root)


def test_compile_cache_dir_from_env_or_fixed_repo_path(tmp_path, monkeypatch):
    from repro.launch.serve import REPO_ROOT, enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # left alone
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = str(REPO_ROOT / ".jax_cache")
        assert enable_compile_cache() == path
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
