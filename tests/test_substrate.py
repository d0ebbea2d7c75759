"""Substrate: checkpoint roundtrip + elastic restore, async manager,
fault-tolerant training loop, straggler watchdog, gradient compression,
data pipeline determinism."""
import os
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # skipped by scripts/ci.sh --fast

import jax
import jax.numpy as jnp

from repro.checkpoint import (CheckpointManager, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.configs import get_config
from repro.data.pipeline import Prefetcher, make_batch
from repro.launch.train import Trainer, TrainerConfig
from repro.runtime import (FailureInjector, SimulatedFailure, Watchdog,
                           quantized_allreduce)


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16), jnp.bfloat16),
                   "layers": {"ln": jnp.ones((4,), jnp.float32)}},
        "opt_mu": {"w": jnp.zeros((8, 16), jnp.float32),
                   "layers": {"ln": jnp.zeros((4,), jnp.float32)}},
        "opt_step": jnp.int32(7),
    }


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        st = _state()
        save_checkpoint(str(tmp_path), 7, st)
        assert latest_step(str(tmp_path)) == 7
        step, back = restore_checkpoint(str(tmp_path))
        assert step == 7
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
            assert a.dtype == b.dtype

    def test_elastic_restore_to_sharded(self, tmp_path):
        """Save unsharded, restore onto a mesh (mesh-shape change across
        restarts — elastic scaling)."""
        st = _state()
        save_checkpoint(str(tmp_path), 1, st)
        mesh = jax.make_mesh((1,), ("data",))
        sh = jax.tree.map(
            lambda x: jax.NamedSharding(mesh, jax.sharding.PartitionSpec()), st)
        _, back = restore_checkpoint(str(tmp_path), shardings=sh)
        assert all(hasattr(l, "sharding") for l in jax.tree.leaves(back))

    def test_retention_and_latest(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), every=1, keep=2,
                                async_mode=False)
        for s in range(1, 5):
            mgr.save(s, _state(s))
        dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
        assert dirs == ["step_00000003", "step_00000004"]
        assert latest_step(str(tmp_path)) == 4

    def test_async_manager(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), every=2, keep=5)
        for s in range(1, 7):
            mgr.save(s, _state(s))
        mgr.wait()
        assert sorted(mgr.saved_steps) == [2, 4, 6]


class TestFaultTolerance:
    def test_training_survives_injected_failures(self, tmp_path):
        cfg = get_config("olmo-1b").reduced().replace(n_layers=2)
        tc = TrainerConfig(batch_size=2, seq_len=32, steps=12,
                           ckpt_dir=str(tmp_path), ckpt_every=3, log_every=100)
        inj = FailureInjector(fail_at_steps=[5, 9])
        tr = Trainer(cfg, tc, injector=inj)
        out = tr.run_with_restarts(max_restarts=4)
        assert tr.restarts == 2
        steps_seen = [h["step"] for h in tr.history]
        assert max(steps_seen) == 11          # completed all 12 steps
        # losses decrease overall
        assert out["history"][-1]["loss"] < out["history"][0]["loss"] + 0.5

    def test_restart_resumes_from_checkpoint_not_scratch(self, tmp_path):
        cfg = get_config("olmo-1b").reduced().replace(n_layers=2)
        tc = TrainerConfig(batch_size=2, seq_len=32, steps=8,
                           ckpt_dir=str(tmp_path), ckpt_every=4, log_every=100)
        inj = FailureInjector(fail_at_steps=[6])
        tr = Trainer(cfg, tc, injector=inj)
        tr.run_with_restarts()
        steps = [h["step"] for h in tr.history]
        # after failing at 6, resume happens from ckpt@4 (not step 0)
        resumed = steps[steps.index(6) + 1:] if 6 in steps else steps
        assert 0 not in resumed

    def test_watchdog_detects_stall(self):
        wd = Watchdog(timeout=0.15, poll=0.02)
        wd.beat()
        time.sleep(0.4)
        wd.stop()
        assert len(wd.stalls) >= 1

    def test_watchdog_quiet_when_beating(self):
        wd = Watchdog(timeout=0.3, poll=0.02)
        for _ in range(10):
            wd.beat()
            time.sleep(0.03)
        wd.stop()
        assert wd.stalls == []


class TestCompression:
    def test_quantized_allreduce_accuracy(self):
        mesh = jax.make_mesh((1,), ("pod",))
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 64), jnp.float32)

        out = jax.shard_map(
            lambda v: quantized_allreduce(v, "pod"), mesh=mesh,
            in_specs=jax.sharding.PartitionSpec(),
            out_specs=jax.sharding.PartitionSpec(),
            check_vma=False, axis_names={"pod"})(x)
        err = np.abs(np.asarray(out) - np.asarray(x)).max()
        scale = float(jnp.abs(x).max()) / 127
        assert err <= scale * 0.51 + 1e-7   # quantization bound

    def test_quantized_wire_is_int8(self):
        mesh = jax.make_mesh((1,), ("pod",))
        f = jax.shard_map(lambda v: quantized_allreduce(v, "pod"), mesh=mesh,
                          in_specs=jax.sharding.PartitionSpec(),
                          out_specs=jax.sharding.PartitionSpec(),
                          check_vma=False, axis_names={"pod"})
        txt = jax.jit(f).lower(
            jax.ShapeDtypeStruct((128, 128), jnp.float32)).as_text()
        assert "all_gather" in txt or "all-gather" in txt
        assert "tensor<1x128x128xi8>" in txt or "s8[" in txt or "i8" in txt


class TestData:
    def test_deterministic_and_distinct(self):
        cfg = get_config("olmo-1b").reduced()
        b1 = make_batch(cfg, 3, 4, 16)
        b2 = make_batch(cfg, 3, 4, 16)
        b3 = make_batch(cfg, 4, 4, 16)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        assert not np.array_equal(b1["tokens"], b3["tokens"])
        # labels are next-token shifted
        np.testing.assert_array_equal(np.asarray(b1["tokens"])[:, 1:],
                                      np.asarray(b1["labels"])[:, :-1])
        assert int(jnp.max(b1["tokens"])) < cfg.vocab_size

    def test_prefetcher(self):
        cfg = get_config("olmo-1b").reduced()
        pf = Prefetcher(cfg, 2, 16, depth=2, start_step=5)
        s0, b0 = next(pf)
        s1, b1 = next(pf)
        pf.stop()
        assert (s0, s1) == (5, 6)
        ref = make_batch(cfg, 5, 2, 16)
        np.testing.assert_array_equal(b0["tokens"], ref["tokens"])
