"""Async tier-hierarchy: LoadFuture opens, chunked pipelined staging,
eviction-as-demotion, prefetch/pinning, and the pipelined cost model."""
import threading

import numpy as np
import pytest

from repro.core import (CapacityError, DiskStore, HardwareModel, MRM,
                        ModelKey, Tier)
from repro.core.pipeline import plan_chunks, run_pipeline

MB = 1 << 20


def _tensors(nbytes=1 * MB, n=4, seed=0):
    rng = np.random.default_rng(seed)
    per = nbytes // n // 4
    return {f"w{i}": rng.standard_normal(per).astype(np.float32) for i in range(n)}


@pytest.fixture
def disk(tmp_path):
    return DiskStore(str(tmp_path / "disk"))


def _mrm(disk, dev=8 * MB, host=32 * MB, **kw):
    return MRM(disk, device_capacity=dev, host_capacity=host, **kw)


# ------------------------------------------------------------- pipeline unit
class TestPipeline:
    def test_plan_chunks_groups_and_preserves_order(self):
        items = [(f"t{i}", 3) for i in range(7)]
        chunks = plan_chunks(items, 6)
        assert chunks == [["t0", "t1"], ["t2", "t3"], ["t4", "t5"], ["t6"]]
        # oversized item gets its own chunk
        assert plan_chunks([("a", 100), ("b", 1)], 10) == [["a"], ["b"]]

    def test_run_pipeline_outputs_and_stats(self):
        outs, report = run_pipeline(
            list(range(5)),
            [("double", lambda x: x * 2), ("inc", lambda x: x + 1)])
        assert outs == [1, 3, 5, 7, 9]
        assert report.n_chunks == 5
        assert all(s.items == 5 for s in report.stages)

    def test_run_pipeline_propagates_errors(self):
        def boom(x):
            if x == 2:
                raise ValueError("x=2")
            return x

        with pytest.raises(ValueError, match="x=2"):
            run_pipeline(list(range(5)), [("a", boom), ("b", lambda x: x)])


# -------------------------------------------------------------- LoadFuture
class TestOpenAsync:
    def test_open_equals_open_async_result(self, disk):
        key = ModelKey("jax", "m0")
        disk.put(key, _tensors())
        mrm = _mrm(disk)
        fut = mrm.open_async(key)
        h = fut.result(timeout=30)
        assert fut.done() and fut.state == "ready"
        assert h.timings.tier_hit == "disk"
        assert mrm.refcount(key) == 1
        h2 = mrm.open(key)
        assert h2.timings.tier_hit == "device"
        mrm.close(h)
        mrm.close(h2)

    def test_error_propagates_through_future(self, disk):
        mrm = _mrm(disk)
        fut = mrm.open_async(ModelKey("jax", "nope"))
        assert isinstance(fut.exception(timeout=30), FileNotFoundError)
        with pytest.raises(FileNotFoundError):
            fut.result(timeout=30)
        with pytest.raises(FileNotFoundError):
            mrm.open(ModelKey("jax", "nope"))

    def test_concurrent_open_async_coalesces_to_one_load(self, disk):
        key = ModelKey("jax", "hot")
        disk.put(key, _tensors(4 * MB))
        mrm = _mrm(disk)
        futs = [mrm.open_async(key) for _ in range(8)]
        handles = [f.result(timeout=60) for f in futs]
        assert mrm.metrics["disk_loads"] == 1
        assert mrm.metrics["coalesced_loads"] >= 7
        assert mrm.refcount(key) == 8
        w0 = handles[0].weights["w0"]
        assert all(h.weights["w0"] is w0 for h in handles)
        for h in handles:
            mrm.close(h)
        assert mrm.refcount(key) == 0

    def test_threaded_open_still_single_load(self, disk):
        key = ModelKey("jax", "herd")
        disk.put(key, _tensors(4 * MB))
        mrm = _mrm(disk)
        handles, errs = [], []

        def worker():
            try:
                handles.append(mrm.open(key))
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=worker) for _ in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs and len(handles) == 6
        assert mrm.metrics["disk_loads"] == 1
        for h in handles:
            mrm.close(h)


# ------------------------------------------------------ pipelined staging
class TestPipelinedStaging:
    def test_multichunk_values_correct(self, disk):
        key = ModelKey("jax", "chunky")
        t = _tensors(2 * MB, n=16, seed=3)
        disk.put(key, t)
        mrm = _mrm(disk, staging_chunk_bytes=64 << 10)  # force many chunks
        h = mrm.open(key)
        assert h.timings.chunks > 1
        assert h.timings.stage_overlap_s >= 0.0
        assert mrm.metrics["disk_loads"] == 1
        for k in t:
            np.testing.assert_array_equal(np.asarray(h.weights[k]), t[k])
        mrm.close(h)

    def test_modeled_pipelined_below_serial(self, disk):
        key = ModelKey("jax", "modeled")
        disk.put(key, _tensors(2 * MB, n=8))
        mrm = _mrm(disk, staging_chunk_bytes=256 << 10)
        h = mrm.open(key)
        t = h.timings
        assert 0 < t.staging_pipelined_modeled_s < t.staging_serial_modeled_s
        mrm.close(h)

    def test_shm_host_tier_pipelined(self, disk):
        key = ModelKey("jax", "shmod")
        t = _tensors(2 * MB, n=8, seed=9)
        disk.put(key, t)
        mrm = _mrm(disk, use_shm=True, staging_chunk_bytes=64 << 10)
        h = mrm.open(key, tier="host")
        for k in t:
            np.testing.assert_array_equal(np.asarray(h.weights[k]), t[k])
        mrm.close(h)
        h.weights = {}  # views must die before the segment unlinks
        for e in list(mrm.host.entries.values()):
            if e.payload is not None:
                e.payload.release()

    def test_cold_device_open_is_host_load_then_stage(self, disk):
        key = ModelKey("jax", "route")
        t = _tensors(seed=5)
        disk.put(key, t)
        mrm = _mrm(disk)
        h = mrm.open(key)
        assert h.timings.tier_hit == "disk"
        host = mrm.host.peek(key).payload.arrays
        for k in t:
            np.testing.assert_array_equal(host[k], t[k])
            np.testing.assert_array_equal(np.asarray(h.weights[k]), t[k])
        assert mrm.metrics["disk_loads"] == 1
        assert mrm.metrics["h2d_stages"] == 1
        mrm.close(h)

        # the host copy the cold open left serves the next device open
        mrm.device.remove(key)
        h = mrm.open(key)
        assert h.timings.tier_hit == "host"
        assert mrm.metrics["disk_loads"] == 1
        assert mrm.metrics["h2d_stages"] == 2
        for k in t:
            np.testing.assert_array_equal(np.asarray(h.weights[k]), t[k])
        mrm.close(h)


# ------------------------------------------------------------- demotion
class TestDemotion:
    def test_device_eviction_demotes_to_host_with_bytes_accounted(self, disk):
        k1, k2 = ModelKey("jax", "a"), ModelKey("jax", "b")
        disk.put(k1, _tensors(4 * MB, seed=1))
        disk.put(k2, _tensors(4 * MB, seed=2))
        mrm = _mrm(disk, dev=5 * MB, host=32 * MB)
        h1 = mrm.open(k1)
        mrm.close(h1)
        # simulate host-tier pressure: k1's host copy is gone, device remains
        e = mrm.host.remove(k1)
        e.payload.release()
        assert not mrm.resident(k1, Tier.HOST)
        assert mrm.resident(k1, Tier.DEVICE)

        h2 = mrm.open(k2)  # device full -> evicts k1 -> demote into HOST
        assert mrm.resident(k1, Tier.HOST)
        assert not mrm.resident(k1, Tier.DEVICE)
        # bytes accounted: demoted k1 + k2's own host copy
        assert mrm.host.used == (mrm.host.peek(k1).nbytes
                                 + mrm.host.peek(k2).nbytes)
        assert mrm.stats()["demotions"] == 1
        assert mrm.stats()["bytes_demoted"] == mrm.host.peek(k1).nbytes
        mrm.close(h2)

        # the demoted copy serves the next open as a HOST hit, not a reload
        loads_before = mrm.metrics["disk_loads"]
        h3 = mrm.open(k1)
        assert h3.timings.tier_hit == "host"
        assert mrm.metrics["disk_loads"] == loads_before
        np.testing.assert_array_equal(
            np.asarray(h3.weights["w0"]),
            _tensors(4 * MB, seed=1)["w0"])
        mrm.close(h3)

    def test_drop_on_evict_reloads_from_disk(self, disk):
        k1, k2 = ModelKey("jax", "a"), ModelKey("jax", "b")
        disk.put(k1, _tensors(4 * MB, seed=1))
        disk.put(k2, _tensors(4 * MB, seed=2))
        mrm = _mrm(disk, dev=5 * MB, host=32 * MB, demote_on_evict=False)
        h1 = mrm.open(k1)
        mrm.close(h1)
        e = mrm.host.remove(k1)
        e.payload.release()
        h2 = mrm.open(k2)
        mrm.close(h2)
        assert not mrm.resident(k1, Tier.HOST)  # dropped, not demoted
        h3 = mrm.open(k1)
        assert h3.timings.tier_hit == "disk"
        mrm.close(h3)

    def test_rotation_with_demotion_avoids_disk(self, disk):
        keys = [ModelKey("jax", f"m{i}") for i in range(3)]
        for i, k in enumerate(keys):
            disk.put(k, _tensors(4 * MB, seed=i))
        loads = {}
        for demote in (False, True):
            mrm = _mrm(disk, dev=10 * MB, host=10 * MB,
                       demote_on_evict=demote)
            for _ in range(3):
                for k in keys:
                    mrm.close(mrm.open(k))
            loads[demote] = mrm.metrics["disk_loads"]
        assert loads[True] < loads[False]

    def test_refcounted_entries_never_demoted(self, disk):
        k1, k2 = ModelKey("jax", "a"), ModelKey("jax", "b")
        disk.put(k1, _tensors(4 * MB, seed=1))
        disk.put(k2, _tensors(4 * MB, seed=2))
        mrm = _mrm(disk, dev=5 * MB)
        h1 = mrm.open(k1)  # hold the reference
        with pytest.raises(CapacityError):
            mrm.open(k2)
        assert mrm.resident(k1, Tier.DEVICE)
        assert mrm.stats()["demotions"] == 0
        mrm.close(h1)

    def test_pinned_entries_never_evicted(self, disk):
        k1, k2 = ModelKey("jax", "a"), ModelKey("jax", "b")
        disk.put(k1, _tensors(4 * MB, seed=1))
        disk.put(k2, _tensors(4 * MB, seed=2))
        mrm = _mrm(disk, dev=5 * MB)
        mrm.close(mrm.open(k1))
        assert mrm.pin(k1)
        with pytest.raises(CapacityError):
            mrm.open(k2)
        assert mrm.unpin(k1)
        h = mrm.open(k2)
        assert not mrm.resident(k1, Tier.DEVICE)
        mrm.close(h)


# -------------------------------------------------------------- prefetch
class TestPrefetch:
    def test_prefetch_warms_device_without_refs(self, disk):
        key = ModelKey("jax", "warm")
        disk.put(key, _tensors())
        mrm = _mrm(disk)
        fut = mrm.prefetch(key)
        assert fut.result(timeout=60) is None
        assert mrm.resident(key, Tier.DEVICE)
        assert mrm.refcount(key) == 0
        assert mrm.metrics["prefetches"] == 1
        h = mrm.open(key)
        assert h.timings.tier_hit == "device"
        assert mrm.metrics["disk_loads"] == 1
        mrm.close(h)

    def test_open_coalesces_onto_prefetch(self, disk):
        key = ModelKey("jax", "race")
        disk.put(key, _tensors(4 * MB))
        mrm = _mrm(disk)
        fut = mrm.prefetch(key)
        h = mrm.open(key)  # either coalesces or hits the finished prefetch
        fut.result(timeout=60)
        assert mrm.metrics["disk_loads"] == 1
        assert mrm.refcount(key) == 1
        mrm.close(h)

    def test_client_and_platform_prewarm(self, disk):
        from repro.core import FaaSPlatform
        key = ModelKey("jax", "alex")
        disk.put(key, _tensors())
        mrm = _mrm(disk)
        platform = FaaSPlatform(mrm)
        c = platform.deploy("f", lambda ctx, p: ctx.load_model("jax", "alex"),
                            allowed_models=[("jax", "alex")])
        assert mrm.metrics["prefetches"] == 1
        platform.invoke("f")
        assert mrm.metrics["disk_loads"] == 1  # prewarm + invoke = one load
        assert c.acct.cold_starts == 0


# ------------------------------------------------------------- cost model
class TestStagingCostModel:
    def test_pipelined_strictly_below_serial_when_chunked(self):
        hw = HardwareModel()
        n = 256 * MB
        assert hw.staging_pipelined_time(n) < hw.staging_serial_time(n)

    def test_single_chunk_equals_serial(self):
        hw = HardwareModel()
        n = 1 * MB
        np.testing.assert_allclose(hw.staging_pipelined_time(n, chunk_bytes=2 * MB),
                                   hw.staging_serial_time(n), rtol=1e-9)

    def test_pipelined_approaches_max_stage_bound(self):
        hw = HardwareModel()
        n = 1 << 30
        bound = max(n / hw.disk_bw, n / hw.cached_read_bw, n / hw.h2d_bw)
        pipe = hw.staging_pipelined_time(n, chunk_bytes=1 * MB)
        assert pipe < hw.staging_serial_time(n)
        assert pipe >= bound  # cannot beat the slowest stage


# ------------------------------------------------- engine version keying
class TestEngineVersioning:
    def test_cfg_cache_keyed_by_name_and_version(self, tmp_path):
        jax = pytest.importorskip("jax")
        from repro.configs import get_config
        from repro.models import init_params
        from repro.serving import InferenceEngine, publish_model

        disk = DiskStore(str(tmp_path / "models"))
        base = get_config("olmo-1b").reduced()
        cfg1 = base.replace(n_layers=1)
        cfg2 = base.replace(n_layers=2)
        publish_model(disk, cfg1, init_params(cfg1, jax.random.PRNGKey(0)),
                      name="olmo-1b", version="1")
        publish_model(disk, cfg2, init_params(cfg2, jax.random.PRNGKey(1)),
                      name="olmo-1b", version="2")
        mrm = MRM(disk, device_capacity=1 << 30)
        engine = InferenceEngine(disk, mrm)
        sm1, _ = engine.load_model("olmo-1b", "1")
        sm2, _ = engine.load_model("olmo-1b", "2")
        assert sm1.cfg.n_layers == 1
        assert sm2.cfg.n_layers == 2  # pre-fix: silently reused version 1 cfg
        engine.release(sm1)
        engine.release(sm2)
