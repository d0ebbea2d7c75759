"""Layer-granular streaming staging (DESIGN.md §9).

Covers the layer planner (window coverage/disjointness, expert splitting),
the StreamAssembler (out-of-order scatter, components filter), the
ObjectStore layer-aligned splitter + in-order shard callbacks, the cost
model recurrence, and the MRM partial-open surface — including the race
regressions: eviction pressure mid-stream must not reap the pinned
placeholder, a gather source dying after layer-k readiness never rolls
readiness back, concurrent wait_prefix + result() callers both complete,
and a corrupt mid-stream shard re-sources from CLOUD without re-fetching
already-verified layers.
"""
import hashlib
import os
import threading

import numpy as np
import pytest

from repro.core import (Cluster, DiskStore, HardwareModel, MRM, ModelKey,
                        ObjectStore, Tier)
from repro.core.costmodel import streaming_ttfl_time
from repro.core.layerplan import (LayerWindow, StreamAssembler,
                                  build_layer_plan, plan_for_file)
from repro.core.store import ModelFile, write_model

MB = 1 << 20
SHARD = 256 << 10


def _layered_tensors(L=4, d=16, moe=False, seed=0):
    rng = np.random.default_rng(seed)
    f32 = lambda *s: rng.standard_normal(s).astype(np.float32)
    t = {
        "embed": f32(64, d),
        "final_norm/scale": f32(d),
        "layers/attn/wq": f32(L, d, d),
        "layers/attn/wo": f32(L, d, d),
        "layers/ffn/w1": f32(L, d, 4 * d),
        "layers/ffn/w2": f32(L, 4 * d, d),
    }
    if moe:
        t["layers/ffn/w_gate"] = f32(L, 8, d)          # router: stays base
        t["layers/ffn/w_up"] = f32(L, 8, d, 2 * d)     # expert banks: 4-D
        t["layers/ffn/w_down"] = f32(L, 8, 2 * d, d)
    return t


def _write(tmp_path, tensors, name="m.trims"):
    path = str(tmp_path / name)
    write_model(path, tensors, meta={"arch": "test"})
    return path


def _mrm(disk, dev=64 * MB, host=256 * MB, **kw):
    return MRM(disk, device_capacity=dev, host_capacity=host,
               hw=kw.pop("hw", HardwareModel()), **kw)


# ------------------------------------------------------------- layer planner
class TestLayerPlan:
    def test_plan_covers_file_exactly(self, tmp_path):
        path = _write(tmp_path, _layered_tensors(L=4))
        plan, _ = plan_for_file(path)
        size = os.path.getsize(path)
        ranges = sorted(r for w in plan for r in w.ranges)
        pos = 0
        for off, n in ranges:            # disjoint and gap-free
            assert off == pos
            pos += n
        assert pos == size
        assert plan[0].group == "stem" and plan[0].layer_index == -1
        assert [w.layer_index for w in plan[1:]] == [0, 1, 2, 3]

    def test_expert_windows_split_from_base(self, tmp_path):
        path = _write(tmp_path, _layered_tensors(L=3, moe=True))
        plan, _ = plan_for_file(path)
        experts = [w for w in plan if w.group == "expert"]
        assert len(experts) == 3
        for w in experts:                # router (3-D) stays in the base
            assert all(n.rsplit("/", 1)[-1] in ("w_up", "w_down")
                       for n in w.tensor_names)
        # expert window i directly follows its base window in plan order
        for w in experts:
            base = plan[w.index - 1]
            assert base.group == "layer" and base.layer_index == w.layer_index

    def test_irregular_depth_falls_back_to_stem(self):
        from repro.core.store import TensorMeta
        tensors = {
            "layers/a": TensorMeta("layers/a", "float32", (4, 8), 0, 128, 0),
            "layers/b": TensorMeta("layers/b", "float32", (3, 8), 128, 96, 0),
        }
        plan = build_layer_plan(tensors, payload_base=64, file_size=288)
        # disagreeing depths: the dissenting group is folded into the stem
        stems = [w for w in plan if w.group == "stem"]
        assert any("layers/b" in w.tensor_names for w in stems)


# --------------------------------------------------------- stream assembler
class TestStreamAssembler:
    def _feed_all(self, path, asm, order="shuffled", chunk=1000):
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            blob = f.read()
        frags = [(o, blob[o:o + chunk]) for o in range(0, size, chunk)]
        if order == "shuffled":
            rng = np.random.default_rng(1)
            rng.shuffle(frags)
        elif order == "reversed":
            frags.reverse()
        for off, data in frags:
            asm.feed(off, data)

    def test_out_of_order_feeds_reproduce_tensors(self, tmp_path):
        tensors = _layered_tensors(L=4)
        path = _write(tmp_path, tensors)
        fired = []
        asm = StreamAssembler(on_window=lambda w: fired.append(w.index))
        self._feed_all(path, asm, order="reversed")
        assert sorted(fired) == [w.index for w in asm.plan]
        for name, ref in tensors.items():
            np.testing.assert_array_equal(asm.arrays[name], ref)

    def test_components_filter_skips_groups(self, tmp_path):
        tensors = _layered_tensors(L=3, moe=True)
        path = _write(tmp_path, tensors)
        asm = StreamAssembler(components=("stem", "layer"))
        self._feed_all(path, asm)
        assert "layers/ffn/w_up" not in asm.arrays       # experts skipped
        assert "layers/attn/wq" in asm.arrays
        # excluded windows are born complete; included ones all landed
        assert asm.complete_count() == len(asm.plan)
        np.testing.assert_array_equal(asm.arrays["layers/attn/wq"],
                                      tensors["layers/attn/wq"])

    def test_duplicate_feeds_are_harmless(self, tmp_path):
        tensors = _layered_tensors(L=2)
        path = _write(tmp_path, tensors)
        fired = []
        asm = StreamAssembler(on_window=lambda w: fired.append(w.index))
        self._feed_all(path, asm, order="linear")
        n = len(fired)
        self._feed_all(path, asm, order="linear")        # full re-delivery
        assert len(fired) == n                           # no double events
        np.testing.assert_array_equal(asm.arrays["embed"], tensors["embed"])


# ------------------------------------------------- object store layer shards
class TestLayerShardedStore:
    def test_layer_put_records_window_rows(self, tmp_path):
        store = ObjectStore(str(tmp_path / "obj"), shard_bytes=SHARD)
        key = ModelKey("jax", "m", "1")
        store.put(key, _layered_tensors(L=4), shard_plan="layers")
        st = store.stat(key)
        assert st["shard_plan"] == "layers"
        shards = st["shards"]
        assert all("ranges" in s and "window" in s for s in shards)
        assert [s["index"] for s in shards] == list(range(len(shards)))
        # window ordinals are monotone across the table (execution order)
        wins = [s["window"] for s in shards]
        assert wins == sorted(wins)
        covered = sum(s["nbytes"] for s in shards)
        assert covered == st["nbytes"]

    def test_layer_fetch_roundtrip_and_callback_order(self, tmp_path):
        store = ObjectStore(str(tmp_path / "obj"), shard_bytes=SHARD)
        disk = DiskStore(str(tmp_path / "disk"))
        key = ModelKey("jax", "m", "1")
        tensors = _layered_tensors(L=4)
        store.put(key, tensors, shard_plan="layers")
        seen = []
        store.fetch(key, disk, on_shard=lambda s, d: seen.append(s["window"]))
        assert seen == sorted(seen) and len(seen) > 1
        mf = disk.open(key)
        for name, ref in tensors.items():
            np.testing.assert_array_equal(mf.read_tensor(name), ref)
        with open(mf.path, "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == \
                store.stat(key)["digest"]

    def test_classic_put_unchanged(self, tmp_path):
        store = ObjectStore(str(tmp_path / "obj"), shard_bytes=SHARD)
        key = ModelKey("jax", "m", "1")
        store.put(key, _layered_tensors(L=2))
        st = store.stat(key)
        assert st.get("shard_plan") is None
        assert st["shard_bytes"] == SHARD


# ----------------------------------------------------------------- cost model
class TestStreamingCostModel:
    def test_recurrence_bounds(self):
        wire = [2.0, 1.0, 1.0]
        post = [0.5, 0.5, 0.5]
        ttfl, done = streaming_ttfl_time(wire, post, lat=0.1)
        assert ttfl == done[0] == pytest.approx(0.1 + 2.0 + 0.5)
        # streamed total never beats the wire and never loses to serial
        assert done[-1] >= 0.1 + sum(wire)
        assert done[-1] <= 0.1 + sum(wire) + sum(post)
        assert done == sorted(done)

    def test_single_window_equals_serial(self):
        _, done = streaming_ttfl_time([3.0], [1.0], lat=0.5)
        assert done[-1] == pytest.approx(0.5 + 3.0 + 1.0)

    def test_hw_streaming_load_time(self):
        hw = HardwareModel()
        _, done = hw.streaming_load_time([MB, MB], 1e9, [0.0, 0.0])
        assert done[-1] < 2 * (MB / 1e9 + MB / hw.ingest_bw + MB / hw.h2d_bw)


# ------------------------------------------------------- MRM partial opens
class TestOpenStream:
    def _store_with(self, tmp_path, tensors, name="m"):
        store = ObjectStore(str(tmp_path / f"obj-{name}"), shard_bytes=SHARD)
        key = ModelKey("jax", name, "1")
        store.put(key, tensors, shard_plan="layers")
        return store, key

    def test_windows_arrive_in_execution_order(self, tmp_path):
        tensors = _layered_tensors(L=4)
        store, key = self._store_with(tmp_path, tensors)
        mrm = _mrm(DiskStore(str(tmp_path / "disk")), objectstore=store)
        fut = mrm.open_stream(key)
        n = fut.wait_prefix(2)
        assert n >= 2
        h = fut.result()
        assert fut.windows_ready() == len(fut.plan)
        for name, ref in tensors.items():
            np.testing.assert_array_equal(fut.arrays[name], ref)
        assert mrm.stats()["stream_loads"] == 1
        mrm.close(h)

    def test_concurrent_wait_prefix_and_result(self, tmp_path):
        """A wait_prefix(k) caller and a full result() caller racing on one
        future both complete (the satellite-3 concurrency case)."""
        tensors = _layered_tensors(L=6)
        store, key = self._store_with(tmp_path, tensors)
        mrm = _mrm(DiskStore(str(tmp_path / "disk")), objectstore=store)
        fut = mrm.open_stream(key)
        got = {}

        def waiter():
            got["prefix"] = fut.wait_prefix(3)

        def resolver():
            got["handle"] = fut.result(timeout=30)

        ts = [threading.Thread(target=waiter), threading.Thread(target=resolver)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(30)
        assert got["prefix"] >= 3
        assert got["handle"] is not None
        assert fut.wait_prefix(10 ** 6) == len(fut.plan)   # clamped, done
        mrm.close(got["handle"])

    def test_coalesced_stream_mirrors_windows(self, tmp_path):
        tensors = _layered_tensors(L=4)
        store, key = self._store_with(tmp_path, tensors)
        mrm = _mrm(DiskStore(str(tmp_path / "disk")), objectstore=store)
        f1 = mrm.open_stream(key)
        f2 = mrm.open_stream(key)
        h1, h2 = f1.result(), f2.result(timeout=30)
        if f2.coalesced:                   # raced onto f1's load
            assert f2.wait_prefix(1) >= 1
        assert mrm.stats()["coalesced_loads"] >= 1
        for h in (h1, h2):
            mrm.close(h)

    def test_private_components_load_bypasses_cache(self, tmp_path):
        tensors = _layered_tensors(L=3, moe=True)
        store, key = self._store_with(tmp_path, tensors)
        mrm = _mrm(DiskStore(str(tmp_path / "disk")), objectstore=store)
        fut = mrm.open_stream(key, components=("stem", "layer"))
        h = fut.result()
        assert h.private
        assert "layers/ffn/w_up" not in h.weights
        assert not mrm.resident(key, Tier.HOST)      # never cached
        assert mrm.stats()["partial_loads"] == 1
        mrm.close(h)                                  # must not underflow
        full = mrm.open(key, tier="host")             # full load still clean
        np.testing.assert_array_equal(
            np.asarray(full.weights["layers/ffn/w_up"]),
            tensors["layers/ffn/w_up"])
        mrm.close(full)

    def test_eviction_pressure_mid_stream_spares_placeholder(self, tmp_path):
        """Host-tier pressure while a stream is in flight: the pinned
        placeholder reservation survives make_room; victims come from the
        unpinned population and the stream completes intact."""
        big = _layered_tensors(L=8, d=64, seed=1)
        store, key = self._store_with(tmp_path, big, name="big")
        disk = DiskStore(str(tmp_path / "disk"))
        big_nb = sum(a.nbytes for a in big.values())
        small = {f"s{i}": np.zeros(big_nb // 16, np.float32)
                 for i in range(4)}
        small_nb = sum(a.nbytes for a in small.values())
        mrm = _mrm(disk, host=big_nb + 3 * small_nb, objectstore=store)
        skeys = []
        for i in range(4):
            sk = ModelKey("jax", f"small{i}", "1")
            disk.put(sk, small)
            skeys.append(sk)
        for sk in skeys[:2]:             # resident, unpinned, evictable
            mrm.close(mrm.open(sk, tier="host"))

        paused, resume = threading.Event(), threading.Event()
        real_fetch = store.fetch

        def pausing_fetch(k, dst, report_out=None, on_shard=None):
            def cb(row, data):
                if on_shard is not None:
                    on_shard(row, data)
                if not paused.is_set():
                    paused.set()
                    assert resume.wait(30)
            return real_fetch(k, dst, report_out=report_out, on_shard=cb)

        store.fetch = pausing_fetch
        try:
            fut = mrm.open_stream(key)
            assert paused.wait(30)
            # mid-stream: thrash the host tier
            for sk in skeys[2:]:
                mrm.close(mrm.open(sk, tier="host"))
            with mrm.host.lock:
                e = mrm.host.peek(key)
                assert e is not None and e.pinned    # placeholder survived
            resume.set()
            h = fut.result(timeout=60)
        finally:
            store.fetch = real_fetch
            resume.set()
        for name, ref in big.items():
            np.testing.assert_array_equal(fut.arrays[name], ref)
        assert mrm.resident(key, Tier.HOST)
        mrm.close(h)


# ------------------------------------------------------ cluster + streaming
def _layered_cluster(tmp_path, n=3, L=6):
    tensors = _layered_tensors(L=L, d=64, seed=2)
    store = ObjectStore(str(tmp_path / "cloud"), shard_bytes=SHARD)
    key = ModelKey("jax", "big", "1")
    store.put(key, tensors, shard_plan="layers")
    cluster = Cluster(objectstore=store)
    for i in range(n):
        cluster.add_node(f"node{i}",
                         _mrm(DiskStore(str(tmp_path / f"disk{i}"))))
    return cluster, store, key, tensors


class TestStreamingGather:
    def test_gather_feeds_windows(self, tmp_path):
        cluster, store, key, tensors = _layered_cluster(tmp_path)
        cluster.scatter(key, node_names=["node1", "node2"])
        n0 = cluster.node("node0")
        fut = n0.mrm.open_stream(key)
        h = fut.result(timeout=60)
        assert fut.timings.tier_hit == "gather"
        assert fut.windows_ready() == len(fut.plan)
        for name, ref in tensors.items():
            np.testing.assert_array_equal(fut.arrays[name], ref)
        n0.mrm.close(h)

    def test_source_death_after_layer_k_keeps_readiness(self, tmp_path,
                                                        monkeypatch):
        """A gather source dropped after early windows fired: the re-plan
        re-sources the remaining shards, readiness never rolls back, and
        the stream still completes every window."""
        cluster, store, key, tensors = _layered_cluster(tmp_path)
        cluster.scatter(key, node_names=["node1", "node2"])
        n0 = cluster.node("node0")
        state = {"fetched": 0, "prefix_at_death": None, "fut": None}
        real = n0._fetch_one_shard

        def dying_fetch(k, st, row, plan_gen, loads):
            data = real(k, st, row, plan_gen, loads)
            state["fetched"] += 1
            if state["fetched"] == 2:
                f = state["fut"]
                state["prefix_at_death"] = f.windows_ready() if f else 0
                cluster.directory.drop_node("node2")
            return data

        monkeypatch.setattr(n0, "_fetch_one_shard", dying_fetch)
        fut = n0.mrm.open_stream(key)
        state["fut"] = fut
        h = fut.result(timeout=60)
        assert state["prefix_at_death"] is not None
        assert fut.windows_ready() == len(fut.plan)
        assert fut.windows_ready() >= state["prefix_at_death"]
        assert n0.stats()["plan_replans"] >= 1
        for name, ref in tensors.items():
            np.testing.assert_array_equal(fut.arrays[name], ref)
        with open(n0.mrm.disk.path_for(key), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == \
                store.stat(key)["digest"]
        n0.mrm.close(h)

    def test_corrupt_shard_falls_back_without_refetching_verified(
            self, tmp_path):
        """A corrupt peer mid-stream: its shards re-source from CLOUD
        individually — shards already verified from the healthy peer are
        NOT re-downloaded (cloud shard count stays below the table size)."""
        cluster, store, key, tensors = _layered_cluster(tmp_path)
        cluster.scatter(key, node_names=["node1", "node2"])
        n0, n1 = cluster.node("node0"), cluster.node("node1")
        # size-preserving corruption of ONE of node1's shard blobs
        bad = n1.local_shards(key)[0]
        with open(n1._shard_path(key, bad), "r+b") as f:
            f.write(b"\xff" * 64)
        fut = n0.mrm.open_stream(key)
        h = fut.result(timeout=60)
        stats = n0.stats()
        n_shards = len(store.stat(key)["shards"])
        assert stats["gather_fallbacks"] > 0
        assert 0 < stats["shards_from_cloud"] < n_shards
        assert fut.windows_ready() == len(fut.plan)
        for name, ref in tensors.items():
            np.testing.assert_array_equal(fut.arrays[name], ref)
        with open(n0.mrm.disk.path_for(key), "rb") as f:
            assert hashlib.sha256(f.read()).hexdigest() == \
                store.stat(key)["digest"]
        n0.mrm.close(h)


# ------------------------------------------------------------ serving engine
class TestStreamingEngine:
    def test_streamed_generate_matches_batch(self, tmp_path):
        import jax
        from repro.configs import get_config
        from repro.models.model import init_params
        from repro.serving.engine import InferenceEngine, publish_model

        cfg = get_config("olmo-1b").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        d_ref = DiskStore(str(tmp_path / "ref"))
        key = publish_model(d_ref, cfg, params, name="olmo-1b")
        eng_ref = InferenceEngine(d_ref, _mrm(d_ref))

        store = ObjectStore(str(tmp_path / "obj"))
        store.put_file(key, d_ref.path_for(key), shard_plan="layers",
                       shard_bytes=SHARD)
        d_cold = DiskStore(str(tmp_path / "cold"))
        eng = InferenceEngine(d_cold, _mrm(d_cold, objectstore=store),
                              streaming=True)
        toks = (np.arange(6, dtype=np.int32).reshape(1, 6)) % cfg.vocab_size
        out_ref, _ = eng_ref.generate("olmo-1b", toks, max_new_tokens=3)
        out_s, st = eng.generate("olmo-1b", toks, max_new_tokens=3)
        assert st.streamed and st.ttft_s > 0
        np.testing.assert_array_equal(out_ref, out_s)
        # warm re-serve falls back to the batch path, same tokens
        out_w, st_w = eng.generate("olmo-1b", toks, max_new_tokens=3)
        assert not st_w.streamed
        np.testing.assert_array_equal(out_ref, out_w)
        # satellite: first-execution compile time folded into compile_s
        assert st.compile_s > 0
