"""Compression-aware cloud/peer transfer + ObjectStore correctness.

Covers the codec abstraction (round trips, streaming), compressed
ObjectStore put/fetch (manifest schema, pre-compression manifest compat,
dedup per codec), the pipelined decompress stage (overlap, error path),
the concurrent-fetch temp-file race fix, blob garbage collection, the
compression-aware cost model, compressed peer wire, and the
``measure()`` page-cache eviction fix (DESIGN.md §4/§6).
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core import (Cluster, DiskStore, HardwareModel, MRM, ModelKey,
                        ObjectStore, Tier, get_codec, sample_ratio)
from repro.core.codec import CODECS
from repro.core.pipeline import run_pipeline

MB = 1 << 20


def _quantized(nbytes=2 * MB, n=4, seed=0):
    """Compressible float32 weights (few distinct values)."""
    rng = np.random.default_rng(seed)
    per = nbytes // n // 4
    return {f"w{i}": (np.round(rng.standard_normal(per) * 64) / 64
                      ).astype(np.float32) for i in range(n)}


def _incompressible(nbytes, seed=0) -> bytes:
    """Deterministic stand-in for os.urandom: reproducible run-to-run
    (seed audit), still incompressible."""
    return np.random.default_rng(seed).bytes(nbytes)


def _mrm(disk, **kw):
    kw.setdefault("device_capacity", 64 * MB)
    kw.setdefault("host_capacity", 128 * MB)
    return MRM(disk, **kw)


# ------------------------------------------------------------------- codecs
class TestCodec:
    @pytest.mark.parametrize("name", sorted(CODECS))
    def test_one_shot_round_trip(self, name):
        codec = get_codec(name)
        data = _incompressible(64 << 10) + bytes(64 << 10)
        assert codec.decompress(codec.compress(data)) == data

    @pytest.mark.parametrize("name", ["zlib", "lzma"])
    def test_streaming_round_trip_chunked(self, name):
        codec = get_codec(name)
        data = bytes(range(256)) * 4096
        comp = codec.compressor()
        wire = b"".join(comp.compress(data[i:i + 1024])
                        for i in range(0, len(data), 1024)) + comp.flush()
        assert len(wire) < len(data)  # repeating payload must compress
        dec = codec.decompressor()
        out = b"".join(dec.decompress(wire[i:i + 777])
                       for i in range(0, len(wire), 777)) + dec.flush()
        assert out == data

    def test_get_codec_resolution(self):
        assert get_codec(None).name == "none"
        assert get_codec("zlib").name == "zlib"
        assert get_codec(get_codec("lzma")).name == "lzma"
        with pytest.raises(ValueError):
            get_codec("zstd-not-built")

    def test_sample_ratio_clamps_incompressible(self, tmp_path):
        p = tmp_path / "rand.bin"
        p.write_bytes(_incompressible(256 << 10))
        assert sample_ratio(str(p), "zlib") == 1.0  # never inflates the model
        z = tmp_path / "zeros.bin"
        z.write_bytes(bytes(256 << 10))
        assert sample_ratio(str(z), "zlib") > 10.0


# -------------------------------------------------- compressed object store
class TestCompressedObjectStore:
    @pytest.mark.parametrize("codec", ["zlib", "lzma"])
    def test_put_fetch_round_trip_compressed(self, tmp_path, codec):
        obj = ObjectStore(str(tmp_path / "cloud"), codec=codec,
                          chunk_bytes=128 << 10)
        key = ModelKey("jax", "m", "1")
        tensors = _quantized()
        obj.put(key, tensors)
        st = obj.stat(key)
        assert st["codec"] == codec
        assert 0 < st["stored_nbytes"] < st["nbytes"]

        dest = DiskStore(str(tmp_path / "disk"))
        sink = []
        modeled, nbytes = obj.fetch(key, dest, report_out=sink)
        got = dest.open(key).read_all(verify=True)
        np.testing.assert_array_equal(got["w0"], tensors["w0"])
        # wire modeled at stored bytes: beats the uncompressed leg
        assert modeled < obj.rtt + nbytes / obj.bw
        assert modeled == pytest.approx(obj.modeled_fetch_s(key))
        report = sink[0]
        assert report is not None and report.n_chunks >= 2
        assert report.stage("decompress").busy_s > 0

    def test_decompress_stage_overlaps(self, tmp_path):
        obj = ObjectStore(str(tmp_path / "cloud"), codec="zlib",
                          chunk_bytes=64 << 10)
        key = ModelKey("jax", "m", "1")
        obj.put(key, _quantized(4 * MB))
        sink = []
        obj.fetch(key, DiskStore(str(tmp_path / "disk")), report_out=sink)
        report = sink[0]
        wire, dec = report.stage("wire_read"), report.stage("decompress")
        # decode began before the wire's last chunk was read. With queues
        # of depth 2 the wire stage cannot start chunk n-1 until decompress
        # has taken chunk n-4, so from 5 chunks on this holds on any host
        # when, and only when, the stages run concurrently
        assert report.n_chunks >= 5
        assert dec.first_start_s < wire.last_end_s

    def test_pre_compression_manifest_compat(self, tmp_path):
        """Entries written before the codec era ({digest, nbytes} only, blob
        at the un-suffixed path) still stat and fetch correctly."""
        obj = ObjectStore(str(tmp_path / "cloud"))
        key = ModelKey("jax", "old", "1")
        obj.put(key, _quantized())
        # rewrite the manifest entry down to the legacy schema
        with open(obj.manifest_path) as f:
            manifest = json.load(f)
        (kid, entry), = manifest.items()
        manifest[kid] = {"digest": entry["digest"], "nbytes": entry["nbytes"]}
        with open(obj.manifest_path, "w") as f:
            json.dump(manifest, f)

        reopened = ObjectStore(obj.root)
        st = reopened.stat(key)
        assert st["codec"] == "none"
        assert st["stored_nbytes"] == st["nbytes"]
        dest = DiskStore(str(tmp_path / "disk"))
        modeled, nbytes = reopened.fetch(key, dest)
        assert dest.open(key).read_all(verify=True)
        assert modeled == pytest.approx(reopened.rtt + nbytes / reopened.bw)

    def test_dedup_within_codec_not_across(self, tmp_path):
        obj = ObjectStore(str(tmp_path / "cloud"), codec="zlib")
        tensors = _quantized(seed=7)
        d1 = obj.put(ModelKey("jax", "m", "1"), tensors)
        d2 = obj.put(ModelKey("jax", "m", "2"), tensors)
        assert d1 == d2  # digest is of the uncompressed content
        assert obj.stats()["dedup_hits"] == 1
        # a different codec stores its own blob for the same digest
        d3 = obj.put(ModelKey("jax", "m", "3"), tensors, codec="none")
        assert d3 == d1
        assert obj.stats()["dedup_hits"] == 1
        assert obj.stats()["blobs"] == 2

    def test_per_put_codec_overrides_store_default(self, tmp_path):
        obj = ObjectStore(str(tmp_path / "cloud"), codec="none")
        key = ModelKey("jax", "m", "1")
        obj.put(key, _quantized(), codec="zlib")
        assert obj.stat(key)["codec"] == "zlib"

    def test_tuned_codec_instance_not_flattened_to_registry_default(
            self, tmp_path):
        """ObjectStore(codec=ZlibCodec(level=0)) must use THAT instance
        (level 0 = stored blocks, no compression), not the registry's
        level-6 default resolved back from the name."""
        from repro.core.codec import ZlibCodec
        key = ModelKey("jax", "m", "1")
        tensors = _quantized()
        stored_raw = ObjectStore(str(tmp_path / "c0"),
                                 codec=ZlibCodec(level=0))
        stored_raw.put(key, tensors)
        default = ObjectStore(str(tmp_path / "c6"), codec="zlib")
        default.put(key, tensors)
        assert (stored_raw.stat(key)["stored_nbytes"]
                > default.stat(key)["stored_nbytes"])

    def test_mrm_cold_open_through_compressed_cloud(self, tmp_path):
        obj = ObjectStore(str(tmp_path / "cloud"), codec="zlib",
                          chunk_bytes=128 << 10)
        key = ModelKey("jax", "m", "1")
        tensors = _quantized()
        obj.put(key, tensors)
        mrm = _mrm(DiskStore(str(tmp_path / "disk")), objectstore=obj)
        h = mrm.open(key)
        assert h.timings.tier_hit == "cloud"
        assert h.timings.cloud_s > 0
        assert h.timings.decompress_s > 0  # inflate measured on the way in
        np.testing.assert_array_equal(np.asarray(h.weights["w0"]),
                                      tensors["w0"])
        mrm.close(h)

    def test_writeback_uses_mrm_cloud_codec(self, tmp_path):
        obj = ObjectStore(str(tmp_path / "cloud"))
        disk = DiskStore(str(tmp_path / "disk"))
        key = ModelKey("jax", "m", "1")
        disk.put(key, _quantized())
        mrm = _mrm(disk, host_capacity=3 * MB, objectstore=obj,
                   writeback_to_cloud=True, cloud_codec="zlib")
        h1 = mrm.open(key, tier="host")
        mrm.close(h1)
        # evict the host entry -> demotion -> background write-back
        k2 = ModelKey("jax", "filler", "1")
        disk.put(k2, _quantized(seed=9))
        mrm.open(k2, tier="host")
        mrm.flush_writebacks()
        st = obj.stat(key)
        assert st is not None and st["codec"] == "zlib"
        assert st["stored_nbytes"] < st["nbytes"]


# ----------------------------------------------------- concurrency bugfixes
class TestConcurrentFetch:
    def test_concurrent_fetch_one_key_no_tmp_race(self, tmp_path):
        """100 concurrent cold fetches of ONE key into one DiskStore: the
        shared ``dst + ".tmp"`` staging name used to make the loser's
        os.replace raise FileNotFoundError."""
        obj = ObjectStore(str(tmp_path / "cloud"), codec="zlib")
        key = ModelKey("jax", "m", "1")
        tensors = _quantized(1 * MB)
        obj.put(key, tensors)
        dest = DiskStore(str(tmp_path / "disk"))
        errors = []
        start = threading.Barrier(8)

        def fetch():
            try:
                start.wait()  # all racers released together
                for _ in range(13):
                    obj.fetch(key, dest)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=fetch) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert obj.fetches >= 100
        got = dest.open(key).read_all(verify=True)
        np.testing.assert_array_equal(got["w0"], tensors["w0"])
        # no orphaned temp files left behind
        d = os.path.dirname(dest.path_for(key))
        assert [f for f in os.listdir(d) if f.startswith(".fetch-")] == []

    def test_concurrent_put_and_fetch_same_key(self, tmp_path):
        obj = ObjectStore(str(tmp_path / "cloud"), codec="zlib")
        key = ModelKey("jax", "m", "1")
        tensors = _quantized(1 * MB)
        obj.put(key, tensors)
        dest = DiskStore(str(tmp_path / "disk"))
        errors = []
        stop = threading.Event()

        def putter():
            try:
                while not stop.is_set():
                    obj.put(key, tensors)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        t = threading.Thread(target=putter)
        t.start()
        try:
            for _ in range(25):
                obj.fetch(key, dest)
        finally:
            stop.set()
            t.join()
        assert not errors
        assert dest.open(key).read_all(verify=True)


class TestPipelineErrorPath:
    def test_mid_stage_exception_reraised_no_hang(self):
        fed = []

        def stage_a(x):
            fed.append(x)
            return x

        def stage_b(x):
            if x == 3:
                raise RuntimeError("chunk 3 is poison")
            return x * 10

        t0 = time.perf_counter()
        with pytest.raises(RuntimeError, match="poison"):
            run_pipeline(list(range(64)), [("a", stage_a), ("b", stage_b)],
                         depth=2)
        assert time.perf_counter() - t0 < 10.0  # aborted, not hung
        assert len(fed) < 64  # the feeder stopped early, no full drain

    def test_error_in_fetch_pipeline_leaves_no_partial_output(self, tmp_path):
        obj = ObjectStore(str(tmp_path / "cloud"), codec="zlib")
        key = ModelKey("jax", "m", "1")
        obj.put(key, _quantized(1 * MB))
        # corrupt the compressed blob: decompress stage must raise cleanly
        st = obj.stat(key)
        blob = obj._blob_path(st["digest"], st["codec"])
        with open(blob, "wb") as f:
            f.write(_incompressible(st["stored_nbytes"]))
        dest = DiskStore(str(tmp_path / "disk"))
        with pytest.raises(Exception):
            obj.fetch(key, dest)
        assert not dest.contains(key)  # no partial .trims landed
        d = os.path.dirname(dest.path_for(key))
        if os.path.isdir(d):
            assert [f for f in os.listdir(d) if f.startswith(".fetch-")] == []


# ------------------------------------------------------------------ gc/blobs
class TestGcBlobs:
    def test_delete_then_gc_reclaims_unreferenced_blob(self, tmp_path):
        obj = ObjectStore(str(tmp_path / "cloud"), codec="zlib")
        k1, k2 = ModelKey("jax", "m", "1"), ModelKey("jax", "m", "2")
        obj.put(k1, _quantized(seed=1))
        obj.put(k2, _quantized(seed=2))  # different bytes -> second blob
        obj.delete(k1)
        reclaimed = obj.gc_blobs()
        assert reclaimed > 0
        st = obj.stats()
        assert st["gc_blobs_removed"] == 1
        assert st["gc_reclaimed_bytes"] == reclaimed
        # the surviving key still fetches
        dest = DiskStore(str(tmp_path / "disk"))
        obj.fetch(k2, dest)
        assert dest.contains(k2)

    def test_gc_keeps_blob_shared_by_another_key(self, tmp_path):
        obj = ObjectStore(str(tmp_path / "cloud"))
        tensors = _quantized(seed=5)
        obj.put(ModelKey("jax", "m", "1"), tensors)
        obj.put(ModelKey("jax", "m", "2"), tensors)  # dedup: shared blob
        obj.delete(ModelKey("jax", "m", "1"))
        assert obj.gc_blobs() == 0  # still referenced by version 2
        dest = DiskStore(str(tmp_path / "disk"))
        obj.fetch(ModelKey("jax", "m", "2"), dest)
        assert dest.contains(ModelKey("jax", "m", "2"))

    def test_gc_noop_when_everything_referenced(self, tmp_path):
        obj = ObjectStore(str(tmp_path / "cloud"), codec="lzma")
        obj.put(ModelKey("jax", "m", "1"), _quantized())
        assert obj.gc_blobs() == 0

    def test_fetch_vs_delete_gc_race_surfaces_cleanly(self, tmp_path):
        """A blob unlinked mid-fetch (concurrent delete + gc) re-stats: a
        deleted key becomes KeyError; a present key with a genuinely
        missing blob still raises after the retry."""
        obj = ObjectStore(str(tmp_path / "cloud"), codec="zlib")
        key = ModelKey("jax", "m", "1")
        obj.put(key, _quantized())
        st = obj.stat(key)
        os.unlink(obj._blob_path(st["digest"], st["codec"]))
        dest = DiskStore(str(tmp_path / "disk"))
        with pytest.raises(FileNotFoundError):  # key present, blob gone
            obj.fetch(key, dest)
        obj.delete(key)
        with pytest.raises(KeyError):  # key gone: a plain miss
            obj.fetch(key, dest)


# ----------------------------------------------------- compression-aware model
class TestCompressionCostModel:
    def test_cloud_fetch_ratio_beats_uncompressed_at_cloud_bw(self):
        hw = HardwareModel()
        n = 256 * MB
        base = hw.cloud_fetch_time(n)
        for ratio in (1.5, 2.0, 3.0):
            assert hw.cloud_fetch_time(n, ratio=ratio) < base

    def test_cloud_fetch_crossover_when_link_outruns_decompress(self):
        """Past link_bw == decompress_bw the decompress stage is the
        max-stage and compression stops paying (DESIGN.md §4)."""
        fast = HardwareModel(cloud_bw=5e9)
        n = 256 * MB
        assert fast.cloud_fetch_time(n, ratio=2.0) > fast.cloud_fetch_time(n)

    def test_pipelined_at_most_serial(self):
        hw = HardwareModel()
        n = 256 * MB
        for ratio in (1.5, 4.0):
            serial = (hw.cloud_rtt + n / ratio / hw.cloud_bw
                      + n / hw.decompress_bw)
            assert hw.cloud_fetch_time(n, ratio=ratio) <= serial + 1e-9

    def test_staging_pipelined_ratio_variant(self):
        hw = HardwareModel()
        n = 256 * MB
        assert (hw.staging_pipelined_time(n, ratio=4.0)
                < hw.staging_pipelined_time(n))
        # ratio=1 path is unchanged: no phantom decompress stage
        assert hw.staging_pipelined_time(n) == pytest.approx(
            hw.staging_pipelined_time(n, ratio=1.0))

    def test_pick_fetch_source_compares_compressed_wire(self):
        """A compressed cloud blob can out-bid a raw disk-bound peer."""
        hw = HardwareModel(cloud_bw=1e9, peer_bw=10e9, disk_bw=1.2e9)
        n = 256 * MB
        raw_src, _ = hw.pick_fetch_source(n, have_peer=True, have_cloud=True)
        comp_src, comp_s = hw.pick_fetch_source(n, have_peer=True,
                                                have_cloud=True,
                                                cloud_ratio=4.0)
        assert raw_src == "peer" and comp_src == "cloud"
        assert comp_s == hw.cloud_fetch_time(n, ratio=4.0)


# ------------------------------------------------------------ peer wire codec
class TestPeerWireCodec:
    def _cluster(self, tmp_path, hw):
        cluster = Cluster(peer_codec="zlib")
        for i in range(2):
            mrm = _mrm(DiskStore(str(tmp_path / f"peer{i}")), hw=hw)
            cluster.add_node(f"node{i}", mrm)
        return cluster

    def test_compressed_peer_transfer(self, tmp_path):
        # wire-bound regime: fast disks, cloud-class link
        hw = HardwareModel(peer_bw=0.5e9, disk_bw=5e9, compress_bw=5e9)
        cluster = self._cluster(tmp_path, hw)
        key = ModelKey("jax", "m", "1")
        tensors = _quantized()
        cluster.node("node0").mrm.disk.put(key, tensors)
        cluster.directory.publish("node0", key, Tier.DISK)
        h = cluster.node("node1").mrm.open(key)
        assert h.timings.tier_hit == "peer"
        assert h.timings.decompress_s > 0
        stats = cluster.node("node1").stats()
        assert 0 < stats["bytes_on_wire"] < stats["bytes_from_peers"]
        np.testing.assert_array_equal(np.asarray(h.weights["w0"]),
                                      tensors["w0"])
        cluster.node("node1").mrm.close(h)

    def test_tuned_peer_codec_instance_kept(self, tmp_path):
        """Cluster(peer_codec=<tuned Codec>) must keep the instance, not
        flatten it to the registry default via its name."""
        from repro.core.codec import ZlibCodec
        cluster = Cluster(peer_codec=ZlibCodec(level=9))
        node = cluster.add_node(
            "n0", _mrm(DiskStore(str(tmp_path / "p0")),
                       hw=HardwareModel()))
        assert node.peer_codec == "zlib"
        assert node._peer_codec.level == 9

    def test_wire_ratio_ignores_other_codecs_manifest(self, tmp_path):
        """A zlib peer wire must not borrow an lzma blob's ratio — it
        samples its own codec instead (and memoizes per key)."""
        hw = HardwareModel(peer_bw=0.5e9, disk_bw=5e9, compress_bw=5e9)
        obj = ObjectStore(str(tmp_path / "cloud"), codec="lzma")
        cluster = Cluster(objectstore=obj, peer_codec="zlib")
        for i in range(2):
            mrm = _mrm(DiskStore(str(tmp_path / f"peer{i}")), hw=hw)
            cluster.add_node(f"node{i}", mrm)
        key = ModelKey("jax", "m", "1")
        tensors = _quantized()
        obj.put(key, tensors)  # lzma entry in the manifest
        node0 = cluster.node("node0")
        node0.mrm.disk.put(key, tensors)
        st = obj.stat(key)
        lzma_ratio = st["nbytes"] / st["stored_nbytes"]
        # the holder peer exposes its local file for ratio sampling
        got = cluster.node("node1")._wire_ratio(key, node0)
        assert got != pytest.approx(lzma_ratio)  # sampled, not borrowed
        assert key in cluster.node("node1")._ratio_cache  # memoized

    def test_raw_copy_when_compression_does_not_pay(self, tmp_path):
        """On a fast peer link the source read caps the stream and the
        compress stage would be the max-stage — the node sends raw."""
        hw = HardwareModel(peer_bw=10e9, disk_bw=500e6)
        cluster = self._cluster(tmp_path, hw)
        key = ModelKey("jax", "m", "1")
        cluster.node("node0").mrm.disk.put(key, _quantized())
        cluster.directory.publish("node0", key, Tier.DISK)
        h = cluster.node("node1").mrm.open(key)
        assert h.timings.tier_hit == "peer"
        stats = cluster.node("node1").stats()
        assert stats["bytes_on_wire"] == stats["bytes_from_peers"]
        cluster.node("node1").mrm.close(h)


# ------------------------------------------------------------- measure() fix
class TestMeasureEviction:
    def test_drop_page_cache_is_graceful(self, tmp_path):
        from repro.core.costmodel import drop_page_cache
        p = tmp_path / "f"
        p.write_bytes(b"x" * 4096)
        drop_page_cache(str(p))  # must not raise either way
        assert drop_page_cache(str(p / "missing")) is False

    def test_measured_disk_bw_below_cached_read_bw(self):
        """The paper's Table-2 distinction: with the post-write eviction
        (plus the tmpfs cached-rate anchor) the buffered-disk and
        cached-read rates actually differ."""
        from repro.core.costmodel import measure
        hw = measure(nbytes=32 * MB)
        assert hw.disk_bw < hw.cached_read_bw
