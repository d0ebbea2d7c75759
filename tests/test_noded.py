"""Node daemon: PeerStub parity, directory-over-RPC, multi-process
lifecycle (DESIGN.md §11)."""
from __future__ import annotations

import glob
import hashlib
import os
import signal
import time

import numpy as np
import pytest

from repro.core.cache import Tier
from repro.core.directory import make_directory
from repro.core.mrm import ModelKey
from repro.core.noded import (DirectoryClient, DirectoryService, NodeDaemon,
                              PeerStub, spawn_node, sync_directory)
from repro.core.objectstore import ObjectStore
from repro.core.store import DiskStore, write_model
from repro.core.transport import (LoopbackTransport, SocketTransport,
                                  TransportError)


def make_model(root: str, key: ModelKey, kib: int = 256,
               seed: int = 0) -> str:
    disk = DiskStore(root)
    rng = np.random.RandomState(seed)
    n = max(1, (kib << 10) // (4 * 256))
    tensors = {f"w{i}": rng.rand(n, 64).astype(np.float32)
               for i in range(4)}
    path = disk.path_for(key)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    write_model(path, tensors, {"framework": key[0], "name": key[1],
                                "version": key[2]})
    h = hashlib.sha256()
    h.update(open(path, "rb").read())
    return h.hexdigest()


@pytest.fixture
def two_daemons(tmp_path):
    """Daemon a (hosts the sharded directory, holds m0 on disk) and
    daemon b (cold), both in-process, linked by real unix sockets."""
    osroot = str(tmp_path / "objstore")
    os.makedirs(osroot)
    key = ModelKey("jax", "m0", "1")
    digest = make_model(str(tmp_path / "a"), key)
    ObjectStore(osroot).put_file(
        key, DiskStore(str(tmp_path / "a")).path_for(key))
    a = NodeDaemon({"name": "a", "disk_root": str(tmp_path / "a"),
                    "listen": f"unix:{tmp_path}/a.sock",
                    "objectstore": {"root": osroot},
                    "directory": {"serve": True, "policy": "sharded",
                                  "n_shards": 4}})
    os.makedirs(tmp_path / "b")
    b = NodeDaemon({"name": "b", "disk_root": str(tmp_path / "b"),
                    "listen": f"unix:{tmp_path}/b.sock",
                    "objectstore": {"root": osroot},
                    "directory": {"connect": a.address}})
    yield a, b, key, digest
    b.shutdown()
    a.shutdown()


class TestPeerStubParity:
    """PeerStub over LoopbackTransport(daemon.handle) answers exactly
    like the in-process ClusterNode surface it proxies."""

    def test_surface_matches_direct(self, tmp_path):
        key = ModelKey("jax", "m0", "1")
        make_model(str(tmp_path / "d"), key)
        d = NodeDaemon({"name": "d", "disk_root": str(tmp_path / "d"),
                        "listen": f"unix:{tmp_path}/d.sock"})
        try:
            stub = PeerStub(LoopbackTransport(d.handle), "d")
            node = d.node
            assert stub.has_model(key) == node.has_model(key) is True
            assert stub.model_nbytes(key) == node.model_nbytes(key)
            assert stub.has_model(ModelKey("jax", "nope", "1")) is False
            assert stub.model_nbytes(ModelKey("jax", "nope", "1")) is None
            # whole-file read: byte-identical to the disk copy
            got = []
            n = stub.read_model(key, got.append)
            raw = open(d.mrm.disk.path_for(key), "rb").read()
            assert b"".join(got) == raw and n == len(raw)
            # ranges slice out of the same file
            assert stub.read_model_ranges(key, [(0, 64), (100, 32)]) == \
                raw[:64] + raw[100:132]
            # remote stubs never expose a local path (raw wire only)
            assert stub.local_model_path(key) is None
            assert node.local_model_path(key) is not None
            assert stub.remote and not node.remote
        finally:
            d.shutdown()

    def test_dead_peer_probes_degrade_not_raise(self, tmp_path):
        stub = PeerStub(SocketTransport(f"unix:{tmp_path}/gone.sock",
                                        timeout_s=0.5), "ghost")
        key = ModelKey("jax", "m0", "1")
        assert stub.has_model(key) is False
        assert stub.model_nbytes(key) is None
        assert stub.has_shard(key, 0) is False
        with pytest.raises(OSError):
            stub.read_model(key, lambda b: None)

    def test_read_model_counts_sink_bytes_not_server_claim(self):
        """read_model must validate against the bytes the sink actually
        received — a server-reported nbytes would let a truncated or
        duplicated stream pass the caller's size check."""
        class _LyingTransport:
            address = "fake:"

            def __init__(self, payload, claim):
                self.payload, self.claim = payload, claim

            def call_stream(self, req, sink):
                sink(self.payload)
                return {"ok": True, "nbytes": self.claim}

        key = ModelKey("jax", "m0", "1")
        got = []
        n = PeerStub(_LyingTransport(b"abcd", 4), "ok").read_model(
            key, got.append)
        assert n == 4 and got == [b"abcd"]
        with pytest.raises(OSError, match="delivered 4 of 999"):
            PeerStub(_LyingTransport(b"abcd", 999), "liar").read_model(
                key, lambda b: None)


class TestDirectoryOverRPC:
    def test_client_roundtrip(self, two_daemons):
        a, b, key, _ = two_daemons
        # b registered over RPC at daemon-a's directory; both are listed
        d = a.dir_service.directory
        names = {n.name for n in d.nodes()}
        assert names == {"a", "b"}
        # a's disk copy was published through the service at init
        assert ("a", Tier.DISK) in d.holders(key)
        # b's client resolves a to a PeerStub at a's advertised address
        peer = b.directory.node("a")
        assert isinstance(peer, PeerStub) and peer.has_model(key)
        # publish/withdraw through the client round-trips
        k2 = ModelKey("jax", "ghost", "9")
        b.directory.publish("b", k2, Tier.HOST)
        assert b.directory.tier_on(k2, "b") == Tier.HOST
        b.directory.withdraw("b", k2, Tier.HOST)
        assert b.directory.tier_on(k2, "b") is None

    def test_cold_open_pulls_over_socket(self, two_daemons):
        a, b, key, digest = two_daemons
        t = SocketTransport(b.address)
        r = t.call({"op": "open", "key": list(key), "tier": "host",
                    "timeout": 60})
        assert r["timings"]["tier_hit"] == "peer"
        assert r["disk_digest"] == digest
        assert r["timings"]["wire_s"] > 0  # measured, not modeled
        # serve counted on a's side, fetch on b's
        assert a.node.metrics["peer_serves"] == 1
        assert b.node.metrics["peer_fetches"] == 1
        t.close()

    def test_hung_peer_times_out_and_falls_back(self, tmp_path):
        """A peer that accepts but never answers must surface as a fetch
        error (cloud fallback), not a hang."""
        import socket as socketlib
        import threading
        osroot = str(tmp_path / "objstore")
        os.makedirs(osroot)
        key = ModelKey("jax", "m0", "1")
        seed_root = str(tmp_path / "seed")
        make_model(seed_root, key)
        ObjectStore(osroot).put_file(
            key, DiskStore(seed_root).path_for(key))

        hung_path = str(tmp_path / "hung.sock")
        hung = socketlib.socket(socketlib.AF_UNIX)
        hung.bind(hung_path)
        hung.listen(4)
        conns = []
        threading.Thread(
            target=lambda: [conns.append(hung.accept()) for _ in range(9)],
            daemon=True).start()

        os.makedirs(tmp_path / "c")
        c = NodeDaemon({"name": "c", "disk_root": str(tmp_path / "c"),
                        "listen": f"unix:{tmp_path}/c.sock",
                        "objectstore": {"root": osroot},
                        "call_timeout_s": 0.5,
                        "directory": {"serve": True}})
        try:
            # a fake warm holder whose data plane never answers
            stub = PeerStub(SocketTransport(f"unix:{hung_path}",
                                            timeout_s=0.5), "hung")
            c.directory.register(stub)
            c.directory.publish("hung", key, Tier.DISK)
            t0 = time.perf_counter()
            fut = c.mrm.open_async(key, tier="host")
            h = fut.result(timeout=30)
            took = time.perf_counter() - t0
            assert h.timings.tier_hit == "cloud"  # fell through, no hang
            assert took < 10, f"hung peer stalled the open {took:.1f}s"
            c.mrm.close(h)
        finally:
            c.shutdown()
            hung.close()

    def test_addressless_member_has_probe_surface(self, tmp_path):
        """A member registered without an advertised address must look
        like a stale hint to planners (every probe misses), not crash
        the open with an AttributeError."""
        a = NodeDaemon({"name": "a", "disk_root": str(tmp_path / "a"),
                        "listen": f"unix:{tmp_path}/a.sock",
                        "directory": {"serve": True}})
        try:
            a.dir_service.handle({"op": "dir.register", "name": "ghost"})
            client = DirectoryClient(LoopbackTransport(a.dir_service.handle))
            key = ModelKey("jax", "m0", "1")
            ghost = client.node("ghost")
            assert ghost is not None and ghost.name == "ghost"
            assert ghost.remote
            assert ghost.has_model(key) is False
            assert ghost.model_nbytes(key) is None
            assert ghost.has_shard(key, 0) is False
            assert ghost.local_model_path(key) is None
            # the directory host's own planner sees the same surface
            rec = a.dir_service.directory.node("ghost")
            assert rec.has_model(key) is False
        finally:
            a.shutdown()

    def test_remote_registration_resolves_to_stub_on_host(self, two_daemons):
        """The directory-HOSTING process must plan against remote members
        through a live PeerStub (b registered over RPC with an address),
        so a reverse fetch a<-b probes real state instead of crashing."""
        a, b, key, _ = two_daemons
        rec = a.dir_service.directory.node("b")
        assert isinstance(rec, PeerStub) and rec.address == b.address
        assert rec.has_model(key) is False  # b is cold: real probe, miss
        t = SocketTransport(b.address)
        t.call({"op": "open", "key": list(key), "tier": "host",
                "timeout": 60})
        t.close()
        assert rec.has_model(key) is True  # warm now: a can plan a<-b

    def test_anti_entropy_sync_converges(self, two_daemons):
        a, b, key, _ = two_daemons
        # a third replica, private, learns the fleet purely via dir.sync
        d3 = make_directory("sharded", n_shards=4)
        t = SocketTransport(a.address)
        merged = sync_directory(d3, t)
        assert merged > 0
        holders = dict(d3.holders(key))
        assert "a" in holders
        # and a dropped node never resurrects through an old snapshot
        snap_stale = d3.export_snapshot()
        a.dir_service.directory.drop_node("b")
        sync_directory(d3, t)  # d3 learns the drop
        assert "b" not in {n.name for n in d3.nodes()}
        # replaying the stale snapshot (still lists b) must not revive it
        d3.merge_snapshot(snap_stale)
        assert "b" not in {n.name for n in d3.nodes()}
        t.close()


class TestWireCalibration:
    def test_observe_wire_thread_safe(self):
        """Concurrent gather threads feed the calibration; no sample may
        be dropped to an interleaved EWMA read-modify-write."""
        import threading
        from repro.core.costmodel import (MIN_WIRE_SAMPLE_BYTES,
                                          HardwareModel)
        hw = HardwareModel()
        n_threads, per = 8, 200
        nb = MIN_WIRE_SAMPLE_BYTES

        def worker():
            for _ in range(per):
                hw.observe_wire("peer", nb, 1e-3)

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        cal = hw.wire_calibration()["peer"]
        assert cal["samples"] == n_threads * per
        assert cal["bytes"] == n_threads * per * nb
        # identical samples: the EWMA must land exactly on the one rate
        assert hw.peer_bw == pytest.approx(nb / 1e-3)


def wait_for(pred, timeout=10.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


@pytest.mark.proc
class TestDaemonLifecycle:
    """Real subprocess daemons: SIGTERM cleanliness, shm hygiene,
    crash-restart incarnations."""

    def _spawn(self, tmp_path, register_daemon, name, extra):
        root = tmp_path / name
        root.mkdir(exist_ok=True)
        err = open(tmp_path / f"{name}.err", "w")
        p, info = spawn_node({"name": name, "disk_root": str(root),
                              "listen": f"unix:{tmp_path}/{name}-dp.sock",
                              **extra}, stderr=err)
        register_daemon(p)
        return p, info

    def test_sigterm_clean_shutdown(self, tmp_path, register_daemon):
        key = ModelKey("jax", "m0", "1")
        make_model(str(tmp_path / "a"), key)
        pa, ia = self._spawn(tmp_path, register_daemon, "a",
                             {"use_shm": True,
                              "directory": {"serve": True,
                                            "policy": "sharded",
                                            "n_shards": 4}})
        pb, ib = self._spawn(tmp_path, register_daemon, "b",
                             {"use_shm": True,
                              "directory": {"connect": ia["address"]}})
        ta = SocketTransport(ia["address"])
        tb = SocketTransport(ib["address"])
        # b pulls the model into its host tier -> owns a shm segment
        r = tb.call({"op": "open", "key": list(key), "tier": "host",
                     "timeout": 60})
        assert r["timings"]["tier_hit"] == "peer"

        def owned(*procs):  # segments are named after the creating pid
            return {path for p in procs
                    for path in glob.glob(f"/dev/shm/trims_{p.pid}_*")}

        assert owned(pb)
        holders = ta.call({"op": "dir.holders", "key": list(key)})["holders"]
        assert any(n == "b" for n, _ in holders)

        pb.send_signal(signal.SIGTERM)
        assert pb.wait(timeout=15) == 0, "SIGTERM exit must be clean"
        # withdrawn from the directory...
        holders = ta.call({"op": "dir.holders", "key": list(key)})["holders"]
        assert not any(n == "b" for n, _ in holders), holders
        # ...and every shm segment b owned is unlinked
        pa.send_signal(signal.SIGTERM)
        assert pa.wait(timeout=15) == 0
        leaked = owned(pa, pb)
        assert not leaked, f"daemons leaked shm: {leaked}"
        ta.close(); tb.close()

    def test_spawn_ready_timeout_enforced_while_blocked(self, tmp_path):
        """A child that stays alive but never prints READY (deadlocked
        during init — here: its directory RPC hangs on a socket that
        accepts and never answers) must fail at ``ready_timeout_s``, not
        block forever inside readline."""
        import socket as socketlib
        hung_path = str(tmp_path / "hungdir.sock")
        hung = socketlib.socket(socketlib.AF_UNIX)
        hung.bind(hung_path)
        hung.listen(4)
        (tmp_path / "z").mkdir(exist_ok=True)
        try:
            t0 = time.monotonic()
            with pytest.raises(TimeoutError, match="never became ready"):
                spawn_node({"name": "z", "disk_root": str(tmp_path / "z"),
                            "listen": f"unix:{tmp_path}/z-dp.sock",
                            "call_timeout_s": 120,
                            "directory": {"connect": f"unix:{hung_path}"}},
                           ready_timeout_s=2.0)
            assert time.monotonic() - t0 < 30
        finally:
            hung.close()

    def test_restart_gets_new_incarnation(self, tmp_path, register_daemon):
        key = ModelKey("jax", "m0", "1")
        pa, ia = self._spawn(tmp_path, register_daemon, "a",
                             {"directory": {"serve": True,
                                            "policy": "sharded",
                                            "n_shards": 4}})
        make_model(str(tmp_path / "b"), key, seed=1)
        pb1, ib1 = self._spawn(tmp_path, register_daemon, "b",
                               {"directory": {"connect": ia["address"]}})
        ta = SocketTransport(ia["address"])
        gen0 = ta.call({"op": "dir.generation"})["generation"]
        assert any(n == "b" for n, _ in ta.call(
            {"op": "dir.holders", "key": list(key)})["holders"])

        pb1.kill()  # crash: no withdraw, hints go stale
        pb1.wait(timeout=10)
        # restart with an EMPTY disk: re-register supersedes (new
        # incarnation), and the stale DISK hint must not survive under
        # the new incarnation
        empty = tmp_path / "b"
        for f in glob.glob(str(empty / "**" / "*.trims"), recursive=True):
            os.unlink(f)
        pb2, ib2 = self._spawn(tmp_path, register_daemon, "b",
                               {"directory": {"connect": ia["address"]}})
        gen1 = ta.call({"op": "dir.generation"})["generation"]
        assert gen1 > gen0, "restart must bump the membership generation"
        holders = ta.call({"op": "dir.holders", "key": list(key)})["holders"]
        assert not any(n == "b" for n, _ in holders), \
            f"stale hint resurrected across restart: {holders}"
        ta.close()
