"""Cross-process TrIMS: msgpack/unix-socket control plane + shm data plane.

Subprocess clients attach the MRM's shared-memory segments and validate
tensor contents — the host-tier analogue of CUDA-IPC sharing (DESIGN.md §2).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.core import DiskStore, MRM, ModelKey
from repro.core.shm_ipc import MRMServer, RemoteTrimsClient

MB = 1 << 20
ROOT = Path(__file__).resolve().parents[1]


def _tensors(nbytes=2 * MB, n=4, seed=0):
    rng = np.random.default_rng(seed)
    per = nbytes // n // 4
    return {f"w{i}": rng.standard_normal(per).astype(np.float32) for i in range(n)}


@pytest.fixture
def server(tmp_path):
    disk = DiskStore(str(tmp_path / "disk"))
    disk.put(ModelKey("jax", "shared"), _tensors(seed=7))
    mrm = MRM(disk, device_capacity=64 * MB, host_capacity=256 * MB, use_shm=True)
    srv = MRMServer(mrm, str(tmp_path / "mrm.sock"))
    yield srv
    srv.stop()
    # release host-tier shm
    for e in list(mrm.host.entries.values()):
        if e.payload is not None:
            e.payload.release()


def test_same_process_client(server):
    client = RemoteTrimsClient(server.sock_path)
    h = client.open("jax", "shared")
    expect = _tensors(seed=7)
    for k, v in expect.items():
        np.testing.assert_array_equal(h.arrays[k], v)
    assert h.timings["tier_hit"] in ("disk", "host")
    h2 = client.open("jax", "shared")
    assert h2.timings["tier_hit"] == "host"      # warm
    assert h2.timings["total_s"] < h.timings["total_s"] + 1e-3
    client.close(h)
    client.close(h2)
    stats = client.stats()
    assert stats["disk_loads"] == 1
    client.disconnect()


CLIENT_SCRIPT = textwrap.dedent("""
    import json, sys, time
    import numpy as np
    sys.path.insert(0, {src!r})
    from repro.core.shm_ipc import RemoteTrimsClient

    c = RemoteTrimsClient({sock!r})
    t0 = time.perf_counter()
    h = c.open("jax", "shared")
    open_s = time.perf_counter() - t0
    checksum = float(sum(float(np.asarray(a, np.float64).sum()) for a in h.arrays.values()))
    out = {{"checksum": checksum, "tier": h.timings["tier_hit"],
           "open_s": open_s, "attach_s": h.attach_s, "nbytes": h.nbytes}}
    c.close(h)
    c.disconnect()
    print(json.dumps(out))
""")


def test_cross_process_sharing(server, tmp_path):
    """Two OS processes open the same model: one load, shared bytes."""
    script = CLIENT_SCRIPT.format(src=str(ROOT / "src"),
                                  sock=server.sock_path)
    results = []
    for _ in range(2):
        out = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                             capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        results.append(json.loads(out.stdout.strip().splitlines()[-1]))

    expect = _tensors(seed=7)
    want = float(sum(np.asarray(a, np.float64).sum() for a in expect.values()))
    for r in results:
        assert abs(r["checksum"] - want) < 1e-3
    # exactly one deserialization served both processes
    assert server.mrm.metrics["disk_loads"] == 1
    assert results[1]["tier"] == "host"
    # after both clients closed, refcount is back to zero
    key = ModelKey("jax", "shared")
    assert server.mrm.host.peek(key).refcount == 0


def test_connection_death_releases_handles(server):
    client = RemoteTrimsClient(server.sock_path)
    h = client.open("jax", "shared")
    key = ModelKey("jax", "shared")
    assert server.mrm.host.peek(key).refcount == 1
    client.disconnect()   # no clean close
    import time
    for _ in range(50):
        if server.mrm.host.peek(key).refcount == 0:
            break
        time.sleep(0.05)
    assert server.mrm.host.peek(key).refcount == 0


def test_client_is_thread_safe(server):
    """Regression: RemoteTrimsClient shares ONE socket; unsynchronized
    threads used to interleave request/response frames and read each
    other's replies. The per-request lock must keep every thread's
    open/stats/close pairing intact under contention."""
    import threading

    client = RemoteTrimsClient(server.sock_path)
    expect = _tensors(seed=7)
    errs = []

    def worker(i):
        try:
            for _ in range(15):
                h = client.open("jax", "shared")
                assert h.timings["tier_hit"] in ("disk", "host")
                np.testing.assert_array_equal(h.arrays["w0"], expect["w0"])
                assert isinstance(client.stats()["opens"], int)
                client.close(h)
        except Exception as e:  # noqa: BLE001 — collected for the assert
            errs.append((i, e))

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[:3]
    # every handle closed: the shared entry's refcount drained to zero
    assert server.mrm.host.peek(ModelKey("jax", "shared")).refcount == 0
    client.disconnect()
