"""Shared test fixtures: multi-process hygiene (DESIGN.md §11).

The noded/daemon suites spawn real subprocesses that own ``trims_*``
POSIX shm segments and unix sockets. A test that dies mid-flight must
not leak either into the next test (or the next CI run), and a wedged
daemon must fail the test instead of hanging the whole session — the
container has no pytest-timeout, so the hard stop is a ``signal.alarm``
armed around ``proc``-marked tests.
"""
from __future__ import annotations

import glob
import os
import signal

import pytest

PROC_TIMEOUT_S = 120


@pytest.fixture
def register_daemon():
    """Collect spawned daemon Popens; the reaper below kills any a test
    leaves behind (even on assertion failure mid-test)."""
    procs = []

    def _register(proc):
        procs.append(proc)
        return proc

    _register.procs = procs
    yield _register


def _segments(pids):
    """The ``trims_<pid>_*`` shm segments the processes ``pids`` created."""
    return {path for pid in pids
            for path in glob.glob(f"/dev/shm/trims_{pid}_*")}


@pytest.fixture(autouse=True)
def _reap_daemons_and_shm(request):
    """Kill leftover daemons and unlink orphaned trims_* shm segments.

    Only segments created DURING the test, by this test process or a
    daemon it registered, are reaped: another worker's segments (a
    different process, same /dev/shm) are left alone."""
    before = _segments([os.getpid()])
    reg = (request.getfixturevalue("register_daemon")
           if "register_daemon" in request.fixturenames else None)
    yield
    if reg is not None:
        for p in reg.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in reg.procs:
            try:
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001 — SIGTERM ignored: force it
                p.kill()
                p.wait(timeout=10)
    pids = [os.getpid()] + ([p.pid for p in reg.procs] if reg else [])
    for path in _segments(pids) - before:
        try:
            os.unlink(path)
        except OSError:
            pass


@pytest.fixture(autouse=True)
def _proc_hard_timeout(request):
    """Hard wall-clock stop for ``proc``-marked tests: a daemon that
    wedges (deadlocked socket, ignored SIGTERM) raises in the test
    instead of stalling the session forever."""
    if request.node.get_closest_marker("proc") is None:
        yield
        return

    def _boom(signum, frame):
        raise TimeoutError(
            f"proc test exceeded {PROC_TIMEOUT_S}s hard timeout")

    old = signal.signal(signal.SIGALRM, _boom)
    signal.alarm(PROC_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
