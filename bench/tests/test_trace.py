"""The reduction from a profiler trace to the per-layer metrics, on interval
sets and on a small trace recorded on a TPU v5e (``data/``)."""
from pathlib import Path

import pytest

from bench import flops as F
from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "olmo-1s.xplane.pb"


def test_interval_algebra():
    u = T.union([(5, 7), (0, 2), (1, 3), (10, 12)], lo=0.5, hi=11)
    assert u == [(0.5, 3), (5, 7), (10, 11)]
    assert T.length(u) == 2.5 + 2 + 1
    assert T.intersect(u, [(2, 6), (6.5, 10.5)]) == [(2, 3), (5, 6), (6.5, 7),
                                                      (10, 10.5)]
    assert T.complement(u, 0, 12) == [(0, 0.5), (3, 5), (7, 10), (11, 12)]


def _synthetic():
    tr = T.Trace(devices=1)
    # request 0: generate over [0, 100]; prefill on the device [10, 30],
    # two decode steps [40, 50] and [60, 70]; one other program [80, 85]
    tr.host.append(T.HostEvent(0, 100, "generate", 1, {"req": 0}))
    tr.host.append(T.HostEvent(-5, 200, "window", 0, {}))
    tr.host.append(T.HostEvent(30, 40, "np.asarray(jax.Array)", 1, {}))
    for i, (a, b) in enumerate([(10, 30), (40, 50), (60, 70)]):
        tr.executions.append(T.Execution(a, b, T.ENGINE_MODULE, "p", i, req=0,
                                         ordinal=i))
        tr.ops.append((a, b, f"%fusion.{i} = bf16[2]"))
    tr.executions.append(T.Execution(80, 85, "jit_argmax", "q", 9))
    tr.ops.append((80, 85, "%reduce.1 = s32[1]"))
    return tr


def test_busy_idle_and_breakdown_on_a_synthetic_trace():
    tr = _synthetic()
    lo, hi = T.window(tr)
    assert (lo, hi) == (-5, 200)
    assert [e.kind for e in tr.executions] == ["prefill", "decode", "decode",
                                               "other"]
    service = T.in_service(tr, lo, hi)
    assert service == [(0, 100)]
    busy = T.intersect(T.busy(tr, lo, hi), service)
    assert T.length(busy) == 20 + 10 + 10 + 5
    ops = dict((k, v) for k, v in T.top_ops(tr, lo, hi))
    assert ops["prefill:fusion.0"] == pytest.approx(20e-9)
    assert ops["other:reduce.1"] == pytest.approx(5e-9)
    gaps = dict((k, v) for k, v in T.idle_gaps(tr, lo, hi))
    # idle: [0,10] [30,40] [50,60] [70,80] [85,100]; [30,40] is the host copy
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(10e-9)
    assert sum(gaps.values()) == pytest.approx(55e-9)


@pytest.fixture(scope="module")
def recorded():
    return T.load(str(RECORDED))


def test_recorded_trace_ties_executions_to_requests(recorded):
    tr = recorded
    assert tr.devices == 1 and tr.ops and tr.executions
    lo, hi = T.window(tr)
    gens = {int(e.stats["req"]): e for e in tr.spans("generate")}
    assert gens
    per_req = {}
    for ex in tr.executions:
        if ex.req is not None:
            per_req.setdefault(ex.req, []).append(ex)
    assert per_req, "no device execution was tied to a request"
    for req, exs in per_req.items():
        g = gens[req]
        kinds = [e.kind for e in sorted(exs, key=lambda e: e.start)]
        assert kinds[0] == "prefill" and set(kinds[1:]) <= {"decode"}
        # executions run after their launch, inside or just after generate
        assert all(e.start >= g.start for e in exs)
        # a request traced whole runs one prefill and out_len - 1 decodes
        assert sorted(e.ordinal for e in exs) == list(range(len(exs)))
    # every module execution of one program is one kind
    kinds = {}
    for ex in tr.executions:
        if ex.req is not None:
            kinds.setdefault(ex.program, set()).add(ex.kind)
    assert all(len(k) == 1 for k in kinds.values())


def test_recorded_trace_metrics_are_shares(recorded):
    tr = recorded
    lo, hi = T.window(tr)
    service = T.in_service(tr, lo, hi)
    busy = T.length(T.intersect(T.busy(tr, lo, hi), service))
    assert 0 < busy <= T.length(service)
    assert T.top_ops(tr, lo, hi) and len(T.top_ops(tr, lo, hi)) <= 10
    assert len(T.idle_gaps(tr, lo, hi)) <= 10
    # a decode step cannot beat its roofline: the recorded decode programs
    # of olmo-1b take longer than weights / HBM bandwidth
    v5e = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    import json
    model = json.loads((DATA.parents[1] / "configs" / "olmo-1b.json")
                       .read_text())["model"]
    least = F.least_seconds(F.decode_flops(model, 0), F.decode_bytes(model, 0),
                            v5e)
    decodes = [e for e in tr.executions if e.kind == "decode"]
    assert decodes
    assert min((e.end - e.start) * 1e-9 for e in decodes) > least
