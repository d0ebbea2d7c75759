"""The per-layer metrics that read the program's own spans, on synthetic
traces: only spans that start inside the window count, and a cell whose
program writes no such span reports nothing."""
import pytest

from bench import run as R
from bench import trace as T

READERS = {"serving_queue_ms": "serving.queue", "engine_open_ms": "engine.open",
           "engine_params_ms": "engine.params",
           "engine_prefill_ms": "engine.prefill", "mrm_stage_ms": "mrm.stage"}
MEAN = {"serving_queue_ms", "mrm_stage_ms"}
MS = 1e6    # trace times are nanoseconds


def _view(tr):
    lo, hi = T.window(tr) if tr is not None else (None, None)
    return R.RunView({}, {}, {}, {}, None, {}, tr,
                     None if tr is None else (lo, hi))


def _synthetic(span, durations_ms, outside_ms=(500.0,)):
    """A window [0, 10 s] holding one ``span`` per duration, and spans of
    the same name that start before it or after it."""
    tr = T.Trace(devices=1)
    tr.host.append(T.HostEvent(0, 10_000 * MS, "window", 0, {}))
    for i, d in enumerate(durations_ms):
        a = (100 + 1000 * i) * MS
        tr.host.append(T.HostEvent(a, a + d * MS, span, 1, {"req": i}))
    for d in outside_ms:
        tr.host.append(T.HostEvent(-d * MS, 1 * MS, span, 2, {"req": 98}))
        tr.host.append(T.HostEvent(10_001 * MS, (10_001 + d) * MS, span, 2,
                                   {"req": 99}))
    # a span of another name in the window is not read
    tr.host.append(T.HostEvent(50 * MS, 9000 * MS, "generate", 1, {"req": 0}))
    return tr


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_takes_spans_starting_in_the_window(metric):
    tr = _synthetic(READERS[metric], [1.0, 2.0, 9.0])
    got = R.read_metric(metric, _view(tr))
    want = 4.0 if metric in MEAN else 2.0
    assert got == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_finds_nothing_without_its_span(metric):
    # a program without the span (as before it was added), and an
    # untraced run
    tr = _synthetic("generate", [1.0, 2.0])
    assert R.read_metric(metric, _view(tr)) is None
    assert R.read_metric(metric, _view(None)) is None


def test_stage_reader_is_none_where_every_open_is_a_device_hit():
    tr = _synthetic("engine.open", [0.2, 0.3], outside_ms=())
    assert R.read_metric("mrm_stage_ms", _view(tr)) is None
    assert R.read_metric("engine_open_ms", _view(tr)) == pytest.approx(0.25)
