"""``decode_rows`` on synthetic traces: the mean ``rows`` of the
``engine.step`` spans that start inside the window, and nothing where the
program writes no such span or the run is untraced."""
import pytest

from bench import run as R
from bench import trace as T

MS = 1e6    # trace times are nanoseconds


def _view(tr):
    return R.RunView({}, {}, {}, {}, None, {}, tr,
                     None if tr is None else T.window(tr))


def _trace(rows, outside=(1, 1)):
    """A window [0, 10 s] with one ``engine.step`` per entry of ``rows``,
    and steps that start before it and after it."""
    tr = T.Trace(devices=1)
    tr.host.append(T.HostEvent(0, 10_000 * MS, "window", 0, {}))
    for i, r in enumerate(rows):
        a = (100 + 10 * i) * MS
        tr.host.append(T.HostEvent(a, a + 0.1 * MS, "engine.step", 1,
                                   {"req": i, "rows": r}))
    before, after = outside
    tr.host.append(T.HostEvent(-5 * MS, 1 * MS, "engine.step", 2,
                               {"req": 98, "rows": before}))
    tr.host.append(T.HostEvent(10_001 * MS, 10_002 * MS, "engine.step", 2,
                               {"req": 99, "rows": after}))
    tr.host.append(T.HostEvent(50 * MS, 9000 * MS, "engine.decode", 1,
                               {"req": 0, "steps": 7}))
    return tr


@pytest.mark.parametrize("rows,want", [([2, 2, 1, 2], 1.75), ([1, 1], 1.0),
                                       ([2], 2.0)])
def test_mean_rows_of_the_steps_in_the_window(rows, want):
    got = R.read_metric("decode_rows", _view(_trace(rows, outside=(2, 2))))
    assert got == pytest.approx(want)


def test_steps_outside_the_window_are_not_read():
    assert R.read_metric("decode_rows", _view(_trace([1], outside=(2, 2)))) \
        == pytest.approx(1.0)


def test_nothing_without_the_span_or_a_trace():
    # a program without ``engine.step`` (as before it was added)
    tr = _trace([], outside=(2, 2))
    tr.host = [e for e in tr.host if e.name != "engine.step"]
    assert R.read_metric("decode_rows", _view(tr)) is None
    assert R.read_metric("decode_rows", _view(None)) is None
