"""The reader of the held experts an MoE decode step read
(``bench/metrics/moe_experts_read.py``), over ``engine.decode`` spans."""
import pytest

from bench import run
from bench.tests.test_arch_mla_moe import _decode_spans, _view


def test_experts_read_reader():
    tr = _decode_spans([{"experts_touched": 1.25, "experts_read": 1.25},
                        {"experts_touched": 1.75, "experts_read": 16.0},
                        {"steps": 4}])
    assert run.read_metric("moe_experts_read", _view(tr)) == pytest.approx(8.625)
    # a program that does not count reads (the spans carry only
    # experts_touched), and an untraced run, give nothing
    assert run.read_metric("moe_experts_read", _view(_decode_spans(
        [{"experts_touched": 1.5, "held_share": 0.25}]))) is None
    assert run.read_metric("moe_experts_read", _view(None)) is None
