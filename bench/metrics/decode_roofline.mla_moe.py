"""Model step in the cells of an MLA/MoE configuration: the
``decode_roofline`` reader's computation, over the counts of the cell's
architecture module (``bench/arch/mla_moe.py``: absorbed attention, the
expected held experts)."""
from bench.run import read_metric


def read(run):
    return read_metric("decode_roofline", run)
