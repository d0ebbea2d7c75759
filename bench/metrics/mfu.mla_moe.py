"""Whole step in the cells of an MLA/MoE configuration: the ``mfu`` reader's
computation, over the counts of the cell's architecture module
(``bench/arch/mla_moe.py``: expanded attention in prefill, absorbed in
decode, the expected held experts)."""
from bench.run import read_metric


def read(run):
    return read_metric("mfu", run)
