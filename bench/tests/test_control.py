"""The float8 control fails the limit that sound runs pass, at a CPU size.

On the chip, ``bench/control.py`` reads both numbers at each cell's own size;
this keeps the comparison itself honest where a test run can hold it."""
import numpy as np
import pytest

from bench import harness
from bench.tests import tiny


@pytest.mark.parametrize("seed", [1, 2, 2 ** 33 + 7])
def test_control_fails_where_program_passes(seed):
    c = harness.Cell(tiny.CONFIG, tiny.TRAFFIC, seed, log=lambda m: None)
    try:
        c.setup()
        win = c.run_window(2.0)
        done = [s for s in win.requests if s.error is None]
        picked = c.sample(done, 6)
        c.release()
        g = c.logit_gaps(picked, control=True)
    finally:
        c.close()
    limit = tiny.CONFIG["check"]["logit_gap_limit"]
    assert g["tokens"] > 20
    assert g["gap"] <= limit < g["control_gap"]
