"""sweep.py's step at one rate, on the CPU at the rehearsal's size: a
light load keeps no backlog, an overload grows one."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import manifest  # noqa: E402
import rehearsal  # noqa: E402
import sweep  # noqa: E402


@pytest.fixture(scope="module")
def session():
    cell = rehearsal.lm_cell()
    lm = manifest.load_module("drivers", "lm")
    sess, eng = lm.build(cell, 2**31 + 7, lambda msg: None)
    del eng
    return lm, cell, sess


@pytest.mark.parametrize("rate,growing", [(5.0, False), (400.0, True)])
def test_backlog_grows_only_past_the_knee(session, rate, growing):
    lm, cell, sess = session
    row = sweep.sweep_rate(lm, cell, sess, rate, 2**31 + 7, 1.0)
    assert row["growing"] is growing
    assert row["arrived"] == round(rate)
    assert (row["no_first_token"] == 0) is not growing
