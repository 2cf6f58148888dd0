"""The control (the plain reference one precision step below the stated
one, put in the program's place) fails the comparison that sound runs
pass, at toy sizes on the CPU. On the chip the same readings, at the
cells' own sizes, set the cells' limits (``chipbench/control.py``)."""
import pytest

from chipbench import control
from chipbench.tests import tiny


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3])
def test_lm_control_fails_where_the_program_passes(seed):
    cell = tiny.cell("tiny-chat")
    row = control.lm_seed(cell, seed, check_weights=True)
    assert all(row["weights_bitwise"])
    for k, lim in cell.limits.items():
        assert row["program"][k] <= lim
    for k in ("prefill_logit_err", "kv_err", "flip_margin"):
        assert row["control"][k] > cell.limits[k], k
    assert row["control"]["recovered_shown"] > 0
    assert row["control"]["recovered_miss"] == 0


def test_cnn_control_fails_where_the_program_passes():
    cell = tiny.cell("tiny-images")
    row = control.cnn_seed(cell, 4, check_weights=True)
    assert all(row["weights_bitwise"])
    for k, lim in cell.limits.items():
        assert row["program"][k] <= lim
    assert any(row["control"][k] > lim for k, lim in cell.limits.items())
