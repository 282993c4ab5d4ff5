"""The bfloat16 control, put in the program's place, comes out as not
correct, while the program's own answers of the same run are correct
(tiny size on the CPU; bench/control.py takes the same readings on the
chip at each cell's own size)."""

import pytest

from bench import control, run


@pytest.mark.parametrize("cell", ["pavlo-scan", "sqlml-logreg"])
def test_control_fails_where_the_program_passes(cell):
    result, checks = run.measure(cell, 5, 1.0, False, rehearsal=True,
                                 control=control.picker(2))
    assert result["correct"], checks
    ctl = result["control"]
    assert any(c["value"] > c["limit"] for c in ctl.values()), ctl
