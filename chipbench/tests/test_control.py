"""The control comes out not correct, and the program correct, at a size a
test run can hold (the chip runs ``chipbench/control.py`` at the cells'
own sizes)."""

import pytest

from conftest import CPU_PEAKS

from chipbench import control, harness


@pytest.mark.parametrize("cell", ["cpu.plan", "cpu.sweep"])
def test_control_fails_and_program_passes(bench_root, cell):
    c = harness.load_cell(cell, bench_root)
    kw = dict(root=bench_root, require_tpu=False, peaks=CPU_PEAKS)
    with harness.fresh_program_dirs():
        ctrl = control.readings(c, [5, 2**33 + 1, 77], 0.3, True, **kw)
        prog = control.readings(c, [5, 2**33 + 1, 77], 0.3, False, **kw)
    limit = c.limits["max_rel_gap"]
    assert all(r["readings"]["max_rel_gap"] > 3 * limit for r in ctrl), ctrl
    assert all(r["failed"] > 0 for r in ctrl)
    assert all(r["readings"]["max_rel_gap"] < limit / 3 for r in prog), prog
    assert all(r["failed"] == 0 for r in prog)
