"""The per-layer metrics that read the program's own spans and counters,
in whole traced runs of the tiny CPU cells.

The CPU has no device plane to trace, so each traced run reads a fixed
trace summary, as in ``test_harness.py``; the program's spans are real.
"""

import math
import sys

import pytest

from conftest import run_json

PLAN = ("plan_us.eval", "kernel_us.eval", "move_us.eval", "compiles.eval")
SWEEP = ("call_us.sweep", "engine_ms_per_point.sweep", "compiles.sweep")


@pytest.fixture
def fixed_trace(monkeypatch):
    """A fixed trace summary, and the program's span table emptied before
    and after the run (a process runs many cells here, one on the chip)."""
    from chipbench import trace
    from repro.runtime import tracing

    monkeypatch.setattr(trace, "reduce", lambda path: trace.TraceSummary(
        window_s=1.0, busy_s=0.25, devices=1, op_s={"k": 0.25},
        gap_s={"wait": 0.75}))
    tracing.reset()
    yield
    tracing.reset()


def _values(res):
    return {k: v["value"] for k, v in res["metrics"].items()}


def test_plan_cell_reports_the_program_spans(bench_root, capsys,
                                             fixed_trace):
    res = run_json(bench_root, "cpu.plan", capsys, trace=1)
    m = _values(res)
    assert res["correct"]
    for name in PLAN:
        assert math.isfinite(m[name]), name
    assert m["compiles.eval"] == 0
    assert m["plan_us.eval"] > 0 and m["kernel_us.eval"] > 0
    assert m["move_us.eval"] >= 0
    assert (m["plan_us.eval"] + m["kernel_us.eval"] + m["move_us.eval"]
            <= m["host_us.eval"])
    assert not set(SWEEP) & set(m)


def test_sweep_cell_reports_the_program_spans(bench_root, capsys,
                                              fixed_trace):
    res = run_json(bench_root, "cpu.sweep", capsys, trace=1)
    m = _values(res)
    assert res["correct"]
    for name in SWEEP:
        assert math.isfinite(m[name]), name
    assert m["compiles.sweep"] == 0
    assert m["call_us.sweep"] > 0 and m["engine_ms_per_point.sweep"] > 0
    assert not set(PLAN) & set(m)


def test_a_program_without_spans_leaves_the_metrics_out(bench_root, capsys,
                                                        fixed_trace,
                                                        monkeypatch):
    """As the program before its spans: the readers find no tracing module
    and the traced run's line leaves their metrics out."""
    import repro.runtime

    monkeypatch.delattr(repro.runtime, "tracing")
    monkeypatch.setitem(sys.modules, "repro.runtime.tracing", None)
    for cell, names in (("cpu.plan", PLAN), ("cpu.sweep", SWEEP)):
        res = run_json(bench_root, cell, capsys, trace=1)
        assert res["correct"] and res["metrics"]
        assert not set(names) & set(res["metrics"])
