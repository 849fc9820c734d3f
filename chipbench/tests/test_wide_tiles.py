"""The ``repro.tiles.shaped`` counter and ``wide_tiles.eval``, which reads
it, on the CPU under a profiler session.

The counter adds one for each kernel dispatch whose tiles the Pallas
backend chose from the shape with an edge over 128; the metric reads it
against the step walker's kernel calls in a traced plan run.
"""

import jax
import numpy as np
import pytest

from conftest import run_json


@pytest.fixture
def fixed_trace(monkeypatch, tmp_path):
    """A fixed trace summary, no tuning table to load, and the program's
    span table emptied before and after the run."""
    from chipbench import trace
    from repro.runtime import tracing

    monkeypatch.setenv("REPRO_PROFILE_DIR", str(tmp_path / "profiles"))
    monkeypatch.setattr(trace, "reduce", lambda path: trace.TraceSummary(
        window_s=1.0, busy_s=0.25, devices=1, op_s={"k": 0.25},
        gap_s={"wait": 0.75}))
    tracing.reset()
    yield tracing
    tracing.reset()


@pytest.mark.parametrize("tuning,shaped", [("auto", 2), (None, 0)])
def test_the_counter_counts_wide_tile_dispatches(tuning, shaped, tmp_path,
                                                 fixed_trace):
    from repro.core.backends.jax_backend import PallasBackend

    ops = PallasBackend(reps=1, tuning=tuning).ops()
    wide = np.ones((256, 256), np.float32)     # 256-edge tiles
    narrow = np.ones((128, 384), np.float32)   # 128 on every edge
    jax.block_until_ready((ops.syrk(wide), ops.syrk(narrow)))
    ops.gemm(wide, wide)
    assert fixed_trace.snapshot() == {}        # no session, no count
    with jax.profiler.trace(str(tmp_path)):
        for a in (wide, narrow):
            jax.block_until_ready(ops.syrk(a))
        jax.block_until_ready(ops.gemm(wide, wide))
    assert fixed_trace.snapshot().get("repro.tiles.shaped", 0) == shaped


def test_wide_tiles_eval_reads_the_counter(bench_root, capsys, fixed_trace):
    """``cpu.plan``'s (130, 30, 50) slot rounds to 256 rows, its
    (40, 60, 80) slots to 128 on every edge."""
    from chipbench import spans

    res = run_json(bench_root, "cpu.plan", capsys, trace=1)
    assert res["correct"]
    snap = fixed_trace.snapshot()
    steps = sum(spans.count(snap, s) for s in spans.COMPUTE_STEPS)
    share = res["metrics"]["wide_tiles.eval"]["value"]
    assert share == pytest.approx(snap["repro.tiles.shaped"] / steps * 100)
    assert 0 < share < 100


def test_wide_tiles_eval_reads_zero_under_the_kill_switch(
        bench_root, capsys, fixed_trace, monkeypatch):
    monkeypatch.setenv("REPRO_NO_TUNING", "1")
    res = run_json(bench_root, "cpu.plan", capsys, trace=1)
    assert res["correct"]
    assert "repro.tiles.shaped" not in fixed_trace.snapshot()
    assert res["metrics"]["wide_tiles.eval"]["value"] == 0


def test_a_program_without_the_shape_rule_leaves_the_metric_out(
        bench_root, capsys, fixed_trace, monkeypatch):
    import repro.core.tuning

    monkeypatch.delattr(repro.core.tuning, "shape_config")
    res = run_json(bench_root, "cpu.plan", capsys, trace=1)
    assert res["correct"] and "kernel_us.eval" in res["metrics"]
    assert "wide_tiles.eval" not in res["metrics"]
