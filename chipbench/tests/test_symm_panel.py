"""The ``repro.symm.panel_cols`` counter and ``symm_panel_cols.eval``,
which reads it, on the CPU under a profiler session.

The counter adds, for each symm or symm_r dispatch of the Pallas backend,
the output columns that one fetch of an S tile serves in the kernel; the
metric reads it per symm call of the step walker in a traced plan run.
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


def test_the_counter_adds_each_symm_calls_panel(tmp_path, fixed_trace):
    from repro.core.backends.jax_backend import PallasBackend
    from repro.kernels import ops as kops

    ops = PallasBackend(reps=1).ops()
    s = np.tril(np.ones((512, 512), np.float32))
    b = np.ones((512, 1536), np.float32)
    jax.block_until_ready((ops.symm(s, b), ops.symm_r(b.T, s)))
    assert fixed_trace.snapshot() == {}        # no session, no count
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(ops.symm(s, b))
        jax.block_until_ready(ops.symm_r(b.T, s))
    cfg = ops._cfg("symm", (512, 1536))
    panel = kops.symm_panel_cols(1536, np.float32, **cfg)
    assert panel == 1536                       # n inside one panel
    assert fixed_trace.snapshot()["repro.symm.panel_cols"] == 2 * panel


def test_symm_panel_cols_eval_reads_the_counter(bench_root, capsys,
                                                fixed_trace):
    """``cpu.plan``'s symm calls pad n to one 128-column block."""
    from chipbench import spans

    res = run_json(bench_root, "cpu.plan", capsys, trace=1)
    assert res["correct"]
    snap = fixed_trace.snapshot()
    calls = spans.count(snap, "step.symm") + spans.count(snap, "step.symm_r")
    assert calls
    cols = res["metrics"]["symm_panel_cols.eval"]["value"]
    assert cols == pytest.approx(snap["repro.symm.panel_cols"] / calls)
    assert cols == 128


def test_a_program_without_the_counter_leaves_the_metric_out(
        bench_root, capsys, fixed_trace, monkeypatch):
    from repro.core.backends import jax_backend

    monkeypatch.setattr(jax_backend, "_count_panel", lambda *a: None)
    res = run_json(bench_root, "cpu.plan", capsys, trace=1)
    assert res["correct"] and "kernel_us.eval" in res["metrics"]
    assert "repro.symm.panel_cols" not in fixed_trace.snapshot()
    assert "symm_panel_cols.eval" not in res["metrics"]
