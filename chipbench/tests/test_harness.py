"""Whole runs of the harness here on the CPU, at tiny sizes.

The look for a chip is skipped (``require_tpu=False``) and the CPU gets
stand-in peaks; every other step of a run is the one the chip runs.
"""

import dataclasses
import hashlib
import json
import os
import subprocess
import sys

import jax.numpy as jnp

from conftest import ROOT, run_json


def test_plan_cell_runs_and_is_correct(bench_root, capsys):
    res = run_json(bench_root, "cpu.plan", capsys)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"eval_ms", "eval_p95_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert list(res)[-1] == "checks"
    assert res["checks"]["max_rel_gap"]["value"] < 0.01


def test_sweep_cell_runs_and_is_correct(bench_root, capsys):
    res = run_json(bench_root, "cpu.sweep", capsys)
    assert res["correct"] and res["attempted"] % 2 == 0
    assert set(res["metrics"]) == {"sweep_points_per_s", "setup_s"}
    assert res["checks"]["records_wrong"]["value"] == 0


def _alter_answers(monkeypatch):
    """Alter one entry of every kernel's answer where it is produced."""
    from repro.kernels import ops

    def altered(fn):
        def wrapper(*args, **kwargs):
            out = fn(*args, **kwargs)
            return out.at[0, 0].add(0.1 * jnp.max(jnp.abs(out)))
        return wrapper

    for name in ("gemm", "syrk", "symm", "chain_gemm", "gemm_syrk"):
        monkeypatch.setattr(ops, name, altered(getattr(ops, name)))


def test_plan_cell_is_not_correct_with_answers_altered(bench_root, capsys,
                                                       monkeypatch):
    _alter_answers(monkeypatch)
    res = run_json(bench_root, "cpu.plan", capsys)
    assert not res["correct"] and res["failed"] > 0
    assert res["checks"]["max_rel_gap"]["value"] > 0.05


def test_sweep_cell_is_not_correct_with_answers_altered(bench_root, capsys,
                                                        monkeypatch):
    _alter_answers(monkeypatch)
    res = run_json(bench_root, "cpu.sweep", capsys)
    assert not res["correct"]
    assert res["checks"]["max_rel_gap"]["value"] > 0.05


def test_sweep_cell_is_not_correct_with_classes_altered(bench_root, capsys,
                                                        monkeypatch):
    import repro.core.sweep as sweep_mod

    classify = sweep_mod.classify

    def flipped(*args, **kwargs):
        c = classify(*args, **kwargs)
        return dataclasses.replace(c, is_anomaly=not c.is_anomaly)

    monkeypatch.setattr(sweep_mod, "classify", flipped)
    res = run_json(bench_root, "cpu.sweep", capsys)
    assert not res["correct"]
    assert res["checks"]["records_wrong"]["value"] == res["attempted"]


def _tree_digest(root):
    h = {}
    for p in sorted(root.rglob("*")):
        if p.is_file() and not {"__pycache__", ".jax_cache",
                                ".chipbench"} & set(p.parts):
            h[str(p.relative_to(root))] = hashlib.sha256(
                p.read_bytes()).hexdigest()
    return h


def test_new_config_cell_and_metric_are_new_files_and_entries(bench_root,
                                                               capsys,
                                                               monkeypatch):
    """A later change adds a configuration, a cell and metrics, end-to-end
    and per-layer, without editing any file the benchmark has,
    BENCHMARK.json aside. The CPU has no device plane to trace, so the
    traced run reads a fixed trace summary."""
    from chipbench import trace

    monkeypatch.setattr(trace, "reduce", lambda path: trace.TraceSummary(
        window_s=1.0, busy_s=0.25, devices=1, op_s={"k": 0.25},
        gap_s={"wait": 0.75}))
    before = _tree_digest(bench_root)
    here = bench_root / "chipbench"
    (here / "configs" / "dummy-aatb.json").write_text(json.dumps(
        {"source": "tiny CPU stand-in", "expression": "aatb",
         "backend": "pallas", "dtype": "bfloat16"}))
    (here / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"kind": "plan",
         "instances": [{"dims": [33, 20, 17], "count": 3}]}))
    (here / "limits" / "dummy.cell.json").write_text('{"max_rel_gap": 0.02}')
    (here / "metrics" / "dummy_evals_per_slot.py").write_text(
        "def read(rec):\n    return rec.completed / 3\n")
    (here / "metrics" / "dummy_busy.eval.py").write_text(
        "def read(rec):\n    return rec.trace.busy_s * 100\n")
    bench = json.loads((bench_root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy-aatb", "source": "tiny",
                             "file": "chipbench/configs/dummy-aatb.json",
                             "reduced": [], "why": "extension test"})
    bench["workloads"].append({"name": "dummy.cell", "config": "dummy-aatb",
                               "traffic": "dummy_mix", "chips": 1,
                               "why": "extension test"})
    bench["end_to_end"].append({"name": "dummy_evals_per_slot", "unit": "1",
                                "better": "higher", "bound": 0.25,
                                "source": "host_clock",
                                "workloads": ["dummy.cell"]})
    bench["per_layer"].append({"name": "dummy_busy.eval", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves":
                               "dummy_evals_per_slot",
                               "workloads": ["dummy.cell"]})
    (bench_root / "BENCHMARK.json").write_text(json.dumps(bench))

    res = run_json(bench_root, "dummy.cell", capsys)
    assert res["correct"]
    assert set(res["metrics"]) == {"dummy_evals_per_slot", "setup_s"}
    assert res["metrics"]["dummy_evals_per_slot"]["value"] > 0
    res = run_json(bench_root, "dummy.cell", capsys, trace=1)
    assert res["correct"]
    assert res["metrics"] == {"dummy_busy.eval": {"value": 25.0, "unit": "%"}}
    assert res["device"]["busy_s"] == 0.25
    assert res["breakdown"]["idle_gaps"] == [["wait", 0.75]]
    after = _tree_digest(bench_root)
    before.pop("BENCHMARK.json")
    assert {k: v for k, v in after.items() if k in before} == before


def test_without_a_chip_the_run_fails_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "aatb.paper_box",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
