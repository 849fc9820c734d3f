"""Shared fixtures: a copy of the benchmark in a temporary checkout, with
tiny cells added as new files and entries, run here on the CPU."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: Stand-in peaks for the CPU, so that the rest of a run can be driven here.
CPU_PEAKS = {"devices": {"cpu": {"flops_per_s": {"bfloat16": 1e12},
                                 "hbm_bytes_per_s": 1e11}}}

TINY_PLAN = {"kind": "plan",
             "instances": [{"dims": [40, 60, 80], "count": 2},
                           {"dims": [130, 30, 50]}]}
TINY_SWEEP = {"kind": "sweep", "reps": 1, "threshold": 0.10,
              "points": [[40, 30, 50, 20, 60], [33, 70, 20, 45, 28]]}


def add_cell(root: Path, name: str, config: str, traffic_name: str,
             traffic: dict, limits: dict) -> None:
    """Add a cell as new files and a new entry, and list it with the
    metrics of its traffic kind."""
    here = root / "chipbench"
    (here / "traffic" / f"{traffic_name}.json").write_text(json.dumps(traffic))
    (here / "limits" / f"{name}.json").write_text(json.dumps(limits))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic_name, "chips": 1,
                               "why": "tiny CPU cell"})
    like = "aatb.ns_widths" if traffic["kind"] == "plan" else "abcd.sweep_box"
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


@pytest.fixture
def bench_root(tmp_path):
    """A checkout holding only the benchmark, plus tiny ``cpu.plan`` and
    ``cpu.sweep`` cells."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    add_cell(tmp_path, "cpu.plan", "aatb-pallas-bf16", "cpu_plan", TINY_PLAN,
             {"max_rel_gap": 0.02})
    add_cell(tmp_path, "cpu.sweep", "abcd-pallas-bf16", "cpu_sweep",
             TINY_SWEEP, {"max_rel_gap": 0.02, "records_wrong": 0})
    return tmp_path


def run_json(root: Path, cell: str, capsys, seed: int = 2**31 + 7,
             trace: int = 0, seconds: float = 0.5) -> dict:
    """Drive a whole run here, skipping the look for a chip; its result."""
    from chipbench import harness

    rc = harness.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)], root=root,
                      require_tpu=False, peaks=CPU_PEAKS)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])
