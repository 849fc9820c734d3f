"""The harness: find a cell, set it up, measure it, check it, print one line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own under this directory, found by the name that
``BENCHMARK.json`` gives it:

* ``configs/<config>.json``       what is run (expression, backend, dtype);
* ``expressions/<expression>.py`` its plain reference and closed-form work;
* ``traffic/<traffic>.json``      the traffic mix, read by the driver its
  ``kind`` names: ``drivers/<kind>.py``;
* ``limits/<cell>.json``          the limit of each number ``correct``
  compares;
* ``metrics/<metric>.py``         one reader per metric, ``read(record)``,
  which returns ``None`` where the run has nothing for it to read.

A new cell, configuration or metric is therefore new files and new entries
in ``BENCHMARK.json``; no file here needs an edit.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from chipbench import trace as tracemod

ROOT = Path(__file__).resolve().parents[1]

#: Length of the traced window of a ``--trace 1`` run, at most: a short
#: window keeps the trace small and its reading quick.
TRACE_SECONDS = 4.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


@dataclasses.dataclass
class Record:
    """What one measured window did; the metric readers read this."""

    kind: str                 # the traffic kind: "plan" | "sweep"
    window_s: float           # host clock, first call to last result
    completed: int            # evaluations (plan) or points (sweep)
    failed: int = 0           # of those, the ones that raised
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    host_s: List[float] = dataclasses.field(default_factory=list)
    required_flops: float = 0.0   # least FLOP count, summed over the work
    least_time_s: float = 0.0     # roofline time of that work, summed
    peak_flops: float = 0.0
    setup_s: float = 0.0
    trace: Optional[tracemod.TraceSummary] = None
    #: Planner regret, measured on demand after the window (plan only).
    regret: Optional[Callable[[], Optional[float]]] = None


@dataclasses.dataclass
class Check:
    """One number ``correct`` compares, with its limit (pass: value ≤ limit)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def load_module(root: Path, group: str, name: str):
    """Import ``<root>/chipbench/<group>/<name>.py`` by its path."""
    path = root / "chipbench" / group / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{group}_{name}".replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {group[:-1]} {name!r}: {path} is missing")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    cfg_file = {c["name"]: c["file"] for c in bench["configs"]}[w["config"]]
    here = root / "chipbench"

    def applies(metric: dict) -> bool:
        return name in metric.get("workloads", [name])

    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=json.loads((root / cfg_file).read_text()),
        traffic=json.loads(
            (here / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((here / "limits" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)],
    )


def load_peaks(root: Path = ROOT) -> dict:
    return json.loads((root / "chipbench" / "peaks.json").read_text())


def chip_peaks(peaks: dict, device_kind: str, dtype: str) -> tuple:
    """(FLOP/s, HBM bytes/s) of one chip; an unknown kind is an error."""
    if device_kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(peaks['devices'])}")
    entry = peaks["devices"][device_kind]
    if dtype not in entry["flops_per_s"]:
        raise KeyError(f"no {dtype} peak for {device_kind!r}")
    return float(entry["flops_per_s"][dtype]), float(entry["hbm_bytes_per_s"])


def configure_compile_cache() -> None:
    """The program's own rule for the cache directory
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), with
    every program cached however quickly it compiled, so that only the
    first run of a cell in a checkout compiles."""
    import jax
    from repro.runtime.compile_cache import configure_compilation_cache

    configure_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@contextlib.contextmanager
def fresh_program_dirs():
    """Point the program's profile and atlas caches at empty directories.

    No calibration or atlas left by an earlier run can then change a pick
    or skip a point.
    """
    keys = ("REPRO_PROFILE_DIR", "REPRO_ATLAS_DIR")
    old = {k: os.environ.get(k) for k in keys}
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        for k in keys:
            os.environ[k] = os.path.join(tmp, k.lower())
            os.makedirs(os.environ[k])
        try:
            yield
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0: not reported)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def open_session(cell: Cell, seed: int, devices, peaks: dict,
                 root: Path = ROOT, control: bool = False):
    """The cell's driver session (not yet set up)."""
    expr = load_module(root, "expressions", cell.config["expression"])
    driver = load_module(root, "drivers", cell.traffic["kind"])
    flops, bw = chip_peaks(peaks, devices[0].device_kind,
                           cell.config["dtype"])
    return driver.Session(cell, expr, seed, devices, flops, bw,
                          control=control)


def chips_or_raise(chips: int, require_tpu: bool = True) -> list:
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             root: Path = ROOT, require_tpu: bool = True,
             peaks: Optional[dict] = None) -> dict:
    """Set up, measure, check; the result object (printed last by main)."""
    devices = chips_or_raise(cell.chips, require_tpu)
    configure_compile_cache()
    session = open_session(cell, seed, devices, peaks or load_peaks(root),
                           root)
    session.setup()
    setup_s = time.perf_counter() - t0

    trace_dir = root / ".chipbench" / "trace" / cell.name if trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    rec = session.window(min(seconds, TRACE_SECONDS) if trace else seconds,
                         trace_dir)
    rec.setup_s = setup_s
    memory_peak = memory_peak_bytes(devices)
    if trace_dir is not None:
        rec.trace = tracemod.reduce(tracemod.find_xplane(trace_dir))

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = load_module(root, "metrics", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    session.release()
    checks, wrong = session.check(cell.limits)
    failed = rec.failed + wrong
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": failed == 0 and bool(checks) and all(c.ok for c in checks),
        "attempted": rec.completed + rec.failed,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        result["breakdown"] = rec.trace.breakdown()
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    return result


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="chipbench/run.py",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t0: Optional[float] = None, root: Path = ROOT,
         require_tpu: bool = True, peaks: Optional[dict] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    cell = load_cell(args.workload, root)
    try:
        with fresh_program_dirs():
            result = run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t0, root, require_tpu, peaks)
    except NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
