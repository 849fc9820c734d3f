"""Sweep-engine throughput: serial vs sharded grid sweeps (instances/sec),
plus adaptive-vs-dense budget efficiency on planted ground truth.

Measures the engine itself, not the kernels: a fixed AAᵀB grid is swept
once serially and once over a process pool, with cache flushing off and
reps=1 so the denominator is engine + dispatch overhead rather than BLAS
time. Derived fields report instances/sec and the sharded speedup; the
atlas write path is exercised in a throwaway directory so persistence cost
is included.

The adaptive rows sweep the planted masks of :mod:`repro.core.synthetic`
(ground truth known by construction) and report frontier recall and
measurement savings against the dense grid — the quantities the
``adaptive-smoke`` CI job gates on (recall ≥ 90 %, savings > 0).

REPRO_BENCH_SCALE=full uses a denser grid and more shards.
"""

from __future__ import annotations

import functools
import os
import tempfile
from pathlib import Path

from repro.core import BlasRunner
from repro.core.adaptive import adaptive_sweep
from repro.core.profile_store import current_fingerprint
from repro.core.sweep import GRAM_AATB, AnomalyAtlas, GridSpec, sweep
from repro.core.synthetic import (
    MaskRunner,
    PlantedSpec,
    frontier_recall,
    planted_masks,
    true_frontier,
)

from .common import FULL, emit, note


def _run(points, backend, shards, factory, atlas_dir):
    atlas = AnomalyAtlas.open(
        GRAM_AATB.name, current_fingerprint(), threshold=0.10,
        directory=Path(atlas_dir) / f"{backend}{shards or 0}")
    if backend == "serial":
        res = sweep(GRAM_AATB, points, runner=factory(), atlas=atlas)
    else:
        res = sweep(GRAM_AATB, points, backend=backend, shards=shards,
                    runner_factory=factory, atlas=atlas)
    atlas.flush()
    return res


def main():
    values = (32, 64, 96, 128) if FULL else (32, 64, 96)
    shards = min(8 if FULL else 2, os.cpu_count() or 1)
    grid = GridSpec.uniform(values, GRAM_AATB.ndims, name="bench")
    points = grid.points()
    factory = functools.partial(BlasRunner, reps=1, flush_cache=False)

    note(f"\n== sweep engine: {len(points)} AAᵀB instances, "
         f"{shards} shards ==")
    with tempfile.TemporaryDirectory() as atlas_dir:
        serial = _run(points, "serial", None, factory, atlas_dir)
        sharded = _run(points, "process", shards, factory, atlas_dir)

    note(f"serial : {serial.instances_per_s:8.1f} inst/s "
         f"({serial.wall_s:.2f}s)")
    note(f"sharded: {sharded.instances_per_s:8.1f} inst/s "
         f"({sharded.wall_s:.2f}s, {shards} procs)")
    speedup = (sharded.instances_per_s / serial.instances_per_s
               if serial.instances_per_s else 0.0)
    note(f"speedup: {speedup:.2f}x")

    emit("sweep_serial", serial.wall_s * 1e6 / max(1, serial.n_measured),
         f"inst_per_s={serial.instances_per_s:.2f};n={serial.n_measured}")
    emit("sweep_sharded", sharded.wall_s * 1e6 / max(1, sharded.n_measured),
         f"inst_per_s={sharded.instances_per_s:.2f};"
         f"shards={shards};speedup={speedup:.2f}")

    adaptive_vs_dense()


def adaptive_vs_dense():
    """Adaptive boundary refinement vs the dense grid, per planted mask."""
    n = 30 if FULL else 20
    spec = PlantedSpec()
    grid = GridSpec.uniform(tuple(range(10, 10 * n + 10, 10)), spec.ndims,
                            name=f"planted{n}")
    budget = int(0.40 * grid.n_points)
    note(f"\n== adaptive vs dense: {grid.n_points}-point planted grid, "
         f"budget {budget} (40%) ==")
    recalls = []
    for name, mask in sorted(planted_masks(grid).items()):
        res = adaptive_sweep(spec, grid, budget, runner=MaskRunner(mask))
        recall = frontier_recall(res.known, true_frontier(mask, grid))
        savings = 1.0 - res.n_measured / grid.n_points
        recalls.append(recall)
        note(f"{name:8s}: recall={recall:6.1%} "
             f"measured={res.n_measured}/{grid.n_points} "
             f"(savings {savings:.1%}) rounds={res.n_refine_rounds} "
             f"stopped={res.stopped}")
        emit(f"adaptive_recall_{name}", 100.0 * recall,
             f"unit=percent;measured={res.n_measured};"
             f"dense={grid.n_points};savings_pct={100 * savings:.1f};"
             f"rounds={res.n_refine_rounds};stopped={res.stopped}")
    emit("adaptive_frontier_recall", 100.0 * min(recalls),
         f"unit=percent;masks={len(recalls)};budget_pct=40.0")


if __name__ == "__main__":
    main()
