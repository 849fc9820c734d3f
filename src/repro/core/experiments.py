"""The paper's three experiments (§3.4), as thin harnesses over the engine.

* Experiment 1 — random search over an instance box: abundance + severity.
* Experiment 2 — axis-aligned line traversal around found anomalies: region
  thickness per dimension.
* Experiment 3 — predict anomalies from *isolated* kernel benchmarks
  (additive model), confusion matrix vs measured ground truth.

All measurement goes through :func:`repro.core.sweep.sweep` — the one
measurement path shared with grid sweeps and the benchmarks — so every
experiment can shard across workers (``backend``/``shards``/
``runner_factory``) and stream results into a persistent
:class:`~repro.core.sweep.AnomalyAtlas` (``atlas=``), making repeated runs
resume instead of re-measure. Experiment 3's isolated kernel benchmarks are
deduplicated and fed through the calibration cache
(:mod:`repro.core.profile_store`).

Scaled-down defaults: the paper used boxes up to 1200 with 10–23k samples on
a 10-core Xeon with MKL; the benchmarks here default to smaller boxes and
sample counts to finish in CI time, with flags to run the full study.

The expression specs (:data:`MATRIX_CHAIN_ABCD`, :data:`GRAM_AATB` and
the rest of the registry in :mod:`repro.core.expressions`),
:class:`Instance` and :func:`measure_instance` are re-exported here for
backwards compatibility; every harness takes *any* registered
:class:`ExpressionSpec`, so the zoo families run through Experiments 1–3
unchanged.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .anomaly import Classification, ConfusionMatrix, RegionScan, scan_line
from .backends import make_backend
from .perfmodel import TableProfile
from .runners import BlasRunner
from .sweep import (
    GRAM_AATB,
    MATRIX_CHAIN_ABCD,
    REGISTRY,
    AnomalyAtlas,
    ExpressionSpec,
    Instance,
    benchmark_unique_calls,
    collect_unique_calls,
    get_spec,
    measure_instance,
    process_pool,
    registered_names,
    sweep,
)

__all__ = [
    "ExpressionSpec", "Instance", "measure_instance",
    "MATRIX_CHAIN_ABCD", "GRAM_AATB", "REGISTRY", "get_spec",
    "registered_names",
    "Experiment1Result", "Experiment2Result", "Experiment3Result",
    "experiment1_random_search", "experiment2_regions",
    "experiment3_predict_from_benchmarks",
]


@dataclasses.dataclass
class Experiment1Result:
    spec_name: str
    samples: int
    anomalies: List[Instance]
    wall_s: float

    @property
    def abundance(self) -> float:
        return len(self.anomalies) / self.samples if self.samples else 0.0


def experiment1_random_search(
    spec: ExpressionSpec,
    runner: Optional[BlasRunner] = None,
    box: Tuple[int, int] = (20, 1200),
    n_anomalies: int = 20,
    max_samples: int = 2000,
    threshold: float = 0.10,
    seed: int = 0,
    verbose: bool = False,
    atlas: Optional[AnomalyAtlas] = None,
    backend: str = "serial",
    shards: Optional[int] = None,
    runner_factory: Optional[Callable[[], object]] = None,
    batch: int = 25,
    exec_backend: Optional[str] = None,
) -> Experiment1Result:
    """Paper §3.4.1: sample instances u.a.r. until n anomalies are found.

    Sampling proceeds in batches of ``batch`` points so the engine can
    shard each batch across workers; the search stops at the end of the
    batch that reaches ``n_anomalies`` (it may slightly overshoot). Points
    already in ``atlas`` count as samples but are served from disk. With
    ``backend="process"`` one worker pool serves the entire search;
    ``runner`` configures only the serial backend — sharded backends build
    their workers from ``runner_factory``. ``exec_backend`` names a
    :mod:`repro.core.backends` registry entry to build default workers
    from (so the harness runs unchanged on blas/numpy/jax/pallas).
    """
    rng = np.random.default_rng(seed)
    if runner is not None and backend != "serial":
        # same guard sweep() enforces: a runner's protocol (reps, cache
        # flushing) must not be silently swapped for worker defaults
        raise ValueError(
            f"runner= only configures the serial backend; backend="
            f"{backend!r} builds workers from runner_factory")
    if runner is None and runner_factory is None and backend == "serial":
        # one instance (and so one flush buffer) for the whole search
        runner = make_backend(exec_backend) if exec_backend \
            else BlasRunner()
    executor = None
    if backend == "process":
        executor = process_pool(shards or os.cpu_count() or 1)
    found: List[Instance] = []
    samples = 0
    wall = 0.0
    try:
        while len(found) < n_anomalies and samples < max_samples:
            n = min(batch, max_samples - samples)
            pts = [tuple(int(x) for x in
                         rng.integers(box[0], box[1] + 1, size=spec.ndims))
                   for _ in range(n)]
            res = sweep(spec, pts, runner=runner,
                        runner_factory=runner_factory, threshold=threshold,
                        backend=backend, shards=shards, atlas=atlas,
                        executor=executor, exec_backend=exec_backend)
            samples += res.n_points
            wall += res.wall_s
            for inst in res.records:
                if inst.cls.is_anomaly:
                    found.append(inst)
                    if verbose:
                        print(f"  anomaly #{len(found)} at {inst.point} "
                              f"ts={inst.cls.time_score:.1%} "
                              f"fs={inst.cls.flop_score:.1%}")
    finally:
        if executor is not None:
            executor.shutdown()
    return Experiment1Result(spec.name, samples, found, wall)


@dataclasses.dataclass
class Experiment2Result:
    spec_name: str
    scans: List[RegionScan]
    # All classified points, reusable by Experiment 3:
    classified: Dict[Tuple[int, ...], Instance]


def experiment2_regions(
    spec: ExpressionSpec,
    runner: Optional[BlasRunner] = None,
    anomalies: Sequence[Instance] = (),
    box: Tuple[int, int] = (20, 1200),
    step: int = 10,
    threshold: float = 0.05,
    atlas: Optional[AnomalyAtlas] = None,
    exec_backend: Optional[str] = None,
) -> Experiment2Result:
    """Paper §3.4.2: intersect regions with axis-aligned lines.

    Line traversal is inherently sequential (each probe decides the next),
    so this harness probes point by point with the engine's measurement
    primitive; with an ``atlas`` every probe is served from / buffered
    into it (chunk-flushed by the atlas, once more on return), so repeat
    traversals resume. ``exec_backend`` names the registry backend to
    probe with when no ``runner`` is given.
    """
    if runner is None:
        # one instance (one flush buffer) for every probe
        runner = make_backend(exec_backend) if exec_backend \
            else BlasRunner()
    classified: Dict[Tuple[int, ...], Instance] = {}

    def classify_at(point: Tuple[int, ...]) -> Classification:
        if point not in classified:
            hit = atlas.get(point) if atlas is not None else None
            if hit is None:
                hit = measure_instance(spec, point, runner, threshold)
                if atlas is not None:
                    atlas.append(hit)  # buffered: fsync per chunk, not probe
            classified[point] = hit
        return classified[point].cls

    scans: List[RegionScan] = []
    try:
        for inst in anomalies:
            for dim in range(spec.ndims):
                scans.append(scan_line(
                    classify_at, inst.point, dim, box[0], box[1],
                    step=step))
    finally:
        if atlas is not None:
            atlas.flush()
    return Experiment2Result(spec.name, scans, classified)


@dataclasses.dataclass
class Experiment3Result:
    spec_name: str
    confusion: ConfusionMatrix
    profile: TableProfile
    n_calls_measured: int = 0
    n_calls_reused: int = 0


def experiment3_predict_from_benchmarks(
    spec: ExpressionSpec,
    runner,
    classified: Dict[Tuple[int, ...], Instance],
    threshold: float = 0.05,
    peak_flops: float = 1e11,
    profile: Optional[TableProfile] = None,
) -> Experiment3Result:
    """Paper §3.4.3: benchmark each distinct kernel call in isolation, then
    predict each instance's fastest/cheapest sets from the additive model and
    compare against measured ground truth.

    ``runner`` is an execution-backend instance, or a registry name
    (``"blas"``/``"jax"``/…) resolved through the backend registry — the
    prediction pipeline is backend-generic.

    The distinct-call set is collected across *all* instances up front and
    deduplicated (:func:`~repro.core.sweep.benchmark_unique_calls`), so
    each (kind, dims) is timed at most once per machine. Pass a persisted
    ``profile`` (see :mod:`repro.core.profile_store`) to reuse prior
    calibrations: only calls it lacks are measured, and the entries added
    here flow back to the caller through the result.

    Scoring is a thin configuration of the discriminant scoreboard
    (:func:`repro.core.evaluate.evaluate_discriminants`): the experiment
    *is* "evaluate the ``perfmodel`` discriminant, armed with the benched
    table, against measured ground truth" — the confusion matrix returned
    here is that evaluation's, so the paper harness and ``--mode
    evaluate`` can never disagree about what recall/precision mean.
    """
    from .evaluate import evaluate_discriminants

    if isinstance(runner, str):
        runner = make_backend(runner)
    if profile is None:
        profile = TableProfile(peak_flops=peak_flops)

    # 1. Benchmark the deduplicated call set (batched; reuses the cache).
    calls = collect_unique_calls(spec, classified)
    profile, n_meas, n_reused = benchmark_unique_calls(
        runner, calls, profile=profile)

    # 2. Score the additive model through the shared evaluation path.
    res = evaluate_discriminants(
        spec, list(classified.values()), ["perfmodel"],
        profile=profile, threshold=threshold, dtype_bytes=8)
    cm = res.scores["perfmodel"].confusion

    return Experiment3Result(spec.name, cm, profile,
                             n_calls_measured=n_meas,
                             n_calls_reused=n_reused)
