"""Calibration: measure this machine's kernel profile, once.

The paper's conclusion — FLOPs alone mispredict; combine them with kernel
performance models — needs those models to exist for *this* hardware.
:func:`calibrate` sweeps the kernel space (gemm/syrk/symm over a
log-spaced dim grid, plus tri2full) with any registered execution backend
(:mod:`repro.core.backends` — the registry key is the profile fingerprint
key), builds a measured :class:`~repro.core.perfmodel.TableProfile`, and
persists it via :mod:`repro.core.profile_store` so the cost is paid once
per machine: subsequent processes auto-load it through
``default_planner()``.

CLI::

    PYTHONPATH=src python -m repro.core.calibrate --grid small --out DIR
    PYTHONPATH=src python -m repro.core.calibrate --backend jax --grid default
    PYTHONPATH=src python -m repro.core.calibrate --backend pallas --grid small

Grids are named (small/default/full) rather than free-form so cache files
produced on different machines cover comparable shape ranges.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from pathlib import Path
from typing import Iterable, List, Optional

from .backends import backend_default_dtype, make_backend, registered_backends
from .flops import KernelCall, gemm, symm, syrk, tri2full
from .perfmodel import TableProfile
from .profile_store import (
    HardwareFingerprint,
    current_fingerprint,
    save_profile,
)

# Log-spaced (power-of-two) dim grids. "small" finishes in seconds and is
# meant for tests/smoke; "default" is the per-machine calibration;
# "full" approaches the paper's boxes (minutes of BLAS time).
GRIDS = {
    "tiny": (64, 128),
    "small": (64, 128, 256),
    "default": (32, 64, 128, 256, 512, 1024),
    "full": (32, 64, 128, 256, 512, 1024, 1536, 2048),
}


def expression_calls(spec, grid_name: str = "small") -> List[KernelCall]:
    """The deduplicated kernel-call set of one registered expression family
    over its named sweep grid — the targeted alternative to the full
    :func:`grid_calls` cross product.

    ``python -m repro.core.calibrate --expr NAME`` uses this so a machine
    can be calibrated for exactly the shapes one family's sweep will
    predict with (``--mode predict`` in :mod:`repro.core.sweep`), instead
    of paying for the whole kernel-space cross product.
    """
    from .sweep import collect_unique_calls
    return collect_unique_calls(spec, spec.grid(grid_name).points())


def grid_calls(grid: Iterable[int]) -> List[KernelCall]:
    """Every kernel call the sweep measures, in deterministic order.

    gemm covers the full (m, n, k) cross product — the aspect-ratio
    extremes are exactly where efficiency cliffs live (paper Fig. 1) —
    while syrk/symm take (m, k)/(m, n) pairs and tri2full the diagonal.
    """
    dims = sorted(set(int(d) for d in grid))
    calls: List[KernelCall] = []
    for m in dims:
        for n in dims:
            for k in dims:
                calls.append(gemm(m, n, k))
    for m in dims:
        for k in dims:
            calls.append(syrk(m, k))
    for m in dims:
        for n in dims:
            calls.append(symm(m, n))
    for m in dims:
        calls.append(tri2full(m))
    return calls


@dataclasses.dataclass
class CalibrationResult:
    profile: TableProfile
    fingerprint: HardwareFingerprint
    path: Optional[Path]      # None when persistence was disabled
    wall_s: float
    n_calls: int


def sweep_kernels(
    runner,
    grid: Iterable[int],
    reps: int = 3,
    dtype: Optional[str] = None,
    progress=None,
    calls: Optional[List[KernelCall]] = None,
) -> TableProfile:
    """Benchmark every grid call in isolation; returns the measured table.

    ``runner`` is any object with ``benchmark_call(call, reps=None) ->
    float`` — every registered :class:`~repro.core.backends
    .ExecutionBackend` qualifies, and dtype/device/flush protocol live on
    the runner instance (one signature across backends). ``dtype`` is a
    consistency guard only: if the runner declares a dtype, a mismatch
    raises rather than stamping a fingerprint the measurements don't
    match. Peak FLOP/s is estimated as the best throughput observed
    anywhere in the sweep, so ``TableProfile.efficiency`` is relative to
    this machine's own best. ``calls`` overrides the measured set (e.g.
    one expression family's deduplicated calls from
    :func:`expression_calls`); ``grid`` is ignored then.
    """
    runner_dtype = getattr(runner, "dtype", None)
    if dtype is not None and runner_dtype is not None \
            and runner_dtype != dtype:
        raise ValueError(
            f"runner measures dtype {runner_dtype!r} but the sweep was "
            f"asked to label {dtype!r}")
    calls = grid_calls(grid) if calls is None else list(calls)
    n_calls = len(calls)
    table = {}
    peak = 1.0
    for i, call in enumerate(calls):
        seconds = runner.benchmark_call(call, reps=reps)
        table[(call.kind, call.dims)] = seconds
        if seconds > 0 and call.flops:
            peak = max(peak, call.flops / seconds)
        if progress:
            progress(i + 1, n_calls, call, seconds)
    return TableProfile(peak_flops=peak, table=table)


def calibrate(
    backend: str = "blas",
    grid: str = "small",
    reps: int = 3,
    out: Optional[Path] = None,
    dtype: Optional[str] = None,
    save: bool = True,
    progress=None,
    expr: Optional[str] = None,
    seed: Optional[int] = None,
) -> CalibrationResult:
    """Measure + persist this machine's kernel profile.

    ``out`` is a *directory*; the filename is derived from the hardware
    fingerprint so calibrations for different backends/dtypes coexist.
    With ``out=None`` the default cache dir is used — which is exactly
    where ``default_planner()`` looks, closing the loop.

    ``expr`` (a registered expression CLI name, see
    :mod:`repro.core.expressions`) restricts the measured set to exactly
    the kernel calls that family's named sweep grid enumerates — ``grid``
    then names a *sweep* grid (smoke/small/default/full, with per-family
    overrides) rather than a calibration grid.

    ``seed`` pins operand synthesis: each benchmark operand becomes a
    pure function of ``(seed, base, shape)``, so two calibration runs of
    the same grid time bit-identical inputs.
    """
    calls = None
    if expr is not None:
        from .expressions import get_spec
        calls = expression_calls(get_spec(expr), grid)
    elif grid not in GRIDS:
        raise ValueError(f"unknown grid {grid!r}; expected {sorted(GRIDS)}")
    if backend not in registered_backends():
        raise ValueError(
            f"unknown backend {backend!r}; registered: "
            f"{registered_backends()}")
    dtype = dtype or backend_default_dtype(backend)
    # Fixed-dtype backends (blas/numpy measure float64 only) raise here on
    # a mismatched label rather than stamping a wrong fingerprint.
    runner = make_backend(backend, reps=reps, dtype=dtype, seed=seed)
    fp = current_fingerprint(backend=backend, dtype=dtype)
    t0 = time.perf_counter()
    profile = sweep_kernels(runner, GRIDS.get(grid, ()), reps=reps,
                            dtype=dtype, progress=progress, calls=calls)
    wall = time.perf_counter() - t0
    if expr is not None:
        # A family-targeted run is *additive*: merge the new measurements
        # into whatever calibration this fingerprint already has — saving
        # the tiny restricted table wholesale would gut a full-grid
        # calibration sharing the same cache path.
        from .profile_store import load_profile, profile_path
        prev_path = profile_path(fp, directory=out)
        if prev_path.is_file():
            prev, _ = load_profile(prev_path, expected_fingerprint=fp)
            # Rebind rather than update() in place: TableProfile's
            # nearest-neighbour bucket index invalidates on rebinding.
            prev.table = {**prev.table, **profile.table}
            prev.observe_peak(profile.peak())
            profile = prev
    path = None
    if save:
        meta = {"grid": grid, "reps": reps, "wall_s": round(wall, 3)}
        if expr is not None:
            meta["expr"] = expr
        path = save_profile(profile, fp, directory=out, meta=meta)
    return CalibrationResult(profile=profile, fingerprint=fp, path=path,
                             wall_s=wall, n_calls=len(profile.table))


@dataclasses.dataclass
class TuneResult:
    table: object                 # repro.core.tuning.TuningTable
    fingerprint: HardwareFingerprint
    path: Optional[Path]          # None when persistence was disabled
    wall_s: float
    n_requests: int


def tune(
    backend: str = "pallas",
    grid: str = "tiny",
    reps: int = 3,
    out: Optional[Path] = None,
    dtype: Optional[str] = None,
    save: bool = True,
    budget: int = 8,
    progress=None,
    seed: Optional[int] = None,
) -> TuneResult:
    """``calibrate --tune``: autotune kernel tiles, persist the winners.

    The tuning sibling of :func:`calibrate`: the same named grids, the
    same fingerprint, the same cache directory — but the measured object
    is a :class:`~repro.core.tuning.TuningTable` of winning tile configs
    (one per ``(kind, dims)``; tri2full has none, and the grid diagonal
    additionally contributes the two fused patterns), pruned by the
    roofline pre-filter before any timing and measured under a
    per-request ``budget``. Only backends whose kernels take tile
    parameters can be tuned — i.e. ``pallas``.
    """
    if grid not in GRIDS:
        raise ValueError(f"unknown grid {grid!r}; expected {sorted(GRIDS)}")
    if backend not in registered_backends():
        raise ValueError(
            f"unknown backend {backend!r}; registered: "
            f"{registered_backends()}")
    dtype = dtype or backend_default_dtype(backend)
    runner = make_backend(backend, reps=reps, dtype=dtype, seed=seed)
    if not getattr(runner, "supports_tuning", False):
        raise ValueError(
            f"backend {backend!r} has no tunable kernel parameters; "
            f"--tune requires a tuning-capable backend (pallas)")
    from repro.kernels.autotune import autotune, default_tune_requests
    from .tuning import save_tuning_table
    dims = GRIDS[grid]
    requests = default_tune_requests(grid_calls(dims), fused_dims=dims)
    fp = current_fingerprint(backend=backend, dtype=dtype)
    t0 = time.perf_counter()
    table = autotune(runner, requests, reps=reps, budget=budget,
                     progress=progress)
    wall = time.perf_counter() - t0
    path = None
    if save:
        meta = {"grid": grid, "reps": reps, "budget": budget,
                "wall_s": round(wall, 3)}
        path = save_tuning_table(table, fp, directory=out, meta=meta)
    return TuneResult(table=table, fingerprint=fp, path=path, wall_s=wall,
                      n_requests=len(requests))


def main(argv: Optional[List[str]] = None) -> int:
    from .cli_help import (analysis_rules_epilog, backends_epilog,
                           discriminants_epilog)
    ap = argparse.ArgumentParser(
        prog="python -m repro.core.calibrate",
        description="Calibrate this machine's kernel performance profile.",
        epilog=backends_epilog() + "\n\n" + discriminants_epilog()
               + "\n\n" + analysis_rules_epilog(),
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--backend", choices=registered_backends(),
                    default="blas",
                    help="execution backend to calibrate (the registry "
                         "key is also the profile fingerprint key)")
    ap.add_argument("--expr", default=None,
                    help="calibrate only the kernel calls of one registered "
                         "expression family (see `python -m repro.core.sweep "
                         "--list-exprs`); --grid then names a sweep grid")
    ap.add_argument("--grid", default="default",
                    help=f"calibration grid {sorted(GRIDS)}, or with "
                         "--expr a sweep grid (smoke/small/default/full)")
    ap.add_argument("--reps", type=int, default=3,
                    help="timing repetitions per kernel call")
    ap.add_argument("--out", type=Path, default=None,
                    help="output directory (default: the profile cache dir "
                         "that default_planner() auto-loads from)")
    ap.add_argument("--dtype", default=None,
                    help="dtype label for the fingerprint (default: the "
                         "backend's own, e.g. float64 for blas/numpy, "
                         "float32 for jax/pallas)")
    ap.add_argument("--tune", action="store_true",
                    help="autotune kernel tile configs instead of "
                         "measuring a kernel profile: prune candidate "
                         "tilings with the roofline pre-filter, time the "
                         "survivors, persist winners as a TuningTable "
                         "the pallas backend auto-loads")
    ap.add_argument("--tune-budget", type=int, default=8,
                    help="with --tune: max candidate configs timed per "
                         "(kind, dims) request after pruning")
    ap.add_argument("--seed", type=int, default=None,
                    help="operand-synthesis seed: benchmark operands "
                         "become pure functions of (seed, base, shape), "
                         "so repeat calibrations time identical inputs")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.tune:
        if args.expr is not None:
            ap.error("--tune and --expr are mutually exclusive")

        def tune_progress(i, n, kind, dims, entry):
            if not args.quiet:
                speedup = entry.default_seconds / max(entry.seconds, 1e-12)
                print(f"  [{i}/{n}] {kind}{dims} -> {entry.config} "
                      f"({entry.timed} timed, {entry.pruned} pruned, "
                      f"{speedup:.2f}x vs default)", file=sys.stderr)

        res = tune(backend=args.backend, grid=args.grid, reps=args.reps,
                   out=args.out, dtype=args.dtype,
                   budget=args.tune_budget, progress=tune_progress,
                   seed=args.seed)
        print(f"tuned {res.n_requests} kernel shapes on "
              f"{res.fingerprint.backend}/{res.fingerprint.device}"
              f"/{res.fingerprint.dtype} in {res.wall_s:.1f}s")
        print(f"tuning table written to {res.path}")
        return 0

    def progress(i: int, n: int, call: KernelCall, seconds: float):
        if not args.quiet and (i % 25 == 0 or i == n):
            print(f"  [{i}/{n}] {call} {seconds * 1e6:.1f}us",
                  file=sys.stderr)

    res = calibrate(backend=args.backend, grid=args.grid, reps=args.reps,
                    out=args.out, dtype=args.dtype, progress=progress,
                    expr=args.expr, seed=args.seed)
    print(f"calibrated {res.n_calls} kernel shapes on "
          f"{res.fingerprint.backend}/{res.fingerprint.device}"
          f"/{res.fingerprint.dtype} in {res.wall_s:.1f}s "
          f"(peak ≈ {res.profile.peak() / 1e9:.1f} GFLOP/s)")
    print(f"profile written to {res.path}")
    return 0


if __name__ == "__main__":
    from repro.runtime.compile_cache import configure_compilation_cache
    configure_compilation_cache()
    raise SystemExit(main())
