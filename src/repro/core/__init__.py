"""repro.core — the paper's contribution: the LAMP planner.

*FLOPs as a Discriminant for Dense Linear Algebra Algorithms*
(López, Karlsson, Bientinesi — ICPP '22) productized:

expression IR → algorithm enumeration → {flops | perfmodel | measured}
discriminant → executable plan, plus the paper's anomaly-study harnesses
(Experiments 1–3).
"""

from .algorithms import (
    Algorithm,
    enumerate_algorithms,
    optimal_chain_order,
)
from .backends import (
    ExecutionBackend,
    get_backend,
    make_backend,
    register_backend,
    registered_backends,
)
from .anomaly import (
    Classification,
    ConfusionMatrix,
    Region,
    classify,
    cluster_regions,
    scan_line,
)
from .expr import (
    Chain,
    Matrix,
    Transpose,
    chain,
    gram_left_times,
    gram_of_product,
    gram_right_times,
    gram_times,
    matrix_chain,
    symmetric_sandwich,
)
from .expressions import (
    GRAM_ABAB,
    GRAM_ABTB,
    GRAM_ATAB,
    MATRIX_CHAIN_ABCDE,
    REGISTRY,
    SANDWICH_BTSB,
    ExpressionSpec,
    GridSpec,
    get_spec,
    register,
    registered_names,
)
from .flops import KernelCall, gemm, kernel_flops, symm, syrk, total_flops, tri2full
from .perfmodel import (
    TPU_V5E,
    AnalyticalTPUProfile,
    HardwareSpec,
    HybridProfile,
    KernelProfile,
    RooflineProfile,
    TableProfile,
    predict_algorithm_time,
)
from .planner import (
    Plan,
    Planner,
    default_planner,
    plan,
    reset_default_planner,
    resolve_profile,
)
from .profile_store import (
    FingerprintMismatchError,
    HardwareFingerprint,
    ProfileStoreError,
    current_fingerprint,
    load_default_profile,
    load_profile,
    profile_path,
    save_profile,
)
from .discriminants import (
    Discriminant,
    DiscriminantContext,
    get_discriminant,
    register_discriminant,
    registered_discriminants,
)
from .runners import BlasRunner, JaxRunner, measure_seconds, reference_execute
from .selector import as_hybrid, select, select_expression

# Lazy (PEP 562) so `python -m repro.core.calibrate` / `python -m
# repro.core.sweep` don't import their CLI modules twice (runpy warns when
# the target is already in sys.modules). NB `repro.core.calibrate` /
# `repro.core.sweep` name the *submodules* (like os.path); the entry-point
# functions are `repro.core.calibrate.calibrate` / `repro.core.sweep.sweep`.
_LAZY_EXPORTS = {
    "GRIDS": ".calibrate",
    "CalibrationResult": ".calibrate",
    "TuneResult": ".calibrate",
    "expression_calls": ".calibrate",
    "sweep_kernels": ".calibrate",
    # autotuning (tuning.py lazily imports kernel VMEM estimators; lazy
    # here keeps the perfmodel/profile_store chain out of base import)
    "TunedEntry": ".tuning",
    "TuningTable": ".tuning",
    "candidate_configs": ".tuning",
    "load_default_tuning_table": ".tuning",
    "load_tuning_table": ".tuning",
    "prune_candidates": ".tuning",
    "save_tuning_table": ".tuning",
    "tuning_path": ".tuning",
    # measurement fast path (arena imports algorithms only; listed lazy to
    # keep symmetry with the sweep engine that consumes it)
    "FastPathStats": ".arena",
    "OperandArena": ".arena",
    "algorithm_structural_key": ".arena",
    "arena_for": ".arena",
    "order_points_for_locality": ".arena",
    "algorithm_cache_stats": ".expressions",
    "clear_algorithm_cache": ".expressions",
    # sweep engine (the `sweep` *function* stays module-scoped to keep the
    # submodule name unambiguous, mirroring calibrate)
    "SWEEP_GRIDS": ".expressions",
    "AnomalyAtlas": ".sweep",
    "AtlasError": ".sweep",
    "BackendComparison": ".sweep",
    "BackendDisagreement": ".sweep",
    "compare_backends": ".sweep",
    "Instance": ".sweep",
    "SweepResult": ".sweep",
    "atlas_path": ".sweep",
    "atlas_shard_path": ".sweep",
    "benchmark_unique_calls": ".sweep",
    "cluster_sweep": ".sweep",
    "collect_unique_calls": ".sweep",
    "predict_classifications": ".sweep",
    # adaptive boundary-refinement engine (imports sweep; lazy likewise —
    # the `adaptive_sweep` *function* mirrors `sweep`/`calibrate` naming)
    "AdaptiveResult": ".adaptive",
    "RoundStats": ".adaptive",
    "adaptive_sweep": ".adaptive",
    "boundary_cells": ".adaptive",
    "refinement_candidates": ".adaptive",
    "seed_points": ".adaptive",
    # paper harnesses (import scipy-backed runners; lazy keeps base import
    # light and keeps `sweep` out of sys.modules at package import)
    "GRAM_AATB": ".expressions",
    "MATRIX_CHAIN_ABCD": ".expressions",
    "experiment1_random_search": ".experiments",
    "experiment2_regions": ".experiments",
    "experiment3_predict_from_benchmarks": ".experiments",
    "measure_instance": ".experiments",
    # atlas-replay evaluation (imports sweep; lazy for the same reason)
    "AtlasReplay": ".evaluate",
    "DiscriminantScore": ".evaluate",
    "EvaluationResult": ".evaluate",
    "evaluate_atlas": ".evaluate",
    "evaluate_discriminants": ".evaluate",
    "load_atlas_records": ".evaluate",
    # static plan verifier (analysis imports algorithms/expressions; lazy
    # keeps the analysis passes out of the base import path)
    "AnalysisError": ".analysis",
    "Finding": ".analysis",
    "assert_algorithms_valid": ".analysis",
    "run_mutation_suite": ".analysis",
    "verify_algorithm": ".analysis",
    "verify_algorithms": ".analysis",
    "verify_family": ".analysis",
    "verify_zoo": ".analysis",
    # deprecated alias (selector.__getattr__ emits the DeprecationWarning
    # at first *use*, not at package import — and it is deliberately NOT
    # in __all__, so star-imports don't trigger it either)
    "DISCRIMINANTS": ".selector",
}


def __getattr__(name):
    target = _LAZY_EXPORTS.get(name)
    if target is not None:
        import importlib
        mod = importlib.import_module(target, __name__)
        value = getattr(mod, name)
        globals()[name] = value  # cache: later lookups skip __getattr__
        return value
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "Algorithm", "enumerate_algorithms", "optimal_chain_order",
    "ExecutionBackend", "get_backend", "make_backend", "register_backend",
    "registered_backends",
    "BackendComparison", "BackendDisagreement", "compare_backends",
    "Classification", "ConfusionMatrix", "Region", "classify",
    "cluster_regions", "scan_line",
    "FastPathStats", "OperandArena", "algorithm_structural_key",
    "arena_for", "order_points_for_locality",
    "algorithm_cache_stats", "clear_algorithm_cache",
    "SWEEP_GRIDS", "AnomalyAtlas", "AtlasError", "GridSpec", "Instance",
    "SweepResult", "atlas_path", "atlas_shard_path",
    "benchmark_unique_calls", "cluster_sweep",
    "collect_unique_calls", "predict_classifications",
    "AdaptiveResult", "RoundStats", "adaptive_sweep", "boundary_cells",
    "refinement_candidates", "seed_points",
    "Chain", "Matrix", "Transpose", "chain", "gram_times", "matrix_chain",
    "gram_left_times", "gram_of_product", "gram_right_times",
    "symmetric_sandwich",
    "GRAM_AATB", "MATRIX_CHAIN_ABCD", "MATRIX_CHAIN_ABCDE", "GRAM_ABTB",
    "GRAM_ATAB", "GRAM_ABAB", "SANDWICH_BTSB", "REGISTRY",
    "ExpressionSpec", "get_spec", "register", "registered_names",
    "experiment1_random_search", "experiment2_regions",
    "experiment3_predict_from_benchmarks", "measure_instance",
    "expression_calls",
    "KernelCall", "gemm", "kernel_flops", "symm", "syrk", "total_flops",
    "tri2full",
    "TPU_V5E", "AnalyticalTPUProfile", "HardwareSpec", "HybridProfile",
    "KernelProfile", "RooflineProfile", "TableProfile",
    "predict_algorithm_time",
    "Plan", "Planner", "default_planner", "plan", "reset_default_planner",
    "resolve_profile",
    "GRIDS", "CalibrationResult", "TuneResult", "sweep_kernels",
    "TunedEntry", "TuningTable", "candidate_configs",
    "load_default_tuning_table", "load_tuning_table", "prune_candidates",
    "save_tuning_table", "tuning_path",
    "FingerprintMismatchError", "HardwareFingerprint", "ProfileStoreError",
    "current_fingerprint", "load_default_profile", "load_profile",
    "profile_path", "save_profile",
    "BlasRunner", "JaxRunner", "measure_seconds", "reference_execute",
    "as_hybrid", "select", "select_expression",
    "Discriminant", "DiscriminantContext", "get_discriminant",
    "register_discriminant", "registered_discriminants",
    "AtlasReplay", "DiscriminantScore", "EvaluationResult",
    "evaluate_atlas", "evaluate_discriminants", "load_atlas_records",
    "AnalysisError", "Finding", "assert_algorithms_valid",
    "run_mutation_suite", "verify_algorithm", "verify_algorithms",
    "verify_family", "verify_zoo",
]
