"""Measurement fast path: operand arena, executable memo,
algorithm-enumeration LRU, and the pipelined serial sweep — gated on what
a sweep must reproduce (planted masks, closed-form times, byte-identical
atlases across reruns) plus kill/resume cleanliness."""

import functools

import numpy as np
import pytest

from repro.core.arena import (
    FastPathStats,
    OperandArena,
    algorithm_structural_key,
    arena_for,
    order_points_for_locality,
)
from repro.core.backends import NumpyBackend, make_backend
from repro.core.expressions import (
    algorithm_cache_stats,
    clear_algorithm_cache,
)
from repro.core import expressions as expressions_mod
from repro.core.profile_store import HardwareFingerprint
from repro.core.sweep import (
    GRAM_AATB,
    AnomalyAtlas,
    GridSpec,
    benchmark_unique_calls,
    main as sweep_main,
    measure_instance,
    sweep,
)
from repro.core.synthetic import MaskRunner, PlantedSpec, planted_masks
from repro.core.flops import gemm, syrk

FP = HardwareFingerprint(backend="blas", device="testdev", dtype="float64")

GRID = GridSpec.uniform((32, 64, 96), GRAM_AATB.ndims, name="test")


class CliffRunner:
    """Deterministic FLOP-proportional timer with a SYRK cliff at m >= 64.

    Reported seconds are a pure function of the algorithm, so any two
    sweeps of the same points must agree byte for byte.
    """

    def make_operands(self, alg):
        return {}

    def time_algorithm(self, alg, operands=None):
        t = 0.0
        for call in alg.calls:
            t += call.flops * 1e-9
            if call.kind == "syrk" and call.dims[0] >= 64:
                t += call.flops * 3e-9
        return t


class SeededFakeTimeNumpy(NumpyBackend):
    """Real (seeded) operand synthesis, deterministic reported time.

    Unlike :class:`CliffRunner` this drives genuine buffers through the
    arena, so the checks also cover operand plumbing.
    """

    def time_algorithm(self, alg, operands=None, reps=None):
        assert operands, f"operands never reached the runner for {alg.name}"
        return 1e-12 * alg.flops * _skew(alg)


def _skew(alg):
    return 1.5 if any(c.kind == "syrk" for c in alg.calls) else 1.0


def _sweep_bytes(tmp_path, tag, spec, points, runner):
    path = tmp_path / f"{tag}.jsonl"
    atlas = AnomalyAtlas(path, FP, spec.name, 0.10)
    res = sweep(spec, points, runner=runner, atlas=atlas)
    atlas.flush()
    return res, path.read_bytes()


# ------------------------------------------------------------------ parity --

def test_fastpath_matches_legacy_on_planted_masks(tmp_path):
    """Planted-mask oracles: every verdict is the planted mask's, and two
    sweeps with fresh runners write byte-identical atlases."""
    spec = PlantedSpec()
    grid = GridSpec.uniform(tuple(range(10, 110, 10)), spec.ndims,
                            name="planted")
    for name, mask in sorted(planted_masks(grid).items()):
        first, first_b = _sweep_bytes(tmp_path, f"{name}-1", spec,
                                      grid.points(), MaskRunner(mask))
        second, second_b = _sweep_bytes(tmp_path, f"{name}-2", spec,
                                        grid.points(), MaskRunner(mask))
        assert first.n_measured == second.n_measured == grid.n_points
        for r in first.records:
            assert r.cls.is_anomaly == bool(mask(r.point)), (name, r.point)
        assert first_b == second_b, name          # reruns agree, bytewise
        assert isinstance(first.fastpath, FastPathStats)


def test_fastpath_matches_legacy_with_real_operands(tmp_path):
    """Seeded numpy operands through the arena: byte-identical atlases,
    and every time the closed form the runner reports."""
    pts = GRID.points()
    first, first_b = _sweep_bytes(
        tmp_path, "first", GRAM_AATB, pts,
        SeededFakeTimeNumpy(reps=1, flush_cache=False, seed=11))
    _, second_b = _sweep_bytes(
        tmp_path, "second", GRAM_AATB, pts,
        SeededFakeTimeNumpy(reps=1, flush_cache=False, seed=11))
    assert first_b == second_b
    for rec in first.records:
        for alg in GRAM_AATB.algorithms(rec.point):
            assert rec.times[alg.name] == 1e-12 * alg.flops * _skew(alg)
    st = first.fastpath
    assert st.arena_hits > 0          # leaf shapes shared across points
    assert st.points_pipelined == len(pts) - 1
    assert 0.0 <= st.overlap_fraction <= 1.0
    assert "arena" in st.summary() and "pipelined" in st.summary()


def test_fastpath_preserves_requested_order():
    pts = list(reversed(GRID.points()))
    res = sweep(GRAM_AATB, pts, runner=CliffRunner())
    assert [r.point for r in res.records] == pts


def test_direct_measure_instance_with_arena_matches_legacy():
    """A shared arena and the per-call arena give equal instances, whose
    times are the closed form."""
    runner = SeededFakeTimeNumpy(reps=1, flush_cache=False, seed=3)
    arena = OperandArena(runner)
    for p in GRID.points()[:4]:
        via_arena = measure_instance(GRAM_AATB, p, runner, 0.10, arena=arena)
        plain = measure_instance(GRAM_AATB, p, runner, 0.10)
        assert via_arena == plain
        assert plain.times == {
            a.name: 1e-12 * a.flops * _skew(a)
            for a in GRAM_AATB.algorithms(p)}


# ------------------------------------------------------------- kill/resume --

def test_killed_fastpath_sweep_resumes_to_legacy_identical_atlas(tmp_path):
    """Kill after 10 points, resume with a *fresh* runner (fresh arena):
    the stitched atlas is byte-identical to an uninterrupted sweep — no
    arena or memo state leaks into resumed results."""
    path = tmp_path / "fast.jsonl"
    atlas = AnomalyAtlas(path, FP, GRAM_AATB.name, 0.10, chunk_size=5)
    res1 = sweep(GRAM_AATB, GRID.points(), runner=CliffRunner(),
                 atlas=atlas, max_instances=10)
    assert res1.n_measured == 10

    atlas2 = AnomalyAtlas(path, FP, GRAM_AATB.name, 0.10)
    res2 = sweep(GRAM_AATB, GRID.points(), runner=CliffRunner(),
                 atlas=atlas2)
    assert res2.n_skipped == 10
    assert res2.n_measured == GRID.n_points - 10

    _, whole_b = _sweep_bytes(tmp_path, "whole", GRAM_AATB, GRID.points(),
                              CliffRunner())
    assert path.read_bytes() == whole_b


def test_fastpath_budget_buys_first_points_in_request_order(tmp_path):
    """max_instances applies to the request-order todo *before* locality
    reordering — the budget semantics are unchanged by the fast path."""
    pts = list(reversed(GRID.points()))
    res = sweep(GRAM_AATB, pts, runner=CliffRunner(), max_instances=5)
    assert [r.point for r in res.records] == pts[:5]


# --------------------------------------------------------------- executors --

@pytest.mark.parametrize("executor", ["serial", "process", "jax"])
def test_every_executor_reports_fastpath_stats(executor):
    pts = GRID.points()[:4]
    if executor == "serial":
        res = sweep(GRAM_AATB, pts, runner=CliffRunner())
    elif executor == "process":
        res = sweep(GRAM_AATB, pts, backend="process", shards=2,
                    chunk_size=2,
                    runner_factory=functools.partial(
                        make_backend, "numpy", reps=1, flush_cache=False))
    else:
        pytest.importorskip("jax")
        res = sweep(GRAM_AATB, pts, backend="jax", reps=1, shards=1)
    assert res.n_measured == len(pts)
    assert isinstance(res.fastpath, FastPathStats)


def test_cli_no_fastpath_flag(tmp_path, capsys):
    """The one measurement path prints its counters; the old switch is
    gone, so argparse refuses it."""
    args = ["--expr", "aatb", "--grid", "smoke", "--reps", "1",
            "--no-flush", "--atlas-dir", str(tmp_path / "a"), "--quiet"]
    assert sweep_main(args) == 0
    assert "fastpath:" in capsys.readouterr().out
    with pytest.raises(SystemExit) as exc:
        sweep_main(args + ["--no-fastpath"])
    assert exc.value.code == 2


# --------------------------------------------------------- enumeration LRU --

def test_algorithms_memo_enumerates_once_per_point(monkeypatch):
    calls = []
    real = expressions_mod.enumerate_algorithms

    def counting(expr):
        calls.append(expr)
        return real(expr)

    monkeypatch.setattr(expressions_mod, "enumerate_algorithms", counting)
    clear_algorithm_cache()
    before = algorithm_cache_stats()
    for _ in range(3):
        a = GRAM_AATB.algorithms((32, 48, 64))
    assert len(calls) == 1                       # memoised after first
    b = GRAM_AATB.algorithms((32, 48, 64))
    assert [x.name for x in a] == [x.name for x in b]
    GRAM_AATB.algorithms((48, 48, 64))
    assert len(calls) == 2                       # distinct point, new entry
    hits, misses = algorithm_cache_stats()
    assert hits - before[0] == 3
    assert misses - before[1] == 2


def test_algorithms_memo_returns_fresh_lists():
    clear_algorithm_cache()
    a = GRAM_AATB.algorithms((32, 32, 32))
    a.clear()                                    # caller-side mutation
    b = GRAM_AATB.algorithms((32, 32, 32))
    assert b and b == GRAM_AATB.algorithms((32, 32, 32))


def test_algorithms_memo_bypassed_under_verify_enumeration(monkeypatch):
    calls = []
    real = expressions_mod.enumerate_algorithms

    def counting(expr):
        calls.append(expr)
        return real(expr)

    monkeypatch.setattr(expressions_mod, "enumerate_algorithms", counting)
    monkeypatch.setenv("REPRO_VERIFY_ENUMERATION", "1")
    clear_algorithm_cache()
    GRAM_AATB.algorithms((32, 32, 32))
    GRAM_AATB.algorithms((32, 32, 32))
    assert len(calls) == 2                       # every call re-enumerates


# ------------------------------------------------------------ operand arena --

def test_seeded_leaf_synthesis_is_reproducible_and_matches_legacy():
    algos = GRAM_AATB.algorithms((32, 48, 64))
    r1 = NumpyBackend(reps=1, flush_cache=False, seed=5)
    r2 = NumpyBackend(reps=1, flush_cache=False, seed=5)
    legacy = r1.make_operands(algos[0])
    arena = OperandArena(r2)
    pooled = arena.operands(algos)
    assert set(legacy) <= set(pooled)
    for base, buf in legacy.items():
        np.testing.assert_array_equal(buf, pooled[base])
    # a second pass is pure hits and returns the same buffers
    hits0, misses0, _ = arena.snapshot()
    again = arena.operands(algos)
    assert all(again[k] is pooled[k] for k in pooled)
    hits1, misses1, _ = arena.snapshot()
    assert misses1 == misses0
    assert hits1 > hits0


def test_seed_makes_leaf_draws_pure_unseeded_stays_stateful():
    algos = GRAM_AATB.algorithms((32, 32, 32))
    # unseeded: the shared rng advances, so repeat draws differ
    stateful = NumpyBackend(reps=1, flush_cache=False)
    a = stateful.make_operands(algos[0])
    b = stateful.make_operands(algos[0])
    assert any(not np.array_equal(a[k], b[k]) for k in a)
    # seeded: each leaf is a pure function of (seed, base, shape)
    pure = NumpyBackend(reps=1, flush_cache=False, seed=5)
    c = pure.make_operands(algos[0])
    d = pure.make_operands(algos[0])
    for k in c:
        np.testing.assert_array_equal(c[k], d[k])


def test_arena_handles_operandless_planted_algorithms():
    spec = PlantedSpec()
    arena = OperandArena(MaskRunner(planted_masks(
        GridSpec.uniform((10, 20), spec.ndims))["full"]))
    assert arena.operands(spec.algorithms((10, 20))) == {}


def test_arena_for_is_stable_per_runner():
    r = NumpyBackend(reps=1, flush_cache=False, seed=1)
    assert arena_for(r) is arena_for(r)
    other = NumpyBackend(reps=1, flush_cache=False, seed=1)
    assert arena_for(r) is not arena_for(other)


# -------------------------------------------------- structural keys / order --

def test_structural_keys_distinct_within_point_shared_across_dims():
    a32 = GRAM_AATB.algorithms((32, 32, 32))
    keys32 = [algorithm_structural_key(a) for a in a32]
    assert len(set(keys32)) == len(keys32)       # no memo collisions
    a64 = GRAM_AATB.algorithms((64, 96, 128))
    keys64 = [algorithm_structural_key(a) for a in a64]
    assert set(keys32) == set(keys64)            # dims-free: shared wrappers


def test_order_points_for_locality_is_sorted_and_total():
    pts = [(3, 1), (1, 2), (2, 9), (1, 1)]
    out = order_points_for_locality(pts)
    assert sorted(out) == out and sorted(pts) == out
    assert order_points_for_locality(list(reversed(pts))) == out


# --------------------------------------------------------------- stats type --

def test_fastpath_stats_merge_and_roundtrip():
    a = FastPathStats(arena_hits=2, arena_misses=1, prep_s=0.5,
                      overlap_s=0.25, points_pipelined=3)
    b = FastPathStats.from_dict(a.as_dict())
    assert b == a
    a.merge(FastPathStats(arena_hits=1, memo_hits=4))
    assert a.arena_hits == 3 and a.memo_hits == 4
    assert a.overlap_fraction == pytest.approx(0.5)
    assert FastPathStats().overlap_fraction == 0.0


# -------------------------------------------------- batched kernel benching --

def test_benchmark_unique_calls_with_arena_counts_reuse():
    runner = SeededFakeTimeNumpy(reps=1, flush_cache=False, seed=9)
    arena = arena_for(runner)
    stats = FastPathStats()
    calls = [gemm(32, 32, 32), syrk(32, 32), gemm(32, 32, 32),
             gemm(32, 48, 32)]
    profile, n_meas, n_reused = benchmark_unique_calls(
        runner, calls, arena=arena, stats=stats)
    assert n_meas == 3 and n_reused == 0         # dedup unchanged
    assert all(c in profile for c in calls)
    _, misses, _ = arena.snapshot()
    assert misses > 0                            # buffers came from the pool
    assert stats.arena_misses == misses
    # second pass: profile cache short-circuits, arena untouched
    _, n2, r2 = benchmark_unique_calls(runner, calls, profile=profile,
                                       arena=arena, stats=stats)
    assert n2 == 0 and r2 == 3
    assert arena.snapshot()[1] == misses


# ------------------------------------------------------------ executable memo --

def test_jax_executable_memo_reuses_wrappers_across_dims():
    pytest.importorskip("jax")
    be = make_backend("jax", reps=1)
    algos = GRAM_AATB.algorithms((16, 16, 16))
    alg = algos[0]
    ops = be.make_operands(alg)
    be.time_algorithm(alg, ops)
    h0, m0 = be.memo_hits, be.memo_misses
    be.time_algorithm(alg, ops)                  # same alg: wrapper reused
    assert (be.memo_hits, be.memo_misses) == (h0 + 1, m0)
    # same structure at other dims: still the same memo entry (jit itself
    # retraces per shape under the shared wrapper)
    key = algorithm_structural_key(alg)
    twin = next(a for a in GRAM_AATB.algorithms((8, 8, 8))
                if algorithm_structural_key(a) == key)
    be.time_algorithm(twin, be.make_operands(twin))
    assert (be.memo_hits, be.memo_misses) == (h0 + 2, m0)


def test_pallas_tuning_generation_invalidates_memo():
    pytest.importorskip("jax")
    from repro.core.backends import PallasBackend

    be = PallasBackend(reps=1, tuning=None)
    g0 = be._memo_generation()
    be.set_tuning(None)
    assert be._memo_generation() != g0           # any set_tuning bumps
    g1 = be._memo_generation()
    with be.tuning_override({("gemm", (32, 32, 32)): {"bm": 32}}):
        g_in = be._memo_generation()
        assert g_in != g1
    assert be._memo_generation() not in (g0, g1, g_in)  # exit bumps again
