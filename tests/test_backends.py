"""Execution-backend registry: protocol conformance, the generic walker,
derived kernel benchmarks, vmap batching, cross-backend comparison, and
the deprecation shims (ISSUE 4)."""

import functools
import pickle

import numpy as np
import pytest

from repro.core.backends import (
    BlasBackend,
    JaxBackend,
    KernelOps,
    NumpyBackend,
    PallasBackend,
    backend_default_dtype,
    backend_shard_mode,
    get_backend,
    get_backend_class,
    make_backend,
    reference_execute,
    register_backend,
    registered_backends,
    synthetic_algorithm,
)
from repro.core.backends import base as backends_base
from repro.core.expressions import REGISTRY
from repro.core.flops import KernelCall, gemm, symm, syrk, tri2full
from repro.core.perfmodel import TableProfile
from repro.core.profile_store import HardwareFingerprint, current_fingerprint
from repro.core.sweep import (
    GRAM_AATB,
    AnomalyAtlas,
    GridSpec,
    compare_backends,
    main as sweep_main,
    sweep,
)

SHIPPED = ("blas", "numpy", "jax", "pallas")


def _cheap(name, **kw):
    """A backend instance configured for test speed (no 64MB flush)."""
    return make_backend(name, reps=1, flush_cache=False, **kw)


# ---------------------------------------------------------------- registry --

def test_registry_ships_four_backends():
    assert set(SHIPPED) <= set(registered_backends())


def test_get_backend_unknown_name_is_helpful():
    with pytest.raises(KeyError, match="registered"):
        get_backend("mkl")


def test_register_backend_rejects_duplicates():
    with pytest.raises(ValueError, match="already registered"):
        register_backend("blas", BlasBackend)


def test_registry_classes_and_metadata():
    assert get_backend_class("blas") is BlasBackend
    assert get_backend_class("numpy") is NumpyBackend
    assert get_backend_class("jax") is JaxBackend
    assert get_backend_class("pallas") is PallasBackend
    assert backend_default_dtype("blas") == "float64"
    assert backend_default_dtype("pallas") == "float32"
    assert backend_shard_mode("numpy") == "process"
    assert backend_shard_mode("jax") == "device"
    assert backend_shard_mode("pallas") == "device"


def test_fingerprint_tags_are_registry_keys():
    for name in SHIPPED:
        tag, dtype = _cheap(name).fingerprint_tags()
        assert tag == name
        assert dtype == backend_default_dtype(name)


def test_make_backend_drops_foreign_options():
    # flush_cache is a CPU-backend knob; jax must not choke on it.
    be = make_backend("jax", reps=2, flush_cache=False)
    assert be.reps == 2
    # ...while get_backend stays strict.
    with pytest.raises(TypeError):
        get_backend("jax", flush_cache=False)


def test_make_backend_partial_pickles_for_process_pool():
    factory = functools.partial(make_backend, "numpy", reps=1,
                                flush_cache=False)
    runner = pickle.loads(pickle.dumps(factory))()
    assert isinstance(runner, NumpyBackend)


def test_fixed_dtype_backends_reject_wrong_labels():
    for name in ("blas", "numpy"):
        with pytest.raises(ValueError, match="float64"):
            get_backend(name, dtype="float32")


# ------------------------------------------------------- protocol / walker --

@pytest.mark.parametrize("name", SHIPPED)
def test_execute_matches_oracle_on_every_aatb_algorithm(name):
    spec = REGISTRY["aatb"]
    point = (24, 16, 32)
    algos = spec.algorithms(point)
    oracle = NumpyBackend(reps=1, flush_cache=False,
                          rng=np.random.default_rng(0))
    operands = {}
    for a in algos:
        for k, v in oracle.make_operands(a).items():
            operands.setdefault(k, v)
    expected = spec.reference_value(point, operands)
    be = _cheap(name)
    ops = {k: be._asarray(np.asarray(v)) for k, v in operands.items()}
    scale = float(np.abs(expected).max())
    tol = 1e-8 if be.dtype == "float64" else 3e-4 * max(1.0, scale)
    for a in algos:
        np.testing.assert_allclose(np.asarray(be.execute(a, ops)), expected,
                                   rtol=3e-4, atol=tol,
                                   err_msg=f"{name} {a.name}")


@pytest.mark.parametrize("name", SHIPPED)
def test_build_is_positional_and_matches_execute(name):
    be = _cheap(name)
    alg = REGISTRY["aatb"].algorithms((16, 8, 12))[0]
    operands = be.make_operands(alg)
    fn = be.build(alg)
    args = [operands.get(i, operands[0]) for i in range(be.num_inputs(alg))]
    np.testing.assert_allclose(np.asarray(fn(*args)),
                               np.asarray(be.execute(alg, operands)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name", ["jax", "pallas"])
@pytest.mark.parametrize("dtype,want", [("float32", "HIGHEST"),
                                        ("bfloat16", "DEFAULT")])
@pytest.mark.parametrize("expr,point", [("aatb", (32, 16, 24)),
                                        ("abcd", (32, 48, 64, 40, 32))])
def test_every_dot_runs_at_the_dtypes_precision(name, dtype, want, expr,
                                                point):
    """A TPU's default precision rounds float32 matmul operands to
    bfloat16; float32 programs must ask for HIGHEST at every dot."""
    import re

    import jax

    be = _cheap(name, dtype=dtype)
    for alg in REGISTRY[expr].algorithms(point):
        operands = be.make_operands(alg)
        args = [operands.get(i, operands[0])
                for i in range(be.num_inputs(alg))]
        text = jax.jit(be.build(alg)).lower(*args).as_text()
        found = set(re.findall(r"precision = \[(\w+), (\w+)\]", text))
        assert found == {(want, want)}, (alg.name, found)


def test_reference_execute_equals_numpy_backend():
    alg = REGISTRY["abab"].algorithms((12, 9, 7))[0]
    be = NumpyBackend(reps=1, flush_cache=False)
    operands = be.make_operands(alg)
    np.testing.assert_allclose(reference_execute(alg, operands),
                               be.execute(alg, operands))


def test_walker_rejects_unknown_kernel_kind():
    import dataclasses

    alg = synthetic_algorithm(gemm(4, 4, 4))
    bad = dataclasses.replace(alg.steps[0],
                              call=KernelCall("cholesky", (4, 4, 4)))
    with pytest.raises(ValueError, match="cholesky"):
        backends_base.walk_steps((bad,), {0: np.eye(4), 1: np.eye(4)}.get,
                                 NumpyBackend(flush_cache=False).ops())


def test_time_algorithm_and_benchmark_call_protocol():
    be = _cheap("numpy")
    alg = REGISTRY["aatb"].algorithms((16, 8, 12))[0]
    assert be.time_algorithm(alg) >= 0.0
    for call in (gemm(16, 16, 16), syrk(16, 8), symm(16, 8), tri2full(16)):
        assert be.benchmark_call(call, reps=1) >= 0.0


@pytest.mark.parametrize("call", [gemm(12, 10, 8), syrk(12, 8),
                                  symm(12, 8), tri2full(12)])
def test_synthetic_algorithms_execute_every_kind(call):
    """benchmark_call's synthetic one-step algorithms are numerically
    valid programs: the oracle executes them and shapes come out right."""
    alg = synthetic_algorithm(call)
    be = NumpyBackend(reps=1, flush_cache=False)
    out = be.execute(alg, be.make_operands(alg))
    if call.kind == "gemm":
        assert out.shape == (12, 10)
    elif call.kind == "syrk":
        assert out.shape == (12, 12)
        assert np.allclose(out, np.tril(out))  # tri storage
    elif call.kind == "symm":
        assert out.shape == (12, 8)
    else:
        np.testing.assert_allclose(out, out.T)  # mirrored


def test_synthetic_algorithm_rejects_unknown_kind():
    with pytest.raises(ValueError):
        synthetic_algorithm(KernelCall("trsm", (8, 8)))


# --------------------------------------------------- sweep engine plumbing --

FP = HardwareFingerprint(backend="blas", device="testdev", dtype="float64")


def test_sweep_exec_backend_serial(tmp_path):
    g = GridSpec.uniform((8, 16), GRAM_AATB.ndims)
    res = sweep(GRAM_AATB, g.points(), exec_backend="numpy", reps=1)
    assert res.n_measured == g.n_points


def test_sweep_exec_backend_process_pool(tmp_path):
    g = GridSpec.uniform((8, 16), GRAM_AATB.ndims)
    factory = functools.partial(make_backend, "numpy", reps=1,
                                flush_cache=False)
    res = sweep(GRAM_AATB, g.points(), backend="process", shards=2,
                runner_factory=factory)
    assert res.n_measured == g.n_points


def test_sweep_use_pallas_is_deprecated_spelling(tmp_path):
    g = GridSpec.uniform((8,), GRAM_AATB.ndims)
    res = sweep(GRAM_AATB, g.points(), backend="jax", reps=1,
                use_pallas=True)
    assert res.n_measured == 1
    with pytest.raises(ValueError, match="conflicts"):
        sweep(GRAM_AATB, g.points(), backend="jax", reps=1,
              use_pallas=True, exec_backend="jax")


# ------------------------------------------------------ backend comparison --

class _CliffRunner:
    """FLOP-proportional fake timer; optional SYRK cliff (pickles)."""

    def __init__(self, syrk_penalty=0.0):
        self.syrk_penalty = syrk_penalty

    def make_operands(self, alg):
        return {}

    def time_algorithm(self, alg, operands=None):
        t = 0.0
        for call in alg.calls:
            t += call.flops * 1e-9
            if call.kind == "syrk":
                t += call.flops * self.syrk_penalty
            if call.kind == "tri2full":
                t += 1e-6
        return t


def test_compare_backends_reports_disjoint_fastest(tmp_path):
    g = GridSpec.uniform((32, 64), GRAM_AATB.ndims, name="cmp")
    pts = g.points()
    # "backend A": SYRK catastrophic -> GEMM algorithms win everywhere.
    res_a = sweep(GRAM_AATB, pts, runner=_CliffRunner(syrk_penalty=5e-9))
    # "backend B": SYRK free-ish -> SYRK algorithms win everywhere.
    res_b = sweep(GRAM_AATB, pts, runner=_CliffRunner(syrk_penalty=-0.9e-9))
    cmp = compare_backends(GRAM_AATB, pts, {"a": res_a, "b": res_b})
    assert cmp.n_points == len(pts)
    assert cmp.backends == ("a", "b")
    # exactly the disjoint-fastest instances are reported (first-principles
    # recomputation from the per-backend records)
    fa = {r.point: set(r.cls.fastest) for r in res_a.records}
    fb = {r.point: set(r.cls.fastest) for r in res_b.records}
    expected = {p for p in fa if not (fa[p] & fb[p])}
    assert expected  # the cliff flip must actually produce disagreements
    assert {d.point for d in cmp.fastest_differs} == expected
    for d in cmp.fastest_differs:
        assert not (set(d.fastest["a"]) & set(d.fastest["b"]))
    # identical sweeps disagree nowhere
    same = compare_backends(GRAM_AATB, pts, {"x": res_a, "y": res_a})
    assert same.fastest_differs == [] and same.anomaly_differs == []


def test_compare_backends_needs_two():
    res = sweep(GRAM_AATB, [(8, 8, 8)], runner=_CliffRunner())
    with pytest.raises(ValueError, match="two"):
        compare_backends(GRAM_AATB, [(8, 8, 8)], {"only": res})


def test_cli_compare_backends_smoke(tmp_path, capsys):
    args = ["--expr", "aatb", "--grid", "8,16",
            "--compare-backends", "numpy,jax", "--reps", "1", "--no-flush",
            "--atlas-dir", str(tmp_path), "--quiet"]
    assert sweep_main(args) == 0
    out = capsys.readouterr().out
    assert "fastest-differs=" in out and "numpy vs jax" in out
    # one atlas per backend, each under its own fingerprint
    assert list(tmp_path.glob("atlas-aatb-*numpy*.jsonl"))
    assert list(tmp_path.glob("atlas-aatb-*jax*.jsonl"))


def test_cli_compare_backends_rejects_bad_pairs(tmp_path, capsys):
    base = ["--expr", "aatb", "--grid", "8", "--atlas-dir", str(tmp_path)]
    assert sweep_main(base + ["--compare-backends", "blas"]) == 2
    assert sweep_main(base + ["--compare-backends", "blas,blas"]) == 2
    assert sweep_main(base + ["--compare-backends", "blas,nope"]) == 2
    # comparison is measured-only: an explicit predict request must error
    # loudly instead of silently running two full measured sweeps
    with pytest.raises(SystemExit):
        sweep_main(base + ["--compare-backends", "numpy,jax",
                           "--mode", "predict"])


def test_flops_planner_memo_survives_observations():
    """Profile-independent discriminants must not re-enumerate per
    observation: the generation key is pinned for them (review fix)."""
    from repro.core.planner import Planner
    from repro.core.expr import gram_times

    table = TableProfile(1e11)
    planner = Planner(discriminant="flops", profile=table, record=True)
    c = gram_times(24, 16, 8)
    plan1 = planner.plan(c)
    planner.observe(plan1, seconds=0.1)  # bumps table.generation
    assert planner.plan(c) is plan1  # flops ranking cannot change


def test_cli_backend_pallas_smoke(tmp_path, capsys):
    args = ["--expr", "aatb", "--grid", "8,16", "--backend", "pallas",
            "--reps", "1", "--atlas-dir", str(tmp_path), "--quiet"]
    assert sweep_main(args) == 0
    out = capsys.readouterr().out
    assert "measured=8" in out
    files = list(tmp_path.glob("atlas-aatb-*pallas*.jsonl"))
    assert len(files) == 1


# ------------------------------------------------------- calibrate / select --

def test_calibrate_accepts_registry_backends(tmp_path):
    from repro.core.calibrate import calibrate

    res = calibrate(backend="numpy", grid="small", reps=1, out=tmp_path,
                    save=True)
    assert res.fingerprint.backend == "numpy"
    assert res.fingerprint.dtype == "float64"
    assert res.path is not None and res.path.is_file()
    with pytest.raises(ValueError, match="unknown backend"):
        calibrate(backend="nope", grid="small")


def test_select_expression_measured_on_named_backend():
    from repro.core.selector import select_expression

    ranked = select_expression("aatb", (16, 8, 12),
                               discriminant="measured", backend="numpy")
    assert len(ranked) == 5
    with pytest.raises(ValueError, match="not both"):
        select_expression("aatb", (16, 8, 12), discriminant="measured",
                          backend="numpy", runner=_CliffRunner())


def test_planner_resolves_backend_via_registry():
    from repro.core.planner import Planner

    p = Planner(backend="numpy")
    assert isinstance(p.runner, NumpyBackend)
    from repro.core.expr import gram_times
    c = gram_times(24, 16, 8)
    rng = np.random.default_rng(0)
    a = rng.standard_normal((24, 16))
    b = rng.standard_normal((24, 8))
    out = p(c, a, a, b)
    assert np.asarray(out).shape == (24, 8)


def test_planner_use_pallas_shim_warns():
    from repro.core.planner import Planner

    with pytest.warns(DeprecationWarning, match="use_pallas"):
        p = Planner(use_pallas=True)
    assert p.backend == "pallas"
    assert isinstance(p.runner, JaxBackend) and p.runner.use_pallas
    with pytest.warns(DeprecationWarning):
        assert Planner(use_pallas=False).backend == "jax"


def test_recording_planner_files_under_its_backend_tag():
    from repro.core.planner import Planner

    p = Planner(backend="pallas", record=True)
    assert (p.profile_backend, p.profile_dtype) == ("pallas", "float32")
    q = Planner(backend="jax")  # read-only: consumes the BLAS calibration
    assert (q.profile_backend, q.profile_dtype) == ("blas", "float64")


def test_jaxrunner_alias_still_works():
    from repro.core.runners import BlasRunner, JaxRunner

    assert BlasRunner is BlasBackend
    r = JaxRunner(use_pallas=True, reps=2, dtype="float32")
    assert isinstance(r, JaxBackend) and r.use_pallas and r.reps == 2
    assert r.fingerprint_tags() == ("pallas", "float32")


def test_current_fingerprint_pallas_uses_device_kind():
    fp = current_fingerprint(backend="pallas", dtype="float32")
    assert fp.backend == "pallas"
    # on this CPU container the jax device kind is "cpu", not the host ISA
    import jax
    assert fp.device == jax.devices()[0].device_kind


# ----------------------------------------------- fifth-backend registration --

class _ScaledNumpyOps(KernelOps):
    def __init__(self, inner):
        self.inner = inner

    def transpose(self, a):
        return self.inner.transpose(a)

    def gemm(self, a, b):
        return self.inner.gemm(a, b)

    def syrk(self, a):
        return self.inner.syrk(a)

    def symm(self, s, b):
        return self.inner.symm(s, b)

    def symm_r(self, b, s):
        return self.inner.symm_r(b, s)

    def tri2full(self, t):
        return self.inner.tri2full(t)


def test_registering_a_fifth_backend_flows_through(monkeypatch, tmp_path):
    """The docs/architecture.md recipe: a new backend registered at
    runtime sweeps, calibrates and fingerprints with no further wiring."""
    monkeypatch.setattr(backends_base, "_REGISTRY",
                        dict(backends_base._REGISTRY))

    class EchoBackend(NumpyBackend):
        name = "echo"

        def ops(self):
            return _ScaledNumpyOps(super().ops())

    register_backend("echo", EchoBackend)
    assert "echo" in registered_backends()
    be = get_backend("echo", reps=1, flush_cache=False)
    assert be.fingerprint_tags() == ("echo", "float64")
    g = GridSpec.uniform((8, 16), GRAM_AATB.ndims)
    atlas = AnomalyAtlas(tmp_path / "echo.jsonl",
                         HardwareFingerprint("echo", "testdev", "float64"),
                         GRAM_AATB.name, 0.10)
    res = sweep(GRAM_AATB, g.points(), exec_backend="echo", reps=1,
                atlas=atlas)
    assert res.n_measured == g.n_points
