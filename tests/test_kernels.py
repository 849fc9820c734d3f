"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

RNG = np.random.default_rng(0)


def randf(*shape, dtype=np.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape).astype(dtype) * scale)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------- gemm ---

@pytest.mark.parametrize("m,n,k", [
    (128, 128, 128), (256, 128, 384), (200, 150, 300), (64, 64, 64),
    (129, 257, 130), (1, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_matches_ref(m, n, k, dtype):
    a = randf(m, k).astype(dtype)
    b = randf(k, n).astype(dtype)
    out = ops.gemm(a, b)
    expect = ref.gemm(a, b)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **tol(dtype))


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 300), n=st.integers(1, 300), k=st.integers(1, 300))
def test_gemm_hypothesis_shapes(m, n, k):
    a = randf(m, k)
    b = randf(k, n)
    np.testing.assert_allclose(
        np.asarray(ops.gemm(a, b)), np.asarray(ref.gemm(a, b)),
        rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------- syrk ---

@pytest.mark.parametrize("m,k", [
    (128, 128), (256, 128), (384, 256), (130, 70), (257, 511),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_syrk_matches_ref(m, k, dtype):
    a = randf(m, k).astype(dtype)
    out = ops.syrk(a)
    expect = ref.syrk(a)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        **tol(dtype))


def test_syrk_strictly_upper_is_zero():
    a = randf(256, 64)
    out = np.asarray(ops.syrk(a))
    assert np.all(np.triu(out, 1) == 0.0)


# ---------------------------------------------------------------- symm ---

#: (m, n, bm, bn) at the tiles the chip uses: 2–4 row blocks of 256 or 512,
#: so the kernel's below-, on- and above-diagonal branches all run; some m
#: and n are padded up to the tile.
WIDE_SYMM = [
    (512, 128, 256, 128), (768, 512, 256, 256), (1000, 300, 256, 128),
    (1024, 512, 512, 512), (1500, 512, 512, 256), (2048, 256, 512, 256),
]


#: (m, n, bm, bn, panel): float32 shapes at which the wrapper's column
#: panel (the output columns one S tile serves) takes each of its forms.
PANEL_SYMM = [
    (1024, 2560, 512, 128, 640),    # 4 panels of 5 blocks, S 2 row blocks
    (1024, 5504, 512, 128, 512),    # n/bn = 43 prime: the 11th overhangs
    (768, 4736, 256, 128, 2432),    # n/bn = 37 prime: 2 panels, ragged
    (512, 1536, 256, 128, 1536),    # n inside one panel, S 2 row blocks
]


def _wide(m, n, bm, bn):
    return pytest.param(m, n, bm, bn, id=f"m{m}-n{n}-bm{bm}-bn{bn}")


WIDE_CASES = [_wide(*c) for c in WIDE_SYMM] + [
    _wide(*c[:4]) for c in PANEL_SYMM]


def _case_rng(m, n, bm, bn):
    """The 128-tile cases draw from the module's stream, the wide cases
    from a generator of their own, so the wide cases do not shift the
    operands of later tests in this module."""
    if (bm, bn) == (128, 128):
        return RNG
    return np.random.default_rng((m, n, bm, bn))


def _sym_with_upper_garbage(m, rng):
    """Lower triangle, and the same with large garbage in its strict
    upper triangle (which covers that of every diagonal block)."""
    low = np.tril(rng.standard_normal((m, m)))
    garbage = low + np.triu(rng.standard_normal((m, m)) * 1e4, 1)
    return low.astype(np.float32), garbage.astype(np.float32)


@pytest.mark.parametrize("m,n,bm,bn", [
    pytest.param(128, 128, 128, 128, id="128-128"),
    pytest.param(256, 64, 128, 128, id="256-64"),
    pytest.param(300, 120, 128, 128, id="300-120"),
    pytest.param(129, 33, 128, 128, id="129-33"),
] + WIDE_CASES)
def test_symm_matches_ref(m, n, bm, bn):
    rng = _case_rng(m, n, bm, bn)
    s_low = jnp.asarray(np.tril(rng.standard_normal((m, m))).astype(
        np.float32))
    b = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(ops.symm(s_low, b, bm=bm, bn=bn)),
        np.asarray(ref.symm(s_low, b)), rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("m,n,bm,bn", [
    pytest.param(192, 64, 128, 128, id="192-64"),
] + WIDE_CASES)
def test_symm_ignores_strict_upper_garbage(m, n, bm, bn):
    rng = _case_rng(m, n, bm, bn)
    low, garbage = _sym_with_upper_garbage(m, rng)
    b = jnp.asarray(rng.standard_normal((m, n)).astype(np.float32))
    out = ops.symm(jnp.asarray(garbage), b, bm=bm, bn=bn)
    expect = ref.symm(jnp.asarray(low), b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("m,n,bm,bn", WIDE_CASES)
def test_symm_r_matches_ref(m, n, bm, bn):
    """B·S through ``PallasOps.symm_r`` (the symm kernel on swapped
    operands) at explicit tiles, with garbage above S's diagonal."""
    from repro.core.backends.jax_backend import PallasOps
    rng = _case_rng(m, n, bm, bn)
    low, garbage = _sym_with_upper_garbage(m, rng)
    b = rng.standard_normal((n, m)).astype(np.float32)
    kops = PallasOps(lambda kind, dims: {"bm": bm, "bn": bn})
    out = kops.symm_r(jnp.asarray(b), jnp.asarray(garbage))
    full = low + np.tril(low, -1).T
    np.testing.assert_allclose(np.asarray(out), b @ full, rtol=1e-4,
                               atol=1e-3)


@pytest.mark.parametrize("m,n,bm,bn,panel", PANEL_SYMM, ids=str)
def test_symm_panel_cases_take_their_panels(m, n, bm, bn, panel):
    """The panel cases above run at the panels they are named for."""
    assert ops.symm_panel_cols(n, jnp.float32, bm, bn) == panel


def test_symm_pallas_panel_overhangs_n_and_drops_the_rest():
    """The kernel at an explicit panel that does not divide n: the last
    panel's columns beyond n are dropped, the rest match ``ref.symm``."""
    from repro.kernels.symm import symm_pallas
    rng = np.random.default_rng(43)
    low, garbage = _sym_with_upper_garbage(512, rng)
    b = jnp.asarray(rng.standard_normal((512, 7 * 128)).astype(np.float32))
    out = symm_pallas(jnp.asarray(garbage), b, bm=256, bn=128, panel=384,
                      interpret=True)
    assert out.shape == b.shape
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.symm(jnp.asarray(low), b)),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------- chain gemm ---

@pytest.mark.parametrize("m,k,l,n", [
    (128, 128, 128, 128), (130, 70, 150, 60), (256, 512, 128, 384),
])
def test_chain_gemm_matches_ref(m, k, l, n):
    a, b, c = randf(m, k), randf(k, l), randf(l, n)
    np.testing.assert_allclose(
        np.asarray(ops.chain_gemm(a, b, c)),
        np.asarray(ref.chain_gemm(a, b, c)), rtol=1e-4, atol=1e-2)


def test_chain_gemm_falls_back_above_vmem_bound():
    # Big enough that the fused kernel would exceed the VMEM bound.
    a, b, c = randf(64, 4096), randf(4096, 4096), randf(4096, 64)
    np.testing.assert_allclose(
        np.asarray(ops.chain_gemm(a, b, c)),
        np.asarray(ref.chain_gemm(a, b, c)), rtol=1e-3, atol=5e-2)


# ----------------------------------------------------------- gemm+syrk ---

@pytest.mark.parametrize("m,k,l", [
    (128, 128, 128), (130, 70, 64), (256, 384, 128), (65, 200, 130),
])
def test_gemm_syrk_matches_ref(m, k, l):
    a, b = randf(m, k), randf(k, l)
    m1 = np.asarray(a) @ np.asarray(b)
    expect = np.tril(m1 @ m1.T)
    np.testing.assert_allclose(
        np.asarray(ops.gemm_syrk(a, b)), expect, rtol=1e-4, atol=1e-2)


def test_gemm_syrk_strictly_upper_is_zero():
    a, b = randf(200, 64), randf(64, 96)
    out = np.asarray(ops.gemm_syrk(a, b))
    assert np.all(np.triu(out, 1) == 0.0)


def test_gemm_syrk_falls_back_above_vmem_bound():
    # A generator of its own: the inputs do not depend on what ran before.
    rng = np.random.default_rng(4096)
    k = 4096
    a64 = rng.standard_normal((64, k)).astype(np.float32).astype(np.float64)
    b64 = rng.standard_normal((k, 4096)).astype(np.float32).astype(np.float64)
    m1 = a64 @ b64
    want = np.tril(m1 @ m1.T)
    # float32 rounding grows with the contraction depth and the largest
    # entries (the diagonal, ~1.8e7 here), not with each entry: an entry
    # near zero carries as much absolute error as any other. So the bound
    # is sqrt(k) float32 epsilons of max|want| (about 30x the error seen).
    atol = np.sqrt(k) * np.finfo(np.float32).eps * np.abs(want).max()
    got = ops.gemm_syrk(jnp.asarray(a64, jnp.float32),
                        jnp.asarray(b64, jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=0, atol=atol)
    # The bound still catches a product that lost one 128-wide k-block.
    short = m1 - a64[:, :128] @ b64[:128]
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(np.tril(short @ short.T), want,
                                   rtol=0, atol=atol)


# --------------------------------------- pad/unpad path, every kernel ---
# Non-multiple-of-128 dims exercise the _pad_to → kernel → slice path in
# ops.py against the numpy oracle (interpret mode on this CPU container).

@settings(max_examples=8, deadline=None)
@given(m=st.integers(1, 300), k=st.integers(1, 300))
def test_syrk_hypothesis_pad_unpad(m, k):
    a = randf(m, k)
    expect = np.tril(np.asarray(a) @ np.asarray(a).T)
    np.testing.assert_allclose(np.asarray(ops.syrk(a)), expect,
                               rtol=1e-4, atol=1e-3)


@settings(max_examples=8, deadline=None)
@given(m=st.integers(1, 300), n=st.integers(1, 300))
def test_symm_hypothesis_pad_unpad(m, n):
    low = np.tril(RNG.standard_normal((m, m))).astype(np.float32)
    b = randf(m, n)
    full = low + np.tril(low, -1).T
    np.testing.assert_allclose(
        np.asarray(ops.symm(jnp.asarray(low), b)),
        full @ np.asarray(b), rtol=1e-4, atol=1e-3)


@settings(max_examples=8, deadline=None)
@given(m=st.integers(1, 200), k=st.integers(1, 200),
       l=st.integers(1, 200), n=st.integers(1, 200))
def test_chain_gemm_hypothesis_pad_unpad(m, k, l, n):
    a, b, c = randf(m, k), randf(k, l), randf(l, n)
    expect = (np.asarray(a) @ np.asarray(b)) @ np.asarray(c)
    np.testing.assert_allclose(np.asarray(ops.chain_gemm(a, b, c)),
                               expect, rtol=1e-4, atol=1e-2)


@settings(max_examples=8, deadline=None)
@given(m=st.integers(1, 200), k=st.integers(1, 200), l=st.integers(1, 200))
def test_gemm_syrk_hypothesis_pad_unpad(m, k, l):
    a, b = randf(m, k), randf(k, l)
    m1 = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(ops.gemm_syrk(a, b)),
                               np.tril(m1 @ m1.T), rtol=1e-4, atol=1e-2)


# ------------------------------------------- shape validation (no -O) ---

def test_kernels_raise_valueerror_naming_dim_and_block():
    from repro.kernels.chain_gemm import chain_gemm_pallas, gemm_syrk_pallas
    from repro.kernels.gemm import gemm_pallas
    from repro.kernels.symm import symm_pallas
    from repro.kernels.syrk import syrk_pallas
    z = jnp.zeros
    with pytest.raises(ValueError, match=r"m=130.*bm=128"):
        gemm_pallas(z((130, 128)), z((128, 128)), interpret=True)
    with pytest.raises(ValueError, match="contraction dim k"):
        gemm_pallas(z((128, 64)), z((128, 128)), interpret=True)
    with pytest.raises(ValueError, match=r"k=100.*bk=128"):
        syrk_pallas(z((128, 100)), interpret=True)
    with pytest.raises(ValueError, match=r"n=100.*bn=128"):
        symm_pallas(z((128, 128)), z((128, 100)), interpret=True)
    with pytest.raises(ValueError, match=r"panel=192.*bn=128"):
        symm_pallas(z((128, 128)), z((128, 256)), panel=192, interpret=True)
    with pytest.raises(ValueError, match=r"l=100.*bl=128"):
        chain_gemm_pallas(z((128, 128)), z((128, 100)), z((100, 128)),
                          interpret=True)
    with pytest.raises(ValueError, match=r"m=130.*bm=128"):
        gemm_syrk_pallas(z((130, 128)), z((128, 128)), interpret=True)


def test_chain_gemm_vmem_bytes_requires_dtype_bytes():
    from repro.kernels.chain_gemm import chain_gemm_vmem_bytes
    with pytest.raises(TypeError):
        chain_gemm_vmem_bytes(128, 128, 128, 128)  # no dtype_bytes
    f32 = chain_gemm_vmem_bytes(128, 256, 256, 128, dtype_bytes=4)
    bf16 = chain_gemm_vmem_bytes(128, 256, 256, 128, dtype_bytes=2)
    assert f32 > bf16  # the old hard-coded 2 halved the f32 footprint


# ------------------------------------------- fused dispatch (walker) ---

def _pallas_backend(reps=1):
    from repro.core.backends.jax_backend import PallasBackend
    return PallasBackend(reps=reps, tuning=None)


@pytest.mark.parametrize("kind,dims", [
    ("chain_gemm", (130, 70, 64, 150)),
    ("chain_gemm", (128, 128, 128, 128)),
    ("gemm_syrk", (130, 70, 64)),
    ("gemm_syrk", (256, 128, 128)),
])
def test_fused_vs_unfused_parity(kind, dims, monkeypatch):
    from repro.core.backends.base import synthetic_fused_algorithm
    backend = _pallas_backend()
    alg = synthetic_fused_algorithm(kind, dims)
    operands = backend.make_operands(alg)
    monkeypatch.delenv("REPRO_NO_FUSION", raising=False)
    assert backend.ops().fused_kinds()  # fusion on: fused launch
    fused = np.asarray(backend.execute(alg, operands))
    monkeypatch.setenv("REPRO_NO_FUSION", "1")
    assert not backend.ops().fused_kinds()  # fusion off: two kernels
    unfused = np.asarray(backend.execute(alg, operands))
    np.testing.assert_allclose(fused, unfused, rtol=1e-4, atol=1e-2)


def test_fusable_pattern_detection():
    from repro.core.backends.base import (
        fusable_pattern,
        synthetic_fused_algorithm,
    )
    chain = synthetic_fused_algorithm("chain_gemm", (128, 128, 128, 128))
    assert fusable_pattern(chain.steps[0], chain.steps[1], ()) == "gemm+gemm"
    gs = synthetic_fused_algorithm("gemm_syrk", (128, 128, 128))
    assert fusable_pattern(gs.steps[0], gs.steps[1], ()) == "gemm+syrk"
    # A later step consuming the intermediate vetoes the fusion.
    assert fusable_pattern(chain.steps[0], chain.steps[1],
                           (chain.steps[1],)) is None
    # C·(A·B) — the intermediate on the rhs — is not the chain_gemm
    # shape and must not match.
    import dataclasses
    s2 = chain.steps[1]
    swapped = dataclasses.replace(s2, lhs=s2.rhs, rhs=s2.lhs)
    assert fusable_pattern(chain.steps[0], swapped, ()) is None


def test_enumerated_gram_algorithms_fuse_and_stay_correct():
    # The real DAGs (not synthetic ones): every enumerated algorithm of a
    # gram family must produce identical results with fusion on and off.
    from repro.core import enumerate_algorithms, gram_times
    backend = _pallas_backend()
    A = randf(130, 100)
    B = randf(130, 64)
    for alg in enumerate_algorithms(gram_times(130, 100, 64)):
        fn = backend.build(alg)
        out = np.asarray(fn(A, A, B))
        expect = (np.asarray(A) @ np.asarray(A).T) @ np.asarray(B)
        np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-2)


# ------------------------------------- shape-chosen tiles (no table) ---

M, N, K = 1024, 768, 640


@pytest.mark.parametrize("kind", ["gemm", "syrk", "symm", "symm_r"])
def test_shape_chosen_tiles_match_numpy(kind):
    """``PallasBackend`` with auto tuning and no table runs the kernels at
    tiles chosen from the shape (512, 256 and 128 edges mixed here)."""
    from repro.core.backends.jax_backend import PallasBackend
    backend = PallasBackend(reps=1)
    assert backend.tuning_table() is None
    kops = backend.ops()
    a, b = np.asarray(randf(M, K)), np.asarray(randf(M, N))
    s = np.asarray(randf(M, M))
    s = (s + s.T) / 2
    if kind == "gemm":
        tiles = kops._cfg("gemm", (M, N, K))
        out, expect = kops.gemm(a, b[:K]), a @ b[:K]
    elif kind == "syrk":
        tiles = kops._cfg("syrk", (M, K))
        out, expect = kops.syrk(a), np.tril(a @ a.T)
    elif kind == "symm":
        tiles = kops._cfg("symm", (M, N))
        out, expect = kops.symm(np.tril(s), b), s @ b
    else:
        tiles = kops._cfg("symm", (M, K))
        out, expect = kops.symm_r(a.T, np.tril(s)), a.T @ s
    assert max(tiles.values()) == 512 and min(tiles.values()) < 512, tiles
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4,
                               atol=1e-3)


# ------------------------------------------------------ flash attention ---

@pytest.mark.parametrize("kwargs", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, logit_softcap=30.0),
    dict(causal=True, window=128),
    dict(causal=True, window=64, logit_softcap=20.0),
])
def test_flash_attention_variants(kwargs):
    B, H, Hkv, S, D = 2, 4, 2, 256, 64
    q = randf(B, H, S, D, scale=0.3)
    k = randf(B, Hkv, S, D, scale=0.3)
    v = randf(B, Hkv, S, D)
    np.testing.assert_allclose(
        np.asarray(ops.flash_attention(q, k, v, **kwargs)),
        np.asarray(ref.flash_attention(q, k, v, **kwargs)),
        rtol=1e-4, atol=1e-4)


def test_flash_attention_mha_no_gqa():
    B, H, S, D = 1, 2, 384, 32
    q = randf(B, H, S, D, scale=0.3)
    k = randf(B, H, S, D, scale=0.3)
    v = randf(B, H, S, D)
    np.testing.assert_allclose(
        np.asarray(ops.flash_attention(q, k, v)),
        np.asarray(ref.flash_attention(q, k, v)), rtol=1e-4, atol=1e-4)


def test_flash_attention_odd_seq_falls_back():
    # No silent switch to the reference: callers gate on s % 128
    # (attention.apply_train), and the kernel refuses anything else.
    B, H, S, D = 1, 2, 100, 32
    q = randf(B, H, S, D, scale=0.3)
    k = randf(B, H, S, D, scale=0.3)
    v = randf(B, H, S, D)
    with pytest.raises(ValueError, match="divisible by 128"):
        ops.flash_attention(q, k, v)


# ------------------------------------------- chunked attention (train) ---

def test_chunked_attention_value_and_grad_match_dense():
    from repro.models import attention
    from repro.models.attention import AttnConfig
    B, H, Hkv, S, D = 1, 4, 2, 1024, 32
    q = randf(B, S, H, D, scale=0.3)
    k = randf(B, S, Hkv, D, scale=0.3)
    v = randf(B, S, Hkv, D)
    for kwargs in (dict(), dict(window=256), dict(logit_softcap=40.0)):
        acfg = AttnConfig(d_model=H * D, n_heads=H, n_kv_heads=Hkv,
                          head_dim=D, **kwargs)

        def f_c(q, k, v):
            return jnp.sum(attention.chunked_attention(acfg, q, k, v) ** 2)

        def f_d(q, k, v):
            return jnp.sum(attention._dense_attention(acfg, q, k, v) ** 2)

        v1, g1 = jax.value_and_grad(f_c, argnums=(0, 1, 2))(q, k, v)
        v2, g2 = jax.value_and_grad(f_d, argnums=(0, 1, 2))(q, k, v)
        assert abs(v1 - v2) / abs(v2) < 1e-5
        # dq accumulates in f32 (strict); dk/dv partials are emitted bf16
        # per block (the collective-halving §Perf trade) → loose tolerance.
        np.testing.assert_allclose(np.asarray(g1[0]), np.asarray(g2[0]),
                                   rtol=1e-3, atol=1e-4)
        for a, b in zip(g1[1:], g2[1:]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-2, atol=3e-2)


# -------------------------------------------------------- planner+pallas --

def test_jax_runner_pallas_path_matches_jnp_path():
    from repro.core import enumerate_algorithms, gram_times
    from repro.core.runners import JaxRunner
    algos = enumerate_algorithms(gram_times(128, 192, 64))
    A = randf(128, 192)
    B = randf(128, 64)
    for a in algos:
        fn_ref = JaxRunner(use_pallas=False).build(a)
        fn_pl = JaxRunner(use_pallas=True).build(a)
        np.testing.assert_allclose(
            np.asarray(fn_pl(A, A, B)), np.asarray(fn_ref(A, A, B)),
            rtol=1e-4, atol=1e-2)
