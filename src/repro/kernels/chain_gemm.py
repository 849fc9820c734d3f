"""Fused matrix-chain kernel: (A·B)·C without an HBM round-trip.

Beyond-paper optimization. The paper's cost model (and its BLAS execution)
materializes every intermediate in main memory; on TPU the intermediate
``M₁ = A·B`` tile can stay in VMEM. For chain instances where M₁ is large
relative to the final output (e.g. the paper's anomaly at
d = (331, 279, 338, 854, 427): M₁ is 331×338 but feeds an 854-wide
contraction), the eliminated ``2·m·l`` HBM traffic moves the memory-roofline
term directly.

Layout: grid ``(M/bm, N/bn)``. For each output row-block i, the fused
intermediate row ``M₁[i, :] = A[i, :]·B`` is computed once (at j == 0) into
a persistent VMEM scratch of shape (bm, L), then every j-step contracts it
with ``C[:, j]``. B and C stream through VMEM in (bk/bl)-sized slabs via
``lax.fori_loop`` + ``pl.ds`` dynamic slices.

VMEM: B is resident whole, next to the (bm, L) fp32 scratch and the
double-buffered A/C/output blocks (:func:`chain_gemm_vmem_bytes`).
``ops.chain_gemm`` falls back to two ``gemm`` calls where that exceeds the
device's kernel VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._checks import check_divisible, check_same
from ._mxu import dot


def _chain_kernel(a_ref, b_ref, c_ref, o_ref, m1_ref, *, bk: int, bl: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _compute_intermediate():
        k_total = a_ref.shape[1]
        l_total = b_ref.shape[1]

        def k_body(kk, acc):
            a_slab = a_ref[:, pl.ds(kk * bk, bk)]
            b_slab = b_ref[pl.ds(kk * bk, bk), :]
            return acc + dot(a_slab, b_slab)

        acc0 = jnp.zeros((a_ref.shape[0], l_total), dtype=jnp.float32)
        m1_ref[...] = jax.lax.fori_loop(0, k_total // bk, k_body, acc0)

    l_total = b_ref.shape[1]

    def l_body(ll, acc):
        m1_slab = m1_ref[:, pl.ds(ll * bl, bl)]
        c_slab = c_ref[pl.ds(ll * bl, bl), :]
        return acc + dot(m1_slab.astype(c_slab.dtype), c_slab)

    acc0 = jnp.zeros_like(o_ref, dtype=jnp.float32)
    out = jax.lax.fori_loop(0, l_total // bl, l_body, acc0)
    o_ref[...] = out.astype(o_ref.dtype)


def chain_gemm_pallas(
    a: jax.Array,   # (m, k)
    b: jax.Array,   # (k, l)
    c: jax.Array,   # (l, n)
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    bl: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """(A@B)@C fused; all dims must divide their block size."""
    m, k = a.shape
    k2, l = b.shape
    l2, n = c.shape
    check_same("chain_gemm_pallas", "contraction dim k",
               ("A.shape[1]", k), ("B.shape[0]", k2))
    check_same("chain_gemm_pallas", "contraction dim l",
               ("B.shape[1]", l), ("C.shape[0]", l2))
    check_divisible("chain_gemm_pallas",
                    ("m", m, "bm", bm), ("n", n, "bn", bn),
                    ("k", k, "bk", bk), ("l", l, "bl", bl))

    return pl.pallas_call(
        functools.partial(_chain_kernel, bk=bk, bl=bl),
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),   # A row-block
            pl.BlockSpec((k, l), lambda i, j: (0, 0)),    # B resident
            pl.BlockSpec((l, bn), lambda i, j: (0, j)),   # C col-block
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, l), jnp.float32)],
        interpret=interpret,
    )(a, b, c)


def _highest_precision_bytes(bm, bk, dtype_bytes) -> int:
    """Scoped VMEM that float32 dots at ``HIGHEST`` precision add.

    Mosaic splits float32 slab operands into bf16 parts. Compiling for
    v5e measured 0.23-0.25 MiB at 128 tiles and up to 1.3 MiB at 256
    tiles, growing with the K-loop slab (bm, bk) and not with l; six
    (bm, bk) float32 tiles bound every measurement.
    """
    return 6 * bm * bk * 4 if dtype_bytes == 4 else 0


def chain_gemm_vmem_bytes(m, k, l, n, bm=128, bn=128, bk=128, *,
                          dtype_bytes) -> int:
    """VMEM the fused kernel needs on a TPU (the wrapper's fallback test).

    Mosaic double-buffers every block whose index moves across the grid
    (the A row-block, the C column-block, the output block) and holds the
    grid-invariant B block once. On top come the (bm, l) fp32 scratch and
    two (bm, l) fp32 values live in the K loop (its carry and the product
    added to it), and for float32 operands the split of ``HIGHEST``
    precision. Compiling for v5e matches this count at every boundary
    shape in tests/test_tpu_compile.py; where one K slab makes the loop
    trivial it over-counts, and the wrapper then falls back needlessly.

    ``dtype_bytes`` is keyword-required with no default: callers must
    pass the actual operand itemsize (``a.dtype.itemsize``).
    """
    blocks = 2 * (bm * k + l * bn + bm * bn) * dtype_bytes
    return (blocks + k * l * dtype_bytes + 3 * bm * l * 4
            + _highest_precision_bytes(bm, bk, dtype_bytes))


def _gemm_syrk_kernel(ii_ref, jj_ref, a_i_ref, a_j_ref, b_ref, o_ref,
                      m1i_ref, m1j_ref, *, bk: int, bm: int):
    t = pl.program_id(0)
    i = ii_ref[t]
    j = jj_ref[t]
    k_total = a_i_ref.shape[1]

    def _m1(a_ref, out_ref):
        # Row-block of the intermediate M₁ = A·B, built K-slab by K-slab.
        def k_body(kk, acc):
            a_slab = a_ref[:, pl.ds(kk * bk, bk)]
            b_slab = b_ref[pl.ds(kk * bk, bk), :]
            return acc + dot(a_slab, b_slab)

        acc0 = jnp.zeros((a_ref.shape[0], b_ref.shape[1]),
                         dtype=jnp.float32)
        out_ref[...] = jax.lax.fori_loop(0, k_total // bk, k_body, acc0)

    _m1(a_i_ref, m1i_ref)
    _m1(a_j_ref, m1j_ref)
    acc = dot(m1i_ref[...], m1j_ref[...].T)
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 1)
    masked = jnp.where(rows >= cols, acc, 0.0)
    o_ref[...] = jnp.where(i == j, masked, acc).astype(o_ref.dtype)


def gemm_syrk_pallas(
    a: jax.Array,   # (m, k)
    b: jax.Array,   # (k, l)
    *,
    bm: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Lower triangle of (A·B)(A·B)ᵀ without materializing M₁ = A·B in HBM.

    The GEMM+SYRK epilogue fusion: SYRK's triangular block grid (scalar-
    prefetched ``ii[t], jj[t]`` as in :mod:`repro.kernels.syrk`), but each
    program rebuilds the two M₁ row-blocks it contracts from A and B in
    VMEM. Trades ``2·bm·k·l`` recompute FLOPs per program for the full
    ``m·l`` HBM round-trip of the intermediate — the same trade
    :func:`chain_gemm_pallas` makes, with SYRK's half-grid savings kept.
    """
    m, k = a.shape
    k2, l = b.shape
    check_same("gemm_syrk_pallas", "contraction dim k",
               ("A.shape[1]", k), ("B.shape[0]", k2))
    check_divisible("gemm_syrk_pallas",
                    ("m", m, "bm", bm), ("k", k, "bk", bk),
                    ("l", l, "lane", 128))
    mt = m // bm
    ii, jj = np.tril_indices(mt)
    ii = jnp.asarray(ii, dtype=jnp.int32)
    jj = jnp.asarray(jj, dtype=jnp.int32)
    t_blocks = int(ii.shape[0])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t_blocks,),
        in_specs=[
            # A block-row i and block-row j (full K extent, slabbed in-kernel)
            pl.BlockSpec((bm, k), lambda t, ii, jj: (ii[t], 0)),
            pl.BlockSpec((bm, k), lambda t, ii, jj: (jj[t], 0)),
            # B stays fully VMEM-resident across the grid
            pl.BlockSpec((k, l), lambda t, ii, jj: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bm), lambda t, ii, jj: (ii[t], jj[t])),
        scratch_shapes=[pltpu.VMEM((bm, l), jnp.float32),
                        pltpu.VMEM((bm, l), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_gemm_syrk_kernel, bk=bk, bm=bm),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, m), a.dtype),
        interpret=interpret,
    )(ii, jj, a, a, b)
    # Strictly-upper blocks are never written; zero them like syrk_pallas.
    return jnp.tril(out)


def gemm_syrk_vmem_bytes(m, k, l, bm=128, bk=128, *, dtype_bytes) -> int:
    """VMEM the fused GEMM+SYRK kernel needs on a TPU.

    The same count as :func:`chain_gemm_vmem_bytes`: the two A row-blocks
    and the output block double-buffered, the grid-invariant B once, two
    (bm, l) fp32 scratches, one (bm, l) fp32 value live in the K loop,
    and the float32 ``HIGHEST`` split. Checked against the compiler at
    bm = 128 only: at bm = 256 Mosaic needs 2.8 MiB (l = 1,024) to
    10.5 MiB (l = 4,096) more than this in bf16.
    """
    blocks = 2 * (2 * bm * k + bm * bm) * dtype_bytes
    return (blocks + k * l * dtype_bytes + 3 * bm * l * 4
            + _highest_precision_bytes(bm, bk, dtype_bytes))
