"""SYMM Pallas kernel: C = S·B with S symmetric, stored as lower triangle.

The paper's SYMM halves memory traffic for S by reading one triangle. On
TPU we do the same at block granularity: the BlockSpec index map fetches
S-block ``(max(i,l), min(i,l))`` — always from the lower triangle — and the
kernel transposes the tile in-register when the logical block lies above
the diagonal (``l > i``). Diagonal blocks are symmetrized in-register from
their stored lower triangle.

HBM traffic for S is thus ``m(m+1)/2`` elements instead of ``m²`` — the
exact asymmetry (SYMM vs GEMM efficiency) whose interplay with FLOP counts
produces the paper's AAᵀB anomalies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._checks import check_divisible, check_same
from ._mxu import dot


def _symm_kernel(s_ref, b_ref, o_ref, acc_ref, *, k_steps: int, bm: int):
    i = pl.program_id(0)
    l = pl.program_id(2)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tile = s_ref[...]
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 1)
    lower = jnp.where(rows >= cols, tile, 0.0)
    # Diagonal block: symmetrize the stored lower triangle.
    sym = lower + jnp.where(rows > cols, tile, 0.0).T
    # Off-diagonal: stored block is (max(i,l), min(i,l)); transpose if the
    # logical block is in the upper triangle (l > i).
    eff = jnp.where(i == l, sym, jnp.where(i > l, tile, tile.T))
    acc_ref[...] += dot(eff.astype(b_ref.dtype), b_ref[...])

    @pl.when(l == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def symm_pallas(
    s_lower: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """C[m,n] = sym(S)·B with S stored lower-triangular; m % bm == 0."""
    m, m2 = s_lower.shape
    mb, n = b.shape
    check_same("symm_pallas", "symmetric dim m",
               ("S.shape[0]", m), ("S.shape[1]", m2), ("B.shape[0]", mb))
    check_divisible("symm_pallas", ("m", m, "bm", bm), ("n", n, "bn", bn))
    k_steps = m // bm

    return pl.pallas_call(
        functools.partial(_symm_kernel, k_steps=k_steps, bm=bm),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            # Always fetch from the lower triangle: block (max(i,l), min(i,l))
            pl.BlockSpec(
                (bm, bm),
                lambda i, j, l: (jnp.maximum(i, l), jnp.minimum(i, l)),
            ),
            pl.BlockSpec((bm, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), b.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(s_lower, b)
