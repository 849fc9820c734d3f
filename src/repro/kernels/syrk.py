"""SYRK Pallas kernel: lower triangle of A·Aᵀ, triangular block grid.

The paper's FLOP asymmetry — SYRK costs (m+1)·m·k vs GEMM's 2·m²·k — is
realized on TPU by iterating only the lower-triangular *block* grid: for an
``mt×mt`` block matrix we run ``T = mt(mt+1)/2`` programs instead of
``mt²``, each contracting over K. MKL does the same thing with cache
blocks; on TPU the unit is the 128×128 MXU tile.

The triangular index space is linearized with **scalar prefetch**
(`pltpu.PrefetchScalarGridSpec`): host-computed index vectors ``ii[t], jj[t]``
map the flat grid coordinate ``t`` to block row/column, so BlockSpec index
maps stay affine — the TPU-idiomatic replacement for the non-rectangular
loop nests a CPU BLAS would use.

Strictly-upper output blocks are never touched by any program; they are
zero-initialized by the wrapper so the result equals ``jnp.tril(A @ A.T)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._checks import check_divisible
from ._mxu import dot


def _syrk_kernel(ii_ref, jj_ref, a_ref, at_ref, o_ref, acc_ref,
                 *, k_steps: int, bm: int):
    t = pl.program_id(0)
    i = ii_ref[t]
    j = jj_ref[t]

    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += dot(a_ref[...], at_ref[...].T)

    @pl.when(pl.program_id(1) == k_steps - 1)
    def _flush():
        acc = acc_ref[...]
        # Diagonal blocks: mask strictly-upper entries so the output is a
        # clean lower triangle (off-diagonal blocks are fully kept).
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 1)
        masked = jnp.where(rows >= cols, acc, 0.0)
        o_ref[...] = jnp.where(i == j, masked, acc).astype(o_ref.dtype)


def syrk_pallas(
    a: jax.Array,
    *,
    bm: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Lower triangle of A[m,k] @ A[m,k]ᵀ; m % bm == 0, k % bk == 0."""
    m, k = a.shape
    check_divisible("syrk_pallas", ("m", m, "bm", bm), ("k", k, "bk", bk))
    mt = m // bm
    k_steps = k // bk
    # Host-side triangular index vectors (scalar-prefetched).
    ii, jj = np.tril_indices(mt)
    ii = jnp.asarray(ii, dtype=jnp.int32)
    jj = jnp.asarray(jj, dtype=jnp.int32)
    t_blocks = int(ii.shape[0])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(t_blocks, k_steps),
        in_specs=[
            # A block-row i tile: (bm, bk) at block (ii[t], l)
            pl.BlockSpec((bm, bk), lambda t, l, ii, jj: (ii[t], l)),
            # A block-row j tile (the transposed operand): (bm, bk)
            pl.BlockSpec((bm, bk), lambda t, l, ii, jj: (jj[t], l)),
        ],
        out_specs=pl.BlockSpec((bm, bm), lambda t, l, ii, jj: (ii[t], jj[t])),
        scratch_shapes=[pltpu.VMEM((bm, bm), jnp.float32)],
    )

    kernel = functools.partial(_syrk_kernel, k_steps=k_steps, bm=bm)

    def _run(x):
        # Contract a_i · a_jᵀ: pass A twice; kernel dots (bm,bk)·(bk,bm).
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, m), x.dtype),
            interpret=interpret,
        )(ii, jj, x, x)

    out = _run(a)
    # Programs only write lower-tri blocks; zero the untouched upper blocks.
    return jnp.tril(out)


def _syrk_kernel_docflops(m: int, k: int) -> int:
    """Block-quantized MXU work actually scheduled (for the perf model)."""
    mt = (m + 127) // 128
    return (mt * (mt + 1) // 2) * ((k + 127) // 128) * 2 * 128 ** 3
