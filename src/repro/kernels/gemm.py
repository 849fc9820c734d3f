"""Blocked GEMM Pallas kernel — the paper's workhorse, TPU edition.

Grid ``(M/bm, N/bn, K/bk)``; each program multiplies a ``(bm, bk)`` A-tile
with a ``(bk, bn)`` B-tile on the MXU and accumulates into an fp32 VMEM
scratch tile that persists across the K grid dimension (last-minor iteration
order on TPU). Block shapes default to 128 — the MXU edge — which the
perf model (core/perfmodel.py) assumes when charging quantized block work.

VMEM footprint per program: bm·bk + bk·bn + 2·bm·bn fp32 words
(= 256 KiB at 128³), far under the v5e budget, leaving room for the
double-buffered pipeline Mosaic inserts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._checks import check_divisible, check_same
from ._mxu import dot


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += dot(a_ref[...], b_ref[...])

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def gemm_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    pipeline: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """C[m,n] = A[m,k] @ B[k,n]. Dims must divide the block shape —
    ``ops.gemm`` pads and unpads around this core.

    ``pipeline=1`` annotates the grid with Mosaic ``dimension_semantics``
    (M/N parallel, K arbitrary) so the compiler may reorder/parallelize
    the output-tile dimensions; the autotuner probes this knob on the
    winning tile shape. Ignored (harmless) in interpret mode.
    """
    m, k = a.shape
    k2, n = b.shape
    check_same("gemm_pallas", "contraction dim k",
               ("A.shape[1]", k), ("B.shape[0]", k2))
    check_divisible("gemm_pallas",
                    ("m", m, "bm", bm), ("n", n, "bn", bn),
                    ("k", k, "bk", bk))
    k_steps = k // bk
    extra = {}
    if pipeline:
        extra["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(
        functools.partial(_gemm_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, l: (i, l)),
            pl.BlockSpec((bk, bn), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        **extra,
    )(a, b)
