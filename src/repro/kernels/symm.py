"""SYMM Pallas kernel: C = S·B with S symmetric, stored as lower triangle.

The paper's SYMM halves memory traffic for S by reading one triangle. On
TPU we do the same at block granularity: the BlockSpec index map fetches
S-block ``(max(i,l), min(i,l))`` — always from the lower triangle — and
each grid step does only the work its logical block ``(i, l)`` needs: below
the diagonal (``l < i``) a plain product, above it (``l > i``) the product
with the transposed tile, and on it the tile mirrored in-register from its
stored lower triangle (whatever the strict upper triangle holds is ignored).

Each grid step multiplies one S tile by a *panel* of output columns, as
wide as the kernel VMEM budget allows (:func:`symm_panel`), not by one
``bn`` column block: S is fetched ``⌈n/panel⌉`` times instead of ``n/bn``,
about ``m²·⌈n/panel⌉`` elements of HBM traffic instead of ``m²·n/bn``.
Where no panel of whole blocks divides n, the last one overhangs it: its
columns beyond n are computed on whatever the pipeline holds there and
dropped on write (:data:`MAX_OVERHANG` bounds that work).

Within one S fetch the kernel reads one triangle, ``m(m+1)/2`` elements
instead of ``m²`` — the exact asymmetry (SYMM vs GEMM efficiency) whose
interplay with FLOP counts produces the paper's AAᵀB anomalies.
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

    @pl.when(l < i)
    def _below():
        acc_ref[...] += dot(s_ref[...].astype(b_ref.dtype), b_ref[...])

    @pl.when(l == i)
    def _diagonal():
        tile = s_ref[...]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bm), 1)
        sym = jnp.where(rows >= cols, tile, tile.T)
        acc_ref[...] += dot(sym.astype(b_ref.dtype), b_ref[...])

    @pl.when(l > i)
    def _above():
        # Stored block (l, i) is the transpose of the logical block (i, l).
        acc_ref[...] += dot(s_ref[...].T.astype(b_ref.dtype), b_ref[...])

    @pl.when(l == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


#: Most work on overhang columns a panel may add, as a share of n.
MAX_OVERHANG = 0.03


def symm_vmem_bytes(bm: int, panel: int, *, dtype_bytes: int) -> int:
    """VMEM the kernel needs at row edge ``bm`` and ``panel`` columns.

    Mosaic double-buffers the S tile, the B panel and the output panel;
    on top come the fp32 accumulator, the diagonal's mirrored tile and,
    for float32 operands, the split of ``HIGHEST`` precision (bounded as
    in ``chain_gemm_vmem_bytes``, with S's edge as the contraction).
    Compiling for v5e accepts every panel this admits.
    """
    blocks = 2 * (bm * bm + 2 * bm * panel) * dtype_bytes
    highest = 6 * bm * bm * 4 if dtype_bytes == 4 else 0
    return blocks + bm * panel * 4 + bm * bm * dtype_bytes + highest


@functools.lru_cache(maxsize=4096)
def symm_panel(n: int, bm: int, bn: int, *, dtype_bytes: int,
               budget: int) -> int:
    """Columns of one grid step for an ``n``-column B (``n % bn == 0``).

    The fewest panels of whole ``bn`` blocks, each as wide as
    ``⌈(n/bn)/panels⌉`` blocks, that fit ``budget`` and leave under
    :data:`MAX_OVERHANG` of n beyond the last panel; ``bn`` where no wider
    panel fits.
    """
    nb = n // bn
    for panels in range(1, nb):
        blocks = -(-nb // panels)
        if (panels * blocks - nb) < MAX_OVERHANG * nb and symm_vmem_bytes(
                bm, blocks * bn, dtype_bytes=dtype_bytes) <= budget:
            return blocks * bn
    return bn


def symm_pallas(
    s_lower: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    panel: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """C[m,n] = sym(S)·B with S stored lower-triangular; m % bm == 0,
    n % bn == 0. Each grid step covers ``panel`` columns (default ``bn``),
    a multiple of ``bn`` that need not divide n."""
    m, m2 = s_lower.shape
    mb, n = b.shape
    check_same("symm_pallas", "symmetric dim m",
               ("S.shape[0]", m), ("S.shape[1]", m2), ("B.shape[0]", mb))
    panel = bn if panel is None else panel
    check_divisible("symm_pallas", ("m", m, "bm", bm), ("n", n, "bn", bn),
                    ("panel", panel, "bn", bn))
    k_steps = m // bm

    return pl.pallas_call(
        functools.partial(_symm_kernel, k_steps=k_steps, bm=bm),
        grid=(m // bm, pl.cdiv(n, panel), k_steps),
        in_specs=[
            # Always fetch from the lower triangle: block (max(i,l), min(i,l))
            pl.BlockSpec(
                (bm, bm),
                lambda i, j, l: (jnp.maximum(i, l), jnp.minimum(i, l)),
            ),
            pl.BlockSpec((bm, panel), lambda i, j, l: (l, j)),
        ],
        out_specs=pl.BlockSpec((bm, panel), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), b.dtype),
        scratch_shapes=[pltpu.VMEM((bm, panel), jnp.float32)],
        interpret=interpret,
    )(s_lower, b)
