"""Public jit'd wrappers for the Pallas kernels.

Responsibilities:
  * pad operands to block multiples (MXU 128-alignment) and slice results —
    the quantization the perf model charges for is made explicit here;
  * compile with Mosaic on a TPU and run in interpret mode on the CPU (the
    kernel body runs in Python there); any other platform is an error;
  * fall back to two unfused kernels where a fused kernel would exceed the
    device's kernel VMEM budget (:func:`repro.core.perfmodel.hardware_spec`),
    and size the symm kernel's column panel to that budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .chain_gemm import (
    chain_gemm_pallas,
    chain_gemm_vmem_bytes,
    gemm_syrk_pallas,
    gemm_syrk_vmem_bytes,
)
from .flash_attention import flash_attention_pallas
from .gemm import gemm_pallas
from .symm import symm_pallas, symm_panel
from .syrk import syrk_pallas


def _interpret() -> bool:
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas TPU kernels run on 'tpu' (Mosaic) or 'cpu' (interpret "
            f"mode), not on {platform!r}")
    return platform == "cpu"


def _vmem_budget() -> int:
    from repro.core.perfmodel import hardware_spec
    return hardware_spec().vmem_bytes


def _pad_to(x: jax.Array, mults) -> jax.Array:
    pads = []
    for dim, q in zip(x.shape, mults):
        pads.append((0, (-dim) % q))
    if any(p[1] for p in pads):
        return jnp.pad(x, pads)
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "pipeline"))
def gemm(a: jax.Array, b: jax.Array, bm: int = 128, bn: int = 128,
         bk: int = 128, pipeline: int = 0) -> jax.Array:
    m, k = a.shape
    _, n = b.shape
    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b, (bk, bn))
    out = gemm_pallas(ap, bp, bm=bm, bn=bn, bk=bk, pipeline=pipeline,
                      interpret=_interpret())
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bk"))
def syrk(a: jax.Array, bm: int = 128, bk: int = 128) -> jax.Array:
    m, _ = a.shape
    ap = _pad_to(a, (bm, bk))
    out = syrk_pallas(ap, bm=bm, bk=bk, interpret=_interpret())
    return out[:m, :m]


def symm_panel_cols(n: int, dtype, bm: int = 128, bn: int = 128) -> int:
    """Output columns one S tile serves in :func:`symm` of an ``n``-column
    B with operands of ``dtype``: the kernel's panel at this device's VMEM
    budget."""
    return symm_panel(-(-n // bn) * bn, bm, bn,
                      dtype_bytes=jnp.dtype(dtype).itemsize,
                      budget=_vmem_budget())


@functools.partial(jax.jit, static_argnames=("bm", "bn"))
def symm(s_lower: jax.Array, b: jax.Array, bm: int = 128,
         bn: int = 128) -> jax.Array:
    m, _ = s_lower.shape
    _, n = b.shape
    sp = _pad_to(s_lower, (bm, bm))
    bp = _pad_to(b, (bm, bn))
    out = symm_pallas(sp, bp, bm=bm, bn=bn,
                      panel=symm_panel_cols(
                          n, jnp.result_type(s_lower, b), bm, bn),
                      interpret=_interpret())
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "bl"))
def chain_gemm(a: jax.Array, b: jax.Array, c: jax.Array, bm: int = 128,
               bn: int = 128, bk: int = 128, bl: int = 128) -> jax.Array:
    m, _ = a.shape
    _, n = c.shape
    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b, (bk, bl))
    cp = _pad_to(c, (bl, bn))
    need = chain_gemm_vmem_bytes(*ap.shape, *cp.shape, bm, bn, bk,
                                 dtype_bytes=a.dtype.itemsize)
    if need > _vmem_budget():
        return gemm(gemm(a, b), c)
    out = chain_gemm_pallas(ap, bp, cp, bm=bm, bn=bn, bk=bk, bl=bl,
                            interpret=_interpret())
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("bm", "bk"))
def gemm_syrk(a: jax.Array, b: jax.Array, bm: int = 128,
              bk: int = 128) -> jax.Array:
    """Lower triangle of (A·B)(A·B)ᵀ, fused (GEMM+SYRK epilogue)."""
    m, _ = a.shape
    ap = _pad_to(a, (bm, bk))
    bp = _pad_to(b, (bk, 128))
    need = gemm_syrk_vmem_bytes(*ap.shape, bp.shape[1], bm, bk,
                                dtype_bytes=a.dtype.itemsize)
    if need > _vmem_budget():
        return syrk(gemm(a, b))
    out = gemm_syrk_pallas(ap, bp, bm=bm, bk=bk, interpret=_interpret())
    return out[:m, :m]


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "logit_softcap", "window", "bq", "bkv"))
def flash_attention(q, k, v, causal: bool = True, scale=None,
                    logit_softcap: float = 0.0, window: int = 0,
                    bq: int = 128, bkv: int = 128) -> jax.Array:
    s = q.shape[2]
    if s % 128:
        raise ValueError(
            f"flash_attention needs a sequence length divisible by 128, "
            f"got {s}")
    if s % bq or s % bkv:
        bq = bkv = 128
    return flash_attention_pallas(
        q, k, v, causal=causal, scale=scale, logit_softcap=logit_softcap,
        window=window, bq=bq, bkv=bkv, interpret=_interpret())


def tri2full(t: jax.Array) -> jax.Array:
    """Pure data movement (paper charges 0 FLOPs); no kernel needed —
    XLA's fused tril/transpose is already bandwidth-bound."""
    return ref.tri2full(t)
