"""The matmul precision of the linear-algebra kernels and the jnp backend.

A TPU's default matmul precision rounds float32 operands to bfloat16 for
one MXU pass, which leaves a float32 program with bfloat16 accuracy.
``HIGHEST`` (XLA's multi-pass float32, Mosaic's fp32 contract precision)
keeps float32 operands float32. bfloat16 operands are exact in one pass
and keep the default.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def precision(*arrays):
    """``HIGHEST`` where any operand is float32, else the default."""
    if any(a.dtype == jnp.float32 for a in arrays):
        return jax.lax.Precision.HIGHEST
    return None


def dot(a, b):
    """``a @ b`` with float32 accumulation, at :func:`precision`."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=precision(a, b))
