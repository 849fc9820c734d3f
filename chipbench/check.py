"""Inputs from the seed, the plain reference, its control, the comparison.

The reference computes an expression in float32 at ``HIGHEST`` precision.
The control is the same reference with every stored array (inputs,
intermediates, output) rounded to float8 e4m3, scaled per array to its
range, with float32 accumulation: the precision below the bfloat16 the
configurations state. ``correct`` compares the widest gap between an
answer and the reference, as a share of the reference's largest entry.
"""

from __future__ import annotations

import functools

import numpy as np

F8_MAX = 448.0  # largest finite float8 e4m3fn


def seed32(seed: int) -> int:
    """A 32-bit key seed from any whole number (``--seed`` may exceed 2**31)."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def device_normals(shapes, dtype, seed: int, device):
    """Standard normal arrays of ``shapes`` in ``dtype``, made on ``device``
    in one jitted call from ``seed``."""
    import jax
    import jax.numpy as jnp

    shapes = tuple(tuple(s) for s in shapes)

    def make(key):
        keys = jax.random.split(key, len(shapes))
        return [jax.random.normal(k, s, jnp.float32).astype(dtype)
                for k, s in zip(keys, shapes)]

    key = jax.device_put(jax.random.key(seed32(seed)), device)
    return jax.jit(make)(key)


def _exact(x):
    import jax.numpy as jnp
    return x.astype(jnp.float32)


def _fp8(x):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(a, b):
    import jax
    import jax.numpy as jnp
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.lru_cache(maxsize=None)
def _reference_fn(expr, control: bool):
    import jax
    return jax.jit(functools.partial(expr.reference, matmul=_matmul,
                                     store=_fp8 if control else _exact))


def reference(expr, ops: dict, control: bool = False):
    """The expression on base-keyed operands: float32, or the control."""
    return _reference_fn(expr, control)(ops)


@functools.lru_cache(maxsize=None)
def _gap_fn():
    import jax
    import jax.numpy as jnp

    def gap(out, ref):
        diff = jnp.abs(out.astype(jnp.float32) - ref)
        return jnp.max(diff) / jnp.max(jnp.abs(ref))

    return jax.jit(gap)


def rel_gap(out, ref) -> float:
    """max|out − ref| / max|ref|; ``inf`` for a missing or non-finite answer."""
    if out is None or tuple(out.shape) != tuple(ref.shape):
        return float("inf")
    g = float(_gap_fn()(out, ref))
    return g if np.isfinite(g) else float("inf")
