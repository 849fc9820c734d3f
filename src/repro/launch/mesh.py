"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch jax device state — required because
the dry-run must set XLA_FLAGS before any jax initialization.

Mesh shapes (assignment):
  single-pod: (16, 16)      axes (data, model)   — 256 chips
  multi-pod:  (2, 16, 16)   axes (pod, data, model) — 512 chips

Axis semantics: ``data`` carries DP + FSDP (param/optimizer ZeRO-3
sharding); ``model`` carries TP/EP; ``pod`` is the cross-DCN data-parallel
replica axis (gradient all-reduce crosses it once per step — the axis
gradient compression targets).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the real local devices (tests / examples)."""
    n = jax.device_count()
    model = min(model, n)
    return _make_mesh((n // model, model), ("data", "model"))
