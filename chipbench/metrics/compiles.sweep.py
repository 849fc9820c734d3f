"""compiles.sweep: JAX traces plus compiles (``jax.traces`` +
``jax.compiles``) in the traced window of a sweep run (layer: device)."""

from chipbench import spans


def read(rec):
    snap = spans.recorded(rec, "sweep", "sweep.point")
    return None if snap is None else spans.compiles(snap)
