"""compiles.eval: JAX traces plus compiles (``jax.traces`` +
``jax.compiles``) in the traced window of a plan run (layer: device)."""

from chipbench import spans


def read(rec):
    snap = spans.recorded(rec, "plan", "planner.call")
    return None if snap is None else spans.compiles(snap)
