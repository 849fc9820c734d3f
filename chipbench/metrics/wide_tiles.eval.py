"""wide_tiles.eval: share of the step walker's kernel calls whose tiles
were chosen from the shape with an edge over 128 (``repro.tiles.shaped``)
among all of them (``repro.step.{gemm,syrk,symm,symm_r,chain_gemm,
gemm_syrk}``), in % (layer: kernel wrappers).

A program whose tile lookup has no shape rule (no
``repro.core.tuning.shape_config``) has no such counter: nothing is read.
"""

from chipbench import spans


def read(rec):
    snap = spans.recorded(rec, "plan", "planner.call")
    if snap is None:
        return None
    try:
        from repro.core.tuning import shape_config  # noqa: F401
    except ImportError:
        return None
    steps = sum(spans.count(snap, s) for s in spans.COMPUTE_STEPS)
    if not steps:
        return None
    return snap.get("repro.tiles.shaped", 0) / steps * 100
