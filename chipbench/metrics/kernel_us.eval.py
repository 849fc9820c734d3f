"""kernel_us.eval: host time in the step walker's kernel calls
(``repro.step.{gemm,syrk,symm,symm_r,chain_gemm,gemm_syrk}``: the tile
lookup and the jitted wrapper's dispatch) per evaluation
(``repro.planner.call``), in µs (layer: kernel wrappers)."""

from chipbench import spans


def read(rec):
    snap = spans.recorded(rec, "plan", "planner.call")
    if snap is None:
        return None
    return (spans.total_s(snap, *spans.COMPUTE_STEPS)
            / spans.count(snap, "planner.call") * 1e6)
