"""move_us.eval: host time in the step walker's eager data movement
(``repro.step.transpose``, ``repro.step.tri2full``) per evaluation
(``repro.planner.call``), in µs (layer: backends)."""

from chipbench import spans


def read(rec):
    snap = spans.recorded(rec, "plan", "planner.call")
    if snap is None:
        return None
    return (spans.total_s(snap, *spans.MOVE_STEPS)
            / spans.count(snap, "planner.call") * 1e6)
