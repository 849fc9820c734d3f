"""plan_us.eval: time in ``Planner.plan`` (``repro.planner.plan``), the
memo lookup included, per evaluation (``repro.planner.call``), in µs
(layer: planner)."""

from chipbench import spans


def read(rec):
    snap = spans.recorded(rec, "plan", "planner.call")
    if snap is None:
        return None
    return (spans.total_s(snap, "planner.plan")
            / spans.count(snap, "planner.call") * 1e6)
