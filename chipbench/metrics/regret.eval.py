"""regret.eval: time of the planner's picks over time of the fastest
enumerated algorithms, summed over the cell's distinct instances, each
algorithm timed the same way after the window (layer: planner)."""


def read(rec):
    if rec.kind != "plan" or rec.regret is None:
        return None
    return rec.regret()
