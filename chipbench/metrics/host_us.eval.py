"""host_us.eval: mean host time from calling the planner until it returns,
before the wait on the result (layer: entry)."""


def read(rec):
    if rec.kind != "plan" or not rec.host_s:
        return None
    return sum(rec.host_s) / len(rec.host_s) * 1e6
