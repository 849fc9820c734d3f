"""idle.eval: the share of the traced window in which no op ran on the
device, in % (plan traffic)."""


def read(rec):
    if rec.kind != "plan" or rec.trace is None:
        return None
    return rec.trace.idle_share * 100
