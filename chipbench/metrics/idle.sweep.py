"""idle.sweep: the share of the traced window in which no op ran on the
device, in %, the mean over the chips (sweep traffic)."""


def read(rec):
    if rec.kind != "sweep" or rec.trace is None:
        return None
    return rec.trace.idle_share * 100
