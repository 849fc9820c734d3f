"""eval_roofline: the least time the chip could take for the required work
of every evaluation in the traced window, over device busy time, in %.

An evaluation's least time is the larger of its least FLOP count over the
paper's algorithms at peak FLOP/s, and its inputs plus output at peak HBM
bandwidth (``expressions/<expr>.py``, ``peaks.json``)."""


def read(rec):
    if rec.kind != "plan" or rec.trace is None or not rec.least_time_s:
        return None
    return rec.least_time_s / (rec.trace.busy_s * rec.trace.devices) * 100
