"""mfu.eval: the least FLOP count of every evaluation in the traced window
over the window times the chips' peak FLOP/s, in %."""


def read(rec):
    if rec.kind != "plan" or rec.trace is None or not rec.required_flops:
        return None
    peak = rec.peak_flops * rec.trace.devices
    return rec.required_flops / (rec.trace.window_s * peak) * 100
