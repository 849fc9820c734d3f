"""device_ms_per_point.sweep: device busy time in the traced window over
the points measured in it (layer: kernels)."""


def read(rec):
    if rec.kind != "sweep" or rec.trace is None or not rec.completed:
        return None
    return rec.trace.busy_s * rec.trace.devices / rec.completed * 1e3
