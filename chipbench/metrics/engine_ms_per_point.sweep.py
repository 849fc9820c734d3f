"""engine_ms_per_point.sweep: time in sweep passes (``repro.sweep.pass``)
outside the backend's synced calls (``repro.backend.call``), per point
(``repro.sweep.point``), in ms (layer: sweep engine)."""

from chipbench import spans


def read(rec):
    snap = spans.recorded(rec, "sweep", "sweep.point")
    if snap is None:
        return None
    engine_s = (spans.total_s(snap, "sweep.pass")
                - spans.total_s(snap, "backend.call"))
    return engine_s / spans.count(snap, "sweep.point") * 1e3
