"""call_us.sweep: mean duration of one synced timed call of the backend
(``repro.backend.call``: dispatch to result ready, warm-ups and reps), in
µs (layer: backends)."""

from chipbench import spans


def read(rec):
    snap = spans.recorded(rec, "sweep", "backend.call")
    if snap is None:
        return None
    return (spans.total_s(snap, "backend.call")
            / spans.count(snap, "backend.call") * 1e6)
