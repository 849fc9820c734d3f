"""sweep_points_per_s: points fully measured (every algorithm, every
repetition) in the window, over the window."""


def read(rec):
    if rec.kind != "sweep" or not rec.completed:
        return None
    return rec.completed / rec.window_s
