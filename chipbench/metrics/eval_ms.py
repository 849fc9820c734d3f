"""eval_ms: the window's time over the evaluations completed in it."""


def read(rec):
    if rec.kind != "plan" or not rec.completed:
        return None
    return rec.window_s / rec.completed * 1e3
