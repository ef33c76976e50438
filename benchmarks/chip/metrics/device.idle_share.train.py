"""Share of the traced window in which no operation ran on the device:
1 - (union of the operation intervals) / window."""


def read(rec, ctx):
    if rec.trace is None or not rec.trace.busy_s:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)
