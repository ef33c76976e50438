"""Device time of one execution of the decode program (``_decode_step``),
from the profiler trace of the window."""


def read(rec, ctx):
    if rec.trace is None:
        return None
    total, n = rec.trace.program("_decode_step")
    return 1e3 * total / n if n else None
