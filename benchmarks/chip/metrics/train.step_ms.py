"""Device time of one execution of the train-step program (``step`` of
``make_train_step``), from the profiler trace of the window."""


def read(rec, ctx):
    if rec.trace is None:
        return None
    total, n = rec.trace.program("step")
    return 1e3 * total / n if n else None
