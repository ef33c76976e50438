"""Host time per training step that the step loop spent waiting for its
batch: the ``data.next`` spans of ``DataPipeline`` (the prefetch queue's
``get`` and the batch's transfer to the device) inside the window, summed,
over the window's ``bench.step`` spans."""
from benchmarks.chip import spans


def read(rec, ctx):
    if rec.trace is None:
        return None
    steps = rec.trace.span_times("bench.step")
    waits = spans.times(rec.trace, "data.next")
    if not steps or not waits:
        return None
    return 1e3 * sum(e - s for s, e in waits) / len(steps)
