"""Host time per tick in the offload tier's write-back: per ``bench.tick``
span, the host seconds inside the program's ``kv.update`` spans
(``KVPool.update``: the step's cache re-split, cold tails and host leaves
written back to host memory, and the wait for them, which also waits for
the step that produced the cache); the mean over the window's ticks."""
from benchmarks.chip import spans

TIER = ("kv.update",)


def read(rec, ctx):
    if rec.trace is None:
        return None
    ticks = rec.trace.span_times("bench.tick")
    if not ticks or not any(spans.times(rec.trace, n) for n in TIER):
        return None
    return 1e3 * sum(spans.within(rec.trace, ticks, TIER)) / len(ticks)
