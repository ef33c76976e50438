"""Host time per tick in the offload tier's copies to the device: per
``bench.tick`` span, the host seconds inside the union of the program's
``kv.materialize`` (``KVPool``: host leaves and cold tails in) and
``offload.fetch`` (``fetch_to_device``: host-placed parameters in) spans;
the mean over the window's ticks. A span is host time in the call: what
it dispatches and what it waits for there."""
from benchmarks.chip import spans

TIER = ("kv.materialize", "offload.fetch")


def read(rec, ctx):
    if rec.trace is None:
        return None
    ticks = rec.trace.span_times("bench.tick")
    if not ticks or not any(spans.times(rec.trace, n) for n in TIER):
        return None
    return 1e3 * sum(spans.within(rec.trace, ticks, TIER)) / len(ticks)
