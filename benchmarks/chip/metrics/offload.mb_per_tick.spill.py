"""Bytes across the host link per tick, in MB (10^6 bytes): the
``h2d_bytes`` of the window's ``kv.materialize`` and ``offload.fetch``
spans and the ``d2h_bytes`` of its ``kv.update`` spans, summed, over the
window's ``bench.tick`` spans. Reads the spans' arguments, which a trace
keeps when read by ``spans.read``."""
from benchmarks.chip import spans

COUNTS = {"kv.materialize": "h2d_bytes", "offload.fetch": "h2d_bytes",
          "kv.update": "d2h_bytes"}


def read(rec, ctx):
    if rec.trace is None:
        return None
    ticks = rec.trace.span_times("bench.tick")
    got = {n: spans.with_args(rec.trace, n) or [] for n in COUNTS}
    if not ticks or not any(got.values()):
        return None
    total = sum(args[COUNTS[n]] for n, kept in got.items()
                for _, _, args in kept)
    return total / len(ticks) / 1e6
