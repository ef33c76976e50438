"""What a tick costs beyond the model's own programs: per ``bench.tick``
span of the trace, its host length less the device time of the prefill
and decode programs that ran inside it; the mean over the window's ticks.
The rest is the offload tier's host-to-device round trip (``KVPool``
materialize, update and paste, ``fetch_to_device``) and host overhead."""


def read(rec, ctx):
    if rec.trace is None:
        return None
    spans = rec.trace.span_times("bench.tick")
    if not spans:
        return None
    dev = rec.trace.program_time_within(spans, ("_decode_step", "_prefill_step"))
    return 1e3 * sum((e - s) - d for (s, e), d in zip(spans, dev)) / len(spans)
