"""Device time of the prefill programs (``_prefill_step``, one per prompt
length) in the window, per prompt token they took."""


def read(rec, ctx):
    if rec.trace is None:
        return None
    total, n = rec.trace.program("_prefill_step")
    tokens = sum(sum(t["prefill"]) for t in rec.data.get("ticks", []))
    return 1e6 * total / tokens if n and tokens else None
