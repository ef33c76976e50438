"""Host time of one ``SliceRuntime.step`` in the window: the summed tick
time over the number of ticks (host clock around each call)."""


def read(rec, ctx):
    ticks = rec.data.get("ticks") or []
    if not ticks:
        return None
    return 1e3 * sum(t["end"] - t["start"] for t in ticks) / len(ticks)
