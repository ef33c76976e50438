"""The whole serving step's share of the chip's peak: the model
operations of every prefill and decode token of the window's ticks (from
the configuration's sizes) over the summed tick time (host clock) times
the peak bf16 FLOP/s."""
from benchmarks.chip import flops


def read(rec, ctx):
    ticks = rec.data.get("ticks") or []
    if not ticks:
        return None
    work = sum(sum(flops.prefill_flops(ctx.spec, p) for p in t["prefill"])
               + flops.decode_flops(ctx.spec, t["decode_ctx"]) for t in ticks)
    busy = sum(t["end"] - t["start"] for t in ticks)
    return 100.0 * work / (busy * ctx.peaks["bf16_flops_per_s"])
