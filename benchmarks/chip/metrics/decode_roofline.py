"""Share of its roofline that the decode program reaches: the least time
the chip could take for the window's decode steps (the larger of the
bytes they need over peak HBM bandwidth and their operations over peak
bf16 FLOP/s, summed over steps), over their device time in the trace.
Bytes: every weight once, the valid KV of each live slot, the new K and
V; operations: one token per live slot over its valid context."""
from benchmarks.chip import flops


def read(rec, ctx):
    if rec.trace is None:
        return None
    total, n = rec.trace.program("_decode_step")
    steps = [t["decode_ctx"] for t in rec.data.get("ticks", []) if t["decode_ctx"]]
    if not n or not steps:
        return None
    bw, peak = ctx.peaks["hbm_bytes_per_s"], ctx.peaks["bf16_flops_per_s"]
    least = sum(max(flops.decode_bytes(ctx.spec, c) / bw,
                    flops.decode_flops(ctx.spec, c) / peak) for c in steps)
    return 100.0 * least / total
