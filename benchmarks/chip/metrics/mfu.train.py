"""The whole training step's share of the chip's peak: the operations the
forward and backward passes need per token (from the configuration's
sizes; recomputation does not count) times the tokens the window's steps
took, over the window's length times the peak bf16 FLOP/s."""
from benchmarks.chip import flops


def read(rec, ctx):
    steps = rec.data.get("steps")
    if not steps:
        return None
    work = (flops.train_flops_per_token(ctx.spec, rec.data["seq"])
            * steps * rec.data["tokens_per_step"])
    return 100.0 * work / (rec.window_s * ctx.peaks["bf16_flops_per_s"])
