"""The whole training step's share of the chip's peak for a sparse-expert
model: the operations the forward and backward passes need per token, with
only the k routed experts counted (``flops_moe``; recomputation does not
count), times the tokens the window's steps took, over the window's length
times the peak bf16 FLOP/s."""
from benchmarks.chip import flops_moe


def read(rec, ctx):
    steps = rec.data.get("steps")
    if not steps:
        return None
    work = (flops_moe.train_flops_per_token(ctx.spec, rec.data["seq"])
            * steps * rec.data["tokens_per_step"])
    return 100.0 * work / (rec.window_s * ctx.peaks["bf16_flops_per_s"])
