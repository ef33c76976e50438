"""The grouped products' share of the chip's peak bf16 FLOP/s: the
operations of the step's T * k routed rows through their experts (6 d f a
row forward, 3x that with the backward; a recomputed forward does not
count) over the device time ``moe.gmm_ms.train`` reads. At 512-row tiles
of d = 1024 the products are bound by compute, not by bytes."""
from pathlib import Path

from benchmarks.chip import flops_moe
from benchmarks.chip.run import load_module


def read(rec, ctx):
    gmm = load_module(Path(__file__).with_name("moe.gmm_ms.train.py"))
    ms = gmm.read(rec, ctx)
    if not ms:
        return None
    work = (flops_moe.routed_train_flops_per_token(ctx.spec)
            * rec.data["tokens_per_step"])
    return 100.0 * work / (1e-3 * ms * ctx.peaks["bf16_flops_per_s"])
