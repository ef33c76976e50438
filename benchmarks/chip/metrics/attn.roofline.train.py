"""The fused attention kernels' share of the chip's peak bf16 FLOP/s: the
least causal attention operations of a step (the scores and the weighted
sum over the positions each query may attend to, ``flops.attn_flops``,
for every row of the step; forward and a backward of twice its matmuls,
so 3x; the forward that the layer checkpoint recomputes does not count)
over the device time ``attn.kernel_ms.train`` reads. The kernels are
bound by compute, not by bytes. Heads of 64 give the 128-wide MXU
operands half filled (two heads of a 128-lane block each take the
other's lanes as zeros), so about 50% is the ceiling at these shapes."""
from pathlib import Path

from benchmarks.chip import flops
from benchmarks.chip.run import load_module


def read(rec, ctx):
    kernels = load_module(Path(__file__).with_name("attn.kernel_ms.train.py"))
    ms = kernels.read(rec, ctx)
    if not ms:
        return None
    seq = rec.data["seq"]
    rows = rec.data["tokens_per_step"] // seq
    work = 3 * rows * flops.attn_flops(ctx.spec, seq * (seq + 1) // 2)
    return 100.0 * work / (1e-3 * ms * ctx.peaks["bf16_flops_per_s"])
