"""The row permute kernels' share of the chip's HBM bandwidth: the least
bytes their passes must move over the time ``moe.permute_ms.train`` reads,
at ``hbm_bytes_per_s``. In each MoE layer the kernels make three passes over
the T * k routed rows (the combine forward, its transpose, and the
dispatch's transpose); each reads or writes T rows of d and T * k rows of d
in the compute dtype. The dispatch forward is XLA's gather and not counted,
nor is a recomputed forward. Any implementation of these passes moves at
least these bytes, so the share cannot pass 100%."""
from pathlib import Path

import jax.numpy as jnp

from benchmarks.chip.run import load_module

PASSES = 3


def least_bytes(spec, tokens: int, itemsize: int) -> int:
    """Bytes of one step's kernel passes: layers x 3 x (T d + T k d) x
    itemsize."""
    return (spec.layers * PASSES * (tokens + tokens * spec.top_k)
            * spec.d_model * itemsize)


def read(rec, ctx):
    ms = load_module(Path(__file__).with_name("moe.permute_ms.train.py")).read(
        rec, ctx)
    if not ms:
        return None
    dtype = ctx.cfg_file["program"]["with"]["dtype"]
    work = least_bytes(ctx.spec, rec.data["tokens_per_step"],
                       jnp.dtype(dtype).itemsize)
    return 100.0 * work / (1e-3 * ms * ctx.peaks["hbm_bytes_per_s"])
