"""Pallas TPU flash attention (forward), MXU-tiled, online softmax.

Grid: (batch*heads, q_blocks, kv_blocks) — the kv dim is innermost, so on TPU
it executes sequentially per (bh, q_block) and the fp32 running max / sum /
accumulator live in VMEM scratch across kv steps. Block shapes are multiples
of 128 on the matmul dims to keep the MXU systolic array full; K/V blocks are
pipelined HBM→VMEM by the grid (the same double-buffering structure that
serves the paper's offload streaming on real hardware).

VMEM budget per step at (block_q, block_k, hd) = (128, 128, 128), bf16 inputs:
q+k+v blocks ≈ 96 KiB, s/p ≈ 64 KiB fp32, scratch ≈ 65 KiB fp32 → well under
the ~16 MiB/core VMEM with double-buffering headroom.

Validated against ``repro.kernels.ref.attention_ref`` in interpret mode
(tests/test_kernels.py sweeps shapes and dtypes).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    # under causality, blocks fully above the diagonal contribute nothing
    needed = jnp.asarray(True) if not causal else (k_start <= q_start + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale            # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq,bk)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_scr[...]                                  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = (acc_scr[...] * corr +
                        jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ik == kv_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def _stats_scratch(block_q: int, hd: int):
    """VMEM scratch: running max and sum as (block_q, 1) columns, and the
    fp32 output accumulator."""
    return [pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32)]


def flash_attention_fwd_stats(q, k, v, *, causal: bool = True, scale=None,
                              block_q: int = 128, block_k: int = 128,
                              interpret: bool = False):
    """Forward + logsumexp stats (for the backward kernel).
    Returns (out (BH,S,hd), lse (BH,S,1)). The stats keep a trailing unit
    axis so each block is a (block_q, 1) column, a shape the TPU tiling
    accepts (a (1, block_q) row block of a (BH, S) array it refuses)."""
    BH, S, hd = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    assert S % block_q == 0 and Sk % block_k == 0
    grid = (BH, S // block_q, Sk // block_k)
    kernel = functools.partial(
        _flash_stats_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, kv_blocks=Sk // block_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
            jax.ShapeDtypeStruct((BH, S, 1), jnp.float32),
        ],
        scratch_shapes=_stats_scratch(block_q, hd),
        interpret=interpret,
    )(q, k, v)


def _flash_stats_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                        acc_scr, *, scale, block_q, block_k, causal,
                        kv_blocks):
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                  scale=scale, block_q=block_q, block_k=block_k,
                  causal=causal, kv_blocks=kv_blocks)

    @pl.when(pl.program_id(2) == kv_blocks - 1)
    def _stats():
        lse_ref[0] = m_scr[...] + jnp.log(jnp.maximum(l_scr[...], 1e-30))


def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, dk_scr, dv_scr, *,
                      scale: float, block_q: int, block_k: int, causal: bool,
                      q_blocks: int):
    """Backward: grid (BH, kv_block, q_block) — q innermost so dk/dv
    accumulate in VMEM scratch per kv block; dq accumulates via the output
    ref (revisited across the kv grid dim is NOT allowed, so dq uses the
    q-block output with accumulation over kv handled by re-running the kv
    loop per q block — see flash_attention_bwd which transposes the grids).
    This kernel computes dk/dv; dq comes from `_flash_dq_kernel`."""
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    needed = jnp.asarray(True) if not causal else (k_start <= q_start + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)                # (bq, hd)
        lse = lse_ref[0]                                  # (bq, 1)
        delta = delta_ref[0]                              # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                              # (bq, bk)
        dv_scr[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                             # (bq, bk)
        dk_scr[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(iq == q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, dq_scr, *, scale: float, block_q: int,
                     block_k: int, causal: bool, kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    needed = jnp.asarray(True) if not causal else (k_start <= q_start + block_q - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        delta = delta_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            rows = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            cols = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(ik == kv_blocks - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, dout, *, causal: bool = True,
                        scale=None, block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """Flash backward: (dq, dk, dv), each (BH, S, hd). ``lse`` (BH, S, 1)
    from flash_attention_fwd_stats. Two pallas_calls: dk/dv with the q dim
    innermost (accumulated in VMEM), dq with the kv dim innermost."""
    BH, S, hd = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (BH, S, 1)

    kv_kernel = functools.partial(
        _flash_bwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, q_blocks=S // block_q)
    dk, dv = pl.pallas_call(
        kv_kernel,
        grid=(BH, Sk // block_k, S // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, block_q, hd), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, ik, iq: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, ik, iq: (bh, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, hd), lambda bh, ik, iq: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, ik, iq: (bh, ik, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((BH, Sk, hd), k.dtype),
                   jax.ShapeDtypeStruct((BH, Sk, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, dout, lse, delta)

    dq_kernel = functools.partial(
        _flash_dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, kv_blocks=Sk // block_k)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, S // block_q, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, iq, ik: (bh, iq, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


def flash_attention_fwd(q, k, v, *, causal: bool = True, scale=None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """q, k, v: (BH, S, hd) with heads folded into the leading dim."""
    BH, S, hd = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, Sk)
    assert S % block_q == 0 and Sk % block_k == 0, (S, Sk, block_q, block_k)
    grid = (BH, S // block_q, Sk // block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, kv_blocks=Sk // block_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, hd), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=_stats_scratch(block_q, hd),
        interpret=interpret,
    )(q, k, v)
