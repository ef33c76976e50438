"""Jit'd public wrappers for the Pallas kernels.

On the CPU backend the wrappers run the kernels in interpret mode (Python
emulation of the kernel body — bit-accurate block semantics, no Mosaic), so
the test suite exercises the real kernel code without a chip; everywhere
else they compile for the device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import moe_gmm as _gmm
from repro.kernels import ssd_scan as _ssd
from repro.kernels import stream_matmul as _sm


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128):
    """q, k, v: (B, S, H, hd) — heads are folded/unfolded here."""
    B, S, H, hd = q.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], hd)
    out = _fa.flash_attention_fwd(
        fold(q), fold(k), fold(v), causal=causal,
        block_q=block_q, block_k=block_k, interpret=_interpret())
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention_grads(q, k, v, dout, *, causal: bool = True,
                          block_q: int = 128, block_k: int = 128):
    """Full flash backward via the Pallas kernels.
    q, k, v, dout: (BH, S, hd). Returns (out, dq, dk, dv)."""
    interp = _interpret()
    out, lse = _fa.flash_attention_fwd_stats(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        interpret=interp)
    dq, dk, dv = _fa.flash_attention_bwd(
        q, k, v, out, lse, dout, causal=causal, block_q=block_q,
        block_k=block_k, interpret=interp)
    return out, dq, dk, dv


@functools.partial(jax.jit, static_argnames=("chunk", "nh_block"))
def ssd(x, dt, A, B_, C_, *, chunk: int = 128, nh_block: int = 8):
    return _ssd.ssd_scan(x, dt, A, B_, C_, chunk=chunk, nh_block=nh_block,
                         interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_k"))
def grouped_matmul(x, w, *, block_c: int = 128, block_f: int = 128,
                   block_k: int = 128):
    return _gmm.grouped_matmul(x, w, block_c=block_c, block_f=block_f,
                               block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def stream_matmul(x, w, *, block_m: int = 128, block_n: int = 128,
                  block_k: int = 512):
    return _sm.stream_matmul(x, w, block_m=block_m, block_n=block_n,
                             block_k=block_k, interpret=_interpret())
