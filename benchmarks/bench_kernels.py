"""Kernel micro-benchmarks: wall time per call (interpret mode on CPU —
structural validation; real-TPU numbers come from the roofline model) and
oracle agreement."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels import ops, ref


def _bench(fn, *args, iters: int = 3) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.tree_util.tree_leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> None:
    key = jax.random.PRNGKey(0)
    # fused causal flash attention
    q = jax.random.normal(key, (2, 256, 4, 64), jnp.float32)
    attn = jax.jit(ops.causal_flash_attention)
    us = _bench(lambda a: attn(a, q, q), q)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(8, 256, 64)
    err = float(np.max(np.abs(
        np.asarray(attn(q, q, q)) -
        np.asarray(ref.attention_ref(fold(q), fold(q), fold(q), causal=True)
                   .reshape(2, 4, 256, 64).transpose(0, 2, 1, 3)))))
    emit("kernel/causal_flash_attention/B2S256H4d64", us,
         f"max_abs_err={err:.2e}")

    # ssd scan
    B, S, nh, hp, N = 2, 256, 8, 32, 64
    ks = jax.random.split(key, 5)
    x = 0.5 * jax.random.normal(ks[0], (B, S, nh, hp))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (nh,)))
    B_ = 0.3 * jax.random.normal(ks[3], (B, S, N))
    C_ = 0.3 * jax.random.normal(ks[4], (B, S, N))
    us = _bench(lambda a: ops.ssd(a, dt, A, B_, C_, chunk=128), x)
    err = float(np.max(np.abs(np.asarray(ops.ssd(x, dt, A, B_, C_,
                                                 chunk=128)) -
                              np.asarray(ref.ssd_ref(x, dt, A, B_, C_)))))
    emit("kernel/ssd_scan/B2S256nh8", us, f"max_abs_err={err:.2e}")

    # grouped products of the MoE layer (jax.lax.ragged_dot)
    xg = jax.random.normal(ks[0], (1024, 128))
    wg = jax.random.normal(ks[1], (4, 128, 256))
    gs = jnp.asarray([384, 0, 512, 128], jnp.int32)
    us = _bench(lambda a: jax.lax.ragged_dot(a, wg, gs), xg)
    emit("kernel/ragged_dot/E4M1024", us,
         f"max_abs_err={float(np.max(np.abs(np.asarray(jax.lax.ragged_dot(xg, wg, gs)) - np.asarray(ref.gmm_ref(xg, wg, gs))))):.2e}")

    # stream matmul (offload streaming analogue)
    xs = jax.random.normal(ks[2], (256, 1024))
    ws = jax.random.normal(ks[3], (1024, 512))
    us = _bench(lambda a: ops.stream_matmul(a, ws, block_k=512), xs)
    emit("kernel/stream_matmul/256x1024x512", us,
         f"max_abs_err={float(np.max(np.abs(np.asarray(ops.stream_matmul(xs, ws)) - np.asarray(ref.matmul_ref(xs, ws))))):.2e}")
