"""Gradient compression for the cross-pod (DCN) reduction.

The pod axis crosses data-center network, ~10× slower than ICI, so the
multi-pod train step optionally compresses gradients before the cross-pod
sync: int8 block quantization with error feedback (the quantization residual
is added back into the next step's gradient, keeping the optimizer unbiased
in expectation — standard EF-SGD construction).

``cross_pod_sync`` runs as a shard_map over ONLY the "pod" axis (data/model
stay under automatic GSPMD partitioning), so the compressed all-gather is
explicit in the HLO and its byte reduction is measurable in the dry-run
(benchmarks/bench_compression.py compares collective bytes on/off).
"""
from __future__ import annotations

import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp

PyTree = Any
BLOCK = 256  # quantization block (last-dim groups)


def quantize_int8(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise symmetric int8 along the LAST dim (shape-preserving up to
    last-dim padding — leading dims keep their sharding; a flatten-based
    quantizer forces GSPMD to replicate the whole gradient)."""
    xf = x.astype(jnp.float32)
    if xf.ndim == 0:
        xf = xf[None]
    last = xf.shape[-1]
    pad = (-last) % BLOCK
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (xf.ndim - 1) + [(0, pad)])
    blocks = xf.reshape(xf.shape[:-1] + (xf.shape[-1] // BLOCK, BLOCK))
    scale = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray, shape, size: int
                    ) -> jnp.ndarray:
    full = (q.astype(jnp.float32) * scale)
    full = full.reshape(full.shape[:-2] + (full.shape[-2] * BLOCK,))
    last = shape[-1] if len(shape) else 1
    if full.shape[-1] != last:
        full = full[..., :last]
    return full.reshape(shape)


def compress_residual(x: jnp.ndarray, err: jnp.ndarray
                      ) -> Tuple[Tuple[jnp.ndarray, jnp.ndarray], jnp.ndarray]:
    """Error-feedback quantization: q(x + err), new_err = (x+err) - deq."""
    target = x.astype(jnp.float32) + err
    q, s = quantize_int8(target)
    deq = dequantize_int8(q, s, x.shape, x.size)
    return (q, s), target - deq


def cross_pod_sync(grads: PyTree, err: PyTree, mesh, *, compress: bool = True
                   ) -> Tuple[PyTree, PyTree]:
    """Mean-reduce grads across the "pod" mesh axis.

    With compress=True: per-pod int8(+EF) quantization, all-gather of the
    compressed payload over "pod", local dequant-sum — 4× fewer DCN bytes
    than an fp32 all-reduce. Without: plain psum.
    """
    from jax.sharding import PartitionSpec as P

    if "pod" not in mesh.axis_names:
        return grads, err
    npods = dict(zip(mesh.axis_names, mesh.devices.shape))["pod"]

    def sync_leaf(g, e):
        if not compress:
            return jax.lax.pmean(g, "pod"), e
        (q, s), new_e = compress_residual(g, e)
        q_all = jax.lax.all_gather(q, "pod")       # (npods, nblk, BLOCK) int8
        s_all = jax.lax.all_gather(s, "pod")
        total = sum(dequantize_int8(q_all[i], s_all[i], g.shape, g.size)
                    for i in range(npods))
        return (total / npods).astype(g.dtype), new_e

    def inner(gs, es):
        flat_g, td = jax.tree_util.tree_flatten(gs)
        flat_e = td.flatten_up_to(es)
        out = [sync_leaf(g, e) for g, e in zip(flat_g, flat_e)]
        return td.unflatten([o[0] for o in out]), td.unflatten([o[1] for o in out])

    spec = P()  # replicated over pod inside; data/model stay automatic
    fn = jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec, spec), axis_names={"pod"},
                       check_vma=False)
    return fn(grads, err)


def init_error_feedback(grads_like: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda g: jnp.zeros(g.shape, jnp.float32), grads_like)
