"""Public wrappers for the Pallas kernels.

* ``causal_flash_attention`` — differentiable causal self-attention in the
  model's (B, S, H, hd) layout (custom VJP over the forward-with-stats and
  the two backward kernels); ``models.attention.attention_core`` sends
  causal self-attention on one TPU device here.
* ``moe_route``, ``moe_dispatch``, ``moe_combine`` — the MoE layer's row
  permute: the dispatch into expert order (XLA's gather) and the weighted
  combine back (``kernels/moe_permute.py``), custom VJPs whose backward
  passes are that file's kernels; ``models.moe.apply_moe`` runs them for
  S > 1.
* ``ssd``, ``stream_matmul``.

On the CPU backend the wrappers run the kernels in interpret mode (Python
emulation of the kernel body — bit-accurate block semantics, no Mosaic), so
the test suite exercises the real kernel code without a chip; everywhere
else they compile for the device.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import moe_permute as _mp
from repro.kernels import ssd_scan as _ssd
from repro.kernels import stream_matmul as _sm


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _fold(t):
    """(B, S, H, hd) -> (B*H, S, hd)."""
    B, S, H, hd = t.shape
    return t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)


def _unfold(t, B: int, H: int):
    """(B*H, S, hd) -> (B, S, H, hd)."""
    return t.reshape(B, H, *t.shape[1:]).transpose(0, 2, 1, 3)


def _in_place(shape) -> bool:
    """Whether the kernels read (B, S, H, hd) in place, as (B, S, H * hd):
    where their blocks of whole heads are 128-lane aligned (gpt2's pairs
    of 64, heads of 128); else heads are folded into the batch."""
    _, _, H, hd = shape
    return _fa.lanes(H * hd, hd) % _fa.LANES == 0


def _to_kernel(t):
    B, S, H, hd = t.shape
    return t.reshape(B, S, H * hd) if _in_place(t.shape) else _fold(t)


def _from_kernel(t, shape):
    B, _, H, _ = shape
    return t.reshape(shape) if _in_place(shape) else _unfold(t, B, H)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def causal_flash_attention(q, k, v, scale=None):
    """Causal self-attention, q, k, v: (B, S, H, hd); scores times
    ``scale`` (None: hd ** -0.5). The forward saves (q, k, v, out, lse) as
    the kernels see them; nothing S x S reaches HBM in the forward or the
    backward."""
    out = _fa.flash_attention_fwd(
        _to_kernel(q), _to_kernel(k), _to_kernel(v), causal=True,
        scale=scale, head_dim=q.shape[-1], interpret=_interpret())
    return _from_kernel(out, q.shape)


def _causal_fwd(q, k, v, scale):
    qk, kk, vk = _to_kernel(q), _to_kernel(k), _to_kernel(v)
    out, lse = _fa.flash_attention_fwd_stats(
        qk, kk, vk, causal=True, scale=scale, head_dim=q.shape[-1],
        interpret=_interpret())
    return _from_kernel(out, q.shape), (qk, kk, vk, out, lse)


def _causal_bwd(scale, res, dout):
    qk, kk, vk, out, lse = res
    grads = _fa.flash_attention_bwd(
        qk, kk, vk, out, lse, _to_kernel(dout), causal=True, scale=scale,
        head_dim=dout.shape[-1], interpret=_interpret())
    return tuple(_from_kernel(g, dout.shape) for g in grads)


causal_flash_attention.defvjp(_causal_fwd, _causal_bwd)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["tok", "order", "inv", "plan"],
                   meta_fields=["k"])
@dataclasses.dataclass(frozen=True)
class MoERoute:
    """Where the T * k (token, expert) pairs go: ``order[r]`` the pair
    (token-major) at sorted row r, ``inv`` its inverse, ``tok[r]`` the
    token of row r, and the combine's copy plan."""
    tok: jnp.ndarray
    order: jnp.ndarray
    inv: jnp.ndarray
    plan: _mp.CombinePlan
    k: int


def moe_route(top_e, order, inv, num_experts: int, dtype) -> MoERoute:
    """The route of ``top_e`` (T, k) sorted by ``order`` (``inv`` its
    inverse), for rows of ``dtype``."""
    k = top_e.shape[-1]
    return MoERoute(order // k, order, inv,
                    _mp.combine_plan(top_e, inv, num_experts, dtype), k)


def _padded(x):
    """x with its rows padded to a width the kernels take."""
    d = x.shape[-1]
    return jnp.pad(x, ((0, 0), (0, _mp.pad_dim(d, x.dtype) - d)))


@jax.custom_vjp
def moe_dispatch(x, route: MoERoute):
    """x (T, d) -> its rows in expert order, (T * k, d): row r is
    ``x[route.tok[r]]``. XLA's gather: from a (T, d) source it runs near
    the bandwidth, faster than a kernel that first flattens the source."""
    return jnp.take(x, route.tok, axis=0, mode="clip")


def _dispatch_fwd(x, route):
    return moe_dispatch(x, route), route


def _dispatch_bwd(route, g):
    # each token's k rows summed back: a combine with unit weights
    T = route.tok.shape[0] // route.k
    dx = _mp.combine(_padded(g), jnp.ones((T, route.k), jnp.float32),
                     route.plan, interpret=_interpret())
    return dx[:, :g.shape[-1]], None


moe_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def moe_combine(rows, w, route: MoERoute):
    """Rows in expert order (T * k, d) and weights w (T, k) float32 -> y
    (T, d), y[t] = sum_j w[t, j] * rows[route.inv[t * k + j]], summed in
    float32 and cast to the rows' dtype."""
    y = _mp.combine(_padded(rows), w, route.plan, interpret=_interpret())
    return y[:, :rows.shape[-1]]


def _combine_fwd(rows, w, route):
    return moe_combine(rows, w, route), (rows, w, route)


def _combine_bwd(res, g):
    rows, w, route = res
    d = rows.shape[-1]
    w_sorted = w.reshape(-1)[route.order]
    drows, dw_sorted = _mp.combine_bwd(_padded(g), _padded(rows), route.tok,
                                       w_sorted, interpret=_interpret())
    return drows[:, :d], dw_sorted[route.inv].reshape(w.shape), None


moe_combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "nh_block"))
def ssd(x, dt, A, B_, C_, *, chunk: int = 128, nh_block: int = 8):
    return _ssd.ssd_scan(x, dt, A, B_, C_, chunk=chunk, nh_block=nh_block,
                         interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k"))
def stream_matmul(x, w, *, block_m: int = 128, block_n: int = 128,
                  block_k: int = 512):
    return _sm.stream_matmul(x, w, block_m=block_m, block_n=block_n,
                             block_k=block_k, interpret=_interpret())
