"""Pure-jnp oracles for the Pallas kernels and the grouped products (the
correctness ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def attention_ref(q, k, v, *, causal: bool = True, scale=None):
    """Naive softmax attention. q, k, v: (BH, S, hd)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        Sq, Sk = s.shape[1], s.shape[2]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(x, dt, A, B_, C_):
    """Naive sequential SSD recurrence (fp32).
    x: (B,S,nh,hp); dt: (B,S,nh); A: (nh,); B_, C_: (B,S,N)."""
    Bb, S, nh, hp = x.shape
    N = B_.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    Bf = B_.astype(jnp.float32)
    Cf = C_.astype(jnp.float32)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp           # (B,nh,hp), (B,nh), (B,N), (B,N)
        decay = jnp.exp(dt_t * Af[None, :])  # (B,nh)
        upd = jnp.einsum("bn,bh,bhp->bhpn", b_t, dt_t, x_t)
        state = state * decay[:, :, None, None] + upd
        y = jnp.einsum("bhpn,bn->bhp", state, c_t)
        return state, y

    s0 = jnp.zeros((Bb, nh, hp, N), jnp.float32)
    _, ys = jax.lax.scan(step, s0, (jnp.moveaxis(xf, 1, 0),
                                    jnp.moveaxis(dtf, 1, 0),
                                    jnp.moveaxis(Bf, 1, 0),
                                    jnp.moveaxis(Cf, 1, 0)))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype)  # (B,S,nh,hp)


def gmm_ref(x, w, group_sizes):
    """Grouped products by a loop over the groups: x (M, d) holds the rows
    of group e (``group_sizes[e]`` of them) after those of groups < e, and
    each row is multiplied by its group's w[e] (E, d, f) in fp32. Rows past
    the last group are zero."""
    out = jnp.zeros((x.shape[0], w.shape[-1]), jnp.float32)
    start = 0
    for e, n in enumerate(np.asarray(group_sizes).tolist()):
        out = out.at[start:start + n].set(
            x[start:start + n].astype(jnp.float32) @ w[e].astype(jnp.float32))
        start += n
    return out.astype(x.dtype)


def matmul_ref(x, w):
    return (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(x.dtype)
