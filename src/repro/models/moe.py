"""Mixture-of-Experts FFN: dropless top-k routing over rows sorted by expert.

Training and prefill (S > 1), over the T = B * S tokens of a call:

  1. the router (float32, d -> E) gives softmax probabilities; each token
     keeps its top-k experts, their weights renormalised to sum to one;
  2. the T * k (token, expert) pairs are sorted by expert, and each
     token's row is gathered once per pair into that order: (T * k, d);
  3. the expert FFN runs as grouped products over the sorted rows
     (``jax.lax.ragged_dot``, group e being expert e's ``sizes[e]`` rows);
     on a TPU, XLA's ragged-dot kernel;
  4. the combine kernel gathers each token's k output rows back and sums
     them with its weights in float32, so no (T * k, d) array in token
     order is written.

The moves are custom VJPs in ``kernels/ops.py``: the dispatch is XLA's
gather from the small (T, d) input; the combine and both transposes are the
Pallas kernels of ``kernels/moe_permute.py`` (interpret mode on the CPU):
the dispatch's transpose is a combine with unit weights, the combine's a
gather that scales each row. No pair is dropped. The device work is set
by the shapes, not by where the tokens go: every pass moves all T * k rows
and none scatters, the sort is over a fixed count of keys, and the grouped
products cover all T * k rows however they fall into groups.

Decode (S == 1) is weight-bandwidth-bound: every expert runs on the token
and the routing weights combine them.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models.common import ParamBuilder


def init_moe(b: ParamBuilder, *, stacked: bool = False):
    cfg = b.cfg
    L = (cfg.num_layers,) if stacked else ()
    lr = ("none",) if stacked else ()
    E = cfg.num_experts
    b.add("router", L + (cfg.d_model, E), lr + ("d_fsdp", "none"), scale=0.02)
    b.add("w_in", L + (E, cfg.d_model, cfg.d_ff), lr + ("experts", "d_fsdp", "none"))
    if cfg.glu:
        b.add("w_gate", L + (E, cfg.d_model, cfg.d_ff), lr + ("experts", "d_fsdp", "none"))
    b.add("w_out", L + (E, cfg.d_ff, cfg.d_model), lr + ("experts", "none", "d_fsdp"))


def route(cfg: ModelConfig, p, x) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x (..., d) -> (router probabilities (..., E), top-k weights (..., k)
    renormalised, top-k expert ids (..., k)). The weights are read out of
    the probabilities through a one-hot mask, so their gradient reaches the
    router without a scatter."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    _, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    mask = jax.nn.one_hot(top_e, cfg.num_experts, dtype=jnp.float32)
    top_w = jnp.sum(mask * probs[..., None, :], axis=-1)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    return probs, top_w, top_e


def load_balance_loss(cfg: ModelConfig, probs, top_e) -> jnp.ndarray:
    """Auxiliary load-balancing loss (Switch-style): E * sum_e f_e * P_e,
    f_e the share of the routed pairs that went to expert e, P_e its mean
    router probability, both over every token of the call."""
    frac = jnp.mean(jax.nn.one_hot(top_e, cfg.num_experts, dtype=jnp.float32),
                    axis=tuple(range(top_e.ndim)))
    mean_p = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    return cfg.num_experts * jnp.sum(frac * mean_p)


def sort_by_expert(top_e):
    """The T * k pairs (token-major, as ``top_e.reshape(-1)``) in expert
    order: (order, inv), ``order[r]`` the pair at sorted row r and ``inv``
    its inverse."""
    order = jnp.argsort(top_e.reshape(-1), stable=True)
    return order, jnp.argsort(order)


def expert_sizes(cfg: ModelConfig, top_e):
    """Routed pairs per expert, (E,) int32."""
    return jnp.sum(top_e.reshape(-1, 1) == jnp.arange(cfg.num_experts),
                   axis=0, dtype=jnp.int32)


def _act(cfg: ModelConfig):
    return jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu


def grouped_ffn(cfg: ModelConfig, p, rows, sizes):
    """Expert FFN of rows sorted by expert, ``sizes[e]`` rows for expert e:
    (M, d) -> (M, d), each row through its own expert's weights."""
    dt = rows.dtype
    h = jax.lax.ragged_dot(rows, p["w_in"].astype(dt), sizes)
    if cfg.glu:
        g = jax.lax.ragged_dot(rows, p["w_gate"].astype(dt), sizes)
        h = _act(cfg)(g) * h
    else:
        h = _act(cfg)(h)
    return jax.lax.ragged_dot(h, p["w_out"].astype(dt), sizes)


def apply_moe(cfg: ModelConfig, p, x):
    """MoE FFN of x (B, S, d). Returns (out, aux, load): out (B, S, d); aux
    the load-balance loss; load the most-loaded expert's routed rows over
    the mean (1 when balanced)."""
    B, S, d = x.shape
    k, E = cfg.experts_per_token, cfg.num_experts
    probs, top_w, top_e = route(cfg, p, x)
    aux = load_balance_loss(cfg, probs, top_e)
    sizes = expert_sizes(cfg, top_e)
    load = jnp.max(sizes).astype(jnp.float32) * E / (B * S * k)
    if S == 1:
        return _apply_moe_decode(cfg, p, x, top_w, top_e), aux, load
    order, inv = sort_by_expert(top_e)
    perm = ops.moe_route(top_e.reshape(B * S, k), order, inv, E, x.dtype)
    rows = ops.moe_dispatch(x.reshape(B * S, d), perm)           # (T*k, d)
    out = grouped_ffn(cfg, p, rows, sizes)
    y = ops.moe_combine(out, top_w.reshape(B * S, k), perm)      # (T, d)
    return y.reshape(B, S, d), aux, load


def _apply_moe_decode(cfg: ModelConfig, p, x, top_w, top_e):
    """Dense-all-experts decode path (weight-bandwidth optimal)."""
    # dense per-token expert weights: sum_j w_j * onehot(e_j)
    w_full = jnp.sum(
        top_w[..., None] * jax.nn.one_hot(top_e, cfg.num_experts,
                                          dtype=jnp.float32), axis=-2)
    act = _act(cfg)
    h = jnp.einsum("bsd,edf->besf", x, p["w_in"].astype(x.dtype))
    if cfg.glu:
        g = jnp.einsum("bsd,edf->besf", x, p["w_gate"].astype(x.dtype))
        h = act(g) * h
    else:
        h = act(h)
    out_e = jnp.einsum("besf,efd->besd", h, p["w_out"].astype(x.dtype))
    return jnp.einsum("besd,bse->bsd", out_e, w_full.astype(x.dtype))
