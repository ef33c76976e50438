"""Shared neural blocks: norms, RoPE / M-RoPE, MLPs, embeddings."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamBuilder


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------
def init_norm(b: ParamBuilder, name: str, dim_role: str = "none",
              *, stacked: bool = False):
    cfg = b.cfg
    L = (cfg.num_layers,) if stacked else ()
    lr = ("none",) if stacked else ()
    b.add(f"{name}_scale", L + (cfg.d_model,), lr + (dim_role,), init="ones")
    if cfg.norm == "layernorm":
        b.add(f"{name}_bias", L + (cfg.d_model,), lr + (dim_role,), init="zeros")


def apply_norm(cfg: ModelConfig, p, name: str, x):
    scale = p[f"{name}_scale"].astype(jnp.float32)
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * scale + p[f"{name}_bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * scale
    return y.astype(x.dtype)


def rms_norm_vec(x, scale, eps: float = 1e-5):
    """RMSNorm over the last dim with an explicit scale vector (SSM gated norm)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embeddings (+ Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions_thw, theta: float, sections=(2, 3, 3)):
    """Qwen2-VL multimodal RoPE.

    positions_thw: (3, ..., S) — temporal / height / width position streams.
    The hd/2 frequency dims are split into three contiguous groups in ratio
    ``sections`` (2:3:3 following the 16:24:24 split of hd=128), each rotated
    by its own position stream.
    """
    hd = x.shape[-1]
    half = hd // 2
    total = sum(sections)
    bounds = []
    acc = 0
    for s in sections:
        acc += s
        bounds.append(half * acc // total)
    freqs = rope_freqs(hd, theta)                       # (half,)
    dim_idx = jnp.arange(half)
    stream = jnp.sum(dim_idx[None, :] >= jnp.asarray([0] + bounds[:-1])[:, None], axis=0) - 1
    # per-dim position: pick the stream's positions
    pos = jnp.take(positions_thw, stream, axis=0)       # (half, ..., S) -> moveaxis
    pos = jnp.moveaxis(pos, 0, -1)                      # (..., S, half)
    ang = pos.astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU or plain 2-matmul)
# ---------------------------------------------------------------------------
def init_mlp(b: ParamBuilder, stacked: bool = False):
    cfg = b.cfg
    L = (cfg.num_layers,) if stacked else ()
    lr = ("none",) if stacked else ()
    b.add("w_in", L + (cfg.d_model, cfg.d_ff), lr + ("d_fsdp", "ffn"))
    if cfg.glu:
        b.add("w_gate", L + (cfg.d_model, cfg.d_ff), lr + ("d_fsdp", "ffn"))
    b.add("w_out", L + (cfg.d_ff, cfg.d_model), lr + ("ffn", "d_fsdp"))
    if cfg.use_bias:
        b.add("b_in", L + (cfg.d_ff,), lr + ("ffn",), init="zeros")
        if cfg.glu:
            b.add("b_gate", L + (cfg.d_ff,), lr + ("ffn",), init="zeros")
        b.add("b_out", L + (cfg.d_model,), lr + ("none",), init="zeros")


def _act(cfg: ModelConfig, x):
    return jax.nn.gelu(x) if cfg.act == "gelu" else jax.nn.silu(x)


def apply_mlp(cfg: ModelConfig, p, x):
    h = jnp.einsum("...d,df->...f", x, p["w_in"].astype(x.dtype))
    if cfg.use_bias:
        h = h + p["b_in"].astype(x.dtype)
    if cfg.glu:
        g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(x.dtype))
        if cfg.use_bias:
            g = g + p["b_gate"].astype(x.dtype)
        h = _act(cfg, g) * h
    else:
        h = _act(cfg, h)
    out = jnp.einsum("...f,fd->...d", h, p["w_out"].astype(x.dtype))
    if cfg.use_bias:
        out = out + p["b_out"].astype(x.dtype)
    return out


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------
def init_embeddings(b: ParamBuilder):
    cfg = b.cfg
    b.add("tok_embed", (cfg.vocab_size, cfg.d_model), ("vocab", "d_fsdp"), scale=0.02)
    if cfg.learned_pos:
        b.add("pos_embed", (cfg.max_position, cfg.d_model), ("none", "d_fsdp"),
              scale=0.02)
    init_norm(b, "final_norm")
    if not cfg.tie_embeddings:
        b.add("lm_head", (cfg.d_model, cfg.vocab_size), ("d_fsdp", "vocab"))


def embed_tokens(cfg: ModelConfig, p, tokens, positions: Optional[jnp.ndarray] = None):
    x = jnp.take(p["tok_embed"], tokens, axis=0)
    if cfg.embedding_multiplier is not None:
        x = x * cfg.embedding_multiplier
    x = x.astype(jnp.dtype(cfg.dtype))
    if cfg.learned_pos:
        if positions is None:
            positions = jnp.arange(tokens.shape[-1])[None, :]
        x = x + jnp.take(p["pos_embed"], positions, axis=0).astype(x.dtype)
    return x


def unembed(cfg: ModelConfig, p, x, seq_shard_spec=None):
    x = apply_norm(cfg, p, "final_norm", x)
    if seq_shard_spec is not None and x.shape[-2] > 1:
        # vocab not model-shardable (uneven) -> shard the TOKEN dim of the
        # logits instead; the loss is per-token so this is communication-free
        # and caps the (B, S, V) fp32 buffer at 1/model_axis per device.
        x = jax.lax.with_sharding_constraint(x, seq_shard_spec)
    if cfg.logits_scaling is not None:
        x = (x.astype(jnp.float32) / cfg.logits_scaling).astype(x.dtype)
    w = p["tok_embed"].T if cfg.tie_embeddings else p["lm_head"]
    return jnp.einsum("...d,dv->...v", x, w.astype(x.dtype))
