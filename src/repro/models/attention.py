"""Attention: fused Pallas flash on the TPU, XLA flash (scan + online
softmax), GQA, RoPE/M-RoPE, decode.

``attention_core`` picks the path from what it is given, not from a flag:

  * causal self-attention (Sq == Sk, no ragged ``kv_len``, no offset, S a
    multiple of 128, at least ``FUSED_MIN_SCORE_BYTES`` of fp32 scores) on
    one TPU device — training and long prefill — runs the differentiable
    Pallas kernels of ``repro.kernels.ops.causal_flash_attention``: score
    tiles stay in VMEM in the forward, the recomputed forward and the
    backward;
  * decode (Sq == 1, not causal) — ``decode_attention``, one pass over the
    cache;
  * everything else, and every call off the TPU (the CPU tests, the
    dry-run) — ``flash_attention``: a lax.scan over ``cfg.attn_chunk``-wide
    KV chunks with online softmax. Peak memory is O(Sq * chunk) instead of
    O(Sq * Sk), which is what keeps 32k-token prefill within its memory.

GQA is handled by gather-expanding K/V head-wise (a local gather — verified to
introduce zero collectives when Q-heads are model-sharded and KV replicated).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import ParamBuilder
from repro.models.layers import apply_mrope, apply_rope, rms_norm_vec


# ---------------------------------------------------------------------------
# parameter init
# ---------------------------------------------------------------------------
def init_attention(b: ParamBuilder, *, stacked: bool = False, prefix: str = "",
                   cross: bool = False):
    cfg = b.cfg
    L = (cfg.num_layers,) if stacked else ()
    lr = ("none",) if stacked else ()
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    b.add(prefix + "wq", L + (cfg.d_model, H * hd), lr + ("d_fsdp", "qout"))
    b.add(prefix + "wk", L + (cfg.d_model, KV * hd), lr + ("d_fsdp", "kvout"))
    b.add(prefix + "wv", L + (cfg.d_model, KV * hd), lr + ("d_fsdp", "kvout"))
    b.add(prefix + "wo", L + (H * hd, cfg.d_model), lr + ("qout", "d_fsdp"))
    if cfg.use_bias:
        b.add(prefix + "bq", L + (H * hd,), lr + ("qout",), init="zeros")
        b.add(prefix + "bk", L + (KV * hd,), lr + ("kvout",), init="zeros")
        b.add(prefix + "bv", L + (KV * hd,), lr + ("kvout",), init="zeros")
        b.add(prefix + "bo", L + (cfg.d_model,), lr + ("none",), init="zeros")
    if cfg.use_qk_norm and not cross:
        b.add(prefix + "q_norm", L + (hd,), lr + ("none",), init="ones")
        b.add(prefix + "k_norm", L + (hd,), lr + ("none",), init="ones")


# ---------------------------------------------------------------------------
# projections
# ---------------------------------------------------------------------------
def _rope(cfg: ModelConfig, x, positions, use_rope: bool):
    if not use_rope:
        return x
    if cfg.mrope:
        return apply_mrope(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta)


def q_proj(cfg: ModelConfig, p, x, positions, *, prefix: str = "",
           use_rope: bool = True):
    B, S, _ = x.shape
    H, hd = cfg.num_heads, cfg.head_dim
    q = jnp.einsum("bsd,dn->bsn", x, p[prefix + "wq"].astype(x.dtype))
    if cfg.use_bias:
        q = q + p[prefix + "bq"].astype(x.dtype)
    q = q.reshape(B, S, H, hd)
    if cfg.use_qk_norm:
        q = rms_norm_vec(q, p[prefix + "q_norm"], cfg.norm_eps)
    return _rope(cfg, q, positions, use_rope and not cfg.learned_pos)


def kv_proj(cfg: ModelConfig, p, x, positions, *, prefix: str = "",
            use_rope: bool = True):
    B, S, _ = x.shape
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    k = jnp.einsum("bsd,dn->bsn", x, p[prefix + "wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dn->bsn", x, p[prefix + "wv"].astype(x.dtype))
    if cfg.use_bias:
        k = k + p[prefix + "bk"].astype(x.dtype)
        v = v + p[prefix + "bv"].astype(x.dtype)
    k = k.reshape(B, S, KV, hd)
    v = v.reshape(B, S, KV, hd)
    if cfg.use_qk_norm:
        k = rms_norm_vec(k, p[prefix + "k_norm"], cfg.norm_eps)
    k = _rope(cfg, k, positions, use_rope and not cfg.learned_pos)
    return k, v


def out_proj(cfg: ModelConfig, p, attn, *, prefix: str = ""):
    B, S = attn.shape[:2]
    out = jnp.einsum("bsn,nd->bsd", attn.reshape(B, S, -1),
                     p[prefix + "wo"].astype(attn.dtype))
    if cfg.use_bias:
        out = out + p[prefix + "bo"].astype(attn.dtype)
    return out


def expand_kv(k, num_heads: int):
    """Gather-expand GQA KV heads to ``num_heads`` (local when KV replicated)."""
    KV = k.shape[2]
    if KV == num_heads:
        return k
    mapping = jnp.arange(num_heads) // (num_heads // KV)
    return k[:, :, mapping, :]


# ---------------------------------------------------------------------------
# flash attention (scan over KV chunks, online softmax)
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, *, causal: bool, q_offset=0,
                    kv_len: Optional[jnp.ndarray] = None,
                    chunk: int = 1024, scale: Optional[float] = None):
    """q: (B,Sq,H,hd); k,v: (B,Sk,H,hd) (already head-expanded).

    ``q_offset``: absolute position of q[0] (decode: cache length).
    ``kv_len``: dynamic count of valid KV entries (mask the tail).
    Differentiable (jax differentiates through the scan); pair with remat at
    the layer level for training.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    chunk = min(chunk, Sk)
    if Sk % chunk:
        pad = chunk - Sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if kv_len is None:
            kv_len = jnp.asarray(Sk, jnp.int32)
    n_chunks = k.shape[1] // chunk

    qf = (q.astype(jnp.float32) * scale).transpose(0, 2, 1, 3)   # (B,H,Sq,hd)
    kc = k.transpose(0, 2, 1, 3).reshape(B, H, n_chunks, chunk, hd)
    vc = v.transpose(0, 2, 1, 3).reshape(B, H, n_chunks, chunk, hd)
    kc = jnp.moveaxis(kc, 2, 0)                                   # (nc,B,H,ck,hd)
    vc = jnp.moveaxis(vc, 2, 0)

    pos_q = q_offset + jnp.arange(Sq)

    def body(carry, inputs):
        m, l, acc = carry
        j, kj, vj = inputs
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kj.astype(jnp.float32))
        pos_k = j * chunk + jnp.arange(chunk)
        mask = jnp.ones((1, 1, Sq, chunk), bool)
        if causal:
            mask &= (pos_q[:, None] >= pos_k[None, :])[None, None]
        if kv_len is not None:
            kvl = jnp.asarray(kv_len)
            if kvl.ndim == 0:
                mask &= (pos_k < kvl)[None, None, None, :]
            else:  # per-row valid lengths (ragged continuous batching)
                mask &= (pos_k[None, :] < kvl[:, None])[:, None, None, :]
        s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> use safe m
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask, p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(n_chunks), kc, vc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)            # (B,Sq,H,hd)


def decode_attention(q, k, v, *, kv_len=None, scale: Optional[float] = None):
    """Single-pass attention for Sq == 1 over a (possibly S-sharded) cache.

    No KV chunk scan: with the decode cache sequence-sharded over "model",
    a chunked scan forces GSPMD to all-gather the cache per chunk; the
    single-pass einsum keeps scores S-sharded and reduces only the (tiny)
    softmax stats and the (B,1,H,hd) output across the model axis.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else hd ** -0.5
    # mixed-precision dots (bf16 in, f32 accumulate) — an explicit
    # .astype(f32) on the cache slice gets hoisted out of the layer scan by
    # XLA and materializes the WHOLE stacked cache in f32 (observed: +6 GiB
    # on phi3-mini decode_32k); preferred_element_type avoids the convert.
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    s = jax.lax.dot_general(qs, k, (((3,), (3,)), ((0, 2), (0, 2))),
                            preferred_element_type=jnp.float32)  # (B,H,Sq,Sk)
    if kv_len is not None:
        kvl = jnp.asarray(kv_len)
        pos_k = jnp.arange(Sk)
        if kvl.ndim == 0:
            mask = (pos_k < kvl)[None, None, None, :]
        else:
            mask = (pos_k[None, :] < kvl[:, None])[:, None, None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)   # (B,H,Sq,Sk)
    out = jax.lax.dot_general(p, v, (((3,), (1,)), ((0, 1), (0, 2))),
                              preferred_element_type=jnp.float32)  # (B,H,Sq,hd)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _on_one_tpu() -> bool:
    """The computation runs on one TPU device: the TPU backend, and no
    context mesh of several devices (a Pallas kernel is not partitioned)."""
    mesh = jax.sharding.get_abstract_mesh()
    return (jax.default_backend() == "tpu"
            and (mesh.empty or mesh.size == 1))


# Below 128 MiB of fp32 scores (B * H * S * S * 4 bytes) the XLA scan
# beats the fused kernels' fixed cost; past it the scan's time jumps. On a
# v5e, a phi3-mini prompt took the scan 100 us at 896 tokens (103 MB) and
# 601 us at 1024 (134 MB); fused took 1.11x and 0.20x of that. The
# gpt2-124m training step (4 GB) fell from 1361 to 740 ms (PERF.md).
FUSED_MIN_SCORE_BYTES = 1 << 27


def _fused(q, k, *, causal: bool, q_offset, kv_len) -> bool:
    """Causal self-attention that the fused Pallas kernels take."""
    B, S, H = q.shape[:3]
    return (causal and S == k.shape[1] and kv_len is None
            and isinstance(q_offset, int) and q_offset == 0
            and S % 128 == 0 and B * H * S * S * 4 >= FUSED_MIN_SCORE_BYTES
            and _on_one_tpu())


def attention_core(cfg: ModelConfig, q, k, v, *, causal: bool, q_offset=0,
                   kv_len=None):
    """Dispatch on the call's shape and backend: decode, the fused kernels,
    or the XLA scan; expands GQA heads first. Scores are scaled by
    ``cfg.attention_multiplier``, or by ``head_dim ** -0.5`` where it is
    None."""
    scale = cfg.attention_multiplier
    k = expand_kv(k, cfg.num_heads)
    v = expand_kv(v, cfg.num_heads)
    if q.shape[1] == 1 and not causal:
        return decode_attention(q, k, v, kv_len=kv_len, scale=scale)
    if _fused(q, k, causal=causal, q_offset=q_offset, kv_len=kv_len):
        from repro.kernels import ops as kops
        return kops.causal_flash_attention(q, k, v, scale)
    return flash_attention(q, k, v, causal=causal, q_offset=q_offset,
                           kv_len=kv_len, chunk=cfg.attn_chunk, scale=scale)


# ---------------------------------------------------------------------------
# full layer applications
# ---------------------------------------------------------------------------
def self_attention(cfg: ModelConfig, p, x, positions, *, causal: bool = True,
                   prefix: str = "") -> Tuple[jnp.ndarray, Tuple]:
    """Training / prefill self-attention. Returns (out, (k, v)) for caching."""
    q = q_proj(cfg, p, x, positions, prefix=prefix)
    k, v = kv_proj(cfg, p, x, positions, prefix=prefix)
    attn = attention_core(cfg, q, k, v, causal=causal)
    return out_proj(cfg, p, attn, prefix=prefix), (k, v)


def decode_self_attention(cfg: ModelConfig, p, x, cache_k, cache_v, cache_pos,
                          positions, *, prefix: str = ""):
    """Single-token decode: insert new KV at ``cache_pos``, attend over cache.

    cache_k/v: (B, S_max, KV, hd). ``cache_pos`` is a scalar, or a (B,)
    vector of per-row positions (ragged continuous batching).
    Returns (out, new_k, new_v).
    """
    q = q_proj(cfg, p, x, positions, prefix=prefix)
    k_new, v_new = kv_proj(cfg, p, x, positions, prefix=prefix)
    pos = jnp.asarray(cache_pos)
    if pos.ndim == 0:
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k_new.astype(cache_k.dtype), pos, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v_new.astype(cache_v.dtype), pos, axis=1)
    else:  # per-row scatter (Sq == 1)
        rows = jnp.arange(cache_k.shape[0])
        cache_k = cache_k.at[rows, pos].set(k_new[:, 0].astype(cache_k.dtype))
        cache_v = cache_v.at[rows, pos].set(v_new[:, 0].astype(cache_v.dtype))
    attn = attention_core(cfg, q, cache_k, cache_v, causal=False,
                          kv_len=pos + x.shape[1])
    return out_proj(cfg, p, attn, prefix=prefix), cache_k, cache_v


def cross_attention(cfg: ModelConfig, p, x, enc_k, enc_v, *, prefix: str = "cross_"):
    """Decoder cross-attention over precomputed encoder KV (no mask, no rope)."""
    positions = jnp.arange(x.shape[1])[None, :]
    q = q_proj(cfg, p, x, positions, prefix=prefix, use_rope=False)
    attn = attention_core(cfg, q, enc_k, enc_v, causal=False)
    return out_proj(cfg, p, attn, prefix=prefix)
