"""Decoder-only LM assembly: dense / MoE / VLM / SSM / hybrid.

Structure: scan-over-layers with stacked parameters (keeps HLO size O(1) in
depth — required for 80-layer configs to compile with 512 host devices on one
CPU core), configurable remat per layer, GSPMD sharding via the role system in
``repro.models.common``.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.configs.base import DENSE, HYBRID, MOE, SSM, VLM, ModelConfig
from repro.models import attention as attn
from repro.models import layers as nn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import AxisEnv, ParamBuilder, ShardingPolicy

PyTree = Any


# ---------------------------------------------------------------------------
# remat policy
# ---------------------------------------------------------------------------
def remat_wrap(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "offload":
        policy = jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=["layer_act"],
            offload_src="device", offload_dst="pinned_host")
        return jax.checkpoint(fn, policy=policy)
    # "layer" (default): save nothing inside the layer; scan carries boundaries
    return jax.checkpoint(fn)


def act_sharding(env: AxisEnv, pol: ShardingPolicy, batch: int):
    if pol.profile == "fsdp_only":
        return P(env.batch_axes_joint(batch), None)
    baxes = env.batch_axes(batch)
    seq_ax = env.tp if pol.seq_sharded_acts else None
    return P(baxes, seq_ax)


def unembed_spec(env: AxisEnv, pol: ShardingPolicy, batch: int):
    """Sequence-sharded spec for the unembed input when the vocab dim cannot
    be model-sharded (uneven vocab) — see layers.unembed."""
    if env.size(env.tp) <= 1:
        return None
    if pol.profile == "fsdp_only":
        baxes = env.batch_axes_joint(batch)
        if baxes and env.tp not in baxes:
            # model axis idle for this batch: spread the logits' token dim
            return P(baxes, env.tp)
        return None
    if pol.profile == "tp" and not pol.vocab_sharded and not pol.seq_sharded_acts:
        return P(env.batch_axes(batch), env.tp)
    return None


def constrain(x, env: AxisEnv, pol: ShardingPolicy, batch: int):
    if all(s == 1 for s in env.axis_sizes.values()):
        return x  # single device: no mesh context required
    spec = act_sharding(env, pol, batch)
    # pad spec to rank with Nones
    full = P(*(tuple(spec) + (None,) * (x.ndim - len(spec))))
    return jax.lax.with_sharding_constraint(x, full)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_decoder_only(cfg: ModelConfig, key, pol: ShardingPolicy, env: AxisEnv,
                      *, abstract: bool = False) -> Tuple[PyTree, PyTree]:
    b = ParamBuilder(cfg, pol, env, key, abstract=abstract)
    nn.init_embeddings(b)
    lb = b.child("layers")
    if cfg.family in (DENSE, MOE, VLM):
        attn.init_attention(lb, stacked=True)
        nn.init_norm(lb, "norm1", stacked=True)
        nn.init_norm(lb, "norm2", stacked=True)
        if cfg.family == MOE:
            moe_mod.init_moe(lb, stacked=True)
        else:
            nn.init_mlp(lb, stacked=True)
    elif cfg.family == SSM:
        ssm_mod.init_ssm(lb, stacked=True)
        nn.init_norm(lb, "norm1", stacked=True)
    elif cfg.family == HYBRID:
        ssm_mod.init_ssm(lb, stacked=True)
        nn.init_norm(lb, "norm1", stacked=True)
        sb = b.child("shared")  # one shared attention + MLP block (Zamba2)
        attn.init_attention(sb, stacked=False)
        nn.init_mlp(sb)
        nn.init_norm(sb, "norm1")
        nn.init_norm(sb, "norm2")
    else:
        raise ValueError(cfg.family)
    return b.params, b.specs


# ---------------------------------------------------------------------------
# layer bodies (shared by train / prefill / decode)
# ---------------------------------------------------------------------------
def _residual(cfg: ModelConfig, y):
    """A block's output as the residual stream takes it."""
    if cfg.residual_multiplier is None:
        return y
    return y * cfg.residual_multiplier


def _attn_mlp_layer(cfg: ModelConfig, lp, x, positions, cache=None,
                    cache_pos=None):
    """Standard pre-norm block. Returns (x, new_kv_or_None, (aux_loss,
    moe_load)); both are 0 in a dense block."""
    h = nn.apply_norm(cfg, lp, "norm1", x)
    if cache is None:
        a, (k, v) = attn.self_attention(cfg, lp, h, positions)
        new_kv = (k, v)
    else:
        ck, cv = cache
        a, ck, cv = attn.decode_self_attention(cfg, lp, h, ck, cv, cache_pos,
                                               positions)
        new_kv = (ck, cv)
    x = x + _residual(cfg, a)
    h = nn.apply_norm(cfg, lp, "norm2", x)
    if cfg.family == MOE:
        f, aux, load = moe_mod.apply_moe(cfg, lp, h)
    else:
        f = nn.apply_mlp(cfg, lp, h)
        aux = load = jnp.zeros((), jnp.float32)
    return x + _residual(cfg, f), new_kv, (aux, load)


def _ssm_layer(cfg: ModelConfig, lp, x, cache=None):
    h = nn.apply_norm(cfg, lp, "norm1", x)
    y, new_cache = ssm_mod.apply_ssm(cfg, lp, h, cache)
    return x + y, new_cache


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------
def _embed_input(cfg: ModelConfig, params, batch) -> Tuple[jnp.ndarray, Any]:
    """Returns (x, positions)."""
    if cfg.family == VLM:
        x = batch["embeds"].astype(jnp.dtype(cfg.dtype))
        positions = batch["positions"]  # (3, B, S) M-RoPE streams
    else:
        tokens = batch["tokens"]
        S = tokens.shape[1]
        start = batch.get("pos", None)
        if start is None:
            positions = jnp.arange(S)[None, :]
        else:
            start = jnp.asarray(start)
            if start.ndim == 1:  # per-row positions (ragged decode)
                positions = start[:, None] + jnp.arange(S)[None, :]
            else:
                positions = start + jnp.arange(S)[None, :]
        x = nn.embed_tokens(cfg, params, tokens, positions if cfg.learned_pos else None)
    return x, positions


def forward_decoder_only(cfg: ModelConfig, params, batch, env: AxisEnv,
                         pol: ShardingPolicy, *, return_cache: bool = False,
                         last_token_only: bool = False, stats: bool = False):
    """Full-sequence forward. Returns (logits, aux_loss, cache_or_None), and
    with ``stats`` a fourth item: a dict of what a step reports beside its
    loss (``moe_load_max``, the largest over layers of the most-loaded
    expert's routed rows over the mean, for a MoE model)."""
    x, positions = _embed_input(cfg, params, batch)
    B = x.shape[0]
    x = constrain(x, env, pol, B)
    lp_all = params["layers"]
    out_stats = {}

    if cfg.family in (DENSE, MOE, VLM):
        def body(x, lp):
            x = checkpoint_name(x, "layer_act")
            x2, kv, aux = _attn_mlp_layer(cfg, lp, x, positions)
            x2 = constrain(x2, env, pol, B)
            ys = (kv if return_cache else None, aux)
            return x2, ys
        x, (kvs, (auxs, loads)) = jax.lax.scan(remat_wrap(cfg, body), x,
                                               lp_all)
        aux = jnp.sum(auxs)
        if cfg.family == MOE:
            out_stats["moe_load_max"] = jnp.max(loads)
        cache = None
        if return_cache:
            cache = {"k": kvs[0], "v": kvs[1]}  # (L, B, S, KV, hd)
    elif cfg.family == SSM:
        def body(x, lp):
            x = checkpoint_name(x, "layer_act")
            x2, c = _ssm_layer(cfg, lp, x,
                               ssm_mod.init_ssm_cache(cfg, B, x.dtype)
                               if return_cache else None)
            x2 = constrain(x2, env, pol, B)
            return x2, (c if return_cache else None)
        x, caches = jax.lax.scan(remat_wrap(cfg, body), x, lp_all)
        aux = jnp.zeros((), jnp.float32)
        cache = {"ssm": caches} if return_cache else None
    elif cfg.family == HYBRID:
        x, aux, cache = _forward_hybrid(cfg, params, x, positions, env, pol,
                                        return_cache)
    else:
        raise ValueError(cfg.family)

    if last_token_only:
        x = x[:, -1:, :]  # prefill: only the next-token logits are needed
    logits = nn.unembed(cfg, params, x,
                        seq_shard_spec=unembed_spec(env, pol, B))
    if stats:
        return logits, aux, cache, out_stats
    return logits, aux, cache


def _hybrid_split(cfg: ModelConfig):
    n_groups = cfg.num_layers // cfg.attn_every
    tail = cfg.num_layers - n_groups * cfg.attn_every
    return n_groups, tail


def _forward_hybrid(cfg: ModelConfig, params, x, positions, env, pol,
                    return_cache: bool):
    """Zamba2: groups of ``attn_every`` SSM layers, shared attn block between."""
    B = x.shape[0]
    n_groups, tail = _hybrid_split(cfg)
    lp_all = params["layers"]
    sp = params["shared"]
    g = cfg.attn_every

    def split_tree(t):
        head = jax.tree_util.tree_map(
            lambda a: a[: n_groups * g].reshape((n_groups, g) + a.shape[1:]), t)
        rest = jax.tree_util.tree_map(lambda a: a[n_groups * g:], t)
        return head, rest

    lp_groups, lp_tail = split_tree(lp_all)

    def ssm_body(x, lp):
        x = checkpoint_name(x, "layer_act")
        x2, c = _ssm_layer(cfg, lp, x,
                           ssm_mod.init_ssm_cache(cfg, B, x.dtype)
                           if return_cache else None)
        return constrain(x2, env, pol, B), (c if return_cache else None)

    def group_body(x, lp_g):
        x = checkpoint_name(x, "layer_act")
        x, ssm_c = jax.lax.scan(remat_wrap(cfg, ssm_body), x, lp_g)
        a, kv = attn.self_attention(cfg, sp, nn.apply_norm(cfg, sp, "norm1", x),
                                    positions)
        x = x + a
        x = x + nn.apply_mlp(cfg, sp, nn.apply_norm(cfg, sp, "norm2", x))
        x = constrain(x, env, pol, B)
        return x, (ssm_c, kv if return_cache else None)

    # remat the whole group (shared attention included) — without this the
    # shared block's attention residuals are saved per application and blow
    # the activation budget (observed: zamba2 train_4k 24 GiB/dev).
    group_body = remat_wrap(cfg, group_body)

    x, (ssm_groups, kvs) = jax.lax.scan(group_body, x, lp_groups)
    ssm_tail = None
    if tail:
        x, ssm_tail = jax.lax.scan(remat_wrap(cfg, ssm_body), x, lp_tail)

    cache = None
    if return_cache:
        def merge(a, b):
            flat = a.reshape((n_groups * g,) + a.shape[2:])
            return jnp.concatenate([flat, b], axis=0) if tail else flat
        ssm_all = (jax.tree_util.tree_map(merge, ssm_groups, ssm_tail)
                   if tail else jax.tree_util.tree_map(
                       lambda a: a.reshape((n_groups * g,) + a.shape[2:]), ssm_groups))
        cache = {"ssm": ssm_all, "k": kvs[0], "v": kvs[1]}  # kv: (n_groups,B,S,KV,hd)
    return x, jnp.zeros((), jnp.float32), cache


# ---------------------------------------------------------------------------
# decode (single token, layer-scanned over stacked cache)
# ---------------------------------------------------------------------------
def decode_decoder_only(cfg: ModelConfig, params, cache, batch, env: AxisEnv,
                        pol: ShardingPolicy):
    """One-token decode. cache arrays are layer-stacked (L leading).
    Returns (logits, new_cache)."""
    x, positions = _embed_input(cfg, params, batch)
    B = x.shape[0]
    x = constrain(x, env, pol, B)
    pos = batch["pos"]
    lp_all = params["layers"]

    if cfg.family in (DENSE, MOE, VLM):
        def body(x, inp):
            lp, ck, cv = inp
            x2, (ck, cv), _ = _attn_mlp_layer(cfg, lp, x, positions,
                                              cache=(ck, cv), cache_pos=pos)
            return x2, (ck, cv)
        x, (ks, vs) = jax.lax.scan(body, x, (lp_all, cache["k"], cache["v"]))
        new_cache = {"k": ks, "v": vs}
    elif cfg.family == SSM:
        def body(x, inp):
            lp, c = inp
            x2, c2 = _ssm_layer(cfg, lp, x, c)
            return x2, c2
        x, cs = jax.lax.scan(body, x, (lp_all, cache["ssm"]))
        new_cache = {"ssm": cs}
    elif cfg.family == HYBRID:
        x, new_cache = _decode_hybrid(cfg, params, x, positions, pos, cache)
    else:
        raise ValueError(cfg.family)

    logits = nn.unembed(cfg, params, x[:, 0:1, :])[:, 0, :]
    return logits, new_cache


def _decode_hybrid(cfg: ModelConfig, params, x, positions, pos, cache):
    n_groups, tail = _hybrid_split(cfg)
    g = cfg.attn_every
    sp = params["shared"]
    lp_all = params["layers"]

    def take(t, lo, hi):
        return jax.tree_util.tree_map(lambda a: a[lo:hi], t)

    def reshape_g(t):
        return jax.tree_util.tree_map(
            lambda a: a[: n_groups * g].reshape((n_groups, g) + a.shape[1:]), t)

    def ssm_body(x, inp):
        lp, c = inp
        x2, c2 = _ssm_layer(cfg, lp, x, c)
        return x2, c2

    def group_body(x, inp):
        lp_g, ssm_c, ck, cv = inp
        x, ssm_c2 = jax.lax.scan(ssm_body, x, (lp_g, ssm_c))
        h = nn.apply_norm(cfg, sp, "norm1", x)
        a, ck, cv = attn.decode_self_attention(cfg, sp, h, ck, cv, pos, positions)
        x = x + a
        x = x + nn.apply_mlp(cfg, sp, nn.apply_norm(cfg, sp, "norm2", x))
        return x, (ssm_c2, ck, cv)

    lp_groups = reshape_g(lp_all)
    ssm_groups = reshape_g(cache["ssm"])
    x, (ssm_new, ks, vs) = jax.lax.scan(
        group_body, x, (lp_groups, ssm_groups, cache["k"], cache["v"]))
    ssm_new = jax.tree_util.tree_map(
        lambda a: a.reshape((n_groups * g,) + a.shape[2:]), ssm_new)
    if tail:
        lp_tail = take(lp_all, n_groups * g, cfg.num_layers)
        ssm_tail = take(cache["ssm"], n_groups * g, cfg.num_layers)
        x, ssm_tail2 = jax.lax.scan(ssm_body, x, (lp_tail, ssm_tail))
        ssm_new = jax.tree_util.tree_map(
            lambda a, b: jnp.concatenate([a, b], axis=0), ssm_new, ssm_tail2)
    return x, {"ssm": ssm_new, "k": ks, "v": vs}


# ---------------------------------------------------------------------------
# cache construction + sharding specs
# ---------------------------------------------------------------------------
def init_cache_decoder_only(cfg: ModelConfig, batch: int, max_seq: int,
                            dtype=jnp.bfloat16) -> PyTree:
    KV, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.family in (DENSE, MOE, VLM):
        shape = (cfg.num_layers, batch, max_seq, KV, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if cfg.family == SSM:
        c = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        return {"ssm": jax.tree_util.tree_map(
            lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype), c)}
    if cfg.family == HYBRID:
        n_groups, _ = _hybrid_split(cfg)
        c = ssm_mod.init_ssm_cache(cfg, batch, dtype)
        kv_shape = (n_groups, batch, max_seq, KV, hd)
        return {
            "ssm": jax.tree_util.tree_map(
                lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype), c),
            "k": jnp.zeros(kv_shape, dtype), "v": jnp.zeros(kv_shape, dtype),
        }
    raise ValueError(cfg.family)


def cache_specs_decoder_only(cfg: ModelConfig, batch: int, env: AxisEnv,
                             pol: ShardingPolicy) -> PyTree:
    """PartitionSpecs matching init_cache: KV caches shard batch over the
    batch axes; the second sharding axis is KV-heads when divisible (keeps
    the per-token cache append shard-local), else the sequence dim."""
    baxes = env.batch_axes(batch)
    if pol.kv_sharded:
        kv_spec = P(None, baxes, None, env.tp, None)
    else:
        kv_spec = P(None, baxes, env.tp, None, None)
    if cfg.family in (DENSE, MOE, VLM):
        return {"k": kv_spec, "v": kv_spec}
    ssm_axis = env.tp if pol.ssm_sharded else None
    ssm_spec = ssm_mod.SSMCache(
        conv=P(None, baxes, None, None),
        state=P(None, baxes, ssm_axis, None, None))
    if cfg.family == SSM:
        return {"ssm": ssm_spec}
    return {"ssm": ssm_spec, "k": kv_spec, "v": kv_spec}
