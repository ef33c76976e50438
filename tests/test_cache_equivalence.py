"""Prefill + single-token decode must equal the full forward pass — per
family, including MoE (dropless, so prefill routes as the full pass does)
and the SSM/hybrid states."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.shapes import PREFILL, ShapeSuite
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model

ENV = host_axis_env()
S_P, S_MAX, B = 96, 128, 2

ARCHS = ["llama3-8b", "qwen3-32b", "starcoder2-7b", "phi3-mini-3.8b",
         "command-r-35b", "granite-moe-1b-a400m", "phi3.5-moe-42b-a6.6b",
         "mamba2-130m", "zamba2-1.2b", "whisper-large-v3", "qwen2-vl-72b",
         "gpt2-124m"]


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).reduced().with_(remat="none")
    model = build_model(cfg, ENV)
    params, _ = model.init(jax.random.PRNGKey(1))
    full = model.synthetic_batch(ShapeSuite("f", PREFILL, S_P + 1, B),
                                 jax.random.PRNGKey(7))
    logits_full, _, _ = model.forward(params, full)
    want = logits_full[:, -1, :].astype(jnp.float32)

    pf = dict(full)
    if "tokens" in pf:
        pf["tokens"] = full["tokens"][:, :S_P]
    if "embeds" in pf:
        pf["embeds"] = full["embeds"][:, :S_P]
    if "positions" in pf:
        pf["positions"] = full["positions"][:, :, :S_P]
    _, _, cache = model.forward(params, pf, return_cache=True)

    big = model.init_cache(B, S_MAX)
    cache2 = jax.tree_util.tree_map(
        lambda d, s: (d.at[:, :, :S_P].set(s.astype(d.dtype))
                      if d.ndim >= 3 and d.shape[2] == S_MAX and
                      s.shape[2] == S_P else s.astype(d.dtype)),
        big, cache)

    db = {"pos": jnp.asarray(S_P, jnp.int32)}
    if cfg.family == "vlm":
        db["embeds"] = full["embeds"][:, S_P:S_P + 1]
        db["positions"] = full["positions"][:, :, S_P:S_P + 1]
    else:
        db["tokens"] = full["tokens"][:, S_P:S_P + 1]
    got, _ = model.decode(params, cache2, db)
    got = got.astype(jnp.float32)

    rel = float(jnp.max(jnp.abs(got - want)) /
                (jnp.max(jnp.abs(want)) + 1e-9))
    assert rel < 0.02, f"{arch}: rel={rel}"


def test_ragged_positions_match_scalar_decode():
    """Per-row cache positions (continuous batching) must agree with running
    each row separately at its own scalar position."""
    cfg = get_config("llama3-8b").reduced().with_(remat="none")
    model = build_model(cfg, ENV)
    params, _ = model.init(jax.random.PRNGKey(3))
    toks = model.synthetic_batch(ShapeSuite("f", PREFILL, 48, 2),
                                 jax.random.PRNGKey(9))["tokens"]
    lens = [16, 32]

    # batched ragged decode
    cache = model.init_cache(2, 64)
    for b, L in enumerate(lens):
        _, _, pc = model.forward(params, {"tokens": toks[b:b + 1, :L]},
                                 return_cache=True)
        cache = jax.tree_util.tree_map(
            lambda d, s, b=b, L=L: (d.at[:, b:b + 1, :L].set(s.astype(d.dtype))
                                    if d.shape[2] == 64 else
                                    d.at[:, b:b + 1].set(s.astype(d.dtype))),
            cache, pc)
    next_toks = jnp.stack([toks[0, lens[0]:lens[0] + 1],
                           toks[1, lens[1]:lens[1] + 1]])
    ragged, _ = model.decode(params, cache, {
        "tokens": next_toks, "pos": jnp.asarray(lens, jnp.int32)})

    # per-row scalar decode
    for b, L in enumerate(lens):
        c1 = model.init_cache(1, 64)
        _, _, pc = model.forward(params, {"tokens": toks[b:b + 1, :L]},
                                 return_cache=True)
        c1 = jax.tree_util.tree_map(
            lambda d, s, L=L: (d.at[:, :, :L].set(s.astype(d.dtype))
                               if d.shape[2] == 64 else s.astype(d.dtype)),
            c1, pc)
        single, _ = model.decode(params, c1, {
            "tokens": next_toks[b:b + 1], "pos": jnp.asarray(L, jnp.int32)})
        rel = float(jnp.max(jnp.abs(single[0] - ragged[b])) /
                    (jnp.max(jnp.abs(single)) + 1e-9))
        assert rel < 0.02, f"row {b}: rel={rel}"
