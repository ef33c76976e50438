"""Plain reference of a sparse-expert decoder-only LM, IBM Granite-3.0 MoE
(``granitemoe``), in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: no kernels, no sort, no
cache. It imports nothing of the program.

The model, as Hugging Face's ``GraniteMoeForCausalLM`` computes it:

    x = embedding_multiplier * embed(tokens)
    each layer:  x += residual_multiplier * attn(rms(x))
                 x += residual_multiplier * moe(rms(x))
    logits = rms(x) @ embed.T / logits_scaling

Attention is causal, grouped-query, with rotate-half RoPE, and its scores
are scaled by ``attention_multiplier``. The MoE runs every expert on every
token and weighs each expert's output by the token's routing weight: its
router softmax probability renormalised over its top-k experts, zero for
the others (equal to Granite's softmax over the top-k logits). Where this
departs from ``GraniteMoe``, the configuration file's ``departures`` says.

Weights are drawn from the seed as the program draws them, with the
helpers of ``dense_decoder``; ``matmul="fp8"`` is its fp8 control, on
every weight matmul but the router's.

The auxiliary load-balancing loss, ``E * sum_e f_e * P_e`` per layer and
summed over the layers, is not additive over row blocks: f_e, the share of
the routed (token, expert) pairs that went to expert e, is taken over the
whole batch. So ``adamw_steps`` first runs the forward pass over every block
to count each layer's routed pairs, and then takes each block's loss and
gradient with those shares held fixed (they carry no gradient); the mean
router probabilities P_e do add up over blocks.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import dense_decoder as dd

F32 = jnp.float32


@dataclass(frozen=True)
class Spec:
    """Sizes and multipliers of one configuration file."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int            # one expert's width
    experts: int
    top_k: int
    vocab: int
    norm_eps: float
    max_position: int
    rope_theta: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    attention_multiplier: float
    aux_coef: float
    param_dtype: str

    @property
    def program_fields(self) -> Dict[str, object]:
        """The program's ``ModelConfig`` fields these sizes set."""
        return dict(num_layers=self.layers, d_model=self.d_model,
                    num_heads=self.heads, num_kv_heads=self.kv_heads,
                    head_dim=self.head_dim, d_ff=self.d_ff,
                    num_experts=self.experts,
                    experts_per_token=self.top_k, vocab_size=self.vocab,
                    norm="rmsnorm", norm_eps=self.norm_eps, act="silu",
                    glu=True, use_bias=False, tie_embeddings=True,
                    learned_pos=False, max_position=self.max_position,
                    rope_theta=self.rope_theta,
                    embedding_multiplier=self.embedding_multiplier,
                    residual_multiplier=self.residual_multiplier,
                    logits_scaling=self.logits_scaling,
                    attention_multiplier=self.attention_multiplier,
                    aux_loss_coef=self.aux_coef,
                    param_dtype=self.param_dtype)


def spec(cfg: dict) -> Spec:
    """Read a configuration file (Hugging Face key names; the aux-loss
    coefficient from ``assumed``)."""
    if cfg["model_type"] != "granitemoe":
        raise ValueError(f"no reference for model_type {cfg['model_type']!r}")
    if cfg["hidden_act"] != "silu" or cfg["attention_bias"]:
        raise ValueError("granitemoe takes silu experts and no attention bias")
    if not cfg["tie_word_embeddings"]:
        raise ValueError("granitemoe ties its embeddings")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return Spec(cfg["num_hidden_layers"], d, h, cfg["num_key_value_heads"],
                d // h, cfg["intermediate_size"], cfg["num_local_experts"],
                cfg["num_experts_per_tok"], cfg["vocab_size"],
                cfg["rms_norm_eps"], cfg["max_position_embeddings"],
                cfg["rope_theta"], cfg["embedding_multiplier"],
                cfg["residual_multiplier"], cfg["logits_scaling"],
                cfg["attention_multiplier"],
                cfg["assumed"]["router_aux_loss_coef"],
                cfg["program"]["with"].get("param_dtype", "float32"))


def init_params(s: Spec, seed: int) -> dict:
    """The program's weights for ``seed``: the same leaves, drawn in the
    same order (norms ones, the router at scale 0.02)."""
    dt = jnp.dtype(s.param_dtype)
    keys = dd._Keys(jax.random.PRNGKey(seed))
    p = {"tok_embed": dd._normal(keys, (s.vocab, s.d_model), dt, 0.02),
         "final_norm_scale": jnp.ones((s.d_model,), dt)}
    lk = dd._Keys(keys.next())
    L, d, E, f = s.layers, s.d_model, s.experts, s.d_ff
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    p["layers"] = {
        "wq": dd._normal(lk, (L, d, q), dt), "wk": dd._normal(lk, (L, d, kv), dt),
        "wv": dd._normal(lk, (L, d, kv), dt), "wo": dd._normal(lk, (L, q, d), dt),
        "norm1_scale": jnp.ones((L, d), dt), "norm2_scale": jnp.ones((L, d), dt),
        "router": dd._normal(lk, (L, d, E), dt, 0.02),
        "w_in": dd._normal(lk, (L, E, d, f), dt),
        "w_gate": dd._normal(lk, (L, E, d, f), dt),
        "w_out": dd._normal(lk, (L, E, f, d), dt)}
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _rms(s: Spec, x, scale):
    return (x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + s.norm_eps)
            * scale.astype(F32))


def route(s: Spec, h, router):
    """h (T, d) -> (probabilities (T, E), top-k ids (T, k), routing weights
    (T, E): the probabilities renormalised over each token's top k, zero
    elsewhere)."""
    probs = jax.nn.softmax(h @ router.astype(F32), axis=-1)
    _, top = jax.lax.top_k(probs, s.top_k)
    mask = jax.nn.one_hot(top, s.experts, dtype=F32).sum(-2)
    w = probs * mask
    return probs, top, w / w.sum(-1, keepdims=True)


def _moe(s: Spec, h, lp, matmul: str):
    """Every expert on every token, weighed by the routing weights; the
    experts' weights side by side, so each product is one matmul."""
    E, d, f = s.experts, s.d_model, s.d_ff
    probs, top, w = route(s, h, lp["router"])
    side = lambda t: t.astype(F32).transpose(1, 0, 2).reshape(d, E * f)  # noqa: E731
    u = dd._mm(h, side(lp["w_in"]), matmul).reshape(-1, E, f)
    g = dd._mm(h, side(lp["w_gate"]), matmul).reshape(-1, E, f)
    a = (jax.nn.silu(g) * u * w[..., None]).reshape(-1, E * f)
    y = dd._mm(a, lp["w_out"].astype(F32).reshape(E * f, d), matmul)
    return y, probs, top


def _layer(s: Spec, x, lp, frac, matmul: str):
    """One block of x (B, S, d). ``frac`` (E,): the whole batch's shares of
    routed pairs, for the aux loss. Returns (x, (aux, routed pairs per
    expert in this call))."""
    B, S, d = x.shape
    h = _rms(s, x, lp["norm1_scale"])
    q = dd._mm(h, lp["wq"], matmul).reshape(B, S, s.heads, s.head_dim)
    k = dd._mm(h, lp["wk"], matmul).reshape(B, S, s.kv_heads, s.head_dim)
    v = dd._mm(h, lp["wv"], matmul).reshape(B, S, s.kv_heads, s.head_dim)
    q, k = dd._rope(q, s.rope_theta), dd._rope(k, s.rope_theta)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * s.attention_multiplier
    causal = np.tril(np.ones((S, S), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    x = x + s.residual_multiplier * dd._mm(a.reshape(B, S, -1), lp["wo"],
                                           matmul)
    h = _rms(s, x, lp["norm2_scale"]).reshape(B * S, d)
    y, probs, top = _moe(s, h, lp, matmul)
    aux = s.experts * jnp.sum(frac * probs.mean(0))
    counts = jax.nn.one_hot(top, s.experts, dtype=jnp.int32).sum((0, 1))
    return x + s.residual_multiplier * y.reshape(B, S, d), (aux, counts)


def forward(s: Spec, params: dict, tokens, frac=None, matmul: str = "f32"):
    """(logits (B, S, V), aux loss summed over layers, routed pairs per
    layer and expert (L, E)) of token ids (B, S). ``frac`` (L, E): the
    shares of routed pairs the aux loss takes; None reads them off this
    call's own routing."""
    x = (jnp.take(params["tok_embed"], tokens, axis=0).astype(F32)
         * s.embedding_multiplier)
    if frac is None:
        frac = forward_counts(s, params, tokens, matmul)
        frac = frac / (tokens.size * s.top_k)

    def body(x, inp):
        lp, fr = inp
        return jax.checkpoint(
            lambda x, lp, fr: _layer(s, x, lp, fr, matmul))(x, lp, fr)

    x, (aux, counts) = jax.lax.scan(body, x, (params["layers"], frac))
    x = _rms(s, x, params["final_norm_scale"])
    logits = dd._mm(x, params["tok_embed"].T, matmul) / s.logits_scaling
    return logits, jnp.sum(aux), counts


def forward_counts(s: Spec, params: dict, tokens, matmul: str = "f32"):
    """Routed pairs per layer and expert (L, E) of a forward pass."""
    zero = jnp.zeros((s.layers, s.experts), F32)
    return forward(s, params, tokens, zero, matmul)[2]


def load_max(s: Spec, counts) -> float:
    """The most-loaded expert's routed pairs over the mean, the largest
    over layers, of counts (L, E)."""
    counts = np.asarray(counts)
    return float(counts.max() * s.experts / counts[0].sum())


# ---------------------------------------------------------------------------
# training check: AdamW steps from the same seed on the same batches
# ---------------------------------------------------------------------------
def loss_fn(s: Spec, params, tokens, labels, frac, matmul: str = "f32"):
    lg, aux, _ = forward(s, params, tokens, frac, matmul)
    lse = jax.nn.logsumexp(lg, -1)
    ll = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - ll) + s.aux_coef * aux


def adamw_steps(s: Spec, seed: int, batches, opt: dict, rows: int,
                matmul: str = "f32") -> dict:
    """``dense_decoder.adamw_steps`` for this model: each step's loss, and
    by leaf the norms of the first step's clipped gradient and of the
    parameters' change. Each batch's loss and gradient are summed over
    blocks of ``rows`` rows with the aux loss's shares of the whole batch.
    The gradient accumulates in place, and AdamW's moments wait in host
    memory between updates, so that the float32 weights and gradient (8 B a
    parameter) are all the state on the device while a block runs."""
    with jax.default_matmul_precision("highest"):
        return _adamw_steps(s, seed, batches, opt, rows, matmul)


def _adamw_steps(s, seed, batches, opt, rows, matmul):
    tmap = jax.tree_util.tree_map
    p = tmap(lambda x: x.astype(F32), init_params(s, seed))
    p0 = jax.device_get(p)
    count = jax.jit(lambda p, t: forward_counts(s, p, t, matmul))

    def acc(p, g, t, l, frac, w):
        loss, gb = jax.value_and_grad(
            lambda p: loss_fn(s, p, t, l, frac, matmul))(p)
        return loss, tmap(lambda a, b: a + w * b, g, gb)
    acc = jax.jit(acc, donate_argnums=(1,))

    def grad(p, batch):
        n, S = batch.shape[0], batch.shape[1] - 1
        blocks = [jnp.asarray(batch[i:i + rows]) for i in range(0, n, rows)]
        counts = sum(count(p, b[:, :-1]) for b in blocks)
        frac = counts.astype(F32) / (n * S * s.top_k)
        loss, g = 0.0, tmap(jnp.zeros_like, p)
        for b in blocks:
            w = b.shape[0] / n
            lb, g = acc(p, g, b[:, :-1], b[:, 1:], frac, w)
            loss += w * float(lb)
        return loss, g

    def lr_at(t):
        warm = min(1.0, (t + 1) / max(opt["warmup_steps"], 1))
        prog = min(1.0, max(0.0, (t - opt["warmup_steps"])
                            / max(opt["total_steps"] - opt["warmup_steps"], 1)))
        cos = 0.5 * (1 + math.cos(math.pi * prog))
        return opt["lr"] * warm * (opt["min_lr_ratio"]
                                   + (1 - opt["min_lr_ratio"]) * cos)

    def update(p, m, v, g, t, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree_util.tree_leaves(g)))
        g = tmap(lambda x: x * jnp.minimum(1.0, opt["clip_norm"] / (gn + 1e-9)),
                 g)
        b1, b2 = opt["beta1"], opt["beta2"]
        m = tmap(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = tmap(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
        p = tmap(lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2)
                                                       + opt["eps"])
                                           + opt["weight_decay"] * p),
                 p, m, v)
        return p, m, v, g
    update = jax.jit(update, donate_argnums=(0, 3))

    m = tmap(np.zeros_like, p0)
    v = tmap(np.zeros_like, p0)
    losses, g1 = [], None
    for t, b in enumerate(batches):
        loss, g = grad(p, b)
        p, m, v, g = update(p, m, v, g, t, lr_at(t))
        m, v = jax.device_get((m, v))
        losses.append(loss)
        if t == 0:
            g1 = dd.leaf_norms(g)
        del g
    delta = dd.leaf_norms(tmap(lambda a, b: a - b, p, p0))
    return {"losses": losses, "grad": g1, "delta": delta}
