"""Plain reference of a dense decoder-only LM (phi3, gpt2 style), in
``jax.numpy`` and float32 at the ``highest`` matmul precision: no kernels,
no cache, no batching tricks. It imports nothing of the program.

Weights. The program draws its weights from a seed with a fixed key order
(one ``jax.random.split`` per random leaf, in the order the layers are
declared; norms ones, biases zeros). ``init_params`` draws them the same
way from the same seed, eagerly and leaf by leaf as the program does, so
the reference holds the same values without taking any array from it.

Lower precision. ``matmul="fp8"`` runs every weight matmul as fp8
training does (the usual recipe): operands rounded to float8_e4m3fn going
forward, the gradient to float8_e5m2 going back, each tensor with one
scale of its own. It is the control that has to fail the comparison.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Spec:
    """Sizes and switches of one configuration file, in one vocabulary."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str            # "rmsnorm" | "layernorm"
    norm_eps: float
    act: str             # "silu" | "gelu_tanh"
    glu: bool
    bias: bool
    tie_embeddings: bool
    learned_pos: bool
    max_position: int
    rope_theta: float
    param_dtype: str

    @property
    def program_fields(self) -> Dict[str, object]:
        """The program's ``ModelConfig`` fields these sizes set."""
        return dict(num_layers=self.layers, d_model=self.d_model,
                    num_heads=self.heads, num_kv_heads=self.kv_heads,
                    head_dim=self.head_dim, d_ff=self.d_ff,
                    vocab_size=self.vocab, norm=self.norm,
                    norm_eps=self.norm_eps,
                    act="gelu" if self.act == "gelu_tanh" else self.act,
                    glu=self.glu, use_bias=self.bias,
                    tie_embeddings=self.tie_embeddings,
                    learned_pos=self.learned_pos,
                    max_position=(self.max_position if self.learned_pos
                                  else 1 << 20),
                    rope_theta=self.rope_theta,
                    param_dtype=self.param_dtype)


def spec(cfg: dict) -> Spec:
    """Read a configuration file (Hugging Face key names)."""
    pdt = cfg["program"]["with"].get("param_dtype", "float32")
    if cfg["model_type"] == "phi3":
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return Spec(cfg["num_hidden_layers"], d, h,
                    cfg["num_key_value_heads"], d // h,
                    cfg["intermediate_size"], cfg["vocab_size"], "rmsnorm",
                    cfg["rms_norm_eps"], cfg["hidden_act"], True, False,
                    cfg["tie_word_embeddings"], False,
                    cfg["max_position_embeddings"], cfg["rope_theta"], pdt)
    if cfg["model_type"] == "gpt2":
        d, h = cfg["n_embd"], cfg["n_head"]
        if cfg["activation_function"] != "gelu_new":
            raise ValueError(cfg["activation_function"])
        return Spec(cfg["n_layer"], d, h, h, d // h,
                    cfg["n_inner"] or 4 * d, cfg["vocab_size"], "layernorm",
                    cfg["layer_norm_epsilon"], "gelu_tanh", False, True, True,
                    True, cfg["n_positions"], 10_000.0, pdt)
    raise ValueError(f"no reference for model_type {cfg['model_type']!r}")


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
class _Keys:
    def __init__(self, key):
        self.key = key

    def next(self):
        self.key, sub = jax.random.split(self.key)
        return sub


def _normal(keys: _Keys, shape, dtype, scale=None):
    if scale is None:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = 1.0 / math.sqrt(max(fan_in, 1))
    return scale * jax.random.normal(keys.next(), shape, dtype)


def init_params(s: Spec, seed: int) -> dict:
    dt = jnp.dtype(s.param_dtype)
    keys = _Keys(jax.random.PRNGKey(seed))
    p = {"tok_embed": _normal(keys, (s.vocab, s.d_model), dt, 0.02)}
    if s.learned_pos:
        p["pos_embed"] = _normal(keys, (s.max_position, s.d_model), dt, 0.02)
    p["final_norm_scale"] = jnp.ones((s.d_model,), dt)
    if s.norm == "layernorm":
        p["final_norm_bias"] = jnp.zeros((s.d_model,), dt)
    if not s.tie_embeddings:
        p["lm_head"] = _normal(keys, (s.d_model, s.vocab), dt)
    lk = _Keys(keys.next())
    L, d, q, kv = s.layers, s.d_model, s.heads * s.head_dim, s.kv_heads * s.head_dim
    lp = {"wq": _normal(lk, (L, d, q), dt), "wk": _normal(lk, (L, d, kv), dt),
          "wv": _normal(lk, (L, d, kv), dt), "wo": _normal(lk, (L, q, d), dt)}
    if s.bias:
        lp.update(bq=jnp.zeros((L, q), dt), bk=jnp.zeros((L, kv), dt),
                  bv=jnp.zeros((L, kv), dt), bo=jnp.zeros((L, d), dt))
    for n in ("norm1", "norm2"):
        lp[f"{n}_scale"] = jnp.ones((L, d), dt)
        if s.norm == "layernorm":
            lp[f"{n}_bias"] = jnp.zeros((L, d), dt)
    lp["w_in"] = _normal(lk, (L, d, s.d_ff), dt)
    if s.glu:
        lp["w_gate"] = _normal(lk, (L, d, s.d_ff), dt)
    lp["w_out"] = _normal(lk, (L, s.d_ff, d), dt)
    if s.bias:
        lp["b_in"] = jnp.zeros((L, s.d_ff), dt)
        if s.glu:
            lp["b_gate"] = jnp.zeros((L, s.d_ff), dt)
        lp["b_out"] = jnp.zeros((L, d), dt)
    p["layers"] = lp
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _round(x, dtype, top: float):
    """Round to an fp8 ``dtype`` with one scale per tensor (its largest
    magnitude maps to the type's largest finite ``top``), back to float32."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _e4m3(x):
    return _round(x, jnp.float8_e4m3fn, 448.0)


@jax.custom_vjp
def _fp8_mm(x, w):
    """``x @ w`` as fp8 training computes it: both operands in e4m3 going
    forward; going back, the incoming gradient in e5m2 with a scale of its
    own, times the same e4m3 operands."""
    return jnp.einsum("...d,df->...f", _e4m3(x), _e4m3(w), precision=HIGHEST)


def _fp8_mm_fwd(x, w):
    xq, wq = _e4m3(x), _e4m3(w)
    return jnp.einsum("...d,df->...f", xq, wq, precision=HIGHEST), (xq, wq)


def _fp8_mm_bwd(res, g):
    xq, wq = res
    gq = _round(g, jnp.float8_e5m2, 57344.0)
    dx = jnp.einsum("...f,df->...d", gq, wq, precision=HIGHEST)
    dw = jnp.einsum("nd,nf->df", xq.reshape(-1, xq.shape[-1]),
                    gq.reshape(-1, gq.shape[-1]), precision=HIGHEST)
    return dx, dw


_fp8_mm.defvjp(_fp8_mm_fwd, _fp8_mm_bwd)


def _mm(x, w, matmul: str):
    w = w.astype(jnp.float32)
    if matmul == "fp8":
        return _fp8_mm(x, w)
    return jnp.einsum("...d,df->...f", x, w, precision=HIGHEST)


def _norm(s: Spec, x, scale, bias=None):
    if s.norm == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        y = (x - mu) / jnp.sqrt(var + s.norm_eps)
        return y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    y = x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + s.norm_eps)
    return y * scale.astype(jnp.float32)


def _rope(x, theta: float):
    """Rotate-half RoPE over (B, S, H, hd) at positions 0..S-1."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = 1.0 / theta ** (np.arange(half, dtype=np.float64) / half)
    ang = np.arange(S)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _act(s: Spec, x):
    if s.act == "silu":
        return x * jax.nn.sigmoid(x)
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _layer(s: Spec, x, lp, matmul: str):
    B, S, _ = x.shape
    f32 = lambda n: lp[n].astype(jnp.float32)
    h = _norm(s, x, lp["norm1_scale"], lp.get("norm1_bias"))
    q = _mm(h, lp["wq"], matmul)
    k = _mm(h, lp["wk"], matmul)
    v = _mm(h, lp["wv"], matmul)
    if s.bias:
        q, k, v = q + f32("bq"), k + f32("bk"), v + f32("bv")
    q = q.reshape(B, S, s.heads, s.head_dim)
    k = k.reshape(B, S, s.kv_heads, s.head_dim)
    v = v.reshape(B, S, s.kv_heads, s.head_dim)
    if not s.learned_pos:
        q, k = _rope(q, s.rope_theta), _rope(k, s.rope_theta)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    sc = sc / math.sqrt(s.head_dim)
    causal = np.tril(np.ones((S, S), bool))
    sc = jnp.where(causal[None, None], sc, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v,
                   precision=HIGHEST).reshape(B, S, -1)
    a = _mm(a, lp["wo"], matmul)
    if s.bias:
        a = a + f32("bo")
    x = x + a
    h = _norm(s, x, lp["norm2_scale"], lp.get("norm2_bias"))
    u = _mm(h, lp["w_in"], matmul)
    if s.bias:
        u = u + f32("b_in")
    if s.glu:
        g = _mm(h, lp["w_gate"], matmul)
        if s.bias:
            g = g + f32("b_gate")
        u = _act(s, g) * u
    else:
        u = _act(s, u)
    u = _mm(u, lp["w_out"], matmul)
    if s.bias:
        u = u + f32("b_out")
    return x + u


def forward(s: Spec, params: dict, tokens, matmul: str = "f32"):
    """Logits (B, S, V) in float32 of token ids (B, S), positions 0..S-1."""
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(jnp.float32)
    if s.learned_pos:
        x = x + params["pos_embed"][: tokens.shape[1]].astype(jnp.float32)

    def body(x, lp):
        return jax.checkpoint(lambda x, lp: _layer(s, x, lp, matmul))(x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _norm(s, x, params["final_norm_scale"], params.get("final_norm_bias"))
    w = params["tok_embed"].T if s.tie_embeddings else params["lm_head"]
    return _mm(x, w, matmul)


# ---------------------------------------------------------------------------
# serving check: the gap of each served token below the reference's best
# ---------------------------------------------------------------------------
def served_gaps(s: Spec, params: dict, seqs: List[np.ndarray],
                served: List[np.ndarray], pad_to: int,
                control: Optional[str] = None) -> np.ndarray:
    """For each sequence (the tokens the program was fed) and the tokens it
    served at its last ``len(served)`` positions: the reference's best logit
    less its logit of the served token, per served token. With
    ``control``, the token is the one the ``control`` precision puts first
    at that position instead of the served one. Sequences are padded to
    ``pad_to`` at the end (causal: padding changes no earlier logit), so
    one program serves every length."""
    ref = jax.jit(lambda p, t: forward(s, p, t))
    ctl = (jax.jit(lambda p, t: forward(s, p, t, matmul=control))
           if control else None)
    out = []
    for seq, tok in zip(seqs, served):
        n, m = len(seq), len(tok)
        t = np.zeros((1, pad_to), np.int32)
        t[0, :n] = seq
        lg = np.asarray(ref(params, t))[0, n - m:n]
        if ctl is not None:
            tok = np.asarray(ctl(params, t))[0, n - m:n].argmax(-1)
        out.append(lg.max(-1) - lg[np.arange(m), np.asarray(tok)])
    return np.concatenate(out) if out else np.zeros(0)


# ---------------------------------------------------------------------------
# training check: AdamW steps from the same seed on the same batches
# ---------------------------------------------------------------------------
def loss_fn(s: Spec, params, tokens, labels, matmul: str = "f32"):
    lg = forward(s, params, tokens, matmul)
    lse = jax.nn.logsumexp(lg, -1)
    ll = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - ll)


def adamw_steps(s: Spec, seed: int, batches, opt: dict, rows: int,
                matmul: str = "f32") -> dict:
    """Run ``len(batches)`` AdamW steps (global-norm clipping, linear
    warm-up into a cosine schedule, decoupled weight decay) from the
    seed's weights, each batch's loss and gradient taken over blocks of
    ``rows`` rows so that it fits. Returns each step's loss, and by leaf
    path the norm of the first step's clipped gradient and of the
    parameters' change over all steps."""
    p0 = init_params(s, seed)
    f32 = lambda t: jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), t)
    block = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss_fn(s, p, t, l, matmul)))

    def grad(p, batch):
        n = batch.shape[0]
        loss, g = 0.0, None
        for i in range(0, n, rows):
            b = batch[i:i + rows]
            w = b.shape[0] / n
            lb, gb = block(p, jnp.asarray(b[:, :-1]), jnp.asarray(b[:, 1:]))
            loss += w * float(lb)
            gb = jax.tree_util.tree_map(lambda x: w * x, gb)
            g = gb if g is None else jax.tree_util.tree_map(jnp.add, g, gb)
        return loss, g

    def lr_at(t):
        warm = min(1.0, (t + 1) / max(opt["warmup_steps"], 1))
        prog = min(1.0, max(0.0, (t - opt["warmup_steps"])
                            / max(opt["total_steps"] - opt["warmup_steps"], 1)))
        cos = 0.5 * (1 + math.cos(math.pi * prog))
        return opt["lr"] * warm * (opt["min_lr_ratio"]
                                   + (1 - opt["min_lr_ratio"]) * cos)

    @jax.jit
    def update(p, m, v, g, t, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                          for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(
            lambda x: x * jnp.minimum(1.0, opt["clip_norm"] / (gn + 1e-9)), g)
        b1, b2 = opt["beta1"], opt["beta2"]
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        c1, c2 = 1 - b1 ** (t + 1), 1 - b2 ** (t + 1)
        p = jax.tree_util.tree_map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                                      + opt["weight_decay"] * p), p, m, v)
        return p, m, v, g

    p = f32(p0)
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, g1 = [], None
    for t, b in enumerate(batches):
        loss, g = grad(p, b)
        p, m, v, g = update(p, m, v, g, t, lr_at(t))
        losses.append(float(loss))
        if t == 0:
            g1 = leaf_norms(g)
    delta = jax.tree_util.tree_map(lambda a, b: a - b.astype(jnp.float32),
                                   p, p0)
    return {"losses": losses, "grad": g1, "delta": leaf_norms(delta)}


def leaf_norms(tree) -> Dict[str, float]:
    """Float32 norm of every leaf, keyed by its path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32))))) for k, x in flat}
