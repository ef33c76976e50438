"""The dropless MoE layer at a small size (width 64, 4 experts, top-2):
its row permute kernels against gathers by ``jnp.take``, its grouped
products against a loop over the experts, the layer and the whole model
against the plain float32 reference of the benchmark
(``benchmarks/chip/reference/moe_decoder.py``), and routing whose device
work does not depend on where the tokens go."""
import functools
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu

from benchmarks.chip.reference import moe_decoder as md
from repro.configs import get_config
from repro.kernels import ops, ref
from repro.models import moe
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model

ROOT = Path(__file__).resolve().parents[1]
SMALL = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 32,
         "num_local_experts": 4, "num_experts_per_tok": 2, "vocab_size": 256}


def _rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


def _spec(**over):
    cfg = json.loads((ROOT / "benchmarks/chip/configs/granite-moe-1b-a400m.json")
                     .read_text())
    cfg.update(SMALL, **over)
    return md.spec(cfg)


def _program(s, dtype="float32"):
    """The program's model at the reference's sizes, computing in
    ``dtype``."""
    cfg = get_config("granite-moe-1b-a400m").with_(
        **s.program_fields, dtype=dtype, remat="none")
    return cfg, build_model(cfg, host_axis_env())


@pytest.mark.parametrize("sizes", [[24, 40, 0, 64], [128, 0, 0, 0],
                                   [1, 127, 0, 0]],
                         ids=["empty-expert", "one-expert-every-row",
                              "one-row"])
def test_grouped_products_match_a_loop_over_experts(sizes):
    """``moe.grouped_ffn`` over rows sorted by expert against each expert's
    rows through its own weights, one expert at a time: the output, and the
    gradients of the rows and of every expert weight."""
    s = _spec()
    cfg, _ = _program(s)
    E, d, f = s.experts, s.d_model, s.d_ff
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    rows = jax.random.normal(ks[0], (sum(sizes), d), jnp.float32)
    p = {"w_in": jax.random.normal(ks[1], (E, d, f)) / 8,
         "w_gate": jax.random.normal(ks[2], (E, d, f)) / 8,
         "w_out": jax.random.normal(ks[3], (E, f, d)) / 6}
    gs = jnp.asarray(sizes, jnp.int32)
    dy = jax.random.normal(ks[4], (sum(sizes), d), jnp.float32)

    def loop(p, rows):
        h = ref.gmm_ref(rows, p["w_in"], sizes)
        g = ref.gmm_ref(rows, p["w_gate"], sizes)
        return ref.gmm_ref(jax.nn.silu(g) * h, p["w_out"], sizes)

    got, vjp = jax.vjp(lambda p, r: moe.grouped_ffn(cfg, p, r, gs), p, rows)
    want, vjp_ref = jax.vjp(loop, p, rows)
    assert _rel_err(got, want) < 1e-5
    for name, a, b in zip(["w_gate", "w_in", "w_out", "rows"],
                          jax.tree_util.tree_leaves(vjp(dy)),
                          jax.tree_util.tree_leaves(vjp_ref(dy))):
        assert _rel_err(a, b) < 1e-5, name
    # an expert with no rows gets no gradient
    for e in np.flatnonzero(np.asarray(sizes) == 0):
        assert not np.any(np.asarray(vjp(dy)[0]["w_in"][e]))


def _routing(kind, T):
    """(top_w, top_e) of T tokens: every pair to one expert (top-1), two of
    four experts left empty (top-2), or spread over eight (top-4)."""
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    if kind == "one-expert":
        return jnp.ones((T, 1), jnp.float32), jnp.zeros((T, 1), jnp.int32)
    k, E = (2, 4) if kind == "empty-experts" else (4, 8)
    logits = jax.random.normal(ks[0], (T, E))
    if kind == "empty-experts":
        logits = logits.at[:, ::2].add(-100.0)
    w, e = jax.lax.top_k(jax.nn.softmax(logits), k)
    return w / jnp.sum(w, -1, keepdims=True), e.astype(jnp.int32)


@pytest.mark.parametrize("d", [64, 256])
@pytest.mark.parametrize("kind", ["one-expert", "empty-experts", "spread"])
def test_permute_kernels_match_take(kind, d, monkeypatch):
    """The row permute, its kernels in TPU interpret mode (DMAs and
    semaphores emulated), against ``jnp.take`` and the float32 weighted
    sum: the dispatch bit for bit, the combine within float32 rounding, and
    both gradients (the combine kernel and its transpose) against
    ``jax.vjp`` of the gathers. 24 tokens fill no whole block, so the
    padding paths run too."""
    monkeypatch.setattr(ops, "_interpret", pltpu.InterpretParams)
    T = 24
    top_w, top_e = _routing(kind, T)
    k = top_e.shape[1]
    E = max(int(jnp.max(top_e)) + 1, 4)
    order, inv = moe.sort_by_expert(top_e)
    ks = jax.random.split(jax.random.PRNGKey(d), 3)
    x = jax.random.normal(ks[0], (T, d), jnp.float32)
    out = jax.random.normal(ks[1], (T * k, d), jnp.float32)

    def kernels(x, out, w):
        perm = ops.moe_route(top_e, order, inv, E, x.dtype)
        return ops.moe_dispatch(x, perm), ops.moe_combine(out, w, perm)

    def takes(x, out, w):
        y = jnp.take(out, inv, axis=0).reshape(T, k, d)
        return (jnp.take(x, order // k, axis=0),
                jnp.sum(y * w[..., None], axis=1))

    @functools.partial(jax.jit, static_argnums=0)
    def both(f, cot):
        got, vjp = jax.vjp(f, x, out, top_w)
        return got, vjp(cot)

    cot = (jax.random.normal(ks[2], (T * k, d)),
           jnp.cos(jnp.arange(T * d, dtype=jnp.float32)).reshape(T, d))
    (rows, y), grads = both(kernels, cot)
    (want_rows, want_y), want_grads = both(takes, cot)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want_rows))
    np.testing.assert_allclose(y, want_y, rtol=1e-6, atol=1e-6)
    for name, a, b in zip(["x", "out", "w"], grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5, err_msg=name)


def _skewed_layer(s, seed=5):
    """One layer's MoE weights whose router sends most tokens to experts 0
    and 1, and its input."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    E, d, f = s.experts, s.d_model, s.d_ff
    router = 0.02 * jax.random.normal(ks[0], (d, E))
    router = router.at[:, :2].add(0.5)
    lp = {"router": router,
          "w_in": jax.random.normal(ks[1], (E, d, f)) / 8,
          "w_gate": jax.random.normal(ks[2], (E, d, f)) / 8,
          "w_out": jax.random.normal(ks[3], (E, f, d)) / 6}
    x = jnp.abs(jax.random.normal(ks[4], (2, 64, d), jnp.float32))
    return lp, x


def test_dropless_layer_matches_every_expert_reference():
    """Under routing skewed onto two of four experts, the layer equals the
    reference's every-expert form for every token, with its gradients: no
    (token, expert) pair is dropped however many land on one expert."""
    s = _spec()
    cfg, _ = _program(s)
    lp, x = _skewed_layer(s)
    _, top_e = jax.lax.top_k(moe.route(cfg, lp, x)[0], s.top_k)
    sizes = np.asarray(moe.expert_sizes(cfg, top_e))
    assert sizes.sum() == x.shape[0] * x.shape[1] * s.top_k
    assert sizes[:2].min() > 0.8 * x.shape[0] * x.shape[1]    # skewed

    def want(lp, x):
        with jax.default_matmul_precision("highest"):
            return md._moe(s, x.reshape(-1, s.d_model), lp, "f32")[0]

    def got(lp, x):
        return moe.apply_moe(cfg, lp, x)[0].reshape(-1, s.d_model)

    g, vjp = jax.vjp(got, lp, x)
    w, vjp_ref = jax.vjp(want, lp, x)
    per_token = np.max(np.abs(np.asarray(g - w)), axis=-1)
    assert per_token.max() < 1e-4 * float(jnp.max(jnp.abs(w))), per_token
    dy = jnp.sin(w)
    for a, b in zip(jax.tree_util.tree_leaves(vjp(dy)),
                    jax.tree_util.tree_leaves(vjp_ref(dy))):
        assert _rel_err(a, b) < 1e-4


def test_routing_gradient_moves_no_row_by_scatter():
    """The layer's backward holds no scatter: rows go back through the
    inverse permutation's gathers, the weights through a one-hot mask."""
    s = _spec()
    cfg, _ = _program(s)
    lp, x = _skewed_layer(s)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda lp, x: jnp.sum(moe.apply_moe(cfg, lp, x)[0]),
        argnums=(0, 1)))(lp, x)
    assert "scatter" not in str(jaxpr)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 3e-2)])
def test_model_loss_and_grads_match_reference(dtype, tol):
    """The whole model (2 layers, embedding, muP multipliers, tied head,
    aux loss) on the reference's seeded weights: loss and every leaf's
    gradient against ``moe_decoder``, in float32 and in the benchmark's
    bfloat16 compute."""
    s = _spec()
    cfg, model = _program(s, dtype)
    seed = 2**31 + 11
    params = model.init(jax.random.PRNGKey(seed))[0]
    ref_params = md.init_params(s, seed)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(ref_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, s.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(
            lambda p: md.loss_fn(s, p, batch["tokens"], batch["labels"],
                                 None))(ref_params)
    assert abs(float(loss) - float(want)) < tol * abs(float(want))
    flat = jax.tree_util.tree_flatten_with_path(want_g)[0]
    for (path, b), a in zip(flat, jax.tree_util.tree_leaves(grads)):
        assert _rel_err(a, b) < 10 * tol, jax.tree_util.keystr(path)


def test_moe_load_max_matches_reference_routing():
    """The train step's ``moe_load_max``: the most-loaded expert's routed
    rows over the mean, the largest over layers, as the reference's routing
    counts them."""
    s = _spec()
    cfg, model = _program(s)
    seed = 2**31 + 12
    params = model.init(jax.random.PRNGKey(seed))[0]
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 65), 0, s.vocab)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    _, stats = model.loss_and_stats(params, batch)
    with jax.default_matmul_precision("highest"):
        counts = md.forward_counts(s, md.init_params(s, seed),
                                   batch["tokens"])
    assert counts.shape == (s.layers, s.experts)
    assert np.all(np.asarray(counts).sum(-1) == toks[:, 1:].size * s.top_k)
    assert float(stats["moe_load_max"]) == pytest.approx(md.load_max(s, counts))
    assert float(stats["moe_load_max"]) > 1.0


def test_train_step_reports_moe_load_max():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_host_mesh
    from repro.optim import adamw
    from repro.train.train_step import TrainStepConfig, make_train_step
    s = _spec()
    cfg, _ = _program(s, "bfloat16")
    mesh = make_host_mesh(1, 1)
    model = build_model(cfg, mesh)
    step, _ = make_train_step(model, mesh, TrainStepConfig(),
                              {"tokens": P(), "labels": P()})
    params = model.init(jax.random.PRNGKey(0))[0]
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 33), 0, s.vocab)
    _, _, met = step(params, adamw.init(params),
                     {"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    assert set(met) == {"loss", "grad_norm", "lr", "moe_load_max"}
    assert 1.0 <= float(met["moe_load_max"]) <= s.experts / s.top_k
