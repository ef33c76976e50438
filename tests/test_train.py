"""Training substrate: loss descent, grad-accumulation exactness, checkpoint
roundtrip + corruption resistance, fault-tolerant restart path."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.shapes import ShapeSuite, TRAIN
from repro.data.pipeline import DataPipeline, SyntheticSource
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model
from repro.optim import adamw
from repro.train import checkpoint as ckpt
from repro.train.train_step import _accumulate_grads

ENV = host_axis_env()


def _tiny_model(arch="gpt2-124m", **kw):
    cfg = get_config(arch).reduced().with_(**kw)
    model = build_model(cfg, ENV)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_loss_decreases():
    cfg, model, params = _tiny_model()
    opt_cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=5, total_steps=100)
    state = adamw.init(params)
    src = SyntheticSource(cfg.vocab_size, seed=3)

    @jax.jit
    def step(params, state, batch):
        loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
        p, s, _ = adamw.update(opt_cfg, grads, state, params)
        return p, s, loss

    losses = []
    for i in range(25):
        arr = src.batch(i, 4, 32)
        batch = {"tokens": jnp.asarray(arr[:, :-1]),
                 "labels": jnp.asarray(arr[:, 1:])}
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, losses


def test_microbatch_grads_match_full_batch():
    # fp32 activations so the only difference is summation order
    cfg, model, params = _tiny_model(remat="none", dtype="float32")
    batch = model.synthetic_batch(ShapeSuite("t", TRAIN, 32, 4))
    loss1, g1, _ = _accumulate_grads(model, params, batch, 1)
    loss4, g4, _ = _accumulate_grads(model, params, batch, 4)
    # microbatch mean-of-means == full mean (equal microbatch sizes)
    np.testing.assert_allclose(float(loss1), float(loss4), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g4)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_checkpoint_roundtrip_and_gc():
    _, model, params = _tiny_model()
    tree = {"params": params, "step": jnp.asarray(7)}
    with tempfile.TemporaryDirectory() as d:
        for s in (10, 20, 30, 40):
            ckpt.save(d, s, tree, keep=2)
        assert ckpt.latest_step(d) == 40
        kept = sorted(os.listdir(d))
        assert len([k for k in kept if k.startswith("step_")]) == 2
        restored, s = ckpt.restore(d, tree)
        assert s == 40
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_volume_matches_files_written():
    # volume_bytes is the quantity PerfModel.checkpoint_cost prices for a
    # preemption: it must equal the payload save() actually writes
    tree = {"a": jnp.ones((8, 4), jnp.float32), "b": jnp.zeros(3, jnp.int32)}
    vol = ckpt.volume_bytes(tree)
    assert vol == 8 * 4 * 4 + 3 * 4
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, tree)
        step_dir = os.path.join(d, "step_00000001")
        on_disk = sum(np.load(os.path.join(step_dir, f)).nbytes
                      for f in os.listdir(step_dir) if f.endswith(".npy"))
        assert on_disk == vol


def test_checkpoint_rejects_wrong_structure():
    _, model, params = _tiny_model()
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(d, 1, {"params": params})
        with pytest.raises(ValueError):
            ckpt.restore(d, {"params": params, "extra": jnp.zeros(3)})


def test_fault_runner_restarts_and_repartitions():
    from repro.core.partitioner import StaticPartitioner
    from repro.core.slices import get_profile
    from repro.train.fault import (FaultTolerantRunner, RunnerConfig,
                                   StepFailure)
    cfg, model, _ = _tiny_model()
    opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=60)
    src = SyntheticSource(cfg.vocab_size, seed=5)
    pipe = DataPipeline(src, 2, 16)

    def build_step(profile):
        params, _ = model.init(jax.random.PRNGKey(0))
        state = {"params": params, "opt": adamw.init(params)}
        latest = ckpt.latest_step(d)
        if latest is not None:
            state, _ = ckpt.restore(d, state)

        @jax.jit
        def jstep(state, batch):
            loss, grads = jax.value_and_grad(model.loss_fn)(state["params"],
                                                            batch)
            p, o, met = adamw.update(opt_cfg, grads, state["opt"],
                                     state["params"])
            met["loss"] = loss
            return {"params": p, "opt": o}, met

        def step(state, batch):
            b = {k: jnp.asarray(v) for k, v in batch.items()}
            state, met = jstep(state, b)
            return state, {k: float(v) for k, v in met.items()}
        return step, state

    part = StaticPartitioner()
    prof = get_profile("8s.128c")
    part.allocate(prof)
    fired = []

    def fail_hook(step):
        if step == 12 and not fired:
            fired.append(step)
            part.fail_chips([(0, 0)])
            raise StepFailure("injected")

    with tempfile.TemporaryDirectory() as d:
        runner = FaultTolerantRunner(
            RunnerConfig(ckpt_dir=d, ckpt_every=5, max_restarts=2),
            part, prof, build_step, pipe.batch_at, lambda s: s, fail_hook)
        stats = runner.run(20)
    assert stats.restarts == 1
    assert stats.repartitions  # moved to a smaller/other slice
    assert stats.steps_done >= 20
