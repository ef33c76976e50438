"""Train-step factory: microbatched grad accumulation, donation, shardings.

The produced step is a single jit'd function
    (params, opt_state, batch [, err]) -> (params, opt_state, metrics [, err])
with in/out shardings derived from the model's spec tree (FSDP × TP per
DESIGN.md §5), buffers donated, bf16 compute / fp32 master params, optional
int8+EF gradient compression across the "pod" axis.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.model_zoo import Model
from repro.optim import adamw
from repro.optim.compression import cross_pod_sync

PyTree = Any


@dataclass(frozen=True)
class TrainStepConfig:
    microbatches: int = 1
    grad_compression: bool = False   # int8+EF across the pod axis
    opt: adamw.AdamWConfig = adamw.AdamWConfig()


def _accumulate_grads(model: Model, params, batch, microbatches: int):
    """lax.scan over microbatches; batch leading dim must divide evenly.
    Returns (loss, grads, stats), each stat (a largest value, as
    ``moe_load_max``) the largest over the microbatches."""
    grad_fn = jax.value_and_grad(model.loss_and_stats, has_aux=True)
    if microbatches <= 1:
        (loss, stats), grads = grad_fn(params, batch)
        return loss, grads, stats

    def reshape(x, axis=0):
        b = x.shape[axis]
        assert b % microbatches == 0, (b, microbatches)
        x = x.reshape(x.shape[:axis] + (microbatches, b // microbatches)
                      + x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)

    # batch dims: "positions" is (3, B, S) — batch on axis 1 (M-RoPE streams)
    mb = {k: reshape(v, 1 if k == "positions" else 0)
          for k, v in batch.items()}

    def body(carry, one):
        loss_acc, grads_acc = carry
        (loss, stats), grads = grad_fn(params, one)
        grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
        return (loss_acc + loss, grads_acc), stats

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, grads), stats = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zeros), mb)
    scale = 1.0 / microbatches
    return (loss * scale, jax.tree_util.tree_map(lambda g: g * scale, grads),
            jax.tree_util.tree_map(lambda s: jnp.max(s, axis=0), stats))


def make_train_step(model: Model, mesh, cfg: TrainStepConfig,
                    batch_specs: PyTree):
    """Returns (jit_step, state_shardings). ``batch_specs``: PartitionSpec
    tree for the batch dict (from Model.batch_specs)."""
    _, param_specs = model.init(None, abstract=True)
    compress = cfg.grad_compression and "pod" in mesh.axis_names

    sh = lambda spec_tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
    params_sh = sh(param_specs)
    opt_sh = adamw.AdamWState(step=NamedSharding(mesh, P()),
                              mu=sh(param_specs), nu=sh(param_specs))
    batch_sh = sh(batch_specs)
    metrics_sh = NamedSharding(mesh, P())    # every metric is a scalar

    if compress:
        # NOTE (documented limitation, EXPERIMENTS §Dry-run): ideally the
        # gradient computation would run inside a shard_map over "pod" so the
        # autodiff-inserted pod reduction disappears and ONLY the int8+EF
        # all-gather crosses DCN. jax 0.8 cannot express that here: the
        # model's internal sharding constraints use P(("pod","data"), …)
        # tuples, and a manual "pod" axis may not mix with auto axes in one
        # PartitionSpec dim. The compressed sync therefore runs *after* the
        # (redundant) automatic reduction in this build; the primitive itself
        # is verified to cut cross-pod bytes 4× in isolation
        # (tests/test_sharding.py::test_compressed_grad_sync_reduces_dcn_bytes).
        def step(params, opt_state, batch, err):
            loss, grads, stats = _accumulate_grads(model, params, batch,
                                                   cfg.microbatches)
            grads, err = cross_pod_sync(grads, err, mesh, compress=True)
            new_params, new_opt, metrics = adamw.update(cfg.opt, grads,
                                                        opt_state, params)
            metrics.update(stats, loss=loss)
            return new_params, new_opt, metrics, err

        jit_step = jax.jit(
            step,
            in_shardings=(params_sh, opt_sh, batch_sh, params_sh),
            out_shardings=(params_sh, opt_sh, metrics_sh, params_sh),
            donate_argnums=(0, 1, 3))
    else:
        def step(params, opt_state, batch):
            loss, grads, stats = _accumulate_grads(model, params, batch,
                                                   cfg.microbatches)
            new_params, new_opt, metrics = adamw.update(cfg.opt, grads,
                                                        opt_state, params)
            metrics.update(stats, loss=loss)
            return new_params, new_opt, metrics

        jit_step = jax.jit(
            step,
            in_shardings=(params_sh, opt_sh, batch_sh),
            out_shardings=(params_sh, opt_sh, metrics_sh),
            donate_argnums=(0, 1))
    return jit_step, {"params": params_sh, "opt": opt_sh, "batch": batch_sh,
                      "compress": compress}


def make_eval_step(model: Model, mesh, batch_specs: PyTree):
    _, param_specs = model.init(None, abstract=True)
    sh = lambda spec_tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))

    def step(params, batch):
        return model.loss_fn(params, batch)

    return jax.jit(step, in_shardings=(sh(param_specs), sh(batch_specs)),
                   out_shardings=NamedSharding(mesh, P()))
