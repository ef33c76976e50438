"""Unified model API over all families + ``input_specs`` for the dry-run.

``Model`` wires a ModelConfig to (init, loss_fn, prefill, decode, caches) and
produces the ShapeDtypeStruct stand-ins used by ``launch/dryrun.py`` — weak-
type-correct, shardable, zero device allocation.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ENCDEC, VLM, ModelConfig
from repro.configs.shapes import DECODE, PREFILL, TRAIN, ShapeSuite
from repro.models import encdec as encdec_mod
from repro.models import transformer as tfm
from repro.models.common import AxisEnv, ShardingPolicy, make_policy

PyTree = Any


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    env: AxisEnv
    pol: ShardingPolicy

    # ------------------------------------------------------------------
    def init(self, key, *, abstract: bool = False) -> Tuple[PyTree, PyTree]:
        """Returns (params, spec-tree). abstract=True -> ShapeDtypeStructs."""
        if self.cfg.family == ENCDEC:
            return encdec_mod.init_encdec(self.cfg, key, self.pol, self.env,
                                          abstract=abstract)
        return tfm.init_decoder_only(self.cfg, key, self.pol, self.env,
                                     abstract=abstract)

    def abstract_params(self, mesh) -> Tuple[PyTree, PyTree]:
        """(ShapeDtypeStructs with shardings, spec tree) — no allocation."""
        shapes, specs = self.init(None, abstract=True)
        out = jax.tree_util.tree_map(
            lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=NamedSharding(mesh, sp)),
            shapes, specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        return out, specs

    # ------------------------------------------------------------------
    def forward(self, params, batch, *, return_cache: bool = False,
                last_token_only: bool = False):
        if self.cfg.family == ENCDEC:
            return encdec_mod.forward_encdec(
                self.cfg, params, batch, self.env, self.pol,
                return_cache=return_cache, last_token_only=last_token_only)
        return tfm.forward_decoder_only(
            self.cfg, params, batch, self.env, self.pol,
            return_cache=return_cache, last_token_only=last_token_only)

    def loss_fn(self, params, batch) -> jnp.ndarray:
        return self.loss_and_stats(params, batch)[0]

    def loss_and_stats(self, params, batch):
        """(loss, stats): the training loss, the load-balance loss summed
        over layers times ``aux_loss_coef`` included, and the dict of what
        a train step reports beside it (empty but for a MoE model's
        ``moe_load_max``)."""
        if self.cfg.family == ENCDEC:
            logits, aux, _ = self.forward(params, batch)
            stats = {}
        else:
            logits, aux, _, stats = tfm.forward_decoder_only(
                self.cfg, params, batch, self.env, self.pol, stats=True)
        loss = softmax_xent(logits, batch["labels"])
        return loss + self.cfg.aux_loss_coef * aux, stats

    def decode(self, params, cache, batch):
        if self.cfg.family == ENCDEC:
            return encdec_mod.decode_encdec(self.cfg, params, cache, batch,
                                            self.env, self.pol)
        return tfm.decode_decoder_only(self.cfg, params, cache, batch,
                                       self.env, self.pol)

    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_seq: int, dtype=jnp.bfloat16):
        if self.cfg.family == ENCDEC:
            return encdec_mod.init_cache_encdec(self.cfg, batch, max_seq, dtype)
        return tfm.init_cache_decoder_only(self.cfg, batch, max_seq, dtype)

    def cache_specs(self, batch: int) -> PyTree:
        if self.cfg.family == ENCDEC:
            return encdec_mod.cache_specs_encdec(self.cfg, batch, self.env, self.pol)
        return tfm.cache_specs_decoder_only(self.cfg, batch, self.env, self.pol)

    def abstract_cache(self, batch: int, max_seq: int, mesh,
                       dtype=jnp.bfloat16) -> PyTree:
        shapes = jax.eval_shape(lambda: self.init_cache(batch, max_seq, dtype))
        specs = self.cache_specs(batch)
        return jax.tree_util.tree_map(
            lambda s, sp: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=NamedSharding(mesh, sp)),
            shapes, specs)

    # ------------------------------------------------------------------
    def serving_inventory(self, params: PyTree, cache: PyTree):
        """TensorInfo inventory of this tenant's *real* serving state.

        The offload planner otherwise works from the analytic
        ``WorkloadEstimate``; a live runtime knows its actual params and KV
        pool, so the plan can be cut against the true byte counts. Leaf
        paths are prefixed ``params/`` and ``kv/`` so the same names flow
        through plan → ``shardings_with_offload`` / ``KVPool`` placement.
        KV leaves are divisible (the pool spills a cold tail of the
        sequence axis — paper §VI-A's fine-grained spill) and so are
        embedding tables (row granularity).
        """
        from dataclasses import replace
        from repro.core.offload import TensorInfo, inventory_from_tree
        inv = inventory_from_tree({"params": params, "kv": cache})
        out = []
        for t in inv:
            if t.name.startswith("kv/"):
                t = TensorInfo(t.name, t.bytes, "kv_cache",
                               offloadable=True, divisible=True)
            elif t.group == "embed":
                t = replace(t, divisible=True)
            out.append(t)
        return out

    def cache_bytes(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> int:
        """KV/state pool footprint without allocating it."""
        shapes = jax.eval_shape(lambda: self.init_cache(batch, max_seq, dtype))
        return sum(int(s.size) * s.dtype.itemsize
                   for s in jax.tree_util.tree_leaves(shapes))

    # ------------------------------------------------------------------
    def batch_specs(self, shape: ShapeSuite) -> Dict[str, Tuple]:
        """(shape, dtype, PartitionSpec) per input — the single source of
        truth for both input_specs (dry-run) and synthetic batches (smoke)."""
        cfg, env = self.cfg, self.env
        B = shape.global_batch
        S = 1 if shape.kind == DECODE else shape.seq_len
        if self.pol.profile == "fsdp_only":
            baxes = env.batch_axes_joint(B)
        else:
            baxes = env.batch_axes(B)
        seq_ax = env.tp if (self.pol.seq_sharded_acts and shape.kind != DECODE) else None
        out: Dict[str, Tuple] = {}
        if cfg.family == VLM:
            out["embeds"] = ((B, S, cfg.d_model), jnp.bfloat16, P(baxes, seq_ax, None))
            out["positions"] = ((3, B, S), jnp.int32, P(None, baxes, None))
        elif cfg.family == ENCDEC:
            if shape.kind != DECODE:
                out["frames"] = ((B, cfg.encoder_seq, cfg.d_model), jnp.bfloat16,
                                 P(baxes, None, None))
            out["tokens"] = ((B, S), jnp.int32, P(baxes, None))
        else:
            out["tokens"] = ((B, S), jnp.int32, P(baxes, seq_ax))
        if shape.kind == TRAIN:
            out["labels"] = ((B, S), jnp.int32, P(baxes, seq_ax))
        if shape.kind == DECODE:
            out["pos"] = ((), jnp.int32, P())
        return out

    def input_specs(self, shape: ShapeSuite, mesh) -> Dict[str, jax.ShapeDtypeStruct]:
        return {
            name: jax.ShapeDtypeStruct(shp, dt, sharding=NamedSharding(mesh, sp))
            for name, (shp, dt, sp) in self.batch_specs(shape).items()
        }

    def synthetic_batch(self, shape: ShapeSuite, key=None) -> Dict[str, jnp.ndarray]:
        key = key if key is not None else jax.random.PRNGKey(0)
        out = {}
        for name, (shp, dt, _) in self.batch_specs(shape).items():
            key, sub = jax.random.split(key)
            if dt == jnp.int32:
                hi = self.cfg.vocab_size if name in ("tokens", "labels") else max(
                    1, min(shp[-1] if shp else 1, 4096))
                out[name] = (jnp.zeros(shp, dt) if not shp else
                             jax.random.randint(sub, shp, 0, hi, dt))
            else:
                out[name] = 0.02 * jax.random.normal(sub, shp, dt)
        if "pos" in out:
            out["pos"] = jnp.asarray(0, jnp.int32)
        return out


def softmax_xent(logits, labels) -> jnp.ndarray:
    """Mean token cross-entropy; one-hot matmul form (vocab-sharding safe)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    onehot = jax.nn.one_hot(labels, lf.shape[-1], dtype=lf.dtype)
    ll = jnp.einsum("...v,...v->...", lf, onehot)
    return jnp.mean(lse - ll)


def build_model(cfg: ModelConfig, mesh_or_env) -> Model:
    env = (mesh_or_env if isinstance(mesh_or_env, AxisEnv)
           else AxisEnv.from_mesh(mesh_or_env))
    return Model(cfg=cfg, env=env, pol=make_policy(cfg, env))
