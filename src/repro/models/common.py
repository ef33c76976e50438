"""Sharding policy, axis environment, and parameter construction helpers.

Design (see DESIGN.md §5):
  * mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
    multi-pod. Parameters never shard over "pod" (pure DP across pods, grad
    all-reduce over DCN once per step); batch shards over ("pod", "data").
  * parameters are FSDP-sharded over "data" on their d_model-sized dim and
    tensor-sharded over "model" on their heads/ffn/experts/vocab dim
    (ZeRO-3: XLA all-gathers one layer slice per scan iteration).
  * archs whose head counts do not divide the model axis (starcoder2: 36,
    whisper: 20) use sequence-parallel attention; tiny archs (mamba2-130m,
    gpt2-124m) use pure-FSDP ("fsdp_only") with model-axis-replicated compute
    — the resulting waste is *the paper's subject* and shows up honestly in
    the roofline table.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig

PyTree = Any


# ---------------------------------------------------------------------------
# axis environment
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class AxisEnv:
    """Logical → physical mesh-axis mapping for one mesh."""
    mesh_axes: Tuple[str, ...]           # e.g. ("pod", "data", "model")
    axis_sizes: Dict[str, int] = field(default_factory=dict)

    @property
    def fsdp(self) -> str:
        return "data"

    @property
    def tp(self) -> str:
        return "model"

    @property
    def has_pod(self) -> bool:
        return "pod" in self.mesh_axes

    def batch_axes(self, global_batch: int) -> Optional[Tuple[str, ...]]:
        """Largest prefix of ("pod","data") that evenly divides the batch."""
        axes: Tuple[str, ...] = ("pod", "data") if self.has_pod else ("data",)
        size = math.prod(self.axis_sizes[a] for a in axes)
        if global_batch % size == 0:
            return axes
        if "data" in axes and global_batch % self.axis_sizes["data"] == 0:
            return ("data",)
        return None  # replicate (e.g. long_500k batch=1)

    def batch_axes_joint(self, global_batch: int) -> Optional[Tuple[str, ...]]:
        """Largest divisible prefix of ("pod","data","model") — used by the
        fsdp_only profile, where the model axis carries no tensor parallelism
        and would otherwise replicate every activation."""
        base = ("pod", "data", "model") if self.has_pod else ("data", "model")
        for end in range(len(base), 0, -1):
            axes = base[:end]
            size = math.prod(self.axis_sizes[a] for a in axes)
            if global_batch % size == 0:
                return axes
        return None

    def size(self, axis: str) -> int:
        return self.axis_sizes[axis]

    def __hash__(self) -> int:   # a Model is a static argument of jit
        return hash((self.mesh_axes, tuple(sorted(self.axis_sizes.items()))))

    @staticmethod
    def from_mesh(mesh) -> "AxisEnv":
        return AxisEnv(tuple(mesh.axis_names),
                       {a: int(s) for a, s in zip(mesh.axis_names, mesh.devices.shape)})


def host_axis_env(model_parallel: int = 1) -> AxisEnv:
    """Single-host env for smoke tests (1 device)."""
    return AxisEnv(("data", "model"), {"data": 1, "model": model_parallel})


# ---------------------------------------------------------------------------
# sharding policy
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ShardingPolicy:
    profile: str            # "tp" | "fsdp_only"
    head_sharded: bool      # q-heads divisible by model axis
    kv_sharded: bool        # kv-heads divisible by model axis
    vocab_sharded: bool
    ffn_sharded: bool
    experts_sharded: bool
    ssm_sharded: bool       # ssm heads divisible
    seq_parallel_attn: bool # used when heads are not shardable
    seq_residuals: bool = False  # Megatron SP: S-sharded layer boundaries

    @property
    def seq_sharded_acts(self) -> bool:
        return self.seq_parallel_attn or self.seq_residuals


def make_policy(cfg: ModelConfig, env: AxisEnv) -> ShardingPolicy:
    tp = env.size(env.tp)
    if cfg.name in ("mamba2-130m", "gpt2-124m") and tp > 1:
        profile = "fsdp_only"
    else:
        profile = "tp"
    if profile == "fsdp_only" or tp == 1:
        return ShardingPolicy(profile, False, False, False, False, False,
                              False, False, False)
    head_ok = cfg.num_heads > 0 and cfg.num_heads % tp == 0
    kv_ok = cfg.num_kv_heads > 0 and cfg.num_kv_heads % tp == 0
    vocab_ok = cfg.vocab_size % tp == 0
    ffn_ok = cfg.d_ff > 0 and cfg.d_ff % tp == 0
    exp_ok = cfg.num_experts > 0 and cfg.num_experts % tp == 0
    ssm_ok = cfg.ssm_state > 0 and cfg.ssm_heads % tp == 0
    seq_par = cfg.num_heads > 0 and not head_ok
    if seq_par:
        # sequence-parallel archs (starcoder2: 36 heads, whisper: 20) keep
        # activations S-sharded over "model"; weights stay data-FSDP only so
        # every einsum is token-local (KV all-gather is the only attn comm).
        kv_ok = vocab_ok = ffn_ok = False
    return ShardingPolicy(profile, head_ok, kv_ok, vocab_ok, ffn_ok, exp_ok,
                          ssm_ok, seq_par,
                          seq_residuals=cfg.seq_shard_residuals and not seq_par)


# dim roles used by param constructors
def role_axis(role: str, pol: ShardingPolicy, env: AxisEnv):
    """Mesh axis (or None) for a logical dim role."""
    if pol.profile == "fsdp_only":
        return (env.fsdp, env.tp) if role == "d_fsdp" else None
    table = {
        "d_fsdp": env.fsdp,
        "vocab": env.tp if pol.vocab_sharded else None,
        "qout": env.tp if pol.head_sharded else None,
        "kvout": env.tp if pol.kv_sharded else None,
        "ffn": env.tp if pol.ffn_sharded else None,
        "experts": env.tp if pol.experts_sharded else None,
        "ssm_inner": env.tp if pol.ssm_sharded else None,
        "none": None,
    }
    return table[role]


def spec_of(roles: Tuple[str, ...], pol: ShardingPolicy, env: AxisEnv) -> P:
    return P(*[role_axis(r, pol, env) for r in roles])


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------
class ParamBuilder:
    """Builds parallel (params, specs) pytrees.

    All constructors take dim-role tuples so the PartitionSpec is declared at
    the same site as the shape — keeps sharding rules impossible to desync.
    """

    def __init__(self, cfg: ModelConfig, pol: ShardingPolicy, env: AxisEnv, key,
                 *, abstract: bool = False):
        self.cfg = cfg
        self.pol = pol
        self.env = env
        self._key = key
        self.abstract = abstract
        self.params: Dict[str, Any] = {}
        self.specs: Dict[str, Any] = {}

    def _next_key(self):
        if self.abstract:
            return None
        self._key, sub = jax.random.split(self._key)
        return sub

    def add(self, name: str, shape: Tuple[int, ...], roles: Tuple[str, ...],
            *, scale: Optional[float] = None, init: str = "normal"):
        assert len(shape) == len(roles), (name, shape, roles)
        dtype = jnp.dtype(self.cfg.param_dtype)
        if self.abstract:
            arr = jax.ShapeDtypeStruct(shape, dtype)
        elif init == "zeros":
            arr = jnp.zeros(shape, dtype)
        elif init == "ones":
            arr = jnp.ones(shape, dtype)
        else:
            if scale is None:
                fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
                scale = 1.0 / math.sqrt(max(fan_in, 1))
            arr = scale * jax.random.normal(self._next_key(), shape, dtype)
        self.params[name] = arr
        self.specs[name] = spec_of(roles, self.pol, self.env)

    def child(self, name: str) -> "ParamBuilder":
        sub = ParamBuilder(self.cfg, self.pol, self.env, self._next_key(),
                           abstract=self.abstract)
        self.params[name] = sub.params
        self.specs[name] = sub.specs
        return sub


def stack_roles(roles: Tuple[str, ...]) -> Tuple[str, ...]:
    """Prepend the scanned layer dim (never sharded)."""
    return ("none",) + tuple(roles)


# ---------------------------------------------------------------------------
# misc numeric helpers shared across model files
# ---------------------------------------------------------------------------
def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def cast_tree(tree: PyTree, dtype) -> PyTree:
    def _c(x):
        if isinstance(x, jax.Array) or hasattr(x, "dtype"):
            if jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(_c, tree)


def tree_bytes(tree: PyTree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree)
               if hasattr(x, "dtype"))
