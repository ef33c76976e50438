"""KVPool — slot-paged KV/state pool with planner-driven host placement.

The pool owns a fixed ``(slots, max_seq)`` cache tree plus the slot free
list and per-slot lengths. Placement is where the paper's §VI-A mechanism
becomes real: an ``OffloadPlan`` maps onto the pool leaf-by-leaf with JAX
memory kinds —

* fully offloaded leaves live whole in ``pinned_host``;
* *partially* spilled leaves are physically split along the sequence axis
  into a device-resident hot prefix and a ``pinned_host`` cold tail (the
  fine-grained spill ``shardings_with_offload`` cannot express, because a
  single JAX buffer has exactly one memory kind);
* everything else stays in device memory.

JAX computes only on operands in one memory space, so a host-resident leaf
never enters computation directly. ``materialize()`` copies every spilled
tail and leaf to device memory explicitly and concatenates the tails back
on; decode returns the updated tree to ``update()``, which re-splits it and
writes the spilled bytes back to ``pinned_host``. Between steps the spilled
bytes stay in host memory. The round trip copies every spilled byte twice
per step; this is the correct form, not yet the fast one.
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding

from repro.core.offload import (OffloadPlan, _flatten_with_paths,
                                device_memory_kind, host_memory_kind)

PyTree = Any

SEQ_AXIS = 2  # layer-stacked caches: (L, slots, seq, heads, head_dim)


def _has_seq_axis(leaf, max_seq: int) -> bool:
    return leaf.ndim > SEQ_AXIS and leaf.shape[SEQ_AXIS] == max_seq


def _seq_slice(leaf, lo: int, hi: int):
    idx = [slice(None)] * leaf.ndim
    idx[SEQ_AXIS] = slice(lo, hi)
    return leaf[tuple(idx)]


def _spec_allows_seq_split(spec, mesh) -> bool:
    """Splitting the seq axis needs that axis unsharded in the leaf spec
    (or sharded only over mesh axes of size 1, where the cut is still a
    whole-shard boundary)."""
    try:
        if len(spec) <= SEQ_AXIS or spec[SEQ_AXIS] is None:
            return True
    except TypeError:
        return True
    if mesh is None:
        return False
    axes = spec[SEQ_AXIS]
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return all(sizes.get(a, 1) == 1 for a in axes)


class KVPool:
    def __init__(self, model, slots: int, max_seq: int, *, mesh=None,
                 plan: Optional[OffloadPlan] = None, offload_all: bool = False,
                 dtype=jnp.bfloat16, prefix: str = "kv"):
        self.model = model
        self.slots = slots
        self.max_seq = max_seq
        self.mesh = mesh
        self.prefix = prefix
        self.positions = np.zeros(slots, np.int32)   # per-slot cache length
        self._free: List[int] = list(range(slots))

        cache = model.init_cache(slots, max_seq, dtype)
        flat = _flatten_with_paths(cache)
        self._paths = [p for p, _ in flat]
        self._treedef = jax.tree_util.tree_structure(cache)
        leaves = [leaf for _, leaf in flat]

        specs = _flatten_with_paths(model.cache_specs(slots))
        spec_by_path = dict(specs)

        # per-leaf placement decision
        self._dev_sharding: Dict[int, NamedSharding] = {}    # whole leaf
        self._host_sharding: Dict[int, NamedSharding] = {}   # spilled part
        self._hot_len: Dict[int, int] = {}            # split leaves only
        self._hot: List[Any] = []
        self._cold: Dict[int, Any] = {}
        self._host_leaves: Set[int] = set()           # fully host-placed

        host_kind = host_memory_kind(mesh) if mesh is not None else None
        dev_kind = device_memory_kind(mesh) if mesh is not None else None
        for i, (path, leaf) in enumerate(zip(self._paths, leaves)):
            full_path = f"{prefix}/{path}" if prefix else path
            kind, hot_len = self._decide(full_path, leaf, plan, offload_all,
                                         spec_by_path.get(path))
            if mesh is not None:
                spec = spec_by_path.get(path)
                self._dev_sharding[i] = NamedSharding(mesh, spec,
                                                      memory_kind=dev_kind)
                if kind != "device":
                    self._host_sharding[i] = NamedSharding(
                        mesh, spec, memory_kind=host_kind)
                if kind == "host":
                    leaf = jax.device_put(leaf, self._host_sharding[i])
                    self._host_leaves.add(i)
                elif kind == "split":
                    self._cold[i] = jax.device_put(
                        _seq_slice(leaf, hot_len, max_seq),
                        self._host_sharding[i])
                    self._hot_len[i] = hot_len
                    leaf = _seq_slice(leaf, 0, hot_len)
                if kind != "host":
                    leaf = jax.device_put(leaf, self._dev_sharding[i])
            self._hot.append(leaf)
        # planned host-tier bytes (cold tails + fully spilled leaves): what
        # materialize copies in and update writes back, every call
        self.host_bytes = (
            self._bytes(self._cold.values())
            + self._bytes(self._hot[i] for i in self._host_leaves))

    # ------------------------------------------------------------------
    def _decide(self, full_path: str, leaf, plan: Optional[OffloadPlan],
                offload_all: bool, spec) -> Tuple[str, int]:
        """('device'|'host'|'split', hot_len) for one leaf."""
        if offload_all or (plan is not None and plan.is_offloaded(full_path)):
            return "host", 0
        if plan is None:
            return "device", 0
        spilled = dict(plan.partial).get(full_path)
        if not spilled:
            return "device", 0
        nbytes = int(leaf.size) * leaf.dtype.itemsize
        frac = min(1.0, spilled / nbytes)
        if (_has_seq_axis(leaf, self.max_seq)
                and (spec is None or _spec_allows_seq_split(spec, self.mesh))):
            cold = min(self.max_seq - 1, max(1, math.ceil(frac * self.max_seq)))
            return "split", self.max_seq - cold
        # no seq axis to cut (ssm state, conv tail): round to majority side
        return ("host", 0) if frac >= 0.5 else ("device", 0)

    # ------------------------------------------------------------------
    # slot management (the "paged" part — one page per request slot)
    # ------------------------------------------------------------------
    @property
    def free_slots(self) -> int:
        return len(self._free)

    def alloc_slot(self) -> Optional[int]:
        return self._free.pop() if self._free else None

    def free_slot(self, slot: int) -> None:
        self.positions[slot] = 0
        self._free.append(slot)

    # ------------------------------------------------------------------
    # cache access
    # ------------------------------------------------------------------
    def materialize(self) -> PyTree:
        """Full cache tree in device memory for one step: spilled leaves and
        cold tails are copied in explicitly, tails concatenated back on."""
        leaves = []
        with TraceAnnotation("kv.materialize", h2d_bytes=self.host_bytes):
            for i, leaf in enumerate(self._hot):
                if i in self._host_leaves:
                    leaf = jax.device_put(leaf, self._dev_sharding[i])
                elif i in self._cold:
                    cold = jax.device_put(self._cold[i], self._dev_sharding[i])
                    leaf = jax.device_put(
                        jnp.concatenate([leaf, cold], axis=SEQ_AXIS),
                        self._dev_sharding[i])
                leaves.append(leaf)
        return jax.tree_util.tree_unflatten(self._treedef, leaves)

    def update(self, new_cache: PyTree) -> None:
        """Absorb a step-updated cache tree (device memory), re-splitting
        spilled tails and writing every spilled byte back to the host tier
        (the write-back half of the DMA round trip)."""
        leaves = jax.tree_util.tree_leaves(new_cache)
        assert len(leaves) == len(self._hot), "cache structure changed"
        with TraceAnnotation("kv.update", d2h_bytes=self.host_bytes):
            for i, leaf in enumerate(leaves):
                if i in self._cold:
                    hot_len = self._hot_len[i]
                    self._hot[i] = jax.device_put(_seq_slice(leaf, 0, hot_len),
                                                  self._dev_sharding[i])
                    self._cold[i] = jax.device_put(
                        _seq_slice(leaf, hot_len, self.max_seq),
                        self._host_sharding[i])
                elif i in self._host_leaves:
                    self._hot[i] = jax.device_put(leaf, self._host_sharding[i])
                elif self.mesh is not None:
                    # keep the pool's layout fixed so the step never recompiles
                    self._hot[i] = jax.device_put(leaf, self._dev_sharding[i])
                else:
                    self._hot[i] = leaf
            # Every step rewrites the whole pool (and a paste, one per admitted
            # request, does too). Left asynchronous, the host queues the next
            # rewrite while earlier pools and their host copies are still
            # alive: on a 16 GB chip that stacked ~3.4 GB of pool copies.
            # Waiting holds it to one old and one new pool.
            jax.block_until_ready((self._hot, list(self._cold.values())))

    def paste(self, slot: int, prefix_cache: PyTree, plen: int) -> None:
        """Write a prefill prefix into one slot (the admit path)."""
        cache = self.materialize()

        def _paste(pool, pref):
            if _has_seq_axis(pool, self.max_seq):
                return pool.at[:, slot:slot + 1, :plen].set(
                    pref.astype(pool.dtype))
            # state caches (ssm): (L, B, ...) — overwrite the slot
            return pool.at[:, slot:slot + 1].set(pref.astype(pool.dtype))

        self.update(jax.tree_util.tree_map(_paste, cache, prefix_cache))
        self.positions[slot] = plen

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------
    def spilled_kinds(self) -> Dict[str, str]:
        """path -> memory kind of every spilled piece (cold tails and
        fully host-placed leaves) as the pool holds it between steps."""
        pieces = {i: self._cold[i] for i in self._cold}
        pieces.update({i: self._hot[i] for i in self._host_leaves})
        return {self._paths[i]: x.sharding.memory_kind
                for i, x in sorted(pieces.items())}

    def _bytes(self, leaves) -> int:
        return sum(int(x.size) * x.dtype.itemsize for x in leaves)

    @property
    def device_bytes(self) -> int:
        """Planned HBM-resident bytes (hot prefixes + unspilled leaves)."""
        return self._bytes(leaf for i, leaf in enumerate(self._hot)
                           if i not in self._host_leaves)

    @property
    def split_leaves(self) -> Dict[str, int]:
        """path -> hot prefix length for every physically split leaf."""
        return {self._paths[i]: n for i, n in self._hot_len.items()}
