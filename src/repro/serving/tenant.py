"""TenantEngine — one tenant's continuous-batching engine over a KVPool.

The refactored core of the old ``ServingEngine``: prefill and decode are
separate paths (``prefill`` writes one request's KV prefix into a pool
slot; ``tick`` advances ALL live slots with one fused ragged decode step),
requests queue behind an admission-control bound, and eviction at the pool
boundary records the partial generation instead of dropping the request —
a truncated answer is still an answer the tenant must bill for.

A tenant never sees another tenant's pool or params; the only shared
surfaces are the ones the paper identifies (host link, pod power), which
``SliceRuntime`` accounts for at the layer above.
"""
from __future__ import annotations

import contextlib
import functools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.offload import OffloadPlan, fetch_to_device
from repro.serving.kv_pool import KVPool

PyTree = Any


# One compiled program per (model, shapes, shardings), shared by every
# engine that serves an equal model.
@functools.partial(jax.jit, static_argnums=0)
def _prefill_step(model, params, tokens):
    _, _, cache = model.forward(params, {"tokens": tokens},
                                return_cache=True, last_token_only=True)
    return cache


@functools.partial(jax.jit, static_argnums=0)
def _decode_step(model, params, cache, tokens, pos):
    logits, new_cache = model.decode(params, cache,
                                     {"tokens": tokens, "pos": pos})
    return logits, jnp.argmax(logits, axis=-1), new_cache


@dataclass
class Request:
    rid: int
    prompt: np.ndarray           # (prompt_len,)
    max_new_tokens: int
    generated: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    truncated: bool = False      # evicted at max_seq before max_new_tokens
    # engine ticks at each stage; they pin the order of admission
    submit_tick: Optional[int] = None   # queued (or first seen at prefill)
    admit_tick: Optional[int] = None    # slot claimed, prefix written
    finish_tick: Optional[int] = None   # completed/evicted, end of that tick
    # time.perf_counter() when queued: the queue wait that the
    # ``engine.admit`` span reports is measured from here
    submit_s: Optional[float] = None

    @property
    def done(self) -> bool:
        return len(self.generated) >= self.max_new_tokens


@dataclass
class TenantStats:
    ticks: int = 0
    tokens_out: int = 0
    prefill_tokens: int = 0
    admitted: int = 0
    rejected: int = 0
    completed: int = 0
    truncated: int = 0


class TenantEngine:
    def __init__(self, model, params: PyTree, *, slots: int, max_seq: int,
                 mesh=None, offload_kv: bool = False,
                 plan: Optional[OffloadPlan] = None,
                 max_queue: Optional[int] = None, name: str = "tenant"):
        self.name = name
        self.model = model
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        self.mesh = mesh
        self.plan = plan
        self.pool = KVPool(model, slots, max_seq, mesh=mesh, plan=plan,
                           offload_all=offload_kv and mesh is not None)
        self.queue: Deque[Request] = deque()
        self.max_queue = max_queue
        self.live: Dict[int, Request] = {}           # slot -> request
        self.outputs: Dict[int, List[int]] = {}      # rid -> generated
        self.stats = TenantStats()
        self.ticks = 0
        self.last_logits = None   # (slots, vocab) logits of the latest tick

    def _inputs(self, x: np.ndarray):
        """Host array -> step input, replicated over the engine's mesh."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, NamedSharding(self.mesh, P()))

    def _mesh_scope(self):
        return (contextlib.nullcontext() if self.mesh is None
                else jax.set_mesh(self.mesh))

    # -- compatibility properties (pre-refactor ServingEngine surface) -----
    @property
    def cache(self) -> PyTree:
        return self.pool.materialize()

    @property
    def positions(self) -> np.ndarray:
        return self.pool.positions

    # ------------------------------------------------------------------
    # admission control
    # ------------------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue a request; False = rejected (queue at its admission bound)."""
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            self.stats.rejected += 1
            return False
        if req.submit_tick is None:
            req.submit_tick = self.ticks
            req.submit_s = time.perf_counter()
        self.queue.append(req)
        return True

    @property
    def idle(self) -> bool:
        return not self.queue and not self.live

    # ------------------------------------------------------------------
    # prefill path
    # ------------------------------------------------------------------
    def prefill(self, req: Request) -> bool:
        """Claim a slot and write the request's KV prefix into the pool."""
        if len(req.prompt) > self.max_seq - 1:
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} "
                f"exceeds max_seq-1 ({self.max_seq - 1}) — queue path "
                f"rejects these; direct prefill callers must pre-check")
        if not self.pool.free_slots:
            return False
        now = time.perf_counter()
        if req.submit_tick is None:
            # direct-admit callers skip submit()
            req.submit_tick, req.submit_s = self.ticks, now
        plen = len(req.prompt)
        with TraceAnnotation("engine.admit", rid=req.rid, prompt_len=plen,
                             wait_ms=1e3 * (now - req.submit_s)):
            slot = req.slot = self.pool.alloc_slot()
            tokens = self._inputs(np.asarray(req.prompt, np.int32)[None, :])
            with self._mesh_scope():
                pc = _prefill_step(self.model, fetch_to_device(self.params),
                                   tokens)
            self.pool.paste(slot, pc, plen)
        self.live[slot] = req
        req.admit_tick = self.ticks
        self.stats.admitted += 1
        self.stats.prefill_tokens += plen
        return True

    def admit(self, req: Request) -> bool:
        """Pre-refactor surface: direct prefill, bypassing the queue."""
        return self.prefill(req)

    def _admit_from_queue(self) -> None:
        while self.queue and self.pool.free_slots:
            req = self.queue.popleft()
            if len(req.prompt) > self.max_seq - 1:
                # prompt can never fit the pool: reject it, visibly — an
                # empty result with the truncated flag, not a crash
                req.truncated = True
                self.outputs[req.rid] = req.generated
                self.stats.rejected += 1
                continue
            self.prefill(req)

    # ------------------------------------------------------------------
    # decode path
    # ------------------------------------------------------------------
    def tick(self) -> int:
        """Admit what fits, then one decode step for every live slot.
        Returns tokens emitted."""
        self._admit_from_queue()
        if not self.live:
            return 0
        # batch the newest token of each live slot; idle slots get token 0
        tokens = np.zeros((self.slots, 1), np.int32)
        for slot, req in self.live.items():
            last = (req.generated[-1] if req.generated else int(req.prompt[-1]))
            tokens[slot, 0] = last
        # per-row cache positions: ragged continuous batching
        with self._mesh_scope():
            logits, next_tokens, new_cache = _decode_step(
                self.model, fetch_to_device(self.params),
                self.pool.materialize(), self._inputs(tokens),
                self._inputs(self.pool.positions.astype(np.int32)))
        self.pool.update(new_cache)
        self.last_logits = logits
        emitted = 0
        next_tokens = np.asarray(next_tokens)
        for slot, req in list(self.live.items()):
            req.generated.append(int(next_tokens[slot]))
            self.pool.positions[slot] += 1
            emitted += 1
            if req.done or self.pool.positions[slot] >= self.max_seq - 1:
                if not req.done:
                    # evicted at the pool boundary: a *truncated* generation,
                    # recorded like any other (the pre-refactor engine
                    # silently dropped these)
                    req.truncated = True
                    self.stats.truncated += 1
                self.stats.completed += 1
                req.finish_tick = self.ticks + 1   # done by this tick's end
                self.outputs[req.rid] = req.generated
                del self.live[slot]
                self.pool.free_slot(slot)
        self.ticks += 1
        self.stats.ticks += 1
        self.stats.tokens_out += emitted
        return emitted

    # ------------------------------------------------------------------
    def run(self, requests: List[Request]) -> Dict[int, List[int]]:
        """Drain a closed batch of requests (single-tenant convenience).
        Every request appears in the result — including ones evicted at
        ``max_seq`` with a partial generation (``req.truncated`` set)."""
        for r in requests:
            self.queue.append(r)    # closed batch: bypass the admission bound
        while not self.idle:
            self.tick()
        return dict(self.outputs)
