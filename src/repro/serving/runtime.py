"""SliceRuntime — multi-tenant serving on one statically partitioned pod.

This is the paper's system put together end-to-end on the *real* engine
(previously only the analytical simulator in ``core/cosched.py`` composed
these pieces):

1. **Place** — each tenant asks for a slice profile;
   ``StaticPartitioner`` packs the rectangles onto the pod grid and fails
   loudly when they don't fit (§IV/§V-A).
2. **Plan** — the tenant's *measured* inventory (its actual params and KV
   pool, via ``Model.serving_inventory``) goes through ``plan_offload``
   against the slice's HBM; an overhang spills to ``pinned_host`` with
   real memory kinds, partial KV spills as a physically split cold tail
   in the tenant's ``KVPool`` (§VI-A).
3. **Serve** — every tenant runs a ``TenantEngine`` (continuous batching,
   admission control); the runtime drives them round-robin and reports
   per-tenant tokens/sec plus pod utilization.
4. **Account** — the shared surfaces partitioning does NOT isolate (pod
   power delivery, §V-B) are priced by ``core.power``: the report includes
   the modeled throttle factor and energy for the co-run, so the paper's
   Figs. 5–7 quantities can be read off a live serving run.

The pod grid is modeled: every tenant executes on the runtime's own
``mesh`` (one chip, the four chips of one host, or CPU devices in tests),
whatever rectangle the partitioner gave it. The offload plan, the memory
kinds it places, and the power accounting are what a pod-scale deployment
would use; parameters the plan spills stay in ``pinned_host`` and are
copied to device memory explicitly for each step (``fetch_to_device``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Union

import jax

from repro.configs.base import ModelConfig
from repro.configs.shapes import get_shape
from repro.core.hw import PodSpec, V5E_POD
from repro.core.offload import OffloadPlan, place_tree, plan_offload
from repro.core.partitioner import SliceAllocation, StaticPartitioner
from repro.core.perfmodel import InstanceLoad, PerfModel, get_model
from repro.core.slices import SliceProfile, get_profile, smallest_fitting
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model
from repro.serving.tenant import Request, TenantEngine


@dataclass(frozen=True)
class TenantSpec:
    """Everything the runtime needs to admit one tenant."""
    name: str
    cfg: ModelConfig
    profile: Union[str, SliceProfile, None] = None  # None -> smallest fitting
    slots: int = 4
    max_seq: int = 128
    max_queue: Optional[int] = None
    # Override the slice's HBM budget for the offload plan. Reduced-scale
    # demo models fit any real slice trivially; pinning the budget below the
    # tenant's footprint exercises the same plan->spill path a full-size
    # model hits on a real 16-chip slice.
    hbm_budget: Optional[int] = None
    # Spill granule for divisible tensors; default (None) keeps the
    # production 64 MiB granule — shrink it alongside hbm_budget in demos.
    spill_granule: Optional[int] = None
    shape: str = "decode_32k"   # ShapeSuite for the modeled power accounting
    seed: int = 0
    # Pin the slice rectangle's origin (must be profile-aligned and free) —
    # set by fragmentation-aware placers (repro.cluster.placement); None
    # keeps the partitioner's first-fit origin.
    origin: Optional[tuple] = None


@dataclass
class Tenant:
    spec: TenantSpec
    alloc: SliceAllocation
    model: object
    params: object
    plan: OffloadPlan
    engine: TenantEngine
    inventory_bytes: int
    wall_s: float = 0.0
    submitted: int = 0

    @property
    def name(self) -> str:
        return self.spec.name


class SliceRuntime:
    def __init__(self, pod: PodSpec = V5E_POD, mesh=None,
                 partitioner: Optional[StaticPartitioner] = None,
                 perf: Optional[PerfModel] = None):
        self.pod = pod
        self.mesh = mesh   # execution mesh shared by every tenant
        # an externally owned partitioner lets a cluster-level scheduler
        # (repro.cluster) share one pod grid between its own modeled jobs
        # and this runtime's live tenants
        self.partitioner = (partitioner if partitioner is not None
                            else StaticPartitioner(pod))
        # shared performance engine: throttle/energy accounting goes through
        # the same memoized PerfModel the cluster scheduler scores with
        self.perf = perf if perf is not None else get_model(pod.chip)
        self.tenants: Dict[str, Tenant] = {}

    # ------------------------------------------------------------------
    # tenant lifecycle
    # ------------------------------------------------------------------
    def _resolve_profile(self, spec: TenantSpec, footprint: int
                         ) -> SliceProfile:
        if isinstance(spec.profile, SliceProfile):
            return spec.profile
        if isinstance(spec.profile, str):
            return get_profile(spec.profile)
        prof = smallest_fitting(footprint, 0.0, self.pod)
        if prof is None:
            raise RuntimeError(
                f"tenant {spec.name!r}: footprint {footprint} bytes exceeds "
                f"every slice profile")
        return prof

    def add_tenant(self, spec: TenantSpec) -> Tenant:
        """Place, plan, and spin up one tenant. Raises (loudly) when the pod
        has no room for the requested profile or the tenant cannot fit its
        slice even with everything offloadable spilled."""
        if spec.name in self.tenants:
            raise ValueError(f"duplicate tenant {spec.name!r}")
        env = (host_axis_env() if self.mesh is None
               else None)
        model = (build_model(spec.cfg, env) if env is not None
                 else build_model(spec.cfg, self.mesh))
        params, param_specs = model.init(jax.random.PRNGKey(spec.seed))
        cache_bytes = model.cache_bytes(spec.slots, spec.max_seq)
        param_bytes = sum(int(x.size) * x.dtype.itemsize
                          for x in jax.tree_util.tree_leaves(params))
        footprint = param_bytes + cache_bytes

        profile = self._resolve_profile(spec, footprint)
        alloc = self.partitioner.allocate(profile, tag=spec.name,
                                          origin=spec.origin)
        try:
            tenant = self._plan_and_build(spec, profile, alloc, model,
                                          params, param_specs, footprint)
        except Exception:
            self.partitioner.release(alloc.slice_id)
            raise
        self.tenants[spec.name] = tenant
        return tenant

    def _plan_and_build(self, spec, profile, alloc, model, params,
                        param_specs, footprint) -> Tenant:
        chip = self.pod.chip
        # abstract cache: the inventory only needs sizes/dtypes, and the
        # engine's KVPool will allocate the real pool itself
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(spec.slots, spec.max_seq))
        inventory = model.serving_inventory(params, cache_shapes)
        hbm_budget = (spec.hbm_budget if spec.hbm_budget is not None
                      else profile.hbm_bytes(chip))
        plan = plan_offload(
            inventory, hbm_budget,
            host_budget=profile.host_dram_bytes(chip),
            **({"spill_granule": spec.spill_granule}
               if spec.spill_granule is not None else {}))
        if not plan.fits:
            raise RuntimeError(
                f"tenant {spec.name!r} does not fit {profile.name}: "
                f"{plan.resident_bytes} resident bytes > {hbm_budget} budget "
                f"even after spilling {plan.host_bytes} to host")
        if self.mesh is not None:
            params = place_tree({"params": params}, {"params": param_specs},
                                plan, self.mesh)["params"]
        engine = TenantEngine(
            model, params, slots=spec.slots, max_seq=spec.max_seq,
            mesh=self.mesh, plan=plan, max_queue=spec.max_queue,
            name=spec.name)
        return Tenant(spec=spec, alloc=alloc, model=model, params=params,
                      plan=plan, engine=engine, inventory_bytes=footprint)

    def remove_tenant(self, name: str, *, repack: bool = False) -> None:
        tenant = self.tenants.pop(name)
        self.partitioner.release(tenant.alloc.slice_id)
        if repack:
            self.partitioner.repack()

    def resize_tenant(self, name: str,
                      profile: Union[str, SliceProfile]) -> Tenant:
        """Move a live tenant to a different slice profile — the serving
        side of the cluster Action API's ``Shrink``/``Grow`` moves, with
        the same probe → price → commit discipline:

        1. **probe** — re-plan the tenant's measured inventory against the
           new profile's HBM/host budgets; a plan that does not fit raises
           before anything moves.
        2. **commit** — ``StaticPartitioner.resize`` swaps the rectangle
           transactionally (the slice keeps its id; growing requires the
           extension chips to be free, and a conflict raises with the grid
           untouched).

        A pinned ``spec.hbm_budget`` (demo tenants) is kept as-is, like
        ``add_tenant`` does. On this host backend the KV pool and engine
        keep running across the resize — what changes is the rectangle,
        the offload plan, and the modeled power/throttle accounting."""
        tenant = self.tenants[name]
        profile = (get_profile(profile) if isinstance(profile, str)
                   else profile)
        if profile.name == tenant.alloc.profile.name:
            return tenant
        spec = tenant.spec
        chip = self.pod.chip
        cache_shapes = jax.eval_shape(
            lambda: tenant.model.init_cache(spec.slots, spec.max_seq))
        inventory = tenant.model.serving_inventory(tenant.params,
                                                   cache_shapes)
        hbm_budget = (spec.hbm_budget if spec.hbm_budget is not None
                      else profile.hbm_bytes(chip))
        plan = plan_offload(
            inventory, hbm_budget,
            host_budget=profile.host_dram_bytes(chip),
            **({"spill_granule": spec.spill_granule}
               if spec.spill_granule is not None else {}))
        if not plan.fits:
            raise RuntimeError(
                f"tenant {name!r} does not fit {profile.name}: "
                f"{plan.resident_bytes} resident bytes > {hbm_budget} "
                f"budget even after spilling {plan.host_bytes} to host")
        self.partitioner.resize(tenant.alloc.slice_id, profile)
        tenant.plan = plan
        return tenant

    # ------------------------------------------------------------------
    # serving
    # ------------------------------------------------------------------
    def submit(self, name: str, requests: Sequence[Request]) -> int:
        """Queue requests for one tenant; returns how many were admitted
        past the tenant's admission bound."""
        tenant = self.tenants[name]
        n = sum(tenant.engine.submit(r) for r in requests)
        tenant.submitted += n
        return n

    def step(self) -> Dict[str, int]:
        """One round-robin sweep: each tenant admits + decodes one tick."""
        out = {}
        for tenant in self.tenants.values():
            if tenant.engine.idle:
                continue
            t0 = time.perf_counter()
            out[tenant.name] = tenant.engine.tick()
            tenant.wall_s += time.perf_counter() - t0
        return out

    def run(self, max_ticks: Optional[int] = None) -> Dict[str, dict]:
        """Drive all tenants until every queue drains (or ``max_ticks``)."""
        ticks = 0
        while any(not t.engine.idle for t in self.tenants.values()):
            if max_ticks is not None and ticks >= max_ticks:
                break
            self.step()
            ticks += 1
        return self.report()

    # ------------------------------------------------------------------
    # accounting (paper Figs. 5-7 quantities, on the live engine)
    # ------------------------------------------------------------------
    def _instance_loads(self, steps: int = 100) -> List[InstanceLoad]:
        """Pod-scale modeled loads for the live tenant mix, scored by the
        shared ``PerfModel`` (full-size analytic numbers even when the
        tenants execute reduced configs on the host backend)."""
        loads = []
        for tenant in self.tenants.values():
            sc = self.perf.score(tenant.spec.cfg,
                                 get_shape(tenant.spec.shape),
                                 tenant.alloc.profile)
            if sc is None:   # cannot fit per the full-scale model: account
                # it as a fully-utilized slice rather than dropping it
                loads.append(InstanceLoad(tenant.alloc.profile.n_chips,
                                          1.0, 1.0, steps))
            else:
                loads.append(sc.load(steps))
        return loads

    def report(self) -> Dict[str, dict]:
        per_tenant = {}
        for tenant in self.tenants.values():
            eng = tenant.engine
            per_tenant[tenant.name] = {
                "profile": tenant.alloc.profile.name,
                "rect": tenant.alloc.rect,
                "tokens_out": eng.stats.tokens_out,
                "prefill_tokens": eng.stats.prefill_tokens,
                "completed": eng.stats.completed,
                "truncated": eng.stats.truncated,
                "rejected": eng.stats.rejected,
                "ticks": eng.stats.ticks,
                "tok_per_s": (eng.stats.tokens_out / tenant.wall_s
                              if tenant.wall_s else 0.0),
                "plan_host_bytes": tenant.plan.host_bytes,
                "plan_offloaded": list(tenant.plan.offloaded),
                "plan_partial": [n for n, _ in tenant.plan.partial],
                "kv_device_bytes": eng.pool.device_bytes,
                "kv_host_bytes": eng.pool.host_bytes,
            }
            if self.perf.twin is not None:
                # twin-offload pricing for this tenant's rectangle: the
                # rung the cluster scheduler would co-execute host-side,
                # or None when the plain score already wins (nothing
                # compute-bearing spilled / speedup below threshold)
                tw = self.perf.score_twin(tenant.spec.cfg,
                                          get_shape(tenant.spec.shape),
                                          tenant.alloc.profile)
                sc = self.perf.score(tenant.spec.cfg,
                                     get_shape(tenant.spec.shape),
                                     tenant.alloc.profile)
                per_tenant[tenant.name]["twin"] = None if tw is None else {
                    "rung": tw.rung,
                    "cpu_fraction": tw.twin.cpu_fraction,
                    "step_time_s": tw.step_time,
                    "speedup": (sc.step_time / tw.step_time
                                if sc is not None else None),
                }
        result = {
            "tenants": per_tenant,
            "pod_utilization": self.partitioner.utilization(),
            "free_chips": self.partitioner.free_chips(),
        }
        if self.tenants:
            run = self.perf.corun(self._instance_loads(), self.pod)
            result["modeled"] = {   # synthetic power calibration (hw.py)
                "throttle": run.throttle,
                "throttled": run.throttled,
                "makespan_s": run.makespan_s,
                "energy_J": run.energy_J,
            }
        return result
