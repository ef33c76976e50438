"""SliceRuntime stack: multi-tenant packing, per-tenant offload plans cut
from real inventories, engine equivalence under offload, truncation
recording, admission control, partitioner repack, and partial-spill
placement rounding."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.hw import V5E_POD
from repro.core.offload import (OffloadPlan, device_memory_kind,
                                host_memory_kind, in_host_memory,
                                plan_offload, shardings_with_offload)
from repro.core.partitioner import StaticPartitioner
from repro.core.slices import get_profile
from repro.launch.mesh import make_host_mesh
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model
from repro.serving import (KVPool, Request, ServingEngine, SliceRuntime,
                           TenantEngine, TenantSpec)

ENV = host_axis_env()


@pytest.fixture(scope="module")
def gpt2():
    cfg = get_config("gpt2-124m").reduced().with_(remat="none")
    model = build_model(cfg, ENV)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def mesh():
    return make_host_mesh(1, 1)


def _partial_kv_plan(model, params, slots, max_seq):
    """A plan whose overhang lands inside a divisible KV leaf."""
    cache = model.init_cache(slots, max_seq)
    inv = model.serving_inventory(params, cache)
    total = sum(t.bytes for t in inv)
    embed = sum(t.bytes for t in inv if t.group == "embed")
    kv = sum(t.bytes for t in inv if t.group == "kv_cache")
    plan = plan_offload(inv, total - embed - kv // 4, spill_granule=1024)
    assert plan.partial, "test setup: expected a partial spill"
    return plan


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------
def test_packing_fails_loudly(gpt2):
    cfg, _, _ = gpt2
    rt = SliceRuntime()
    rt.add_tenant(TenantSpec("big", cfg, profile="16s.256c",
                             slots=1, max_seq=16))
    free_before = rt.partitioner.free_chips()
    with pytest.raises(RuntimeError, match="no room"):
        rt.add_tenant(TenantSpec("late", cfg, profile="1s.16c",
                                 slots=1, max_seq=16))
    # failed admission must not leak a slice or a tenant
    assert rt.partitioner.free_chips() == free_before
    assert "late" not in rt.tenants
    with pytest.raises(ValueError, match="duplicate"):
        rt.add_tenant(TenantSpec("big", cfg, profile="1s.16c",
                                 slots=1, max_seq=16))


def test_resize_tenant_grow_shrink_roundtrip(gpt2):
    cfg, _, _ = gpt2
    rt = SliceRuntime()
    tenant = rt.add_tenant(TenantSpec("t", cfg, profile="1s.16c",
                                      slots=1, max_seq=16))
    sid = tenant.alloc.slice_id
    origin = tenant.alloc.origin
    free0 = rt.partitioner.free_chips()
    grown = rt.resize_tenant("t", "4s.64c")
    assert grown is tenant and tenant.alloc.slice_id == sid
    assert tenant.alloc.profile.name == "4s.64c"
    assert rt.partitioner.free_chips() == free0 - (64 - 16)
    assert tenant.plan.fits
    rt.partitioner.validate()
    back = rt.resize_tenant("t", "1s.16c")
    assert back.alloc.profile.name == "1s.16c"
    assert back.alloc.origin == origin
    assert rt.partitioner.free_chips() == free0
    rt.partitioner.validate()
    # no-op resize returns the tenant untouched
    assert rt.resize_tenant("t", "1s.16c") is tenant


def test_resize_tenant_grow_conflict_is_transactional(gpt2):
    cfg, _, _ = gpt2
    rt = SliceRuntime()
    rt.add_tenant(TenantSpec("a", cfg, profile="1s.16c", slots=1,
                             max_seq=16))         # origin (0,0)
    rt.add_tenant(TenantSpec("b", cfg, profile="1s.16c", slots=1,
                             max_seq=16,
                             origin=(0, 4)))      # blocks a's 4x8 extension
    a = rt.tenants["a"]
    plan_before = a.plan
    grid_before = rt.partitioner._grid.copy()
    with pytest.raises(RuntimeError, match="extend failed"):
        rt.resize_tenant("a", "2s.32c")
    assert (rt.partitioner._grid == grid_before).all()
    assert a.alloc.profile.name == "1s.16c" and a.plan is plan_before
    rt.partitioner.validate()


def test_resize_tenant_probe_rejects_unfit_profile(gpt2, monkeypatch):
    cfg, _, _ = gpt2
    rt = SliceRuntime()
    tenant = rt.add_tenant(TenantSpec("t", cfg, profile="2s.32c", slots=1,
                                      max_seq=16))
    plan_before = tenant.plan
    grid_before = rt.partitioner._grid.copy()
    # the plan probe reports the new profile cannot hold the tenant: the
    # resize must fail BEFORE the rectangle moves (probe → commit order)
    import repro.serving.runtime as runtime_mod
    unfit = dataclasses.replace(plan_before, fits=False)
    monkeypatch.setattr(runtime_mod, "plan_offload",
                        lambda *a, **k: unfit)
    with pytest.raises(RuntimeError, match="does not fit"):
        rt.resize_tenant("t", "1s.16c")
    assert (rt.partitioner._grid == grid_before).all()
    assert tenant.alloc.profile.name == "2s.32c"
    assert tenant.plan is plan_before


def test_partitioner_repack_defragments():
    part = StaticPartitioner()
    p = get_profile("1s.16c")
    allocs = [part.allocate(p, tag=f"t{i}") for i in range(4)]
    part.release(allocs[0].slice_id)
    part.release(allocs[2].slice_id)
    moved = part.repack()
    part.validate()
    # survivors compacted to the lowest-aligned origins
    origins = sorted(a.origin for a in part.allocations.values())
    assert origins == [(0, 0), (0, 4)]
    assert set(moved) <= {a.slice_id for a in allocs}
    assert part.free_chips() == V5E_POD.n_chips - 2 * p.n_chips


def test_repack_preserves_dead_chips():
    part = StaticPartitioner()
    a = part.allocate(get_profile("1s.16c"), tag="victim")
    part.fail_chips([(0, 0)])          # kills the slice, marks chip dead
    assert a.slice_id not in part.allocations
    b = part.allocate(get_profile("1s.16c"), tag="evacuee")
    part.repack()
    part.validate()
    # dead chip's aligned rectangle cannot host the survivor
    assert part.allocations[b.slice_id].origin != (0, 0)


def test_repack_rolls_back_when_replacement_fails(monkeypatch):
    part = StaticPartitioner()
    for _ in range(3):
        part.allocate(get_profile("1s.16c"))
    part.release(1)  # leave a hole so repack has something to move
    grid_before = part._grid.copy()
    origins_before = {sid: a.origin for sid, a in part.allocations.items()}
    original = StaticPartitioner._find_origin
    calls = {"n": 0}

    def flaky(self, profile):
        calls["n"] += 1
        return None if calls["n"] >= 2 else original(self, profile)

    monkeypatch.setattr(StaticPartitioner, "_find_origin", flaky)
    with pytest.raises(RuntimeError, match="repack failed"):
        part.repack()
    monkeypatch.setattr(StaticPartitioner, "_find_origin", original)
    # full rollback: grid and every allocation origin untouched
    assert (part._grid == grid_before).all()
    assert {sid: a.origin
            for sid, a in part.allocations.items()} == origins_before
    part.validate()


def test_allocate_at_pinned_origin():
    part = StaticPartitioner()
    p = get_profile("1s.16c")
    a = part.allocate(p, origin=(4, 8))
    assert a.origin == (4, 8)
    with pytest.raises(RuntimeError, match="not free"):
        part.allocate(p, origin=(4, 8))
    with pytest.raises(ValueError, match="not aligned"):
        part.allocate(p, origin=(2, 8))
    assert (4, 8) not in part.origins_for(p)
    part.validate()


def test_spilled_fraction_is_a_true_fraction():
    """Pins the fixed semantics: partial entries report spilled/total in
    [0,1] (previously they leaked raw spilled *bytes*)."""
    GiB = 1024 ** 3
    from repro.core.offload import TensorInfo
    inv = [TensorInfo("cold", 2 * GiB, "kv_cache", traffic_multiplier=0.05),
           TensorInfo("warm", 8 * GiB, "kv_cache", divisible=True,
                      traffic_multiplier=2.0),
           TensorInfo("stays", 1 * GiB, "param")]
    plan = plan_offload(inv, 6 * GiB)
    assert plan.fits
    assert plan.spilled_fraction("cold") == 1.0
    assert plan.spilled_fraction("stays") == 0.0
    spilled = dict(plan.partial)["warm"]
    assert 0 < spilled < 8 * GiB
    assert plan.spilled_fraction("warm") == pytest.approx(
        spilled / (8 * GiB))
    assert 0.0 < plan.spilled_fraction("warm") < 1.0
    # caller-supplied total overrides the recorded one
    assert plan.spilled_fraction("warm", total_bytes=spilled) == 1.0
    # hand-built plans without recorded totals must demand one
    bare = OffloadPlan((), (("x", 7),), 0, 7, 0.0, True)
    with pytest.raises(ValueError):
        bare.spilled_fraction("x")
    assert bare.spilled_fraction("x", total_bytes=14) == 0.5


# ---------------------------------------------------------------------------
# plans vs inventory
# ---------------------------------------------------------------------------
def test_tenant_plans_match_inventory(gpt2, mesh):
    cfg, model, params = gpt2
    rt = SliceRuntime(mesh=mesh)
    cache = model.init_cache(2, 32)
    inv = model.serving_inventory(params, cache)
    total = sum(t.bytes for t in inv)
    names = {t.name for t in inv}

    fits = rt.add_tenant(TenantSpec("fits", cfg, profile="1s.16c",
                                    slots=2, max_seq=32))
    spilled = rt.add_tenant(TenantSpec(
        "spilled", cfg, profile="1s.16c", slots=2, max_seq=32,
        hbm_budget=int(total * 0.8), spill_granule=1024))

    # plan conservation: every byte is either resident or on the host
    for t in (fits, spilled):
        assert t.plan.resident_bytes + t.plan.host_bytes == total
        assert set(t.plan.offloaded) <= names
        assert {n for n, _ in t.plan.partial} <= names
    assert fits.plan.host_bytes == 0 and not fits.plan.offloaded
    assert spilled.plan.host_bytes > 0
    assert spilled.plan.resident_bytes <= int(total * 0.8)
    # the engine's pool accounts for every cache byte, wherever it lives
    pool = spilled.engine.pool
    assert pool.host_bytes + pool.device_bytes == model.cache_bytes(2, 32)


# ---------------------------------------------------------------------------
# engine equivalence + truncation + admission
# ---------------------------------------------------------------------------
def test_engine_equivalence_offload_on_off(gpt2, mesh):
    cfg, model, params = gpt2
    prompts = [np.arange(2, 8, dtype=np.int32) % cfg.vocab_size,
               np.arange(5, 14, dtype=np.int32) % cfg.vocab_size]
    reqs = lambda: [Request(i, p, 5) for i, p in enumerate(prompts)]  # noqa: E731

    base = ServingEngine(model, params, slots=2, max_seq=48).run(reqs())
    full_off = ServingEngine(model, params, slots=2, max_seq=48,
                             mesh=mesh, offload_kv=True).run(reqs())
    assert base == full_off

    plan = _partial_kv_plan(model, params, 2, 48)
    eng = TenantEngine(model, params, slots=2, max_seq=48, mesh=mesh,
                       plan=plan)
    assert eng.pool.split_leaves, "partial plan must split a kv leaf"
    assert eng.pool.host_bytes > 0 and eng.pool.device_bytes > 0
    assert base == eng.run(reqs())


def test_split_pool_and_host_params_logits_match_device(gpt2, mesh):
    """A plan that spills the embedding table and a cold KV tail to
    pinned_host runs the same program on the same values as the all-device
    tenant: every tick's logits are bit-identical, and between ticks the
    spilled bytes stay in host memory."""
    cfg, model, _ = gpt2
    prompts = [np.arange(3, 11, dtype=np.int32) % cfg.vocab_size,
               np.arange(1, 6, dtype=np.int32) % cfg.vocab_size]

    def serve(budget):
        rt = SliceRuntime(mesh=mesh)
        t = rt.add_tenant(TenantSpec("t", cfg, profile="1s.16c", slots=2,
                                     max_seq=48, hbm_budget=budget,
                                     spill_granule=1024))
        rt.submit("t", [Request(i, p, 6) for i, p in enumerate(prompts)])
        logits = []
        while not t.engine.idle:
            rt.step()
            logits.append(np.asarray(t.engine.last_logits))
            if budget is not None:
                assert in_host_memory(t.params["tok_embed"])
                kinds = t.engine.pool.spilled_kinds()
                assert set(kinds) == set(t.engine.pool.split_leaves)
                assert set(kinds.values()) == {"pinned_host"}
        return t, logits

    base, want = serve(None)
    inv = model.serving_inventory(
        base.params, model.init_cache(2, 48))
    embed = sum(x.bytes for x in inv if x.group == "embed")
    kv = sum(x.bytes for x in inv if x.group == "kv_cache")
    off, got = serve(base.inventory_bytes - embed - kv // 4)
    assert set(off.plan.offloaded) == {"params/tok_embed",
                                       "params/pos_embed"}
    assert off.engine.pool.split_leaves
    assert off.engine.pool.host_bytes > 0
    assert base.engine.pool.host_bytes == 0
    assert len(got) == len(want)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)


def test_spans_count_the_tiers_bytes(gpt2, mesh, tmp_path):
    """Under the profiler every admission is an ``engine.admit`` span with
    its request, prompt length and queue wait, and every prefill and
    decode moves the host-placed parameters in (``offload.fetch``) and the
    pool's host bytes in and out (``kv.materialize``, ``kv.update``)."""
    from jax.profiler import ProfileData
    cfg, model, params = gpt2
    inv = model.serving_inventory(params, model.init_cache(2, 48))
    total = sum(x.bytes for x in inv)
    embed = sum(x.bytes for x in inv if x.group == "embed")
    kv = sum(x.bytes for x in inv if x.group == "kv_cache")
    rt = SliceRuntime(mesh=mesh)
    t = rt.add_tenant(TenantSpec(
        "t", cfg, profile="1s.16c", slots=2, max_seq=48,
        hbm_budget=total - embed - kv // 4, spill_granule=1024))
    prompts = [np.arange(3, 11, dtype=np.int32),
               np.arange(1, 6, dtype=np.int32)]
    rt.submit("t", [Request(i, p, 4) for i, p in enumerate(prompts)])
    with jax.profiler.trace(str(tmp_path)):
        rt.step()      # two admissions, then a decode
        rt.step()      # a decode alone
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = ProfileData.from_file(str(path)).find_plane_with_name("/host:CPU")
    spans = {}
    for line in host.lines:
        for e in line.events:
            if e.name.startswith(("engine.", "kv.", "offload.")):
                spans.setdefault(e.name, []).append(dict(e.stats))

    admits = spans["engine.admit"]
    assert [(a["rid"], a["prompt_len"]) for a in admits] == [(0, 8), (1, 5)]
    assert all(a["wait_ms"] >= 0 for a in admits)
    pool = t.engine.pool
    params_host = sum(x.nbytes for x in jax.tree_util.tree_leaves(t.params)
                      if in_host_memory(x))
    assert params_host == embed and pool.host_bytes > 0
    moves = len(admits) + 2      # each prefill and each decode
    assert spans["offload.fetch"] == [{"h2d_bytes": embed}] * moves
    assert spans["kv.materialize"] == [{"h2d_bytes": pool.host_bytes}] * moves
    assert spans["kv.update"] == [{"d2h_bytes": pool.host_bytes}] * moves


def test_eviction_records_partial_generation(gpt2):
    cfg, model, params = gpt2
    prompt = np.arange(1, 9, dtype=np.int32) % cfg.vocab_size
    eng = ServingEngine(model, params, slots=1, max_seq=16)
    # wants 50 tokens but the slot caps at max_seq: evicted after ~7
    out = eng.run([Request(0, prompt, 50)])
    assert 0 in out, "evicted request must still be reported"
    assert 0 < len(out[0]) < 50
    assert eng.stats.truncated == 1
    # and the engine kept serving afterwards (slot recycled)
    out2 = eng.run([Request(1, prompt, 3)])
    assert len(out2[1]) == 3


def test_overlong_prompt_rejected_not_crashed(gpt2):
    cfg, model, params = gpt2
    eng = ServingEngine(model, params, slots=1, max_seq=8)
    long_prompt = np.arange(1, 13, dtype=np.int32) % cfg.vocab_size  # 12 > 7
    ok_prompt = np.arange(1, 5, dtype=np.int32) % cfg.vocab_size
    out = eng.run([Request(0, long_prompt, 4), Request(1, ok_prompt, 3)])
    assert out[0] == [] and eng.stats.rejected == 1
    assert len(out[1]) == 3


def test_admission_control_bounds_queue(gpt2):
    cfg, model, params = gpt2
    eng = TenantEngine(model, params, slots=1, max_seq=32, max_queue=2)
    prompt = np.arange(1, 5, dtype=np.int32) % cfg.vocab_size
    accepted = [eng.submit(Request(i, prompt, 2)) for i in range(5)]
    assert accepted == [True, True, False, False, False]
    assert eng.stats.rejected == 3
    while not eng.idle:
        eng.tick()
    assert set(eng.outputs) == {0, 1}


# ---------------------------------------------------------------------------
# runtime end-to-end
# ---------------------------------------------------------------------------
def test_runtime_serves_tenants_concurrently(gpt2, mesh):
    cfg, model, params = gpt2
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, size=5).astype(np.int32)
               for _ in range(3)]

    # reference: the same requests through a lone engine
    want = ServingEngine(model, params, slots=2, max_seq=32).run(
        [Request(i, p, 4) for i, p in enumerate(prompts)])

    rt = SliceRuntime(mesh=mesh)
    rt.add_tenant(TenantSpec("a", cfg, profile="1s.16c", slots=2, max_seq=32))
    rt.add_tenant(TenantSpec("b", cfg, profile="2s.32c", slots=2, max_seq=32,
                             seed=1))
    rt.submit("a", [Request(i, p, 4) for i, p in enumerate(prompts)])
    rt.submit("b", [Request(i, p, 4) for i, p in enumerate(prompts)])
    report = rt.run()

    assert rt.tenants["a"].engine.outputs == want, \
        "co-running another tenant must not change tenant a's tokens"
    for name in ("a", "b"):
        row = report["tenants"][name]
        assert row["tokens_out"] == 12 and row["completed"] == 3
    assert report["pod_utilization"] == pytest.approx(48 / 256)
    assert 0 < report["modeled"]["throttle"] <= 1.0
    # release + repack path
    rt.remove_tenant("a", repack=True)
    assert report["pod_utilization"] > rt.partitioner.utilization()


def test_report_twin_block_gated_on_perf_model(gpt2):
    # the per-tenant "twin" row surfaces only when the runtime's PerfModel
    # prices twin-offload rungs; the default model leaves the key out so
    # existing report consumers see an unchanged schema
    from repro.core.offload import TwinSpec
    from repro.core.perfmodel import get_model

    cfg, _, _ = gpt2
    rt = SliceRuntime()
    rt.add_tenant(TenantSpec("t", cfg, profile="1s.16c", slots=1, max_seq=16))
    assert "twin" not in rt.report()["tenants"]["t"]

    rt2 = SliceRuntime(perf=get_model(twin=TwinSpec()))
    rt2.add_tenant(TenantSpec("t", cfg, profile="1s.16c", slots=1, max_seq=16))
    row = rt2.report()["tenants"]["t"]
    assert "twin" in row
    # the reduced demo model fits its slice outright — nothing spills, so
    # no twin rung exists and the row says so explicitly rather than
    # omitting the key
    tw = row["twin"]
    assert tw is None or (
        "+cpu" in tw["rung"]
        and 0.0 < tw["cpu_fraction"] <= 1.0
        and tw["step_time_s"] > 0.0)


# ---------------------------------------------------------------------------
# placement rounding for partial spills
# ---------------------------------------------------------------------------
def test_shardings_with_offload_partial_rounding(mesh):
    from jax.sharding import PartitionSpec as P
    host_kind, dev_kind = host_memory_kind(mesh), device_memory_kind(mesh)
    specs = {"a": P(), "b": P(), "c": P()}
    sizes = {"a": 100, "b": 100, "c": 100}
    plan = OffloadPlan(offloaded=("a",), partial=(("b", 75), ("c", 25)),
                       resident_bytes=100, host_bytes=200,
                       host_traffic_per_step=0.0, fits=True)
    sh = shardings_with_offload(specs, plan, mesh, sizes=sizes)
    assert sh["a"].memory_kind == host_kind     # fully offloaded
    assert sh["b"].memory_kind == host_kind     # 75% spilled -> host side
    assert sh["c"].memory_kind == dev_kind      # 25% spilled -> device side
    # without sizes the fraction is unknowable -> partial stays on device
    sh2 = shardings_with_offload(specs, plan, mesh)
    assert sh2["b"].memory_kind == dev_kind


def test_kv_pool_slot_lifecycle(gpt2, mesh):
    cfg, model, params = gpt2
    pool = KVPool(model, slots=3, max_seq=16, mesh=mesh)
    slots = [pool.alloc_slot() for _ in range(3)]
    assert pool.alloc_slot() is None
    pool.free_slot(slots[1])
    assert pool.free_slots == 1
    assert pool.positions[slots[1]] == 0
