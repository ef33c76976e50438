"""Property-based tests (hypothesis) on the paper-core invariants:
partitioner packing, shared-cap power throttling, offload-planner knapsack,
quantization, reward metric."""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.hw import GiB, V5E_POD
from repro.core.offload import (MIN_SPILL_BYTES, OffloadPlan, TensorInfo,
                                plan_offload)
from repro.core.partitioner import StaticPartitioner
from repro.core.power import InstanceLoad, pod_draw, throttle_factor
from repro.core.slices import PROFILES, get_profile
from repro.optim.compression import compress_residual, dequantize_int8, quantize_int8


# ---------------------------------------------------------------------------
# partitioner
# ---------------------------------------------------------------------------
profile_strategy = st.sampled_from([p.name for p in PROFILES])


@settings(max_examples=40, deadline=None)
@given(st.lists(profile_strategy, min_size=1, max_size=20))
def test_partitioner_never_overlaps(names):
    part = StaticPartitioner()
    allocated = []
    for name in names:
        try:
            allocated.append(part.allocate(get_profile(name)))
        except RuntimeError:
            break
    part.validate()  # raises on overlap / corruption
    assert part.used_chips() == sum(a.profile.n_chips for a in allocated)
    assert part.used_chips() + part.free_chips() == V5E_POD.n_chips


@settings(max_examples=25, deadline=None)
@given(st.lists(profile_strategy, min_size=2, max_size=10),
       st.data())
def test_partitioner_release_restores_capacity(names, data):
    part = StaticPartitioner()
    allocs = []
    for name in names:
        try:
            allocs.append(part.allocate(get_profile(name)))
        except RuntimeError:
            break
    if not allocs:
        return
    victim = data.draw(st.sampled_from(allocs))
    before = part.free_chips()
    part.release(victim.slice_id)
    part.validate()
    assert part.free_chips() == before + victim.profile.n_chips


def test_partitioner_full_pod_of_smallest():
    part = StaticPartitioner()
    prof = get_profile("1s.16c")
    for _ in range(prof.max_instances(V5E_POD)):
        part.allocate(prof)
    assert part.free_chips() == 0
    with pytest.raises(RuntimeError):
        part.allocate(prof)


def test_fail_chips_releases_and_marks_dead():
    part = StaticPartitioner()
    a = part.allocate(get_profile("8s.128c"))
    affected = part.fail_chips([(0, 0)])
    assert affected == [a.slice_id]
    part.validate()
    # dead chip cannot be reallocated into a slice covering it
    b = part.allocate(part.largest_free_profile())
    r, c, r2, c2 = b.rect
    assert not (r <= 0 < r2 and c <= 0 < c2)


def test_fail_chips_drops_cached_index_eagerly():
    # regression: fail_chips used to bump the generation directly instead
    # of routing through mark_dirty(), so a free-rectangle index built
    # *before* the failure stayed cached. A self-restoring probe trial
    # that later re-stamped the pre-failure generation via
    # restore_generation() would then serve the stale index — and offer
    # origins covering dead chips.
    part = StaticPartitioner()
    g = part.generation
    part._index()                        # build the lazy cache at gen g
    part.fail_chips([(0, 0)])
    assert part.generation != g          # failure is a grid mutation
    assert part._idx is None and part._idx_gen == -1   # dropped eagerly
    part.restore_generation(g)           # a trial re-stamp must not revive it
    assert part._idx is None
    # the full-pod profile covers the dead cell — no origin may exist
    assert part.origins_for(get_profile("16s.256c")) == []
    part.validate()


# ---------------------------------------------------------------------------
# repack (the defrag move behind repro.cluster's repack-enabled policy)
# ---------------------------------------------------------------------------
def _churned_partitioner(names, data):
    """Allocate a profile sequence, release a random subset, optionally kill
    random chips — the interleaved-lifetime state repack() exists for."""
    part = StaticPartitioner()
    for name in names:
        try:
            part.allocate(get_profile(name))
        except RuntimeError:
            break
    live = sorted(part.allocations)
    if live:
        victims = data.draw(st.lists(st.sampled_from(live), unique=True,
                                     max_size=len(live)))
        for sid in victims:
            part.release(sid)
    coords = data.draw(st.lists(
        st.tuples(st.integers(0, V5E_POD.rows - 1),
                  st.integers(0, V5E_POD.cols - 1)),
        unique=True, max_size=6))
    part.fail_chips(coords)
    return part


@settings(max_examples=40, deadline=None)
@given(st.lists(profile_strategy, min_size=1, max_size=14), st.data())
def test_repack_no_overlap_and_dead_chips_stay_dead(names, data):
    part = _churned_partitioner(names, data)
    grid_before = part._grid.copy()
    live_before = dict(part.allocations)
    try:
        part.repack()
    except RuntimeError:
        # failed repack must be a full rollback: grid untouched
        assert (part._grid == grid_before).all()
        assert part.allocations == live_before
        return
    part.validate()  # disjoint rectangles matching the grid marks
    assert set(part.allocations) == set(live_before)
    # dead chips never move, never get reused
    assert ((part._grid == -2) == (grid_before == -2)).all()
    for a in part.allocations.values():
        r, c, r2, c2 = a.rect
        assert (part._grid[r:r2, c:c2] != -2).all()


@settings(max_examples=40, deadline=None)
@given(st.lists(profile_strategy, min_size=1, max_size=14), st.data())
def test_repack_never_shrinks_largest_placeable(names, data):
    part = _churned_partitioner(names, data)
    before = part.largest_free_profile()
    try:
        part.repack()
    except RuntimeError:
        return
    after = part.largest_free_profile()
    assert ((after.n_chips if after else 0)
            >= (before.n_chips if before else 0))


def test_repack_keeps_layout_when_first_fit_would_strand():
    """Largest-first first-fit from a clean grid can strand more than the
    layout it replaces (a 64-chip hole became 32 here): repack then keeps
    the old layout and moves nothing."""
    part = StaticPartitioner()
    for name in ["1s.16c", "1s.16c", "4s.64c", "1s.16c", "4s.64c"]:
        part.allocate(get_profile(name))
    origins = {sid: a.origin for sid, a in part.allocations.items()}
    assert part.largest_free_profile().n_chips == 64
    assert part.repack() == {}
    assert part.largest_free_profile().n_chips == 64
    assert {sid: a.origin for sid, a in part.allocations.items()} == origins
    part.validate()


# (the deterministic rollback test lives in test_slice_runtime.py so it
# also runs where hypothesis is unavailable)


# ---------------------------------------------------------------------------
# extend (the elastic-grow primitive behind ClusterScheduler(grow=True));
# properties mirror the repack() suite above
# ---------------------------------------------------------------------------
def _alloc_signature(part):
    return {sid: (a.profile.name, a.origin)
            for sid, a in part.allocations.items()}


@settings(max_examples=40, deadline=None)
@given(st.lists(profile_strategy, min_size=1, max_size=14), st.data())
def test_extend_no_overlap_and_rollback_restores_state(names, data):
    part = _churned_partitioner(names, data)
    if not part.allocations:
        return
    sid = data.draw(st.sampled_from(sorted(part.allocations)))
    target = get_profile(data.draw(profile_strategy))
    grid_before = part._grid.copy()
    sig_before = _alloc_signature(part)
    old = part.allocations[sid]
    old_profile, (r0, c0) = old.profile, old.origin
    try:
        part.extend(sid, target)
    except (RuntimeError, ValueError):
        # failed extend is a full rollback: grid and table bit-identical
        assert (part._grid == grid_before).all()
        assert _alloc_signature(part) == sig_before
        return
    part.validate()  # disjoint rectangles matching the grid marks
    sig_after = _alloc_signature(part)
    # only the extended slice changed; every live neighbour is untouched
    assert set(sig_after) == set(sig_before)
    for s in sig_after:
        if s != sid:
            assert sig_after[s] == sig_before[s]
    assert sig_after[sid][0] == target.name
    # the old rectangle is contained in the new one (state stays local)
    nr, nc = part.allocations[sid].origin
    assert nr <= r0 and nc <= c0
    assert r0 + old_profile.rows <= nr + target.rows
    assert c0 + old_profile.cols <= nc + target.cols
    # dead chips are never absorbed and never move
    assert ((part._grid == -2) == (grid_before == -2)).all()


@settings(max_examples=40, deadline=None)
@given(st.lists(profile_strategy, min_size=1, max_size=14), st.data())
def test_extend_then_shrink_roundtrips_profile(names, data):
    """Growing a slice and then shrinking it back (the scheduler's shrink
    move: release + re-allocate the original profile at the original
    origin) restores the exact free/occupied footprint."""
    part = _churned_partitioner(names, data)
    if not part.allocations:
        return
    sid = data.draw(st.sampled_from(sorted(part.allocations)))
    target = get_profile(data.draw(profile_strategy))
    free_before = (part._grid == -1).copy()
    old = part.allocations[sid]
    old_profile, old_origin = old.profile, old.origin
    try:
        part.extend(sid, target)
    except (RuntimeError, ValueError):
        return
    part.release(sid)
    back = part.allocate(old_profile, origin=old_origin)
    part.validate()
    assert back.profile is old_profile and back.origin == old_origin
    assert ((part._grid == -1) == free_before).all()


# ---------------------------------------------------------------------------
# power model (the §V-B shared-cap surface PerfModel/PodSimulator sit on)
# ---------------------------------------------------------------------------
instance_strategy = st.builds(
    InstanceLoad,
    n_chips=st.sampled_from([16, 32, 64, 128]),
    u_compute=st.floats(0.0, 1.0, allow_nan=False),
    step_time=st.floats(0.01, 100.0, allow_nan=False),
    steps=st.integers(1, 100),
)


def _fitting_mixes(instances):
    """Clip a drawn instance list to the pod's 256 chips."""
    out, used = [], 0
    for i in instances:
        if used + i.n_chips > V5E_POD.n_chips:
            break
        out.append(i)
        used += i.n_chips
    return out


@settings(max_examples=60, deadline=None)
@given(st.lists(instance_strategy, min_size=1, max_size=16), st.data())
def test_throttle_never_decreases_when_instance_removed(instances, data):
    mix = _fitting_mixes(instances)
    if not mix:
        return
    before = throttle_factor(mix, V5E_POD)
    victim = data.draw(st.integers(0, len(mix) - 1))
    after = throttle_factor(mix[:victim] + mix[victim + 1:], V5E_POD)
    # removing load can only relax the shared cap (f closer to 1)
    assert after >= before - 1e-12


@settings(max_examples=60, deadline=None)
@given(st.lists(instance_strategy, min_size=0, max_size=16))
def test_throttle_is_one_under_the_cap(instances):
    mix = _fitting_mixes(instances)
    if pod_draw(mix, V5E_POD) <= V5E_POD.power_cap_watts:
        assert throttle_factor(mix, V5E_POD) == 1.0


@settings(max_examples=60, deadline=None)
@given(st.lists(instance_strategy, min_size=1, max_size=16))
def test_throttled_implied_draw_respects_cap(instances):
    mix = _fitting_mixes(instances)
    if not mix:
        return
    f = throttle_factor(mix, V5E_POD)
    if f >= 1.0:
        return
    # dynamic power scales with f, idle cannot be throttled away
    idle_floor = V5E_POD.n_chips * V5E_POD.chip.idle_watts
    dynamic = pod_draw(mix, V5E_POD) - idle_floor
    implied = idle_floor + f * dynamic
    # f is floored at 0.1, so the implied draw may legitimately exceed the
    # cap only when even maximal throttling cannot get under it
    if f > 0.1:
        assert implied <= V5E_POD.power_cap_watts * (1 + 1e-9)


# ---------------------------------------------------------------------------
# offload planner
# ---------------------------------------------------------------------------
tensor_strategy = st.builds(
    TensorInfo,
    name=st.uuids().map(str),
    bytes=st.integers(1 * 1024 * 1024, 64 * GiB),
    group=st.sampled_from(["opt_state", "param", "embed", "kv_cache",
                           "activation"]),
    offloadable=st.booleans(),
    divisible=st.booleans(),
)


@settings(max_examples=60, deadline=None)
@given(st.lists(tensor_strategy, min_size=1, max_size=12),
       st.integers(1 * GiB, 512 * GiB))
def test_plan_respects_budget_iff_fits(inventory, budget):
    plan = plan_offload(inventory, budget)
    total = sum(t.bytes for t in inventory)
    assert plan.resident_bytes + plan.host_bytes == total
    if plan.fits:
        assert plan.resident_bytes <= budget
    else:
        # everything offloadable was spilled and it still didn't fit
        non_off = sum(t.bytes for t in inventory if not t.offloadable)
        assert plan.resident_bytes >= min(non_off, budget)
    # never offload a non-offloadable tensor
    names_off = set(plan.offloaded) | {n for n, _ in plan.partial}
    for t in inventory:
        if not t.offloadable:
            assert t.name not in names_off
    # partial spills only on divisible tensors, never more than the tensor
    by_name = {t.name: t for t in inventory}
    for n, b in plan.partial:
        assert by_name[n].divisible
        assert 0 < b < by_name[n].bytes


@settings(max_examples=30, deadline=None)
@given(st.lists(tensor_strategy, min_size=1, max_size=10),
       st.integers(1 * GiB, 256 * GiB))
def test_bigger_budget_never_more_traffic(inventory, budget):
    small = plan_offload(inventory, budget)
    large = plan_offload(inventory, budget * 2)
    assert large.host_traffic_per_step <= small.host_traffic_per_step + 1e-6


def test_fine_grained_spills_only_overhang():
    """The paper's headline case: footprint slightly above the slice →
    spill ≈ the overhang, not whole tensors."""
    inv = [TensorInfo("params", 16 * GiB, "param", divisible=True),
           TensorInfo("kv", 500 * GiB, "kv_cache", divisible=True,
                      traffic_multiplier=0.05)]
    budget = 512 * GiB
    plan = plan_offload(inv, budget)
    assert plan.fits
    overhang = 4 * GiB
    assert plan.host_bytes <= overhang + MIN_SPILL_BYTES
    assert plan.resident_bytes <= budget


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(10, 5000))
def test_quantize_roundtrip_error_bounded(seed, n):
    import jax, jax.numpy as jnp
    x = jax.random.normal(jax.random.PRNGKey(seed), (n,), jnp.float32)
    q, s = quantize_int8(x)
    deq = dequantize_int8(q, s, x.shape, x.size)
    blockwise_max = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(deq - x))) <= blockwise_max / 127.0 + 1e-6


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_error_feedback_is_exact_residual(seed):
    import jax, jax.numpy as jnp
    x = jax.random.normal(jax.random.PRNGKey(seed), (300,), jnp.float32)
    err0 = jnp.zeros_like(x)
    (q, s), err1 = compress_residual(x, err0)
    deq = dequantize_int8(q, s, x.shape, x.size)
    np.testing.assert_allclose(np.asarray(deq + err1), np.asarray(x),
                               rtol=0, atol=1e-5)
