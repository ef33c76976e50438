"""Twin-offload co-execution (PR 9): CPU-side throughput priced as
elastic rungs, plus the property-test hardening pass over the
offload/pricing core.

Layers covered:

* ``plan_offload`` / ``plan_twin`` invariants — budget respect,
  indivisible tensors never split, spill monotone in budget, shard
  fractions in (0, 1], the two-resource step time is the max of its
  terms — via hypothesis when available and a seeded sweep otherwise
  (the ``test_actions.py`` convention).
* ``estimated_step_slowdown``'s replacement: the old model assumed the
  host link overlaps perfectly with compute (``max(base, t_host)``);
  the new one charges a non-overlappable serial prefix, which bites
  hardest in the crossover region where the terms are comparable.
* The twin rungs end-to-end: ``options`` ordering, the default-off
  bit-identity contract, the probe-cache key discipline, the
  ``twin_showcase`` SLO flip, and the serving runtime's report block.
"""
import pytest

from repro.cluster import ClusterScheduler, PolicySpec, twin_showcase
from repro.cluster.trace import SERVING, Job
from repro.configs import get_config, get_shape
from repro.core.hw import V5E, V5E_HOST, V5E_HOST_C2C, GiB, HostSpec
from repro.core.offload import (OVERLAP_SERIAL_FRACTION, TensorInfo,
                                TwinOffloadPlan, TwinSpec,
                                estimated_step_slowdown, overlap_step_time,
                                plan_offload, plan_twin)
from repro.core.perfmodel import PerfModel, get_model
from repro.core.slices import PROFILES, get_profile
from repro.core.workload import WorkloadEstimate

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:   # the properties still run via the seeded sweeps below
    HAVE_HYPOTHESIS = False

TWIN = TwinSpec()


# ---------------------------------------------------------------------------
# property bodies (shared by the hypothesis wrappers and the seeded sweeps)
# ---------------------------------------------------------------------------
def _inventory(sizes, divisibility):
    return [TensorInfo(name=f"t{i}", bytes=b, group="param", divisible=d)
            for i, (b, d) in enumerate(zip(sizes, divisibility))]


def _offload_invariants_body(sizes, divisibility, budget_frac):
    """plan_offload respects both budgets, never splits an indivisible
    tensor, and spills monotonically less as the budget grows."""
    inv = _inventory(sizes, divisibility)
    total = sum(t.bytes for t in inv)
    budget = int(total * budget_frac)
    host_budget = total * 2
    plan = plan_offload(inv, budget, host_budget=host_budget)
    if plan.fits:
        assert plan.resident_bytes <= budget
        assert plan.host_bytes <= host_budget
    # indivisible tensors are moved whole or not at all
    partial_names = {n for n, _ in plan.partial}
    for t in inv:
        if not t.divisible:
            assert t.name not in partial_names
    # monotone: a strictly larger budget never spills more
    bigger = plan_offload(inv, budget + max(1, total // 7),
                          host_budget=host_budget)
    assert bigger.host_bytes <= plan.host_bytes
    return 1


_TWIN_CASES = [
    ("llama3-8b", "decode_32k", "1s.16c"),
    ("llama3-8b", "decode_32k", "2s.32c"),
    ("qwen3-32b", "decode_32k", "2s.32c"),
    ("qwen3-32b", "train_4k", "4s.64c"),
    ("command-r-35b", "decode_32k", "2s.32c"),
    ("phi3.5-moe-42b-a6.6b", "decode_32k", "2s.32c"),
    ("qwen2-vl-72b", "decode_32k", "4s.64c"),
    ("gpt2-124m", "decode_32k", "1s.16c"),
]


def _twin_invariants_body(arch, shape_name, profile_name, host):
    """plan_twin shard fractions live in (0, 1], the plan's step time is
    exactly the max of its three resource terms, and the overlap-model
    slowdown never undercuts the ideal-overlap bound."""
    wl = WorkloadEstimate(get_config(arch), get_shape(shape_name))
    profile = get_profile(profile_name)
    tp = wl.twin_plan_for(profile, host=host)
    if tp is None:
        return 0
    assert tp.shards, "a twin plan with no shards should be None"
    for shard in tp.shards:
        assert 0.0 < shard.cpu_fraction <= 1.0
        assert shard.flops >= 0 and shard.cpu_bytes >= 0
    assert 0.0 <= tp.cpu_fraction <= 1.0
    assert tp.t_cpu >= 0.0 and tp.t_link >= 0.0
    assert tp.step_time == max(tp.gpu_floor_s, tp.t_cpu, tp.t_link)
    for base in (tp.gpu_floor_s * 0.5, tp.gpu_floor_s, tp.gpu_floor_s * 4):
        slow = estimated_step_slowdown(tp, base, profile)
        assert slow >= max(base, tp.gpu_floor_s, tp.t_cpu, tp.t_link)
    return 1


if HAVE_HYPOTHESIS:
    @settings(max_examples=40, deadline=None)
    @given(sizes=st.lists(st.integers(1 * GiB // 8, 64 * GiB),
                          min_size=1, max_size=8),
           div=st.data(),
           budget_frac=st.floats(0.05, 1.2))
    def test_offload_invariants(sizes, div, budget_frac):
        divisibility = [div.draw(st.booleans()) for _ in sizes]
        _offload_invariants_body(sizes, divisibility, budget_frac)

    @settings(max_examples=20, deadline=None)
    @given(case=st.sampled_from(_TWIN_CASES),
           host=st.sampled_from([V5E_HOST, V5E_HOST_C2C,
                                 HostSpec(name="fat", cpu_flops=12e12,
                                          dram_bw=800e9)]))
    def test_twin_invariants(case, host):
        _twin_invariants_body(*case, host)


def test_offload_invariants_seeded_sweep():
    import random
    rng = random.Random(0)
    total = 0
    for _ in range(20):
        n = rng.randint(1, 8)
        sizes = [rng.randint(1 * GiB // 8, 64 * GiB) for _ in range(n)]
        divisibility = [rng.random() < 0.5 for _ in range(n)]
        total += _offload_invariants_body(sizes, divisibility,
                                          rng.uniform(0.05, 1.2))
    assert total >= 5


def test_twin_invariants_seeded_sweep():
    """Hypothesis-free sweep of the same property; at least a handful of
    cases must actually produce a twin plan (the sweep is not vacuous)."""
    total = 0
    for case in _TWIN_CASES:
        for host in (V5E_HOST, V5E_HOST_C2C):
            total += _twin_invariants_body(*case, host)
    assert total >= 5


def test_coherent_link_never_slows_the_twin():
    # the C2C-coherent host scales the effective link up 8x, so the best
    # twin step time can only improve (or the plan disappears because the
    # plain path no longer needs help)
    wl = WorkloadEstimate(get_config("llama3-8b"), get_shape("decode_32k"))
    profile = get_profile("1s.16c")
    base = wl.twin_plan_for(profile, host=V5E_HOST)
    c2c = wl.twin_plan_for(profile, host=V5E_HOST_C2C)
    assert base is not None
    if c2c is not None:
        assert c2c.step_time <= base.step_time
    assert V5E_HOST.effective_link_scale() == 1.0
    assert V5E_HOST_C2C.effective_link_scale() == V5E_HOST_C2C.c2c_scale


# ---------------------------------------------------------------------------
# estimated_step_slowdown: the full-overlap assumption is gone
# ---------------------------------------------------------------------------
def test_overlap_step_time_crossover_region():
    # the old model returned max(base, t_host): perfect overlap, so two
    # equal terms cost the same as one. The replacement charges a serial
    # prefix of the second-largest term, which is exactly where the old
    # model was most wrong.
    assert overlap_step_time(1.0, 0.0, 0.0) == 1.0     # nothing to overlap
    for t in (0.1, 0.5, 1.0, 2.0, 10.0):
        v = overlap_step_time(1.0, 0.0, t)
        ideal = max(1.0, t)
        assert v >= ideal                              # never below the bound
        assert v == ideal + OVERLAP_SERIAL_FRACTION * min(1.0, t)
    # the overhead RATIO over ideal overlap peaks at the crossover
    ratio = {t: overlap_step_time(1.0, 0.0, t) / max(1.0, t)
             for t in (0.1, 1.0, 10.0)}
    assert ratio[1.0] == 1.0 + OVERLAP_SERIAL_FRACTION
    assert ratio[1.0] > ratio[0.1] and ratio[1.0] > ratio[10.0]
    # three-term form: only the second-largest pays the serial prefix
    assert overlap_step_time(1.0, 0.8, 0.3) == 1.0 + 0.1 * 0.8


def test_step_slowdown_charges_serial_prefix_on_real_plan():
    # a plan with real host traffic: the old max() model would price the
    # crossover point at exactly base_step_time; the replacement must
    # price it strictly higher, and converge to ~base under dominance
    wl = WorkloadEstimate(get_config("llama3-8b"), get_shape("decode_32k"))
    profile = get_profile("1s.16c")
    plan = wl.plan_for(profile)
    assert plan.fits and plan.host_traffic_per_step > 0
    t_link = plan.host_traffic_per_step / profile.host_link_bw(V5E)
    crossover = estimated_step_slowdown(plan, t_link, profile)
    assert crossover == pytest.approx(t_link * (1 + OVERLAP_SERIAL_FRACTION))
    assert crossover > max(t_link, t_link)             # old model's answer
    dominated = estimated_step_slowdown(plan, 100.0 * t_link, profile)
    assert dominated == pytest.approx(100.0 * t_link, rel=0.01)
    # a coherent host scales the link term down
    c2c = estimated_step_slowdown(plan, t_link, profile, host=V5E_HOST_C2C)
    assert c2c < crossover


# ---------------------------------------------------------------------------
# the rungs: options ordering, default-off bit-identity, memoization
# ---------------------------------------------------------------------------
def _job(arch="llama3-8b", shape="decode_32k", profile=None, steps=10):
    return Job(job_id=0, kind=SERVING, arch=arch, shape=shape,
               arrival_s=0.0, steps=steps, profile=profile)


def test_options_emit_twin_rungs_plain_first():
    on = PerfModel(V5E, twin=TWIN)
    rungs = [sc.rung for sc in on.options(_job())]
    assert any("+cpu" in r for r in rungs), rungs
    for sc in on.options(_job()):
        if sc.twin is None:
            continue
        assert sc.rung == f"{sc.profile.name}+cpu{sc.twin.cpu_fraction:.2f}"
        plain = next(s for s in on.options(_job())
                     if s.profile.name == sc.profile.name and s.twin is None)
        # the twin rung is strictly better perf-per-chip at equal chips...
        assert plain.step_time / sc.step_time >= TWIN.min_speedup
        # ...and sorts right after its plain sibling
        assert rungs.index(plain.rung) + 1 == rungs.index(sc.rung)
    # chips stay non-decreasing across the whole row
    chips = [sc.profile.n_chips for sc in on.options(_job())]
    assert chips == sorted(chips)


def test_twin_disabled_is_bit_identical():
    off = PerfModel(V5E)
    on = PerfModel(V5E, twin=TWIN)
    job = _job()
    plain_on = [sc for sc in on.options(job) if sc.twin is None]
    assert [sc.rung for sc in off.options(job)] == \
        [sc.rung for sc in plain_on]
    for a, b in zip(off.options(job), plain_on):
        assert a.step_time == b.step_time          # bit-identical floats
        assert a.terms == b.terms
        assert a.perf_per_chip == b.perf_per_chip
    # the twin-off profile_key carries no twin token; twin-on does
    assert not any("twin" in str(part) for part in off.profile_key)
    assert on.profile_key[:len(off.profile_key)] == off.profile_key
    assert "twin" in str(on.profile_key[-1])


def test_get_model_memoizes_per_twin_spec():
    assert get_model() is get_model()
    assert get_model(twin=TWIN) is get_model(twin=TwinSpec())
    assert get_model(twin=TWIN) is not get_model()
    assert get_model().twin is None
    assert get_model(twin=TWIN).twin == TWIN


def test_scheduler_twin_kwarg_forms():
    assert ClusterScheduler(n_pods=1).perf.twin is None
    assert ClusterScheduler(n_pods=1, twin=True).perf.twin == TwinSpec()
    custom = TwinSpec(host=V5E_HOST_C2C)
    assert ClusterScheduler(n_pods=1, twin=custom).perf.twin == custom


# ---------------------------------------------------------------------------
# the showcase: one flag, opposite SLO verdicts
# ---------------------------------------------------------------------------
def _run_twin_showcase(twin, **kw):
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             spec=PolicySpec(actions=("shrink", "preempt")),
                             twin=twin, **kw)
    records, metrics = sched.run(twin_showcase())
    deadline_job = next(r for r in records if r.job.job_id == 4)
    victim = next(r for r in records if r.job.job_id == 2)
    return records, metrics, deadline_job, victim


def test_twin_showcase_off_misses_slo():
    _, metrics, dj, victim = _run_twin_showcase(False)
    # no plain rung both meets the deadline and fits the 4x4 a shrink can
    # mint; preemption finds no lower-priority victim — the job queues
    # behind the holders and misses
    assert metrics.shrinks == 0 and metrics.preemptions == 0
    assert dj.place_s > dj.deadline_s
    assert dj.finish_s > dj.deadline_s
    assert "+cpu" not in dj.rung
    assert victim.profile_name == "2s.32c" and not victim.shrunk


def test_twin_showcase_on_rescues_via_twin_rung():
    _, metrics, dj, victim = _run_twin_showcase(True)
    assert metrics.shrinks == 1 and metrics.preemptions == 0
    assert victim.shrunk and victim.profile_name == "1s.16c"
    assert dj.place_s == pytest.approx(10.0)
    assert dj.finished and dj.finish_s <= dj.deadline_s
    # the committed rung is the twin: same rectangle, CPU co-execution
    assert dj.rung.startswith("1s.16c+cpu")
    assert dj.profile_name == "1s.16c"   # grid bookkeeping keeps base names


def test_twin_showcase_deadline_identical_both_modes():
    # the deadline derives from the big clean profiles (no twin rungs
    # there), so enabling twin pricing must not move the goalposts
    _, _, dj_off, _ = _run_twin_showcase(False)
    _, _, dj_on, _ = _run_twin_showcase(True)
    assert dj_off.deadline_s == dj_on.deadline_s


def test_twin_probe_cache_never_collides_rungs():
    # Shrink/Preempt/Migrate cache keys use PerfScore.rung, so a twin and
    # a plain score on the same rectangle stay distinct entries: cached
    # and uncached replays must commit identical timelines
    a = _run_twin_showcase(True, probe_cache=True)
    b = _run_twin_showcase(True, probe_cache=False)
    ta = [(r.job.job_id, r.place_s, r.finish_s) for r in a[0]]
    tb = [(r.job.job_id, r.place_s, r.finish_s) for r in b[0]]
    assert ta == tb
    assert a[2].rung == b[2].rung


# ---------------------------------------------------------------------------
# the default-off pin contract, in the same session as the twin modules
# ---------------------------------------------------------------------------
def test_trace0_pins_bit_identical_with_twin_models_loaded():
    """Replaying the seeded trace-0 pins AFTER twin-enabled models have
    been built and scored must still match them: the twin machinery lives
    in separate memo tables and never leaks into default pricing."""
    from test_timeline_pins import TRACE0_POLICIES, assert_pinned, run_pin
    on = get_model(twin=TWIN)
    on.options(_job())                      # populate twin memo tables
    for policy in TRACE0_POLICIES:
        _, records, metrics = run_pin(f"trace0-{policy}")
        assert_pinned(f"trace0-{policy}", records, metrics)


def test_showcase_pins_bit_identical_with_twin_models_loaded():
    from test_timeline_pins import SHOWCASE_PINS, assert_pinned, run_pin
    get_model(twin=TWIN).options(_job())    # twin tables live and warm
    for name in sorted(SHOWCASE_PINS):
        _, records, metrics = run_pin(name)
        assert_pinned(name, records, metrics)
