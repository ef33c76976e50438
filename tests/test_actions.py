"""The Action API transaction contract (cluster/actions.py).

The headline property: for ANY action, ``apply()`` followed by
``rollback()`` leaves the observable cluster state exactly as it was —
partitioner rectangles (by tenant, with free/dead chip masks),
``PodSimulator`` job sets (every progress/delay/throttle input), pod
power draw, the scheduler queue, and every counter — across randomized
action sequences on randomized mid-flight cluster states (hypothesis).
Slice ids may advance (probe trials release/re-allocate rectangles in
place; that is the documented PR 4 contract), which is why the
fingerprint is id-agnostic.

Also here: probes are observably side-effect-free, probed outcomes price
what apply() then charges, and the uniform probe API returns reasons on
infeasible bindings.
"""
import pytest

from repro.core.hw import MI300_POD, V5E_POD
from repro.cluster import (ClusterScheduler, PolicySpec, TraceConfig,
                           generate_trace, lookahead_showcase,
                           migration_showcase, preemption_showcase,
                           reconfigure_showcase)
from repro.cluster.actions import (Grow, MigrateAcrossPods, Place, Preempt,
                                   ReconfigurePartition, Repack, Shrink,
                                   capture, restore)
from repro.cluster.scheduler import JobRecord
from repro.cluster.trace import BATCH, TRAINING, Job

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:   # the property still runs via the seeded sweep below
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# fingerprinting (id-agnostic observable state)
# ---------------------------------------------------------------------------
def fingerprint(sched):
    out = []
    for pod in sched.pods:
        part = pod.partitioner
        out.append({
            "mode": pod.mode,
            "ladder": tuple(p.name for p in part.profiles),
            "rects": sorted((a.tag, a.profile.name, a.origin)
                            for a in part.allocations.values()),
            "free": (part._grid == -1).tobytes(),
            "dead": (part._grid == -2).tobytes(),
            "sim_now": pod.sim.now,
            "sim": {k: (j.n_chips, j.u_compute, j.step_time, j.steps,
                        j.work_total, j.work_done, j.delay_s, j.fixed_s)
                    for k, j in pod.sim.jobs.items()},
            "draw": pod.sim.draw(),
            "throttle": pod.sim.throttle(),
            "jobs": {jid: (r.profile_name, r.origin, r.finish_s,
                           r.resident_bytes, r.preemptions, r.migrations,
                           r.shrunk, r.grown, r.suspended)
                     for jid, r in pod.jobs.items()},
        })
    out.append(tuple(id(r) for r in sched._queue))
    out.append({n: getattr(sched, n) for n in (
        "_repacks", "_repack_failures", "_shrinks", "_grows",
        "_preemptions", "_resumes", "_wasted_checkpoint_chip_s",
        "_migrated_bytes", "_migration_s", "_migrations",
        "_dcn_migrated_bytes", "_dcn_migration_s", "_power_deferrals",
        "_reconfigs")})
    return out


_PODS = {"v5e": V5E_POD, "mi300": MI300_POD}


def _mid_state(seed, n_pods=2, horizon=400.0, chip="v5e"):
    """A mid-flight cluster: a seeded trace scheduled up to ``horizon``
    virtual seconds, pods still holding running jobs."""
    trace = generate_trace(TraceConfig(seed=seed, n_jobs=14,
                                       mean_interarrival_s=20.0))
    sched = ClusterScheduler(n_pods=n_pods, policy="frag_repack",
                             horizon_s=horizon, spec=PolicySpec(),
                             pod=_PODS[chip])
    sched.run(trace)
    return sched


def _beneficiary(sched, i, profile, kind=TRAINING, arch="llama3-8b",
                 shape="train_4k", slo=50.0):
    """A synthetic high-priority deadline job record the rescue actions
    can fight for."""
    t = sched._now
    job = Job(job_id=10_000 + i, kind=kind, arch=arch, shape=shape,
              arrival_s=t, steps=5, profile=profile, slo_factor=slo,
              priority=3)
    from repro.cluster.placement import ideal_duration
    ideal = ideal_duration(job, sched.chip, sched.perf)
    return JobRecord(job, deadline_s=(t + slo * ideal
                                      if ideal is not None else None))


_PROFILES = ("1s.16c", "2s.32c", "4s.64c", "8s.128c")
_KINDS = ("place", "repack", "shrink", "preempt", "migrate", "grow",
          "reconfigure")


def _find_action(sched, kind, rec, t):
    """Bind one feasible action of ``kind`` on the current state, or
    None."""
    if kind == "place":
        cands = sched.policy.candidates(rec.job, sched.pods, sched.chip,
                                        t, rec.deadline_s, perf=sched.perf)
        for cand in cands:
            act = Place(rec, cand)
            if act.probe(sched, t).feasible:
                return act
        return None
    if kind == "repack":
        return Repack.find(sched, rec, t)
    if kind == "shrink":
        return Shrink.find(sched, rec, t)
    if kind == "preempt":
        return Preempt.find(sched, rec, t)
    if kind == "migrate":
        return MigrateAcrossPods.find(sched, rec, t)
    if kind == "reconfigure":
        return ReconfigurePartition.find(sched, rec, t)
    if kind == "grow":
        for pod in sched.pods:
            for r in sorted(pod.jobs.values(), key=lambda r: r.job.job_id):
                if r.executed or r.finished or r.job.duration_s is not None:
                    continue
                act = Grow.find(sched, pod, r, t)
                if act is not None:
                    return act
        return None
    raise AssertionError(kind)


# ---------------------------------------------------------------------------
# the round-trip property (ISSUE satellite): apply();rollback() == identity
# across randomized action sequences. The body is shared between the
# hypothesis test (CI, where hypothesis is installed) and a deterministic
# seeded sweep (runs everywhere).
# ---------------------------------------------------------------------------
def _roundtrip_body(seed, kinds, profiles, chip="v5e"):
    sched = _mid_state(seed, chip=chip)
    t = sched._now
    before = fingerprint(sched)
    applied = []
    for i, kind in enumerate(kinds):
        rec = _beneficiary(sched, i, profiles[i % len(profiles)])
        act = _find_action(sched, kind, rec, t)
        if act is None:
            continue
        act.apply(sched, t)
        applied.append(act)
    for act in reversed(applied):
        act.rollback(sched)
    assert fingerprint(sched) == before
    return len(applied)


def _probe_body(seed, profile):
    sched = _mid_state(seed)
    t = sched._now
    rec = _beneficiary(sched, 0, profile)
    before = fingerprint(sched)
    for kind in ("place", "shrink", "preempt", "migrate"):
        act = _find_action(sched, kind, rec, t)
        if act is not None:
            act.probe(sched, t)
    # Repack/Grow probe via snapshot+restore
    Repack(rec).probe(sched, t)
    for pod in sched.pods:
        for r in pod.jobs.values():
            if not r.executed and r.job.duration_s is None:
                Grow(r, pod).probe(sched, t)
                break
    assert fingerprint(sched) == before


if HAVE_HYPOTHESIS:
    @settings(max_examples=12, deadline=None)
    @given(seed=st.integers(0, 7),
           kinds=st.lists(st.sampled_from(_KINDS), min_size=1, max_size=4),
           profiles=st.lists(st.sampled_from(_PROFILES), min_size=4,
                             max_size=4),
           chip=st.sampled_from(("v5e", "mi300")))
    def test_apply_rollback_roundtrip_over_random_sequences(seed, kinds,
                                                            profiles, chip):
        _roundtrip_body(seed, kinds, profiles, chip=chip)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 7),
           profile=st.sampled_from(_PROFILES))
    def test_probe_is_observably_side_effect_free(seed, profile):
        _probe_body(seed, profile)


def _equivalence_body(seed, kinds, profiles):
    """The undo-log ↔ snapshot equivalence oracle: the same action
    sequence on two byte-identical mid-flight states — one rolling back
    through the copy-on-write undo log (default), one through the legacy
    full capture/restore (``snapshot_rollback=True``) — must agree on
    the observable state after every apply AND after the rollbacks."""
    undo = _mid_state(seed)
    snap = _mid_state(seed)
    snap.snapshot_rollback = True
    assert _x_fingerprint(undo) == _x_fingerprint(snap)
    before = fingerprint(undo)
    applied = []
    for i, kind in enumerate(kinds):
        pair = []
        for sched in (undo, snap):
            rec = _beneficiary(sched, i, profiles[i % len(profiles)])
            act = _find_action(sched, kind, rec, sched._now)
            if act is not None:
                act.apply(sched, sched._now)
            pair.append(act)
        assert (pair[0] is None) == (pair[1] is None)
        assert _x_fingerprint(undo) == _x_fingerprint(snap)
        if pair[0] is not None:
            applied.append(pair)
    for u_act, s_act in reversed(applied):
        u_act.rollback(undo)
        s_act.rollback(snap)
        assert _x_fingerprint(undo) == _x_fingerprint(snap)
    assert fingerprint(undo) == before
    assert not undo._txns          # no leaked open transactions
    return len(applied)


def _x_fingerprint(sched):
    """``fingerprint`` made comparable across scheduler instances: queue
    membership by job id instead of record identity."""
    fp = fingerprint(sched)
    fp[-2] = tuple(r.job.job_id for r in sched._queue)
    return fp


if HAVE_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 7),
           kinds=st.lists(st.sampled_from(_KINDS), min_size=1, max_size=4),
           profiles=st.lists(st.sampled_from(_PROFILES), min_size=4,
                             max_size=4))
    def test_undo_log_matches_snapshot_rollback(seed, kinds, profiles):
        _equivalence_body(seed, kinds, profiles)


def test_undo_log_matches_snapshot_rollback_seeded_sweep():
    import random
    rng = random.Random(1)
    total = 0
    for seed in range(4):
        kinds = rng.sample(_KINDS, k=4)
        profiles = [rng.choice(_PROFILES) for _ in range(4)]
        total += _equivalence_body(seed, kinds, profiles)
    for kind in _KINDS:
        total += _equivalence_body(1, [kind] * 2, list(_PROFILES))
    assert total >= 5


def test_apply_rollback_roundtrip_seeded_sweep():
    """Hypothesis-free sweep of the same property: every action kind must
    round-trip on several mid-flight states, and at least a handful of
    actions must actually have been applied (the sweep is not vacuous)."""
    import itertools
    import random
    rng = random.Random(0)
    total = 0
    for seed in range(4):
        kinds = rng.sample(_KINDS, k=4)
        profiles = [rng.choice(_PROFILES) for _ in range(4)]
        total += _roundtrip_body(seed, kinds, profiles)
    # every kind individually, on one state
    for kind in _KINDS:
        total += _roundtrip_body(1, [kind] * 2, list(_PROFILES))
    assert total >= 5


def test_apply_rollback_roundtrip_mi300_seeded_sweep():
    """The same round-trip property on multi-mode (mi300) mid-flight
    states, with ``reconfigure`` in every sequence — pod ``mode`` and the
    partitioner's profile ladder are part of the fingerprint, so a mode
    switch that survives rollback fails loudly."""
    import random
    rng = random.Random(2)
    total = 0
    for seed in range(4):
        kinds = ["reconfigure"] + rng.sample(_KINDS, k=3)
        profiles = [rng.choice(_PROFILES) for _ in range(4)]
        total += _roundtrip_body(seed, kinds, profiles, chip="mi300")
    assert total >= 3


def test_probe_side_effect_free_seeded_sweep():
    for seed, profile in ((0, "8s.128c"), (1, "1s.16c"), (2, "4s.64c")):
        _probe_body(seed, profile)


# ---------------------------------------------------------------------------
# deterministic transaction checks on the crafted showcase states
# ---------------------------------------------------------------------------
def _paused(trace_fn, n_pods, horizon, spec=None, pod=V5E_POD):
    sched = ClusterScheduler(n_pods=n_pods, policy="frag_repack",
                             horizon_s=horizon,
                             spec=spec or PolicySpec(), pod=pod)
    sched.run(trace_fn())
    return sched


def test_preempt_apply_rollback_exact_on_showcase_state():
    # pause the preemption showcase before the deadline arrival, then
    # drive the eviction by hand
    sched = _paused(preemption_showcase, 1, horizon=5.0)
    t = 10.0
    rec = _beneficiary(sched, 0, "8s.128c")
    before = fingerprint(sched)
    act = Preempt.find(sched, rec, t)
    assert act is not None and act.outcome.feasible
    assert act.victim_id == 0                 # the priority-0 batch holder
    cost = act.outcome.cost_s
    assert cost == pytest.approx(
        2 * act.victim.resident_bytes / sched._pod_host_bw)
    act.apply(sched, t)
    assert sched._preemptions == 1
    assert act.victim.suspended is not None
    assert any(q is act.victim for q in sched._queue)
    act.rollback(sched)
    assert fingerprint(sched) == before
    assert act.victim.suspended is None and act.victim.preemptions == 0
    assert rec.place_s is None                # beneficiary fields restored


def test_migrate_apply_rollback_exact_on_showcase_state():
    sched = _paused(migration_showcase, 2, horizon=5.0)
    t = 10.0
    rec = _beneficiary(sched, 0, "8s.128c", arch="qwen3-32b")
    before = fingerprint(sched)
    act = MigrateAcrossPods.find(sched, rec, t)
    assert act is not None and act.outcome.feasible
    # DCN pricing, not host links
    assert act.outcome.cost_s == pytest.approx(
        2 * act.victim.resident_bytes / sched._dcn_bw)
    victim = act.victim
    src_idx = victim.pod_idx
    act.apply(sched, t)
    assert victim.pod_idx != src_idx and victim.migrations == 1
    assert sched._migrations == 1 and sched._dcn_migrated_bytes > 0
    act.rollback(sched)
    assert fingerprint(sched) == before
    assert victim.pod_idx == src_idx and victim.migrations == 0


def test_lookahead_enabler_rollback_is_exact():
    # the exact path LookAheadPolicy exercises: apply a beneficiary-less
    # eviction, then roll it back
    sched = _paused(lookahead_showcase, 1, horizon=5.0)
    t = 10.0
    rec = _beneficiary(sched, 0, "8s.128c")
    before = fingerprint(sched)
    enablers = list(Preempt.enablers(sched, rec, t))
    assert [e.victim_id for e in enablers] == [0, 1]
    enabler = enablers[0]
    out = enabler.probe(sched, t)
    assert out.feasible and out.start_delay_s > 0
    enabler.apply(sched, t)
    assert sched._preemptions == 1
    enabler.rollback(sched)
    assert fingerprint(sched) == before


def test_shrink_apply_rollback_exact_on_showcase_state():
    from repro.cluster import elastic_showcase
    sched = _paused(elastic_showcase, 1, horizon=5.0)
    t = 10.0
    rec = _beneficiary(sched, 0, "4s.64c", arch="qwen3-32b")
    before = fingerprint(sched)
    act = Shrink.find(sched, rec, t)
    assert act is not None and act.outcome.feasible
    assert act.victim.job.kind == BATCH
    assert act.outcome.cost_s == pytest.approx(
        int(act.small.plan.resident_bytes) / sched._pod_host_bw)
    act.apply(sched, t)
    assert sched._shrinks == 1 and act.victim.shrunk
    assert rec.place_s == t
    act.rollback(sched)
    assert fingerprint(sched) == before
    assert not act.victim.shrunk and rec.place_s is None


def test_repack_find_apply_rollback_spans_the_scan():
    from repro.cluster import fragmentation_showcase
    # pause right after the five short jobs complete (t=100): 128 chips
    # free but scattered — the stranding state repack() exists for
    sched = _paused(fragmentation_showcase, 1, horizon=100.5)
    t = 101.0
    rec = _beneficiary(sched, 0, "8s.128c", arch="qwen3-32b")
    before = fingerprint(sched)
    act = Repack.find(sched, rec, t)
    assert act is not None and act.outcome.feasible
    assert act.outcome.cost_s > 0          # moved resident bytes, priced
    act.apply(sched, t)
    assert sched._repacks == 1 and rec.place_s == t
    act.rollback(sched)                     # spans find()+apply()
    assert fingerprint(sched) == before


def test_grow_find_apply_rollback_on_showcase_state():
    from repro.cluster import grow_showcase
    # pause after the short neighbour completed (t=50): the training job
    # may extend into the freed rectangle
    sched = _paused(grow_showcase, 1, horizon=60.0)
    t = 60.0
    pod = sched.pods[0]
    rec = next(iter(pod.jobs.values()))
    before = fingerprint(sched)
    act = Grow.find(sched, pod, rec, t)
    assert act is not None and act.outcome.feasible
    act.apply(sched, t)
    assert sched._grows == 1 and rec.grown
    act.rollback(sched)
    assert fingerprint(sched) == before
    assert not rec.grown


def test_reconfigure_apply_rollback_exact_on_showcase_state():
    # pause the reconfigure showcase before the deadline arrival, then
    # drive the mode switch by hand: drain, flip, place — and undo it all
    sched = _paused(reconfigure_showcase, 2, horizon=5.0, pod=MI300_POD)
    t = 10.0
    # the slack must cover the 30 s switch downtime: steps=5 of decode is
    # milliseconds of work, so the slo factor carries the slack
    rec = _beneficiary(sched, 0, "16s.256c", kind=BATCH,
                       arch="llama3-8b", shape="decode_32k", slo=1e5)
    before = fingerprint(sched)
    act = ReconfigurePartition.find(sched, rec, t)
    assert act is not None and act.outcome.feasible
    mode = sched._modes[act.mode_name]
    # priced as drain traffic + the fixed mode-switch downtime
    assert act.outcome.cost_s == pytest.approx(
        act.drain_total_s + mode.switch_downtime_s)
    assert act.outcome.start_delay_s >= mode.switch_downtime_s
    act.apply(sched, t)
    assert sched._reconfigs == 1
    assert act.pod.mode == act.mode_name != sched.base_mode
    assert sched._migrations == 1      # the drained holder moved over DCN
    assert rec.place_s == t and rec.pod_idx == act.pod.idx
    act.rollback(sched)
    assert fingerprint(sched) == before
    assert act.pod.mode == sched.base_mode
    assert rec.place_s is None


def test_reconfigure_infeasible_on_single_mode_chip():
    # v5e has only its fixed mode: find() has nothing to scan, so legacy
    # configurations are untouched even with "reconfigure" enabled
    sched = _paused(preemption_showcase, 1, horizon=5.0)
    rec = _beneficiary(sched, 0, "8s.128c")
    assert ReconfigurePartition.find(sched, rec, 10.0) is None


def test_infeasible_probes_carry_reasons():
    sched = _paused(preemption_showcase, 1, horizon=5.0)
    t = 10.0
    # no migration target on a single-pod cluster
    rec = _beneficiary(sched, 0, "8s.128c")
    assert MigrateAcrossPods.find(sched, rec, t) is None
    # a deadline with no slack: every preempt probe must explain itself
    rec_tight = _beneficiary(sched, 1, "8s.128c", slo=1e-9)
    act = Preempt.find(sched, rec_tight, t)
    assert act is None
    pod = sched.pods[0]
    victim = next(r for r in pod.jobs.values() if r.job.kind == BATCH)
    from repro.cluster.actions import slo_profiles
    sc = next(iter(sched.perf.options(rec_tight.job)))
    probe = Preempt(rec_tight, pod, victim, sc).probe(sched, t)
    assert not probe.feasible and "SLO" in probe.reason


def test_capture_restore_roundtrip_direct():
    sched = _paused(preemption_showcase, 1, horizon=5.0)
    before = fingerprint(sched)
    snap = capture(sched)
    # brutalize the state
    pod = sched.pods[0]
    victim = next(iter(pod.jobs.values()))
    pod.sim.jobs[victim.job.job_id].delay_s += 123.0
    pod.partitioner.release(victim.slice_id)
    sched._shrinks += 7
    sched._queue.append(victim)
    assert fingerprint(sched) != before
    restore(sched, snap)
    assert fingerprint(sched) == before
