"""ClusterScheduler stack: trace determinism, MISO-style placement,
fragmentation stranding + repack recovery (the bench_cluster scenario),
modeled migration cost, power-cap admission, the progress-based engine
(retro-active stretching, the seeded trace-0 golden, elastic SLO
rescue), the Action API (PolicySpec allowlist,
deprecation shims, cross-pod migration over the DCN, look-ahead
chaining), live SliceRuntime execution, and metrics sanity."""
import subprocess
import sys
from collections import Counter

import numpy as np
import pytest

from repro.cluster import (ClusterScheduler, PolicySpec, TraceConfig,
                           elastic_showcase, fragmentation_showcase,
                           generate_trace, grow_showcase,
                           lookahead_showcase, migration_showcase,
                           parse_actions, preemption_showcase,
                           select_cheapest)
from repro.cluster.placement import (FirstFitPolicy, FragAwarePolicy,
                                     feasible_options, get_policy)
from repro.cluster.trace import (BATCH, KIND_PRIORITY, KINDS, SERVING,
                                 TRAINING, Job)
from repro.core.hw import V5E_POD
from test_timeline_pins import assert_pinned, trace0


# ---------------------------------------------------------------------------
# trace generator
# ---------------------------------------------------------------------------
def test_trace_deterministic_and_mixed():
    a = generate_trace(TraceConfig(seed=3))
    b = generate_trace(TraceConfig(seed=3))
    assert a == b
    assert a != generate_trace(TraceConfig(seed=4))
    kinds = Counter(j.kind for j in a)
    assert set(kinds) <= set(KINDS) and len(kinds) == 3
    arrivals = [j.arrival_s for j in a]
    assert arrivals == sorted(arrivals)
    assert all(j.requests > 0 for j in a if j.kind == SERVING)
    assert all(j.u_compute is not None and j.u_compute < 0.2
               for j in a if j.kind == BATCH)


def test_feasible_options_pinned_profile():
    job = Job(0, TRAINING, "llama3-8b", "train_4k", 0.0, 10,
              profile="4s.64c")
    opts = feasible_options(job)
    assert [p.name for p, _, _ in opts] == ["4s.64c"]
    free = Job(0, TRAINING, "llama3-8b", "train_4k", 0.0, 10)
    assert len(feasible_options(free)) > 1


# ---------------------------------------------------------------------------
# placement policies
# ---------------------------------------------------------------------------
def test_first_fit_takes_smallest_feasible():
    sched = ClusterScheduler(n_pods=1, policy="first_fit")
    job = Job(0, SERVING, "llama3-8b", "decode_32k", 0.0, 100)
    cands = sched.policy.candidates(job, sched.pods, sched.chip, 0.0, None)
    smallest = feasible_options(job)[0][0]
    assert cands[0].profile.name == smallest.name
    assert cands[0].origin == (0, 0)


def test_frag_aware_candidates_sorted_and_scored():
    sched = ClusterScheduler(n_pods=2, policy="frag")
    job = Job(0, TRAINING, "qwen3-32b", "train_4k", 0.0, 20)
    cands = sched.policy.candidates(job, sched.pods, sched.chip, 0.0, None)
    assert cands, "empty cluster must offer candidates"
    flags = [c.meets_deadline for c in cands]
    assert flags == sorted(flags, reverse=True)
    for c in cands:
        assert c.perf_per_chip > 0
        assert c.largest_after >= 0


def test_get_policy_unknown():
    with pytest.raises(KeyError):
        get_policy("optimal")


# ---------------------------------------------------------------------------
# the stranding scenario (acceptance criterion: repack places a job
# first-fit leaves queued, on the same deterministic trace)
# ---------------------------------------------------------------------------
STRANDED = 10


def _run_showcase(policy):
    sched = ClusterScheduler(n_pods=1, policy=policy, horizon_s=3000.0)
    records, metrics = sched.run(fragmentation_showcase())
    big = next(r for r in records if r.job.job_id == STRANDED)
    return sched, records, metrics, big


def test_first_fit_strands_big_job():
    _, _, metrics, big = _run_showcase("first_fit")
    assert not big.placed, "first-fit should leave the 8x16 job queued"
    assert metrics.left_queued == 1
    assert metrics.repacks == 0
    assert metrics.frag_time_avg > 0.3  # scattered holes persist


def test_repack_places_stranded_job_with_migration_cost():
    sched, records, metrics, big = _run_showcase("frag_repack")
    assert big.placed and big.finished
    assert big.profile_name == "8s.128c"
    assert metrics.left_queued == 0
    assert metrics.repacks == 1 and metrics.repack_failures == 0
    assert metrics.migrated_bytes > 0
    assert metrics.migration_s == pytest.approx(
        metrics.migrated_bytes / sched._pod_host_bw)
    # the stranded job starts only after the migration delay
    assert big.finish_s > big.place_s + big.job.duration_s
    # defrag is visible in the time-averaged fragmentation ratio
    assert metrics.frag_time_avg < 0.05
    sched.pods[0].partitioner.validate()


def test_repack_stretches_moved_running_jobs():
    _, records, _, _ = _run_showcase("frag_repack")
    moved_long = [r for r in records
                  if r.job.duration_s == 10_000.0 and r.placed]
    assert moved_long, "long jobs should be running when repack fires"
    stretched = [r for r in moved_long
                 if r.finish_s > r.place_s + r.job.duration_s]
    assert stretched, "migration must delay at least one moved running job"


# ---------------------------------------------------------------------------
# power-cap admission (paper §V-B)
# ---------------------------------------------------------------------------
def _hot_job(jid, arrival, duration):
    return Job(jid, TRAINING, "llama3-8b", "train_4k", arrival, 1,
               profile="8s.128c", duration_s=duration, u_compute=1.0)


def test_power_cap_defers_second_hot_job():
    # two full-power 128-chip jobs together draw 51.2 kW > the 43.5 kW cap
    # (throttle 0.79 < 0.8) -> the second waits for the first to finish
    jobs = [_hot_job(0, 0.0, 100.0), _hot_job(1, 1.0, 100.0)]
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             min_throttle=0.8)
    records, metrics = sched.run(jobs)
    second = next(r for r in records if r.job.job_id == 1)
    assert metrics.power_deferrals >= 1
    assert second.place_s == pytest.approx(100.0)  # admitted at completion
    # with the gate off, both co-run and the pod throttles instead
    sched2 = ClusterScheduler(n_pods=1, policy="frag_repack",
                              min_throttle=0.0)
    records2, metrics2 = sched2.run(jobs)
    second2 = next(r for r in records2 if r.job.job_id == 1)
    assert metrics2.power_deferrals == 0
    assert second2.place_s == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# end-to-end on a generated trace
# ---------------------------------------------------------------------------
def test_scheduler_deterministic_and_metrics_sane():
    trace = generate_trace(TraceConfig(seed=0, n_jobs=16))
    m1 = ClusterScheduler(n_pods=2, policy="frag_repack").run(trace)[1]
    m2 = ClusterScheduler(n_pods=2, policy="frag_repack").run(trace)[1]
    assert m1 == m2
    assert m1.placed == m1.n_jobs == 16
    assert m1.completed == 16 and m1.still_running == 0
    assert 0.0 < m1.chip_hour_utilization <= 1.0
    assert 0.0 <= m1.slo_attainment <= 1.0
    assert 0.0 <= m1.frag_time_avg <= 1.0
    assert m1.energy_J > 0 and m1.makespan_s > 0


def test_pods_empty_after_drain():
    trace = generate_trace(TraceConfig(seed=1, n_jobs=10))
    sched = ClusterScheduler(n_pods=2, policy="frag")
    sched.run(trace)
    for pod in sched.pods:
        assert pod.partitioner.free_chips() == V5E_POD.n_chips
        assert not pod.jobs and not pod.slice_jobs
        pod.partitioner.validate()


def test_scheduler_single_use():
    sched = ClusterScheduler(n_pods=1)
    sched.run([])
    with pytest.raises(AssertionError):
        sched.run([])


# ---------------------------------------------------------------------------
# progress-based engine (PerfModel / PodSimulator rewrite)
# ---------------------------------------------------------------------------
# The progress engine's golden on the seeded 48-job trace: every scalar
# metric of the frag_repack run, compared within 1e-12 relative (a
# regrouped float sum moves the last ulp; a changed decision moves far
# more), plus its timeline pin (decisions exact, times within 1e-9).
_TRACE0_GOLDEN = {
    "n_jobs": 48,
    "placed": 48,
    "completed": 48,
    "left_queued": 0,
    "still_running": 0,
    "makespan_s": 5890.25934641167,
    "mean_queue_delay_s": 157.75783280464077,
    "p95_queue_delay_s": 364.18618643706225,
    "slo_attainment": 0.1875,
    "chip_hour_utilization": 0.3702098211438245,
    "frag_time_avg": 0.2792904051365157,
    "energy_J": 168843007.22412205,
    "energy_per_chip_hour_kJ": 1047.222235013925,
    "repacks": 1,
    "repack_failures": 0,
    "shrinks": 0,
    "grows": 0,
    "preemptions": 0,
    "resumes": 0,
    "wasted_checkpoint_chip_s": 0.0,
    "migrated_bytes": 3848290697216,
    "migration_s": 3.758096384,
    "migrations": 0,
    "dcn_migrated_bytes": 0,
    "dcn_migration_s": 0.0,
    "power_deferrals": 0,
    "reconfigs": 0,
    "rescue_probes_priced": 0,
    "probe_cache_hits": 0,
    "serving_p50_s": 0.0,
    "serving_p99_s": 0.0,
    "serving_slo_hit_rate": 0.0,
    "serving_chip_hours": 0.0,
    "chip_hours_per_slo_hit": 0.0,
    "autoscale_resizes": 0,
}


def _assert_trace0_golden(records, m):
    got = m.as_dict()
    assert got.keys() - {"policy"} == _TRACE0_GOLDEN.keys()
    for key, want in _TRACE0_GOLDEN.items():
        assert got[key] == pytest.approx(want, rel=1e-12, abs=0.0), key
    assert_pinned("trace0-frag_repack", records, m)


def test_trace0_golden_progress_engine():
    records, m = ClusterScheduler(n_pods=1, policy="frag_repack").run(
        trace0())
    assert m.makespan_s == 5890.25934641167
    _assert_trace0_golden(records, m)


def _stretch_jobs():
    # two full-power 128-chip training jobs; together they exceed the cap
    return [Job(0, TRAINING, "llama3-8b", "train_4k", 0.0, 50,
                profile="8s.128c", u_compute=1.0),
            Job(1, TRAINING, "llama3-8b", "train_4k", 10.0, 50,
                profile="8s.128c", u_compute=1.0)]


def test_later_arrival_retroactively_stretches_in_flight_job():
    alone_rec, _ = ClusterScheduler(
        n_pods=1, policy="frag", min_throttle=0.0).run(_stretch_jobs()[:1])
    both_rec, _ = ClusterScheduler(
        n_pods=1, policy="frag", min_throttle=0.0).run(_stretch_jobs())
    alone = alone_rec[0]
    p_a = next(r for r in both_rec if r.job.job_id == 0)
    # alone, job 0 runs unthrottled; job 1's arrival re-solves the mix
    # and stretches job 0
    assert p_a.finish_s > alone.finish_s
    # the stretch is retro-active within the run: the projection at
    # placement time (duration_s) is exceeded by the actual finish
    assert p_a.finish_s > p_a.place_s + p_a.duration_s
    # and job 1 finishes *earlier* than projected at its admission: once
    # job 0 completes, the survivor speeds back up
    p_b = next(r for r in both_rec if r.job.job_id == 1)
    assert p_b.finish_s < p_b.place_s + p_b.duration_s


def test_pinned_duration_traces_identical_in_both_modes():
    # the fragmentation showcase pins every duration: each job finishes
    # at place + delays + duration_s whatever the throttle does; the only
    # delay is the repack's host-link migration, paid by the moved jobs
    # and by the job that waited for the repack
    records, m = ClusterScheduler(
        n_pods=1, policy="frag_repack",
        horizon_s=3000.0).run(fragmentation_showcase())
    assert m.repacks == 1 and m.migration_s > 0
    delays = []
    for r in records:
        assert r.job.duration_s is not None and r.placed
        delay = r.finish_s - r.place_s - r.job.duration_s
        assert (delay == pytest.approx(0.0, abs=1e-9)
                or delay == pytest.approx(m.migration_s)), (r.job, delay)
        delays.append(delay > 1e-9)
    assert any(delays) and not all(delays)


# ---------------------------------------------------------------------------
# elastic shrink (online profile re-selection: SLO miss -> SLO hit)
# ---------------------------------------------------------------------------
def _run_elastic(elastic):
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             horizon_s=3000.0, elastic=elastic)
    records, metrics = sched.run(elastic_showcase())
    deadline_job = next(r for r in records if r.job.job_id == 2)
    victim = next(r for r in records if r.job.job_id == 0)
    return sched, metrics, deadline_job, victim


def test_without_elastic_deadline_job_misses_slo():
    _, metrics, deadline_job, victim = _run_elastic(False)
    assert not deadline_job.placed          # queued behind two long holders
    assert metrics.shrinks == 0
    assert metrics.slo_attainment == 0.0
    assert victim.profile_name == "8s.128c" and not victim.shrunk


def test_elastic_shrink_turns_slo_miss_into_hit():
    sched, metrics, deadline_job, victim = _run_elastic(True)
    # the low-priority batch job was shrunk to the smallest feasible profile
    assert metrics.shrinks == 1
    assert victim.shrunk and victim.profile_name == "1s.16c"
    # the deadline job placed immediately (plus migration delay) and hit
    assert deadline_job.placed and deadline_job.finished
    assert deadline_job.place_s == pytest.approx(10.0)
    assert deadline_job.finish_s <= deadline_job.deadline_s
    # the shrink is priced as a migration over the pod's host links
    assert metrics.migrated_bytes > 0
    assert metrics.migration_s == pytest.approx(
        metrics.migrated_bytes / sched._pod_host_bw)
    # the victim paid: its finish moved past its pinned duration
    assert victim.finish_s > victim.place_s + victim.job.duration_s
    assert metrics.slo_attainment > 0.0
    sched.pods[0].partitioner.validate()


def test_elastic_shrink_lifts_power_gate():
    # the pod HAS an aligned origin for the deadline job, but admitting it
    # next to the full-power batch holder trips the power gate; shrinking
    # the batch job cuts its dynamic draw and lifts the cap
    jobs = [Job(0, BATCH, "gpt2-124m", "decode_32k", 0.0, 1,
                profile="8s.128c", duration_s=10_000.0, u_compute=1.0),
            Job(1, TRAINING, "llama3-8b", "train_4k", 5.0, 1,
                profile="8s.128c", duration_s=200.0, u_compute=1.0,
                slo_factor=2.0)]
    base_rec, base_m = ClusterScheduler(
        n_pods=1, policy="frag_repack", min_throttle=0.8).run(jobs)
    blocked = next(r for r in base_rec if r.job.job_id == 1)
    assert base_m.power_deferrals == 1
    assert blocked.place_s == pytest.approx(10_000.0)  # waited out the holder
    el_rec, el_m = ClusterScheduler(
        n_pods=1, policy="frag_repack", min_throttle=0.8,
        elastic=True).run(jobs)
    rescued = next(r for r in el_rec if r.job.job_id == 1)
    assert el_m.shrinks == 1 and el_m.power_deferrals == 0
    assert rescued.place_s == pytest.approx(5.0)
    assert rescued.finish_s <= rescued.deadline_s


def test_elastic_never_hurts_generated_trace_slo():
    trace = generate_trace(TraceConfig(seed=0, n_jobs=48,
                                       mean_interarrival_s=5.0))
    base = ClusterScheduler(n_pods=1, policy="frag_repack").run(trace)[1]
    el = ClusterScheduler(n_pods=1, policy="frag_repack",
                          elastic=True).run(trace)[1]
    assert el.slo_attainment >= base.slo_attainment


# ---------------------------------------------------------------------------
# checkpoint preemption (priorities: SLO miss -> hit where shrink cannot)
# ---------------------------------------------------------------------------
def test_trace_priorities_follow_kind():
    for j in generate_trace(TraceConfig(seed=2)):
        assert j.priority == KIND_PRIORITY[j.kind]


def _run_preemption(priorities, elastic=True):
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             priorities=priorities, elastic=elastic)
    records, metrics = sched.run(preemption_showcase())
    deadline_job = next(r for r in records if r.job.job_id == 2)
    victim = next(r for r in records if r.job.job_id == 0)
    return sched, metrics, deadline_job, victim


def test_without_priorities_deadline_job_misses_slo():
    # elastic shrink alone cannot mint an 8x16 origin here (the shrunk
    # victim stays at its origin), so the deadline job waits and misses
    _, metrics, deadline_job, victim = _run_preemption(False)
    assert metrics.preemptions == 0 and metrics.shrinks == 0
    assert deadline_job.place_s > deadline_job.deadline_s
    assert deadline_job.finish_s > deadline_job.deadline_s
    assert victim.preemptions == 0 and not victim.suspended


def test_preemption_turns_slo_miss_into_hit():
    sched, metrics, deadline_job, victim = _run_preemption(True)
    # the deadline job placed immediately after the priced save delay
    assert metrics.preemptions == 1 and metrics.resumes == 1
    assert metrics.shrinks == 0     # shrink could not mint the origin
    assert deadline_job.place_s == pytest.approx(10.0)
    assert deadline_job.finished
    assert deadline_job.finish_s <= deadline_job.deadline_s
    # the save delay is the checkpoint volume over the pod's host links
    # (checkpoint_bytes counts save + restore, i.e. the volume twice)
    save_s = victim.checkpoint_bytes / 2 / sched._pod_host_bw
    assert deadline_job.finish_s == pytest.approx(
        10.0 + save_s + deadline_job.job.duration_s)
    sched.pods[0].partitioner.validate()


def test_preempted_job_resumes_with_work_done_preserved():
    sched, metrics, deadline_job, victim = _run_preemption(True)
    assert victim.finished and victim.preemptions == 1 and victim.resumes == 1
    assert victim.suspend_s == pytest.approx(10.0)
    # resumed as soon as the deadline job freed the rectangle
    assert victim.resume_s == pytest.approx(deadline_job.finish_s)
    # no lost progress beyond the priced checkpoint delta: total wall time
    # = nominal work + the suspension gap + the save+restore seconds paid
    nominal = victim.job.steps * victim.step_time_s
    gap = victim.resume_s - victim.suspend_s
    restore_s = victim.checkpoint_delay_s / 2   # save_s == restore_s here
    assert victim.finish_s == pytest.approx(
        victim.job.arrival_s + nominal + gap + restore_s)
    assert metrics.wasted_checkpoint_chip_s == pytest.approx(
        128 * victim.checkpoint_delay_s)
    # the comparator recorded checkpoint traffic, not slice migration
    assert victim.checkpoint_bytes > 0


def test_preemption_requires_strictly_lower_priority():
    # same showcase but the batch holder outranks the arrival: no eviction
    from dataclasses import replace
    jobs = [j if j.job_id != 0 else replace(j, priority=5)
            for j in preemption_showcase()]
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             priorities=True)
    records, metrics = sched.run(jobs)
    assert metrics.preemptions == 0
    deadline_job = next(r for r in records if r.job.job_id == 2)
    assert deadline_job.place_s > deadline_job.deadline_s


def test_preemption_skipped_when_save_delay_blows_deadline():
    # slack of ~0.04 s < the ~0.15 s save drain: suspending the victim
    # could not save the SLO, so the scheduler must leave it running
    from dataclasses import replace
    jobs = [j if j.job_id != 2 else replace(j, slo_factor=1.0001)
            for j in preemption_showcase()]
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             priorities=True)
    records, metrics = sched.run(jobs)
    victim = next(r for r in records if r.job.job_id == 0)
    assert metrics.preemptions == 0 and metrics.resumes == 0
    assert victim.preemptions == 0 and victim.finished
    # sanity: a slack comfortably above the save drain does preempt
    assert ClusterScheduler(n_pods=1, policy="frag_repack",
                            priorities=True).run(
        preemption_showcase())[1].preemptions == 1


def test_preemption_picks_cheapest_victim():
    # two priority-0 batch holders could each mint the rectangle; the
    # scheduler must checkpoint the one with the least resident state
    # (gpt2 ~144 GiB), not the first by job id (qwen3 ~1 TiB)
    jobs = [
        Job(job_id=0, kind=BATCH, arch="qwen3-32b", shape="decode_32k",
            arrival_s=0.0, steps=1, profile="8s.128c",
            duration_s=10_000.0, u_compute=0.05, priority=0),
        Job(job_id=1, kind=BATCH, arch="gpt2-124m", shape="decode_32k",
            arrival_s=0.0, steps=1, profile="8s.128c",
            duration_s=10_000.0, u_compute=0.05, priority=0),
        Job(job_id=2, kind=TRAINING, arch="qwen3-32b", shape="train_4k",
            arrival_s=10.0, steps=1, profile="8s.128c",
            duration_s=400.0, u_compute=0.3, slo_factor=2.0, priority=2),
    ]
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             priorities=True)
    records, metrics = sched.run(jobs)
    expensive = next(r for r in records if r.job.job_id == 0)
    cheap = next(r for r in records if r.job.job_id == 1)
    assert metrics.preemptions == 1
    assert cheap.preemptions == 1 and expensive.preemptions == 0


def test_evicted_victim_resumes_immediately_when_space_exists():
    # the victim's 4x4 blocks the only 8x8 origin, but after eviction a
    # different 4x4 hole is still free: the victim must resume in the
    # same event, not idle until the next completion drains the queue
    jobs = [
        Job(job_id=0, kind=BATCH, arch="gpt2-124m", shape="decode_32k",
            arrival_s=0.0, steps=1, profile="1s.16c",
            duration_s=10_000.0, u_compute=0.05, priority=0),
        Job(job_id=1, kind=TRAINING, arch="llama3-8b", shape="train_4k",
            arrival_s=0.0, steps=1, profile="2s.32c",
            duration_s=10_000.0, u_compute=0.3, priority=1),
        Job(job_id=2, kind=TRAINING, arch="llama3-8b", shape="train_4k",
            arrival_s=0.0, steps=1, profile="8s.128c",
            duration_s=10_000.0, u_compute=0.3, priority=1),
        Job(job_id=3, kind=TRAINING, arch="qwen3-32b", shape="train_4k",
            arrival_s=10.0, steps=1, profile="4s.64c",
            duration_s=400.0, u_compute=0.3, slo_factor=2.0, priority=2),
    ]
    sched = ClusterScheduler(n_pods=1, policy="first_fit", priorities=True)
    records, metrics = sched.run(jobs)
    victim = next(r for r in records if r.job.job_id == 0)
    deadline_job = next(r for r in records if r.job.job_id == 3)
    assert metrics.preemptions == 1 and metrics.resumes == 1
    assert deadline_job.finished
    assert deadline_job.finish_s <= deadline_job.deadline_s
    # resumed at eviction time, in the remaining free 4x4 hole
    assert victim.resume_s == pytest.approx(10.0)
    restore_s = victim.checkpoint_delay_s / 2
    assert victim.finish_s == pytest.approx(10.0 + restore_s + 9_990.0)
    sched.pods[0].partitioner.validate()


def test_preemption_preserves_unpaid_migration_delay():
    # a repack at t=101 charges the moved batch jobs ~0.7 s of host-link
    # delay; a deadline arrival at t=101.5 evicts one mid-burn. The
    # unpaid remainder must survive the suspension: the resume owes
    # restore + leftover migration debt on top of the remaining wall time
    jobs = fragmentation_showcase() + [
        Job(job_id=11, kind=TRAINING, arch="llama3-8b", shape="train_4k",
            arrival_s=101.5, steps=1, profile="1s.16c", duration_s=50.0,
            u_compute=0.3, priority=2)]
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             priorities=True)
    records, metrics = sched.run(jobs)
    assert metrics.repacks == 1 and metrics.preemptions == 1
    victim = next(r for r in records if r.preemptions)
    assert victim.job.kind == BATCH and victim.resumes == 1
    debt = metrics.migration_s - 0.5        # burned 101 -> 101.5 only
    assert debt > 0
    restore_s = victim.checkpoint_delay_s / 2
    # pinned 10 000 s wall: 101 s ran pre-repack, none during the delay
    # burn, so 9 899 s remained at eviction
    assert victim.finish_s == pytest.approx(
        victim.resume_s + restore_s + debt + 9_899.0)


def test_infeasible_heavy_victim_does_not_mask_feasible_one():
    # victim A (priority 0, ~1 TiB resident) is scanned first, but its
    # ~1.1 s save drain alone would blow the ~0.6 s deadline slack; the
    # probe must fall through to victim B (priority 1, ~144 GiB,
    # ~0.15 s save) instead of abandoning the pod
    jobs = [
        Job(job_id=0, kind=BATCH, arch="qwen3-32b", shape="decode_32k",
            arrival_s=0.0, steps=1, profile="8s.128c",
            duration_s=10_000.0, u_compute=0.05, priority=0),
        Job(job_id=1, kind=BATCH, arch="gpt2-124m", shape="decode_32k",
            arrival_s=0.0, steps=1, profile="8s.128c",
            duration_s=10_000.0, u_compute=0.05, priority=1),
        Job(job_id=2, kind=TRAINING, arch="qwen3-32b", shape="train_4k",
            arrival_s=10.0, steps=1, profile="8s.128c", duration_s=400.0,
            u_compute=0.3, slo_factor=1.0015, priority=2),
    ]
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             priorities=True)
    records, metrics = sched.run(jobs)
    heavy = next(r for r in records if r.job.job_id == 0)
    light = next(r for r in records if r.job.job_id == 1)
    deadline_job = next(r for r in records if r.job.job_id == 2)
    # without the per-victim check the probe dies on A and the deadline
    # job queues to a miss; with it, B is evicted and the SLO holds
    assert light.preemptions == 1
    assert deadline_job.place_s == pytest.approx(10.0)
    assert deadline_job.finished
    assert deadline_job.finish_s <= deadline_job.deadline_s
    # bonus cascade, by priority design: the resumed B (priority 1)
    # immediately reclaims chips from A (priority 0) — its own slack is
    # huge, so evicting the heavy victim is legal for *it*
    assert heavy.preemptions == 1 and light.resumes == 1
    assert metrics.preemptions == 2 and metrics.resumes == 2
    assert metrics.completed == 3


def test_drain_survives_nested_resume_of_suspended_victim():
    # the hard case: a deadline job D queues at t=5 (power gate), victim
    # Y is checkpoint-evicted at t=10 by another arrival, and at t=50 a
    # completion lets D preempt victim Z mid-drain — the nested rescue
    # resumes Y while the drain sweep still holds it in its snapshot.
    # The sweep must not place Y a second time (double-admit crash).
    def tj(jid, prof, dur, u, prio, arrive=0.0, arch="llama3-8b"):
        return Job(job_id=jid, kind=TRAINING, arch=arch, shape="train_4k",
                   arrival_s=arrive, steps=1, profile=prof, duration_s=dur,
                   u_compute=u, priority=prio, slo_factor=1000.0)
    jobs = [
        Job(job_id=0, kind=BATCH, arch="llama3-8b", shape="decode_32k",
            arrival_s=0.0, steps=1, profile="4s.64c", duration_s=10_000.0,
            u_compute=0.05, priority=0),                       # Z
        tj(1, "4s.64c", 10_000.0, 1.0, 1),                     # holder
        tj(2, "2s.32c", 50.0, 1.0, 1),                         # short C
        Job(job_id=3, kind=BATCH, arch="gpt2-124m", shape="decode_32k",
            arrival_s=0.0, steps=1, profile="1s.16c", duration_s=10_000.0,
            u_compute=0.05, priority=0),                       # Y
        tj(4, "1s.16c", 10_000.0, 1.0, 1),
        tj(5, "1s.16c", 10_000.0, 1.0, 1),
        tj(6, "1s.16c", 10_000.0, 1.0, 1),
        tj(7, "1s.16c", 10_000.0, 1.0, 1),
        tj(8, "1s.16c", 10_000.0, 1.0, 1),                     # pod full
        tj(9, "4s.64c", 200.0, 1.0, 2, arrive=5.0),            # D (blocked)
        tj(10, "1s.16c", 200.0, 0.05, 2, arrive=10.0),         # evicts Y
    ]
    sched = ClusterScheduler(n_pods=1, policy="first_fit",
                             priorities=True, min_throttle=0.9)
    records, metrics = sched.run(jobs)     # must not raise
    y = next(r for r in records if r.job.job_id == 3)
    z = next(r for r in records if r.job.job_id == 0)
    d = next(r for r in records if r.job.job_id == 9)
    assert metrics.preemptions == 2 and metrics.resumes == 2
    assert y.preemptions == 1 and y.resumes == 1
    assert z.preemptions == 1 and z.resumes == 1
    # Y was resumed by D's mid-drain preempt, in the same event
    assert d.place_s == pytest.approx(50.0)
    assert y.resume_s == pytest.approx(50.0)
    assert metrics.completed == len(jobs)
    sched.pods[0].partitioner.validate()


def test_select_cheapest_comparator():
    from repro.cluster.actions import Action, ActionOutcome

    class _Opt(Action):
        def __init__(self, kind, cost, vid, feasible=True):
            super().__init__(None)
            self.kind = kind
            self._vid = vid
            self.outcome = ActionOutcome(feasible, cost_s=cost)

        @property
        def victim_id(self):
            return self._vid

    assert select_cheapest([]) is None
    assert select_cheapest([None, None]) is None
    mk = _Opt
    a, b = mk("preempt", 1.0, 7), mk("shrink", 2.0, 3)
    assert select_cheapest([a, b]) is a          # cheapest wins
    c, d = mk("preempt", 1.0, 7), mk("shrink", 1.0, 3)
    assert select_cheapest([c, d]) is d          # tie -> least disruptive
    e, f = mk("shrink", 1.0, 9), mk("shrink", 1.0, 3)
    assert select_cheapest([e, f]) is f          # then lowest victim id
    g, h = mk("migrate", 1.0, 1), mk("preempt", 1.0, 1)
    assert select_cheapest([g, h]) is g          # migrate beats preempt
    i = mk("shrink", 0.1, 1, feasible=False)
    assert select_cheapest([i, a]) is a          # infeasible filtered out


def test_priorities_off_reproduces_trace0_golden():
    # the full golden check lives in test_trace0_golden_progress_engine;
    # this pins the flag semantics — priorities/grow default OFF and
    # change nothing
    m_default = ClusterScheduler(n_pods=1, policy="frag_repack").run(
        trace0())[1]
    records, m_flags = ClusterScheduler(n_pods=1, policy="frag_repack",
                                        priorities=False,
                                        grow=False).run(trace0())
    assert m_flags == m_default
    _assert_trace0_golden(records, m_flags)


# ---------------------------------------------------------------------------
# elastic grow (extend(): absorb freed neighbour chips)
# ---------------------------------------------------------------------------
def _run_grow(grow):
    sched = ClusterScheduler(n_pods=1, policy="frag_repack", grow=grow)
    records, metrics = sched.run(grow_showcase())
    job = next(r for r in records if r.job.job_id == 0)
    return sched, metrics, job


def test_grow_absorbs_freed_neighbors_and_improves_finish():
    _, m_off, base = _run_grow(False)
    sched, m_on, grown = _run_grow(True)
    assert m_off.grows == 0 and not base.grown
    assert m_on.grows == 1 and grown.grown
    assert grown.profile_name == "8s.128c"      # 4s.64c extended in place
    assert grown.finish_s < base.finish_s       # projected finish improved
    # priced symmetrically to shrink: resident state over the host links
    assert m_on.migrated_bytes > 0
    assert m_on.migration_s == pytest.approx(
        m_on.migrated_bytes / sched._pod_host_bw)
    sched.pods[0].partitioner.validate()


def test_grow_respects_power_gate():
    # the 16x16 grow (256 chips at u=1.0) would throttle below the default
    # 0.8 gate, so the scheduler settles for 8s.128c; with the gate
    # dropped it takes the full pod
    _, _, job = _run_grow(True)
    assert job.profile_name == "8s.128c"
    sched = ClusterScheduler(n_pods=1, policy="frag_repack", grow=True,
                             min_throttle=0.0)
    records, metrics = sched.run(grow_showcase())
    job = next(r for r in records if r.job.job_id == 0)
    assert job.profile_name == "16s.256c" and metrics.grows == 1


def test_grow_projected_finish_improves_in_finish_times():
    # drive the simulator directly: the re-solved projection after a grow
    # resize moves the job's entry in finish_times earlier
    from repro.core.hw import V5E_POD as pod
    from repro.core.perfmodel import PodSimulator
    sim = PodSimulator(pod)
    sim.admit(0, 64, 0.9, 4.0, 100, 0.0)
    sim.advance(40.0)
    before = sim.finish_times(40.0)[0]
    sim.resize(0, 128, 0.9, 2.0)    # grown: twice the chips, half the step
    after = sim.finish_times(40.0)[0]
    assert after < before


def test_queued_jobs_have_first_claim_over_grow():
    # fill the bottom half so an arrival queues; when the short neighbour
    # frees its rectangle the *queued* job takes it — the running job may
    # only grow into it after that tenant also completes
    jobs = grow_showcase() + [
        Job(job_id=2, kind=TRAINING, arch="llama3-8b", shape="train_4k",
            arrival_s=10.0, steps=1, profile="4s.64c", duration_s=500.0,
            u_compute=0.3, priority=1),
        Job(job_id=3, kind=TRAINING, arch="llama3-8b", shape="train_4k",
            arrival_s=0.0, steps=1, profile="8s.128c", duration_s=5000.0,
            u_compute=0.3, priority=1)]
    sched = ClusterScheduler(n_pods=1, policy="frag_repack", grow=True)
    records, metrics = sched.run(jobs)
    queued = next(r for r in records if r.job.job_id == 2)
    grower = next(r for r in records if r.job.job_id == 0)
    # the freed 8x8 went to the queued job at t=50, not to the grower ...
    assert queued.place_s == pytest.approx(50.0)
    # ... which grows only at t=550 when that tenant finishes: well after
    # the ~1026 s finish an immediate t=50 grow would have produced
    assert metrics.grows == 1 and grower.grown
    assert grower.profile_name == "8s.128c"
    assert grower.finish_s > 1200.0


# ---------------------------------------------------------------------------
# Action API surface: PolicySpec, deprecation shims, exports
# ---------------------------------------------------------------------------
def test_policy_spec_validates_and_canonicalizes():
    spec = PolicySpec(actions=("preempt", "shrink", "shrink"))
    assert spec.actions == ("shrink", "preempt")   # canonical order, deduped
    assert spec.enabled("shrink") and not spec.enabled("grow")
    with pytest.raises(ValueError):
        PolicySpec(actions=("evict",))
    with pytest.raises(ValueError):
        PolicySpec(selector="optimal")
    assert parse_actions("grow, migrate") == ("grow", "migrate")
    assert parse_actions("") == ()
    with pytest.raises(ValueError):
        parse_actions("shrink,teleport")


def test_policy_spec_from_flags_matches_booleans():
    assert PolicySpec.from_flags() == PolicySpec()
    assert PolicySpec.from_flags(elastic=True, priorities=True) == \
        PolicySpec(actions=("shrink", "preempt"))
    assert PolicySpec.from_flags(grow=True).actions == ("grow",)


def test_deprecated_booleans_warn_and_map_to_spec():
    with pytest.warns(DeprecationWarning):
        sched = ClusterScheduler(n_pods=1, elastic=True, priorities=True)
    assert sched.spec == PolicySpec(actions=("shrink", "preempt"))
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError):   # both surfaces at once is an error
            ClusterScheduler(n_pods=1, elastic=True,
                             spec=PolicySpec(actions=("shrink",)))
    # the new surface alone is warning-free
    import warnings as _warnings
    with _warnings.catch_warnings():
        _warnings.simplefilter("error", DeprecationWarning)
        ClusterScheduler(n_pods=1, spec=PolicySpec(actions=("shrink",)))


def test_star_import_clean_under_deprecation_errors():
    # the satellite contract: the re-exported surface itself must not
    # touch any deprecated path at import time
    proc = subprocess.run(
        [sys.executable, "-W", "error::DeprecationWarning", "-c",
         "from repro.cluster import *"],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


def test_boolean_shim_equivalent_to_spec_on_showcases():
    with pytest.warns(DeprecationWarning):
        shim = ClusterScheduler(n_pods=1, policy="frag_repack",
                                horizon_s=3000.0, elastic=True)
    m_shim = shim.run(elastic_showcase())[1]
    m_spec = ClusterScheduler(
        n_pods=1, policy="frag_repack", horizon_s=3000.0,
        spec=PolicySpec(actions=("shrink",))).run(elastic_showcase())[1]
    assert m_shim == m_spec


def test_trace0_golden_identical_under_equivalent_policy_spec():
    # the trace-0 golden holds for BOTH compat surfaces: the defaults
    # (test_trace0_golden_progress_engine) and the explicit empty
    # PolicySpec
    records, m = ClusterScheduler(n_pods=1, policy="frag_repack",
                                  spec=PolicySpec()).run(trace0())
    _assert_trace0_golden(records, m)


def test_rescue_selection_not_hardcoded_in_scheduler():
    # the acceptance grep: all rescue selection lives in actions.py/policies
    import inspect
    from repro.cluster import scheduler as sched_mod
    src = inspect.getsource(sched_mod)
    for pattern in ("if self.elastic", "if self.priorities", "if self.grow"):
        assert pattern not in src


# ---------------------------------------------------------------------------
# cross-pod migration (MigrateAcrossPods: DCN-priced relocation)
# ---------------------------------------------------------------------------
def _run_migration(migrate):
    spec = PolicySpec(actions=("shrink", "preempt", "migrate") if migrate
                      else ("shrink", "preempt"))
    sched = ClusterScheduler(n_pods=2, policy="frag_repack", spec=spec)
    records, metrics = sched.run(migration_showcase())
    deadline_job = next(r for r in records if r.job.job_id == 3)
    victim = next(r for r in records if r.job.job_id == 0)
    return sched, metrics, deadline_job, victim


def test_without_migrate_deadline_job_misses_slo():
    # the load imbalance: pod 1's free half is power-blocked for the hot
    # arrival, pod 0 is full, and every holder is a training job — no
    # shrink/preempt victim exists, so greedy in-pod rescues all fail
    _, metrics, deadline_job, victim = _run_migration(False)
    assert metrics.migrations == 0 and metrics.preemptions == 0
    assert metrics.shrinks == 0
    assert metrics.power_deferrals == 1
    assert deadline_job.place_s == pytest.approx(10_000.0)  # waited out
    assert deadline_job.finish_s > deadline_job.deadline_s
    assert victim.pod_idx == 0 and victim.migrations == 0


def test_migrate_turns_slo_miss_into_hit():
    sched, metrics, deadline_job, victim = _run_migration(True)
    assert metrics.migrations == 1 and metrics.power_deferrals == 0
    assert metrics.preemptions == 0 and metrics.shrinks == 0
    # the cold victim relocated to the hot pod; the hot arrival took its
    # drained rectangle on the cold pod — hot/cold balanced per pod
    assert victim.pod_idx == 1 and victim.migrations == 1
    assert victim.migrate_s == pytest.approx(10.0)
    assert deadline_job.pod_idx == 0
    assert deadline_job.place_s == pytest.approx(10.0)
    assert deadline_job.finished
    assert deadline_job.finish_s <= deadline_job.deadline_s
    for pod in sched.pods:
        pod.partitioner.validate()


def test_migrate_priced_over_dcn_not_host_links():
    sched, metrics, deadline_job, victim = _run_migration(True)
    # the DCN term: volume = the victim's resident bytes, once across the
    # fabric; save_s = restore_s = bytes / PodSpec.dcn_bw
    assert metrics.dcn_migrated_bytes == victim.dcn_bytes > 0
    assert sched._dcn_bw == V5E_POD.dcn_bw
    assert V5E_POD.dcn_bw == pytest.approx(32 * 12.5e9)
    save_s = metrics.dcn_migrated_bytes / sched._dcn_bw
    assert metrics.dcn_migration_s == pytest.approx(2 * save_s)
    assert victim.dcn_delay_s == pytest.approx(2 * save_s)
    # the beneficiary starts after the victim's state drained (save_s)
    assert deadline_job.finish_s == pytest.approx(
        10.0 + save_s + deadline_job.job.duration_s)
    # the victim never suspended: it pays save+restore plus nothing else
    assert victim.preemptions == 0 and victim.suspended is None
    assert victim.finish_s == pytest.approx(
        10.0 + 2 * save_s + (victim.job.duration_s - 10.0))
    # in-pod migration counters stay untouched — different price basis
    assert metrics.migrated_bytes == 0 and metrics.migration_s == 0.0
    # DCN is meaningfully slower than the pod's aggregate host links
    assert V5E_POD.dcn_bw < sched._pod_host_bw


def test_migrate_requires_strictly_lower_priority():
    from dataclasses import replace
    jobs = [j if j.job_id != 0 else replace(j, priority=2)
            for j in migration_showcase()]
    jobs = [j if j.job_id != 2 else replace(j, priority=2) for j in jobs]
    sched = ClusterScheduler(n_pods=2, policy="frag_repack",
                             spec=PolicySpec(actions=("migrate",)))
    records, metrics = sched.run(jobs)
    assert metrics.migrations == 0
    deadline_job = next(r for r in records if r.job.job_id == 3)
    assert deadline_job.finish_s > deadline_job.deadline_s


def test_migrate_needs_two_pods():
    # the same stream collapsed onto one pod can never migrate
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             spec=PolicySpec(actions=("migrate",)))
    _, metrics = sched.run(lookahead_showcase())
    assert metrics.migrations == 0


def test_migrated_progress_job_keeps_work_done():
    # a progress-based victim (steps, not pinned duration) must carry its
    # nominal work across the pods: total wall = nominal + save/restore
    from repro.cluster.trace import _steps_for
    jobs = migration_showcase()
    victim_steps = _steps_for("llama3-8b", "train_4k", "8s.128c", 10_000.0)
    from dataclasses import replace
    jobs[0] = replace(jobs[0], duration_s=None, steps=victim_steps,
                      u_compute=0.2)
    sched = ClusterScheduler(n_pods=2, policy="frag_repack",
                             spec=PolicySpec(actions=("migrate",)))
    records, metrics = sched.run(jobs)
    victim = next(r for r in records if r.job.job_id == 0)
    assert metrics.migrations == 1 and victim.finished
    nominal = victim.job.steps * victim.step_time_s
    assert victim.finish_s == pytest.approx(
        victim.job.arrival_s + nominal + victim.dcn_delay_s)


# ---------------------------------------------------------------------------
# look-ahead policy (two-action chains)
# ---------------------------------------------------------------------------
def _run_lookahead(selector):
    spec = PolicySpec(selector=selector, actions=("shrink", "preempt"))
    sched = ClusterScheduler(n_pods=1, policy="frag_repack", spec=spec)
    records, metrics = sched.run(lookahead_showcase())
    deadline_job = next(r for r in records if r.job.job_id == 3)
    return sched, metrics, records, deadline_job


def test_greedy_cannot_rescue_two_blocker_trace():
    # evicting either 8x8 batch job alone mints no 8x16 origin
    _, metrics, _, deadline_job = _run_lookahead("greedy")
    assert metrics.preemptions == 0 and metrics.shrinks == 0
    assert deadline_job.place_s > deadline_job.deadline_s


def test_lookahead_chains_two_evictions_and_hits_slo():
    sched, metrics, records, deadline_job = _run_lookahead("lookahead")
    assert metrics.preemptions == 2 and metrics.resumes == 2
    assert deadline_job.place_s == pytest.approx(10.0)
    assert deadline_job.finished
    assert deadline_job.finish_s <= deadline_job.deadline_s
    # both victims were evicted, later resumed, and completed
    for vid in (0, 1):
        victim = next(r for r in records if r.job.job_id == vid)
        assert victim.preemptions == 1 and victim.resumes == 1
        assert victim.finished
    # BOTH checkpoint drains delay the beneficiary (save of each victim)
    v0 = next(r for r in records if r.job.job_id == 0)
    v1 = next(r for r in records if r.job.job_id == 1)
    save_each = v0.checkpoint_bytes / 2 / sched._pod_host_bw
    assert v0.checkpoint_bytes == v1.checkpoint_bytes
    assert deadline_job.finish_s == pytest.approx(
        10.0 + 2 * save_each + deadline_job.job.duration_s)
    assert metrics.completed == 4
    sched.pods[0].partitioner.validate()


def test_lookahead_rollback_leaves_no_trace_when_chain_fails():
    # deadline slack (~0.2 s) above ONE checkpoint drain (~0.15 s) but
    # below two: each enabler trial-applies, its closer fails the SLO
    # check, and the rollback must leave the run indistinguishable from
    # the greedy one
    from dataclasses import replace
    jobs = [j if j.job_id != 3 else replace(j, slo_factor=1.0005)
            for j in lookahead_showcase()]
    m_greedy = ClusterScheduler(
        n_pods=1, policy="frag_repack",
        spec=PolicySpec(selector="greedy",
                        actions=("shrink", "preempt"))).run(jobs)[1]
    m_look = ClusterScheduler(
        n_pods=1, policy="frag_repack",
        spec=PolicySpec(selector="lookahead",
                        actions=("shrink", "preempt"))).run(jobs)[1]
    assert m_look.preemptions == 0 and m_look.resumes == 0
    assert m_look == m_greedy


def test_lookahead_single_action_path_matches_greedy():
    # when one action suffices, the look-ahead must commit exactly the
    # greedy plan (its chaining only engages on greedy failure)
    m_greedy = ClusterScheduler(
        n_pods=1, policy="frag_repack",
        spec=PolicySpec(selector="greedy",
                        actions=("shrink", "preempt"))).run(
        preemption_showcase())[1]
    m_look = ClusterScheduler(
        n_pods=1, policy="frag_repack",
        spec=PolicySpec(selector="lookahead",
                        actions=("shrink", "preempt"))).run(
        preemption_showcase())[1]
    assert m_greedy == m_look
    assert m_look.preemptions == 1


def test_lookahead_chains_grow_after_preempt():
    # a single preempt rescues the arrival; with the look-ahead policy a
    # running neighbour absorbs the leftover free rectangle in the same
    # event instead of waiting for the next completion
    from repro.cluster.trace import _steps_for
    jobs = [
        Job(job_id=0, kind=TRAINING, arch="llama3-8b", shape="train_4k",
            arrival_s=0.0, profile="4s.64c", u_compute=0.3, priority=1,
            steps=_steps_for("llama3-8b", "train_4k", "4s.64c", 2_000.0)),
        Job(job_id=1, kind=BATCH, arch="gpt2-124m", shape="decode_32k",
            arrival_s=0.0, steps=1, profile="8s.128c",
            duration_s=10_000.0, u_compute=0.05, priority=0),
        Job(job_id=2, kind=TRAINING, arch="llama3-8b", shape="train_4k",
            arrival_s=10.0, steps=1, profile="8s.128c", duration_s=400.0,
            u_compute=0.3, priority=2, slo_factor=2.0),
    ]
    finishes = {}
    for selector in ("greedy", "lookahead"):
        sched = ClusterScheduler(
            n_pods=1, policy="frag_repack",
            spec=PolicySpec(selector=selector,
                            actions=("preempt", "grow")))
        records, metrics = sched.run(jobs)
        grower = next(r for r in records if r.job.job_id == 0)
        assert metrics.preemptions == 1 and metrics.grows == 1
        assert grower.grown and grower.profile_name == "8s.128c"
        finishes[selector] = grower.finish_s
    # the chained grow fires at the rescue (t=10), not at the first
    # completion (t≈410) — the grower finishes strictly earlier
    assert finishes["lookahead"] < finishes["greedy"]


# ---------------------------------------------------------------------------
# live SliceRuntime execution of serving jobs
# ---------------------------------------------------------------------------
def test_serving_jobs_execute_on_live_runtime():
    jobs = [
        Job(0, SERVING, "gpt2-124m", "decode_32k", 0.0, 50, requests=2),
        Job(1, BATCH, "mamba2-130m", "decode_32k", 5.0, 50, u_compute=0.1),
    ]
    sched = ClusterScheduler(n_pods=1, policy="frag_repack",
                             execute_serving=True)
    records, metrics = sched.run(jobs)
    serving = next(r for r in records if r.job.kind == SERVING)
    assert serving.executed and serving.tokens_out > 0
    batch = next(r for r in records if r.job.kind == BATCH)
    assert not batch.executed
    assert metrics.completed == 2
    # tenant removed and rectangle released at completion
    pod = sched.pods[0]
    assert not pod.runtime.tenants
    assert pod.partitioner.free_chips() == V5E_POD.n_chips


# ---------------------------------------------------------------------------
# metrics table at fleet scale (ISSUE 6 small fix)
# ---------------------------------------------------------------------------
def test_format_metrics_separates_thousands_and_stays_aligned():
    from repro.cluster import ClusterMetrics, format_metrics
    m = ClusterMetrics(
        policy="frag_repack", n_jobs=1_269_134, placed=1_234_567,
        completed=1_200_000, left_queued=34_567, still_running=34_567,
        makespan_s=1_196_063.29, mean_queue_delay_s=12.5,
        p95_queue_delay_s=99.9, slo_attainment=0.97,
        chip_hour_utilization=0.55, frag_time_avg=0.123,
        energy_J=4.2e12, energy_per_chip_hour_kJ=1234.5,
        repacks=1_000_001, repack_failures=7, shrinks=2_500_000,
        grows=1_000, preemptions=3_000_000, resumes=2_999_999,
        wasted_checkpoint_chip_s=1e7, migrated_bytes=5 * 2**40,
        migration_s=1e5, migrations=1_234_567,
        dcn_migrated_bytes=2**41, dcn_migration_s=2e5,
        power_deferrals=9_999_999)
    table = format_metrics([m, m])
    lines = table.splitlines()
    # the grid must not misalign once counters run past six digits
    assert len({len(line) for line in lines}) == 1
    assert "1,234,567/1,200,000/34,567" in table
    assert "(+34,567 running at horizon)" in table
    assert "1,000,001/7" in table          # repacks ok/failed
    assert "2,500,000/1,000" in table      # shrinks/grows
    assert "3,000,000/2,999,999" in table  # preemptions/resumes
    assert "1,234,567 moves" in table      # cross-pod DCN migrations
    assert "9,999,999" in table            # power-deferred jobs
