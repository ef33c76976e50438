"""Cluster scheduling benchmark — placement policies, rescue actions, and
scheduler policies on fixed traces (modeled runs, no live engine).

Rows (CSV: name,us_per_call,derived):
  cluster/showcase.<policy>   the crafted stranding trace (one pod): the
                              8×16 job fits free chips but no rectangle;
                              first_fit leaves it queued at the horizon,
                              frag_repack repacks once and places it
  cluster/showcase.stranded-job  the head-to-head verdict for that job
  cluster/elastic.<on|off>    crafted SLO-rescue trace: shrink flips miss→hit
  cluster/preempt.<on|off>    crafted checkpoint-eviction trace: priorities
                              flip miss→hit where a shrink cannot; the
                              victim resumes with work_done preserved
  cluster/grow.<on|off>       crafted elastic-grow trace: extend() absorbs
                              freed neighbour chips, finish improves
  cluster/migrate.<on|off>    crafted load-imbalanced two-pod trace: only a
                              DCN-priced MigrateAcrossPods meets the
                              deadline (the victim keeps running on the
                              destination pod)
  cluster/lookahead.<policy>  crafted two-blocker trace: no single action
                              rescues the deadline job; the look-ahead's
                              two-eviction chain does (and the search
                              policy matches it)
  cluster/search.<policy>     crafted three-blocker trace: the rescue
                              chain is one action deeper than the
                              two-step look-ahead explores; only the
                              budgeted best-first search finds it
  cluster/twin.<off|on>       crafted twin-offload trace: with twin pricing
                              on, the PerfModel's "+cpuX.XX" rung (spilled
                              KV tail co-executed host-side) lets a shrink
                              rescue a deadline job no plain rung can reach
  cluster/trace0.<policy>     seeded mixed trace (one pod, seed 0, heavy
                              enough that queues form and repack triggers)

Run directly for a custom comparison (the Action-API flags mirror
``repro.launch.cluster``):

    PYTHONPATH=src python -m benchmarks.bench_cluster \
        --policy lookahead --actions shrink,preempt,migrate --pods 2

``--scale N`` switches to the seeded large-trace perf mode (the ISSUE-6
100k-job acceptance run): one deterministic Poisson trace of N jobs
replayed through an 8-pod cluster, reporting jobs/sec, probes/sec and
peak RSS as JSON. ``--json PATH`` additionally writes the record —
``benchmarks/BENCH_cluster.json`` is the committed baseline that
``benchmarks/check_perf.py`` gates CI against:

    PYTHONPATH=src python benchmarks/bench_cluster.py --scale 100000

``--search-scale N`` produces the search-policy companion record
(``benchmarks/BENCH_search.json``): the search showcase suite, one
seeded N-job trace under ``--policy search``, and a look-ahead
probe-cache A/B whose ``probe_drop_ratio`` the CI gate holds at >= 3x.
``--twin-scale N`` produces the twin-offload companion record
(``benchmarks/BENCH_twin.json``): the twin showcase verdicts plus one
seeded N-job trace replayed with twin pricing on, which the CI gate
holds at >= 0.75x the twin-off throughput of the same trace.
``--profile N`` wraps any mode in cProfile and prints the top-N
functions by cumulative time.
"""
from __future__ import annotations

import json
import os
import resource
import sys
import time

if __package__ in (None, ""):   # `python benchmarks/bench_cluster.py`
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_ROOT, os.path.join(_ROOT, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)

from benchmarks.common import emit, timed
from repro.cluster import (ClusterScheduler, PolicySpec, TraceConfig,
                           elastic_showcase, fragmentation_showcase,
                           generate_trace, grow_showcase,
                           lookahead_showcase, migration_showcase,
                           preemption_showcase, search_showcase,
                           twin_showcase)
from repro.cluster.placement import POLICY_NAMES

SHOWCASE_HORIZON_S = 3000.0
STRANDED_JOB_ID = 10
SLO_JOB_ID = 2
PREEMPT_SLO_JOB_ID = 2
PREEMPT_VICTIM_ID = 0
GROW_JOB_ID = 0
MIGRATE_SLO_JOB_ID = 3
MIGRATE_VICTIM_ID = 0
LOOKAHEAD_SLO_JOB_ID = 3
SEARCH_SLO_JOB_ID = 3
TWIN_SLO_JOB_ID = 4
TWIN_VICTIM_ID = 2


def _run(policy: str, jobs, n_pods: int, horizon=None, **kw):
    sched = ClusterScheduler(n_pods=n_pods, policy=policy, horizon_s=horizon,
                             **kw)
    with timed() as t:
        records, metrics = sched.run(jobs)
    return records, metrics, t["us"]


def _slo_verdict(records, job_id):
    rec = next(r for r in records if r.job.job_id == job_id)
    return rec, (rec.finished and rec.finish_s <= rec.deadline_s)


def run() -> None:
    # crafted stranding trace: same jobs under every policy
    jobs = fragmentation_showcase()
    verdicts = {}
    for policy in POLICY_NAMES:
        records, m, us = _run(policy, jobs, n_pods=1,
                              horizon=SHOWCASE_HORIZON_S)
        big = next(r for r in records if r.job.job_id == STRANDED_JOB_ID)
        verdicts[policy] = big
        emit(f"cluster/showcase.{policy}", us,
             f"placed={m.placed}/{m.n_jobs} queued={m.left_queued} "
             f"repacks={m.repacks} migrated_gib={m.migrated_bytes / 2**30:.1f} "
             f"frag_avg={m.frag_time_avg:.3f}")
    ff, rp = verdicts["first_fit"], verdicts["frag_repack"]
    emit("cluster/showcase.stranded-job", 0.0,
         f"first_fit={'queued' if not ff.placed else 'placed'} "
         f"frag_repack={'placed@t=' + format(rp.place_s, '.0f') if rp.placed else 'queued'}")

    # elastic SLO rescue: the same crafted trace with and without shrink
    for elastic in (False, True):
        spec = PolicySpec(actions=("shrink",) if elastic else ())
        records, m, us = _run("frag_repack", elastic_showcase(), n_pods=1,
                              horizon=SHOWCASE_HORIZON_S, spec=spec)
        _, hit = _slo_verdict(records, SLO_JOB_ID)
        emit(f"cluster/elastic.{'on' if elastic else 'off'}", us,
             f"slo_job={'hit' if hit else 'miss'} shrinks={m.shrinks} "
             f"slo={m.slo_attainment:.2f} "
             f"migrated_gib={m.migrated_bytes / 2**30:.1f}")

    # checkpoint preemption: priorities flip the deadline job's SLO verdict
    # on the same crafted trace (a shrink cannot mint the 8x16 origin);
    # the evicted batch job resumes from its checkpoint and completes
    for priorities in (False, True):
        spec = PolicySpec(actions=("shrink", "preempt") if priorities
                          else ("shrink",))
        records, m, us = _run("frag_repack", preemption_showcase(), n_pods=1,
                              spec=spec)
        victim = next(r for r in records if r.job.job_id == PREEMPT_VICTIM_ID)
        _, hit = _slo_verdict(records, PREEMPT_SLO_JOB_ID)
        if priorities:   # the showcase contract, asserted end-to-end
            assert hit and m.preemptions == 1 and m.resumes == 1
            assert victim.finished and victim.resumes == 1
        else:
            assert not hit and m.preemptions == 0
        emit(f"cluster/preempt.{'on' if priorities else 'off'}", us,
             f"slo_job={'hit' if hit else 'miss'} "
             f"preemptions={m.preemptions} resumes={m.resumes} "
             f"wasted_ckpt_chip_s={m.wasted_checkpoint_chip_s:.1f} "
             f"victim_ckpt_delay_s={victim.checkpoint_delay_s:.2f}")

    # elastic grow: a running job absorbs the chips a short neighbour
    # frees, via the partitioner's extend() — projected finish improves
    finishes = {}
    for grow in (False, True):
        spec = PolicySpec(actions=("grow",) if grow else ())
        records, m, us = _run("frag_repack", grow_showcase(), n_pods=1,
                              spec=spec)
        job = next(r for r in records if r.job.job_id == GROW_JOB_ID)
        finishes[grow] = job.finish_s
        if grow:
            assert m.grows == 1 and job.grown
            assert finishes[True] < finishes[False]   # finish improved
        emit(f"cluster/grow.{'on' if grow else 'off'}", us,
             f"job0_profile={job.profile_name} finish={job.finish_s:.0f}s "
             f"grows={m.grows} migrated_gib={m.migrated_bytes / 2**30:.1f}")

    # cross-pod migration: on the load-imbalanced two-pod trace every
    # in-pod rescue fails (training holders are never shrunk/evicted, the
    # only free rectangle is power-blocked); relocating the cold holder
    # over the DCN re-balances the pods and flips the SLO verdict
    for migrate in (False, True):
        spec = PolicySpec(actions=("shrink", "preempt", "migrate")
                          if migrate else ("shrink", "preempt"))
        records, m, us = _run("frag_repack", migration_showcase(), n_pods=2,
                              spec=spec)
        victim = next(r for r in records if r.job.job_id == MIGRATE_VICTIM_ID)
        _, hit = _slo_verdict(records, MIGRATE_SLO_JOB_ID)
        if migrate:   # the showcase contract, asserted end-to-end
            assert hit and m.migrations == 1
            assert victim.migrations == 1 and victim.pod_idx == 1
            assert victim.finished and not victim.preemptions
            assert m.dcn_migrated_bytes == victim.dcn_bytes > 0
        else:
            assert not hit and m.migrations == 0
        emit(f"cluster/migrate.{'on' if migrate else 'off'}", us,
             f"slo_job={'hit' if hit else 'miss'} migrations={m.migrations} "
             f"dcn_gib={m.dcn_migrated_bytes / 2**30:.1f} "
             f"dcn_s={m.dcn_migration_s:.2f} "
             f"power_deferrals={m.power_deferrals}")

    # look-ahead selection: no single action mints the 8x16 origin (each
    # eviction frees one 8x8), so greedy queues the job to a miss; the
    # look-ahead chains two evictions and commits the pair — and the
    # best-first search finds the same chain without pricing extra probes
    for selector in ("greedy", "lookahead", "search"):
        spec = PolicySpec(selector=selector, actions=("shrink", "preempt"))
        records, m, us = _run("frag_repack", lookahead_showcase(), n_pods=1,
                              spec=spec)
        _, hit = _slo_verdict(records, LOOKAHEAD_SLO_JOB_ID)
        if selector == "greedy":
            assert not hit and m.preemptions == 0
        else:   # the showcase contract: both chain policies commit the pair
            assert hit and m.preemptions == 2 and m.resumes == 2
        emit(f"cluster/lookahead.{selector}", us,
             f"slo_job={'hit' if hit else 'miss'} "
             f"preemptions={m.preemptions} resumes={m.resumes} "
             f"probes_priced={m.rescue_probes_priced} "
             f"completed={m.completed}")

    # best-first search: freeing the 16x16 origin takes *three* evictions
    # (two enablers + the closing preempt), one deeper than the two-step
    # look-ahead explores; only the budgeted search commits the chain
    for selector in ("greedy", "lookahead", "search"):
        spec = PolicySpec(selector=selector, actions=("shrink", "preempt"))
        records, m, us = _run("frag_repack", search_showcase(), n_pods=1,
                              spec=spec)
        _, hit = _slo_verdict(records, SEARCH_SLO_JOB_ID)
        if selector == "search":   # the showcase contract
            assert hit and m.preemptions == 3 and m.resumes == 3
        else:
            assert not hit and m.preemptions == 0
        emit(f"cluster/search.{selector}", us,
             f"slo_job={'hit' if hit else 'miss'} "
             f"preemptions={m.preemptions} resumes={m.resumes} "
             f"probes_priced={m.rescue_probes_priced} "
             f"cache_hits={m.probe_cache_hits}")

    # twin-offload co-execution: the same crafted trace with twin pricing
    # off and on — same shrink/preempt allowlist both times, so the only
    # difference is whether the PerfModel emits the "+cpuX.XX" rung that
    # makes the minted 4x4 hole fast enough for the deadline
    for twin in (False, True):
        spec = PolicySpec(actions=("shrink", "preempt"))
        records, m, us = _run("frag_repack", twin_showcase(), n_pods=1,
                              spec=spec, twin=twin)
        rec, hit = _slo_verdict(records, TWIN_SLO_JOB_ID)
        victim = next(r for r in records if r.job.job_id == TWIN_VICTIM_ID)
        if twin:   # the showcase contract, asserted end-to-end
            assert hit and m.shrinks == 1 and m.preemptions == 0
            assert rec.rung.startswith("1s.16c+cpu")
            assert victim.shrunk and victim.profile_name == "1s.16c"
        else:
            assert not hit and m.shrinks == 0 and m.preemptions == 0
            assert "+cpu" not in rec.rung
        emit(f"cluster/twin.{'on' if twin else 'off'}", us,
             f"slo_job={'hit' if hit else 'miss'} rung={rec.rung} "
             f"shrinks={m.shrinks} slo={m.slo_attainment:.2f} "
             f"queue_s={rec.place_s - rec.job.arrival_s:.0f}")

    # seeded mixed trace, heavier than the CLI default so queues form;
    # every admission/completion re-solves the shared-cap throttle
    trace = generate_trace(TraceConfig(seed=0, n_jobs=48,
                                       mean_interarrival_s=5.0))
    for policy in POLICY_NAMES:
        _, m, us = _run(policy, trace, n_pods=1)
        emit(f"cluster/trace0.{policy}", us,
             f"makespan={m.makespan_s:.0f}s slo={m.slo_attainment:.2f} "
             f"util={m.chip_hour_utilization:.2f} "
             f"queue_p95={m.p95_queue_delay_s:.0f}s "
             f"energy_MJ={m.energy_J / 1e6:.0f} repacks={m.repacks} "
             f"power_deferrals={m.power_deferrals}")


# the committed-baseline regime: 8 pods keep a 12s-interarrival Poisson
# stream busy without collapsing into one unbounded queue, so throughput
# measures the scheduler hot path, not a pathological backlog
SCALE_PODS = 8
SCALE_INTERARRIVAL_S = 12.0


def run_scale(scale: int, *, pods: int = SCALE_PODS,
              mean_interarrival_s: float = SCALE_INTERARRIVAL_S,
              seed: int = 0, spec: PolicySpec = PolicySpec(),
              placement: str = "frag_repack",
              probe_cache: bool = True, twin: bool = False) -> dict:
    """Seeded large-trace perf mode: one deterministic N-job Poisson trace
    replayed end-to-end, returning the JSON perf-baseline record
    (jobs/sec, probes/sec, peak RSS). Pure function of its arguments —
    the committed ``BENCH_cluster.json`` and ``check_perf.py``'s fresh
    run replay the identical stream, so makespan/completed must match
    exactly and only the timings may differ."""
    t0 = time.perf_counter()
    trace = generate_trace(TraceConfig(
        seed=seed, n_jobs=scale, mean_interarrival_s=mean_interarrival_s))
    gen_s = time.perf_counter() - t0
    sched = ClusterScheduler(n_pods=pods, policy=placement, spec=spec,
                             probe_cache=probe_cache, twin=twin)
    t0 = time.perf_counter()
    records, metrics = sched.run(trace)
    wall_s = time.perf_counter() - t0
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    peak_rss_mb = rss / (1024.0 if sys.platform != "darwin" else 1024.0 ** 2)
    return {
        "bench": "cluster.scale",
        "scale": scale,
        "pods": pods,
        "mean_interarrival_s": mean_interarrival_s,
        "seed": seed,
        "placement": placement,
        "gen_s": round(gen_s, 3),
        "wall_s": round(wall_s, 3),
        "jobs_per_s": round(scale / wall_s, 1),
        "probes": sched._probes,
        "probes_per_s": round(sched._probes / wall_s, 1),
        "probes_priced": metrics.rescue_probes_priced,
        "probe_cache_hits": metrics.probe_cache_hits,
        "peak_rss_mb": round(peak_rss_mb, 1),
        "completed": metrics.completed,
        "makespan_s": metrics.makespan_s,
    }


# the search-policy companion regime: 4 pods under the same 12s Poisson
# stream stay loaded enough that deadline jobs actually trigger rescue
# scans (8 pods never do), yet queues stay transient — so probes_priced
# is a real hot-path signal rather than a backlog pathology
SEARCH_PODS = 4
SEARCH_ACTIONS = ("shrink", "preempt", "migrate")


def run_search(scale: int = 10000, *, pods: int = SEARCH_PODS,
               mean_interarrival_s: float = SCALE_INTERARRIVAL_S,
               seed: int = 0) -> dict:
    """The ``BENCH_search.json`` record: the search showcase suite (the
    three-eviction chain only the search policy finds), one seeded
    ``scale``-job trace replayed under ``--policy search``, and a
    look-ahead probe-cache A/B on the same trace whose
    ``probe_drop_ratio`` (uncached / cached probes priced) the CI gate
    holds at >= 3x. Pure function of its arguments: every count and
    timeline field must replay bit-identically; only timings may differ.

    Refreshing after an intentional change:

        PYTHONPATH=src python -m benchmarks.bench_cluster \\
            --search-scale 10000 --json benchmarks/BENCH_search.json
    """
    showcase = {}
    for selector in ("greedy", "lookahead", "search"):
        spec = PolicySpec(selector=selector, actions=("shrink", "preempt"))
        records, m, _ = _run("frag_repack", search_showcase(), n_pods=1,
                             spec=spec)
        _, hit = _slo_verdict(records, SEARCH_SLO_JOB_ID)
        showcase[selector] = {
            "slo_hit": hit,
            "preemptions": m.preemptions,
            "probes_priced": m.rescue_probes_priced,
        }
    search_spec = PolicySpec(selector="search", actions=SEARCH_ACTIONS)
    s_rec = run_scale(scale, pods=pods,
                      mean_interarrival_s=mean_interarrival_s, seed=seed,
                      spec=search_spec)
    la_spec = PolicySpec(selector="lookahead", actions=SEARCH_ACTIONS)
    la_on = run_scale(scale, pods=pods,
                      mean_interarrival_s=mean_interarrival_s, seed=seed,
                      spec=la_spec)
    la_off = run_scale(scale, pods=pods,
                       mean_interarrival_s=mean_interarrival_s, seed=seed,
                       spec=la_spec, probe_cache=False)
    keep = ("wall_s", "jobs_per_s", "probes_priced", "probe_cache_hits",
            "completed", "makespan_s", "peak_rss_mb")
    return {
        "bench": "cluster.search",
        "scale": scale,
        "pods": pods,
        "mean_interarrival_s": mean_interarrival_s,
        "seed": seed,
        "actions": list(SEARCH_ACTIONS),
        "showcase": showcase,
        "search": {k: s_rec[k] for k in keep},
        "lookahead_cache_on": {k: la_on[k] for k in keep},
        "lookahead_cache_off": {k: la_off[k] for k in keep},
        "probe_drop_ratio": round(
            la_off["probes_priced"] / max(1, la_on["probes_priced"]), 2),
    }


def run_twin(scale: int = 10000, *, pods: int = SCALE_PODS,
             mean_interarrival_s: float = SCALE_INTERARRIVAL_S,
             seed: int = 0) -> dict:
    """The ``BENCH_twin.json`` record: the twin showcase verdicts (twin
    pricing off → the deadline job queues past its SLO; on → the shrink
    commits the "+cpuX.XX" rung and the job hits), plus one seeded
    ``scale``-job trace replayed with twin pricing enabled. The showcase
    block and the replay's count/timeline fields are pure functions of
    the arguments and must match the committed record bit-exactly; the
    CI gate additionally holds the twin-on replay's throughput at >=
    0.75x a fresh twin-off replay of the same trace (the extra rungs are
    priced per profile, so scoring cost rises but must stay bounded).

    Refreshing after an intentional change:

        PYTHONPATH=src python -m benchmarks.bench_cluster \\
            --twin-scale 10000 --json benchmarks/BENCH_twin.json
    """
    showcase = {}
    for twin in (False, True):
        spec = PolicySpec(actions=("shrink", "preempt"))
        records, m, _ = _run("frag_repack", twin_showcase(), n_pods=1,
                             spec=spec, twin=twin)
        rec, hit = _slo_verdict(records, TWIN_SLO_JOB_ID)
        victim = next(r for r in records if r.job.job_id == TWIN_VICTIM_ID)
        showcase["on" if twin else "off"] = {
            "slo_hit": hit,
            "rung": rec.rung,
            "queue_s": round(rec.place_s - rec.job.arrival_s, 2),
            "shrinks": m.shrinks,
            "victim_profile": victim.profile_name,
            "slo_attainment": m.slo_attainment,
        }
    on = run_scale(scale, pods=pods,
                   mean_interarrival_s=mean_interarrival_s, seed=seed,
                   twin=True)
    keep = ("wall_s", "jobs_per_s", "probes", "completed", "makespan_s",
            "peak_rss_mb")
    return {
        "bench": "cluster.twin",
        "scale": scale,
        "pods": pods,
        "mean_interarrival_s": mean_interarrival_s,
        "seed": seed,
        "showcase": showcase,
        "twin_on": {k: on[k] for k in keep},
    }


def main() -> None:
    """Custom comparison CLI: schedule one seeded trace under the given
    placement policy and ``PolicySpec`` and print the metrics table;
    ``--scale N`` switches to the large-trace perf mode and
    ``--search-scale N`` to the search-policy companion record instead.
    ``--profile N`` wraps whichever mode runs in cProfile."""
    import argparse

    from repro.cluster import format_metrics
    from repro.launch.cluster import add_policy_args, spec_from_args

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--pods", type=int, default=None,
                    help=f"default 1 (comparison) / {SCALE_PODS} (--scale)")
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--jobs", type=int, default=48)
    ap.add_argument("--mean-interarrival", type=float, default=None,
                    help="default 5.0 (comparison) / "
                         f"{SCALE_INTERARRIVAL_S} (--scale)")
    ap.add_argument("--placement", default="frag_repack",
                    choices=POLICY_NAMES)
    ap.add_argument("--scale", type=int, default=None, metavar="N",
                    help="large-trace perf mode: replay one seeded N-job "
                         "trace and print the JSON baseline record")
    ap.add_argument("--search-scale", type=int, default=None, metavar="N",
                    help="search-policy perf mode: showcase suite + one "
                         "seeded N-job trace under --policy search + a "
                         "look-ahead probe-cache A/B; prints the JSON "
                         "record committed as benchmarks/BENCH_search.json")
    ap.add_argument("--twin-scale", type=int, default=None, metavar="N",
                    help="twin-offload perf mode: the twin showcase "
                         "verdicts + one seeded N-job trace replayed with "
                         "twin pricing on; prints the JSON record "
                         "committed as benchmarks/BENCH_twin.json")
    ap.add_argument("--twin", action="store_true",
                    help="enable twin-offload co-execution pricing in the "
                         "comparison/--scale modes")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="with --scale/--search-scale/--twin-scale: also "
                         "write the record to PATH")
    ap.add_argument("--profile", type=int, default=None, metavar="N",
                    help="run under cProfile and print the top-N "
                         "functions by cumulative time after the output")
    add_policy_args(ap)
    args = ap.parse_args()
    spec = spec_from_args(args)

    def work() -> None:
        if args.scale or args.search_scale or args.twin_scale:
            if args.search_scale:
                rec = run_search(
                    args.search_scale,
                    pods=(args.pods if args.pods is not None
                          else SEARCH_PODS),
                    mean_interarrival_s=(args.mean_interarrival
                                         if args.mean_interarrival
                                         is not None
                                         else SCALE_INTERARRIVAL_S),
                    seed=args.trace_seed)
            elif args.twin_scale:
                rec = run_twin(
                    args.twin_scale,
                    pods=(args.pods if args.pods is not None
                          else SCALE_PODS),
                    mean_interarrival_s=(args.mean_interarrival
                                         if args.mean_interarrival
                                         is not None
                                         else SCALE_INTERARRIVAL_S),
                    seed=args.trace_seed)
            else:
                rec = run_scale(
                    args.scale,
                    pods=args.pods if args.pods is not None else SCALE_PODS,
                    mean_interarrival_s=(args.mean_interarrival
                                         if args.mean_interarrival
                                         is not None
                                         else SCALE_INTERARRIVAL_S),
                    seed=args.trace_seed, spec=spec,
                    placement=args.placement, twin=args.twin)
            out = json.dumps(rec, indent=2)
            print(out)
            if args.json:
                with open(args.json, "w") as fh:
                    fh.write(out + "\n")
            return
        trace = generate_trace(TraceConfig(
            seed=args.trace_seed, n_jobs=args.jobs,
            mean_interarrival_s=(args.mean_interarrival
                                 if args.mean_interarrival is not None
                                 else 5.0)))
        _, metrics, us = _run(
            args.placement, trace,
            n_pods=args.pods if args.pods is not None else 1, spec=spec,
            twin=args.twin)
        print(f"# placement={args.placement} policy={spec.selector} "
              f"actions={','.join(spec.actions) or '-'} "
              f"jobs={len(trace)} sched_us={us:.0f}")
        print(format_metrics([metrics]))

    if args.profile:
        import cProfile
        import pstats
        prof = cProfile.Profile()
        prof.enable()
        try:
            work()
        finally:
            prof.disable()
            pstats.Stats(prof).sort_stats("cumulative").print_stats(
                args.profile)
    else:
        work()


if __name__ == "__main__":
    main()
