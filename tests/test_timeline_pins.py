"""Pinned end-to-end timelines of the cluster scheduler.

The scale work (free-rectangle index, drain gate, indexed event heap,
undo-log rollback) is pure mechanism: every showcase and the seeded
48-job trace must make the same decisions as when the pins were
recorded. Any drift here means a hot-path rewrite changed a *decision*,
not just its speed.

A pin holds, for each job, its decisions exactly — pod, slice profile,
priced rung, origin, whether it shrank or grew, its preemption and
migration counts — and the run's action counters exactly. Place and
finish times are compared within ``TIME_RTOL`` relative: a regrouped
float sum moves a time by an ulp, a changed decision moves it by far
more. The pins live in ``tests/data/timeline_pins.json``.
"""
import json
import math
from pathlib import Path

import pytest

from repro.cluster import (ClusterScheduler, PolicySpec, TraceConfig,
                           elastic_showcase, fragmentation_showcase,
                           generate_trace, grow_showcase,
                           lookahead_showcase, migration_showcase,
                           preemption_showcase, reconfigure_showcase,
                           search_showcase, twin_showcase)
from repro.core.hw import MI300_POD

PINS_PATH = Path(__file__).parent / "data" / "timeline_pins.json"
TIME_RTOL = 1e-9
ACTION_COUNTERS = ("placed", "completed", "left_queued", "still_running",
                   "repacks", "repack_failures", "shrinks", "grows",
                   "preemptions", "resumes", "migrations", "reconfigs",
                   "power_deferrals")


def trace0():
    """The seeded 48-job trace of the trace-0 goldens."""
    return generate_trace(TraceConfig(seed=0, n_jobs=48,
                                      mean_interarrival_s=5.0))


# name -> (trace factory, ClusterScheduler kwargs)
SHOWCASE_PINS = {
    "fragmentation": (
        fragmentation_showcase,
        dict(n_pods=1, horizon_s=3000.0, spec=PolicySpec())),
    "elastic": (
        elastic_showcase,
        dict(n_pods=1, horizon_s=3000.0,
             spec=PolicySpec(actions=("shrink",)))),
    "preemption": (
        preemption_showcase,
        dict(n_pods=1, spec=PolicySpec(actions=("shrink", "preempt")))),
    "grow": (
        grow_showcase,
        dict(n_pods=1, horizon_s=3000.0, spec=PolicySpec(actions=("grow",)))),
    "migration": (
        migration_showcase,
        dict(n_pods=2, horizon_s=3000.0,
             spec=PolicySpec(actions=("shrink", "preempt", "migrate")))),
    "lookahead": (
        lookahead_showcase,
        dict(n_pods=1, horizon_s=3000.0,
             spec=PolicySpec(selector="lookahead",
                             actions=("shrink", "preempt")))),
    # PR 8: the three-eviction chain only the best-first search commits
    "search": (
        search_showcase,
        dict(n_pods=1,
             spec=PolicySpec(selector="search",
                             actions=("shrink", "preempt")))),
    # PR 9: the twin-offload trace replayed with twin pricing left OFF —
    # the deadline job queues to a miss; the twin-on flip is asserted in
    # test_twin.py. This pin holds the default-off path.
    "twin-off": (
        twin_showcase,
        dict(n_pods=1, spec=PolicySpec(actions=("shrink", "preempt")))),
    # PR 10: the MI300 mode-switch trace replayed with reconfigure OFF —
    # every pod stays pinned in the boot mode (spx-nps1) and the deadline
    # job waits out the tenants to a miss; the reconfigure-on flip is
    # asserted in test_reconfigure.py. This pin holds the mode-less
    # default path.
    "reconfigure-off": (
        reconfigure_showcase,
        dict(n_pods=2, pod=MI300_POD,
             spec=PolicySpec(actions=("migrate",)))),
}
TRACE0_POLICIES = ("frag", "frag_repack")
PINS = {**{name: (fn, dict(policy="frag_repack", **kw))
           for name, (fn, kw) in SHOWCASE_PINS.items()},
        **{f"trace0-{p}": (trace0, dict(n_pods=1, policy=p))
           for p in TRACE0_POLICIES}}


def timeline(records, metrics):
    """A run's pin: one row per job (decisions, then place/finish times)
    and the action counters."""
    jobs = [[r.job.job_id, r.pod_idx, r.profile_name, r.rung,
             list(r.origin) if r.origin is not None else None,
             r.shrunk, r.grown, r.preemptions, r.migrations,
             r.place_s, r.finish_s] for r in records]
    return {"jobs": jobs,
            "actions": {k: getattr(metrics, k) for k in ACTION_COUNTERS}}


def _close(got, want):
    if got is None or want is None:
        return got is want
    return math.isclose(got, want, rel_tol=TIME_RTOL)


def assert_pinned(name, records, metrics):
    """Decisions and action counters exact, times within ``TIME_RTOL``."""
    want = json.loads(PINS_PATH.read_text())[name]
    got = timeline(records, metrics)
    assert got["actions"] == want["actions"], (
        f"{name}: action counters drifted — a change altered a decision")
    assert len(got["jobs"]) == len(want["jobs"]), name
    for g, w in zip(got["jobs"], want["jobs"]):
        assert g[:-2] == w[:-2], (
            f"{name}: job {w[0]} decision drifted: {g[:-2]} != {w[:-2]}")
        assert _close(g[-2], w[-2]) and _close(g[-1], w[-1]), (
            f"{name}: job {w[0]} (place, finish) drifted: "
            f"{g[-2:]} != {w[-2:]}")


def run_pin(name):
    trace_fn, kwargs = PINS[name]
    sched = ClusterScheduler(**kwargs)
    records, metrics = sched.run(trace_fn())
    return sched, records, metrics


@pytest.mark.parametrize("name", sorted(SHOWCASE_PINS))
def test_showcase_timeline_pinned(name):
    sched, records, metrics = run_pin(name)
    assert_pinned(name, records, metrics)
    assert not sched._txns   # every recorded trial was closed


@pytest.mark.parametrize("policy", TRACE0_POLICIES)
def test_trace0_timeline_pinned(policy):
    _, records, metrics = run_pin(f"trace0-{policy}")
    assert_pinned(f"trace0-{policy}", records, metrics)
