"""PerfModel + PodSimulator: memoized scoring, measured-anchor calibration,
progress-based execution (retro-active re-solve, resize, delays), and the
piecewise co-run energy integration in core.power."""
import json

import pytest

from repro.configs import get_config, get_shape
from repro.core.hw import V5E, V5E_POD
from repro.core.perfmodel import (InstanceLoad, PerfModel, PodSimulator,
                                  get_model, load_anchors)
from repro.core.power import co_run, pod_draw, throttle_factor
from repro.core.slices import PROFILES, get_profile
from repro.cluster.trace import TRAINING, Job


# ---------------------------------------------------------------------------
# PerfModel scoring + memoization
# ---------------------------------------------------------------------------
def test_score_matches_direct_roofline():
    perf = PerfModel()
    cfg, shape = get_config("llama3-8b"), get_shape("decode_32k")
    sc = perf.score(cfg, shape, get_profile("4s.64c"))
    assert sc is not None
    from repro.core.workload import WorkloadEstimate
    wl = WorkloadEstimate(cfg, shape)
    plan = wl.plan_for(get_profile("4s.64c"), V5E)
    spilled = plan.offloaded or plan.partial
    terms = wl.roofline_on(get_profile("4s.64c"), V5E,
                           plan if spilled else None)
    assert sc.step_time == terms.step_time
    assert sc.u_compute == pytest.approx(terms.t_compute / terms.step_time)
    assert sc.perf_per_chip > 0 and not sc.calibrated


def test_score_memoized_and_none_for_oversized():
    perf = PerfModel()
    cfg, shape = get_config("llama3-8b"), get_shape("train_4k")
    a = perf.score(cfg, shape, PROFILES[-1])
    assert a is perf.score(cfg, shape, PROFILES[-1])  # same object: memo hit
    # 8B training state (params+grads+adam fp32 ≈ 128 GiB + activations)
    # cannot fit 16 chips even with every offloadable tensor spilled? it can
    # via host DRAM — but some profile/arch combo must be infeasible:
    huge = get_config("qwen2-vl-72b")
    assert perf.score(huge, get_shape("train_4k"), get_profile("1s.16c")) is None


def test_options_smallest_first_and_pin():
    perf = PerfModel()
    free = Job(0, TRAINING, "llama3-8b", "train_4k", 0.0, 10)
    opts = perf.options(free)
    assert len(opts) > 1
    chips = [sc.profile.n_chips for sc in opts]
    assert chips == sorted(chips)
    pinned = Job(0, TRAINING, "llama3-8b", "train_4k", 0.0, 10,
                 profile="4s.64c")
    assert [sc.profile.name for sc in perf.options(pinned)] == ["4s.64c"]
    unpinned = perf.options(pinned, ignore_pin=True)
    assert len(unpinned) == len(opts)
    assert perf.options(pinned) is perf.options(pinned)  # memoized


def test_get_model_shared_instance():
    assert get_model(V5E) is get_model(V5E)


# ---------------------------------------------------------------------------
# measured-anchor calibration
# ---------------------------------------------------------------------------
def _write_anchor(tmp_path, arch, shape, flops_pc, bytes_pc, n_chips):
    d = tmp_path / "single"
    d.mkdir(exist_ok=True)
    rec = {"arch": arch, "shape": shape,
           "roofline": {"n_chips": n_chips,
                        "hlo_flops_per_chip": flops_pc,
                        "hlo_bytes_per_chip": bytes_pc,
                        "step_time_s": 0.5}}
    (d / f"{arch}__{shape}.json").write_text(json.dumps(rec))


def test_anchor_calibration_scales_terms(tmp_path):
    cfg, shape = get_config("gpt2-124m"), get_shape("train_4k")
    base = PerfModel().score(cfg, shape, get_profile("1s.16c"))
    from repro.core.workload import WorkloadEstimate
    wl = WorkloadEstimate(cfg, shape)
    # measured = 2× the analytic FLOPs, 3× the analytic bytes
    _write_anchor(tmp_path, "gpt2-124m", "train_4k",
                  2.0 * wl.flops() / 64, 3.0 * wl.hbm_bytes() / 64, 64)
    perf = PerfModel.from_artifacts(str(tmp_path))
    assert ("gpt2-124m", "train_4k") in perf.anchors
    sc = perf.score(cfg, shape, get_profile("1s.16c"))
    assert sc.calibrated
    assert sc.terms.t_compute == pytest.approx(2.0 * base.terms.t_compute)
    assert sc.terms.t_memory == pytest.approx(3.0 * base.terms.t_memory)
    # collective and host terms are untouched by the anchor
    assert sc.terms.t_collective == base.terms.t_collective
    # other (arch, shape) cells stay analytic
    other = perf.score(get_config("llama3-8b"), shape, get_profile("4s.64c"))
    assert not other.calibrated


def test_load_anchors_missing_and_broken(tmp_path):
    assert load_anchors(str(tmp_path / "nope")) == {}
    d = tmp_path / "single"
    d.mkdir()
    (d / "a__b.json").write_text(json.dumps({"arch": "a", "shape": "b",
                                             "error": "boom"}))
    assert load_anchors(str(tmp_path)) == {}


# ---------------------------------------------------------------------------
# PodSimulator — progress-based execution
# ---------------------------------------------------------------------------
def _sim():
    return PodSimulator(V5E_POD)


def test_single_job_unthrottled_finish():
    sim = _sim()
    fin = sim.admit(0, 128, 1.0, 2.0, 10, 0.0)
    assert fin == pytest.approx(20.0)   # alone: f=1, no stretch
    assert sim.finish_times(0.0)[0] == pytest.approx(20.0)


def test_admission_stretches_and_completion_unstretches():
    sim = _sim()
    sim.admit(0, 128, 1.0, 2.0, 10, 0.0)
    sim.advance(5.0)
    f0 = sim.finish_times(5.0)[0]
    assert f0 == pytest.approx(20.0)
    # second full-power 128-chip instance pushes the pod over the cap
    sim.admit(1, 128, 1.0, 2.0, 10, 5.0)
    f = sim.throttle()
    assert f < 1.0
    stretched = sim.finish_times(5.0)[0]
    assert stretched > f0   # retro-active: in-flight job re-projected later
    # progress during the contended window accrues slower than wall time
    sim.advance(10.0)
    assert sim.jobs[0].work_done == pytest.approx(5.0 + 5.0 * f, rel=1e-9)
    # removing the rival restores full speed for the remainder
    sim.remove(1)
    recovered = sim.finish_times(10.0)[0]
    assert f0 < recovered < stretched


def test_pinned_duration_ignores_throttle():
    sim = _sim()
    fin = sim.admit(0, 128, 1.0, 2.0, 10, 0.0, duration_s=50.0)
    assert fin == pytest.approx(50.0)
    sim.admit(1, 128, 1.0, 2.0, 10, 0.0)
    assert 0 not in sim.finish_times(0.0)  # fixed jobs are never re-projected
    sim.delay(0, 7.0)
    assert sim.jobs[0].delay_s == pytest.approx(7.0)


def test_resize_preserves_progress_fraction():
    sim = _sim()
    sim.admit(0, 128, 0.5, 2.0, 10, 0.0)   # work_total = 20 nominal seconds
    sim.advance(10.0)
    assert sim.jobs[0].progress == pytest.approx(0.5)
    sim.resize(0, 16, 0.5, 8.0)            # smaller slice: slower steps
    j = sim.jobs[0]
    assert j.progress == pytest.approx(0.5)
    assert j.work_total == pytest.approx(80.0)
    assert sim.finish_times(10.0)[0] == pytest.approx(10.0 + 40.0)


def test_resize_keeps_pinned_duration():
    pinned = _sim()
    pinned.admit(0, 128, 0.5, 2.0, 10, 0.0, duration_s=50.0)
    pinned.resize(0, 16, 0.5, 8.0)
    assert pinned.jobs[0].fixed_s == pytest.approx(50.0)  # contract holds


def test_delay_burns_before_work():
    sim = _sim()
    sim.admit(0, 16, 1.0, 1.0, 10, 0.0, start_delay=4.0)
    sim.advance(4.0)
    assert sim.jobs[0].work_done == pytest.approx(0.0)
    assert sim.jobs[0].delay_s == pytest.approx(0.0)
    sim.advance(6.0)
    assert sim.jobs[0].work_done == pytest.approx(2.0)


def test_checkpoint_cost_prices_save_and_restore_volumes():
    perf = PerfModel()
    gib = 2 ** 30
    cost = perf.checkpoint_cost(4 * gib, host_link_bw=2 * gib)
    assert cost.bytes == 4 * gib
    assert cost.save_s == pytest.approx(2.0)      # bytes / bw, once out ...
    assert cost.restore_s == pytest.approx(2.0)   # ... and once back in
    assert cost.total_s == pytest.approx(4.0)
    assert perf.checkpoint_cost(0, 2 * gib).total_s == 0.0


def test_admit_with_work_done_resumes_progress():
    # an instance resumed with work_done behaves exactly like one that
    # was admitted at t=0 and ran unthrottled to the same point
    fresh = _sim()
    fin_fresh = fresh.admit(0, 64, 1.0, 1.0, 100, 0.0)
    fresh.advance(40.0)
    resumed = _sim()
    fin_resumed = resumed.admit(0, 64, 1.0, 1.0, 100, 40.0, work_done=40.0)
    assert fin_resumed == pytest.approx(fin_fresh)
    assert resumed.jobs[0].work_done == pytest.approx(
        fresh.jobs[0].work_done)
    assert (resumed.finish_times(40.0)[0]
            == pytest.approx(fresh.finish_times(40.0)[0]))
    # work_done is clamped to the total (an already-finished resume)
    clamped = _sim()
    fin = clamped.admit(1, 64, 1.0, 1.0, 10, 0.0, work_done=99.0)
    assert fin == pytest.approx(0.0)


def test_preempted_pinned_job_resumes_with_remaining_wall_time():
    # a checkpoint eviction carries a pinned job's remaining wall time,
    # which the resume re-admits as duration_s on another mix and profile
    sim = _sim()
    sim.admit(0, 128, 1.0, 2.0, 10, 0.0, duration_s=50.0)
    sim.admit(1, 128, 1.0, 2.0, 10, 0.0)   # a rival pushes f below 1
    assert sim.throttle() < 1.0
    sim.advance(20.0)
    left = sim.remove(0).fixed_s
    assert left == pytest.approx(30.0)     # wall seconds, never stretched
    other = _sim()
    fin = other.admit(0, 16, 0.3, 8.0, 10, 25.0, duration_s=left,
                      start_delay=2.0)
    assert fin == pytest.approx(25.0 + 2.0 + 30.0)   # t + restore + left
    assert other.jobs[0].fixed_s == pytest.approx(30.0)
    assert 0 not in other.finish_times(25.0)


def test_sim_draw_matches_power_model():
    sim = _sim()
    sim.admit(0, 64, 0.9, 1.0, 5, 0.0)
    sim.admit(1, 128, 0.8, 1.0, 5, 0.0)
    loads = [InstanceLoad(64, 0.9, 1.0, 1), InstanceLoad(128, 0.8, 1.0, 1)]
    assert sim.draw(capped=False) == pod_draw(loads, V5E_POD)
    assert sim.throttle() == throttle_factor(loads, V5E_POD)


# ---------------------------------------------------------------------------
# piecewise co-run energy (core.power)
# ---------------------------------------------------------------------------
def test_corun_energy_integrates_piecewise_over_completions():
    short = InstanceLoad(64, 0.9, 1.0, steps=10)
    long = InstanceLoad(64, 0.9, 1.0, steps=100)
    makespan, energy, eff = co_run([short, long], V5E_POD)
    assert makespan == pytest.approx(max(eff))
    cap = V5E_POD.power_cap_watts
    both = min(pod_draw([short, long], V5E_POD), cap)
    alone = min(pod_draw([long], V5E_POD), cap)
    expect = both * min(eff) + alone * (max(eff) - min(eff))
    assert energy == pytest.approx(expect)
    # strictly below the old constant-at-initial-draw account
    assert energy < both * makespan


def test_corun_energy_single_instance_unchanged():
    inst = InstanceLoad(128, 0.5, 2.0, steps=10)
    makespan, energy, eff = co_run([inst], V5E_POD)
    draw = min(pod_draw([inst], V5E_POD), V5E_POD.power_cap_watts)
    assert energy == pytest.approx(draw * makespan)


def test_perfmodel_corun_summary():
    perf = PerfModel()
    loads = [InstanceLoad(128, 1.0, 1.0, 10)] * 2
    run = perf.corun(loads, V5E_POD)
    assert run.throttled and run.throttle == throttle_factor(loads, V5E_POD)
    assert run.makespan_s == max(run.effective_times)
    assert run.energy_J > 0


def test_score_many_fills_memo_and_matches_score():
    perf = PerfModel()
    cfgs = [get_config("gpt2-124m"), get_config("llama3-8b")]
    shapes = [get_shape("decode_32k")]
    table = perf.score_many(cfgs, shapes)
    assert len(table) == len(cfgs) * len(shapes) * len(PROFILES)
    for cfg in cfgs:
        for shape in shapes:
            for p in PROFILES:
                assert table[(cfg.name, shape.name, p.name)] is \
                    perf.score(cfg, shape, p)   # shared memo, same objects


def test_slo_table_rows_match_options_and_lru_hits():
    perf = PerfModel()
    job = Job(0, TRAINING, "llama3-8b", "train_4k", 0.0, steps=40)
    rows = perf.slo_table(job)
    assert rows == tuple((sc, job.steps * sc.step_time)
                         for sc in perf.options(job))
    assert perf.slo_table(job) is rows          # LRU hit, no rebuild
    pinned = Job(1, TRAINING, "llama3-8b", "train_4k", 0.0, steps=1,
                 profile="4s.64c", duration_s=123.0)
    assert [d for _, d in perf.slo_table(pinned)] == [123.0]   # pinned wall


def test_slo_table_lru_bounded():
    perf = PerfModel()
    perf._MAX_SLO_MEMO = 4
    for i in range(8):
        perf.slo_table(Job(i, TRAINING, "llama3-8b", "train_4k", 0.0,
                           steps=i + 1))
    assert len(perf._slo) == 4
