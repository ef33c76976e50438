"""Each cell's whole path on the CPU at a small size: set-up, window, the
comparison with the reference; the comparison failing when the timed path
is broken underneath; the control separating from the program; and new
files found by name."""
import json
import shutil
import time

import jax
import numpy as np
import pytest

from benchmarks.chip import run
from benchmarks.chip.drivers.train import worst_leaf_gap
from benchmarks.chip.tests.small import MIXES, PEAKS, SIZES, bench, run_small
from benchmarks.chip.traffic import loadgen

CELLS = ["phi3-mini.chat", "phi3-mini.spill", "gpt2-124m.train"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(workload):
    out = run_small(workload)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    names = {m["name"] for m in run.metrics_of(bench(), run.cell_of(
        bench(), workload), False)}
    assert set(out["metrics"]) == names
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", ["phi3-mini.chat", "gpt2-124m.train"])
def test_traced_run_reads_host_metrics(workload):
    out = run_small(workload, trace=True)
    assert out["correct"]
    assert out["device"]["window_s"] > 0
    host = {"phi3-mini.chat": ["engine.tick_ms.serve", "mfu.serve"],
            "gpt2-124m.train": ["mfu.train"]}[workload]
    assert all(out["metrics"][m]["value"] > 0 for m in host)


def test_token_altered_where_it_is_produced_fails(monkeypatch):
    from repro.serving import tenant
    real = tenant._decode_step

    def off_by_one(model, params, cache, tokens, pos):
        logits, nxt, new = real(model, params, cache, tokens, pos)
        return logits, (nxt + 1) % logits.shape[-1], new

    monkeypatch.setattr(tenant, "_decode_step", off_by_one)
    out = run_small("phi3-mini.chat")
    assert not out["correct"], out["checks"]


def _broken_step(monkeypatch, wrap):
    from repro.train import train_step
    real = train_step.make_train_step

    def factory(model, mesh, cfg, specs):
        step, sh = real(model, mesh, cfg, specs)
        return wrap(model, step), sh

    monkeypatch.setattr(train_step, "make_train_step", factory)


def test_step_returning_its_state_unchanged_fails(monkeypatch):
    def wrap(model, step):
        return jax.jit(lambda p, s, b: (p, s, {"loss": model.loss_fn(p, b)}))
    _broken_step(monkeypatch, wrap)
    out = run_small("gpt2-124m.train")
    assert not out["correct"]
    assert out["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_fails(monkeypatch):
    def wrap(model, step):
        return lambda p, s, b: step(p, s, {k: v[: v.shape[0] // 2]
                                           for k, v in b.items()})
    _broken_step(monkeypatch, wrap)
    out = run_small("gpt2-124m.train")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", ["phi3-mini.chat", "gpt2-124m.train"])
def test_control_is_not_correct(workload):
    """The reference computed in fp8 in the program's place comes out not
    correct under the cell's limits, while the program is correct."""
    out = run_small(workload, control="fp8")
    assert out["correct"], out["checks"]
    ctl = out["control"]["fp8"]
    assert not ctl["correct"], (ctl, out["checks"])
    assert list(out)[-1] == "checks"


def test_fp8_control_trains():
    """The fp8 control computes its gradients in fp8, not to zero: its
    first step's gradient and its change stay near the reference's."""
    ref = run.load_module(run.HERE / "reference" / "dense_decoder.py")
    cfg = run.read_json(run.HERE / "configs" / "gpt2-124m.json")
    cfg.update(SIZES["gpt2-124m"])
    spec = ref.spec(cfg)
    mix = {**loadgen.load_mix("train"), **MIXES["train"]}
    src = loadgen.TokenBatches(mix, spec.vocab, 2**31 + 3)
    batches = [src.batch(i, mix["batch"], mix["seq"]) for i in range(3)]
    hi = ref.adamw_steps(spec, 2**31 + 3, batches, mix["optimizer"], rows=2)
    lo = ref.adamw_steps(spec, 2**31 + 3, batches, mix["optimizer"], rows=2,
                         matmul="fp8")
    # a control whose gradients vanished would read about 1 on both
    assert worst_leaf_gap(lo["grad"], hi["grad"]) < 0.2
    assert worst_leaf_gap(lo["delta"], hi["delta"]) < 0.2


@pytest.mark.parametrize("config", ["phi3-mini-3.8b", "gpt2-124m"])
def test_reference_draws_the_programs_weights(config):
    from benchmarks.chip.drivers.serve import program_config
    from repro.models.common import host_axis_env
    from repro.models.model_zoo import build_model
    cfg_file = run.read_json(run.HERE / "configs" / f"{config}.json")
    cfg_file.update(SIZES[config])
    ref = run.load_module(run.HERE / "reference" / "dense_decoder.py")
    spec = ref.spec(cfg_file)

    class Ctx:
        pass
    ctx = Ctx()
    ctx.cfg_file, ctx.spec = cfg_file, spec
    model = build_model(program_config(ctx), host_axis_env())
    seed = 2**31 + 5
    want = jax.tree_util.tree_flatten_with_path(
        model.init(jax.random.PRNGKey(seed))[0])[0]
    got = dict(jax.tree_util.tree_flatten_with_path(ref.init_params(spec, seed))[0])
    assert [k for k, _ in want] == list(got)
    for k, w in want:
        assert w.dtype == got[k].dtype and np.array_equal(w, got[k]), k


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """A configuration, a traffic mix, a per-layer metric and a limit
    added as files, with entries in BENCHMARK.json, need no other edit."""
    chip = tmp_path / "chip"
    shutil.copytree(run.HERE, chip, ignore=shutil.ignore_patterns("__pycache__"))
    monkeypatch.setattr(run, "HERE", chip)
    monkeypatch.setattr(loadgen, "HERE", chip / "traffic")
    cfg = json.loads((chip / "configs" / "phi3-mini-3.8b.json").read_text())
    cfg.update(SIZES["phi3-mini-3.8b"])
    (chip / "configs" / "phi3-tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((chip / "traffic" / "chat.json").read_text())
    mix.update(MIXES["chat"])
    (chip / "traffic" / "chat-tiny.json").write_text(json.dumps(mix))
    (chip / "metrics" / "engine.ticks.serve.py").write_text(
        "def read(rec, ctx):\n    return float(len(rec.data['ticks']))\n")
    (chip / "limits" / "phi3-tiny.chat.json").write_text('{"logit_gap": 1.0}')
    b = bench()
    b["configs"].append({"name": "phi3-tiny", "source": "x",
                         "file": "benchmarks/chip/configs/phi3-tiny.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "phi3-tiny.chat", "config": "phi3-tiny",
                           "traffic": "chat-tiny", "chips": 1, "why": "x"})
    for m in b["end_to_end"]:
        if "workloads" in m and "phi3-mini.chat" in m["workloads"]:
            m["workloads"].append("phi3-tiny.chat")
    b["per_layer"].append({"name": "engine.ticks.serve", "unit": "1",
                           "better": "lower", "source": "host_clock",
                           "layer": "runtime / engine", "moves": "itl_p95_ms",
                           "workloads": ["phi3-tiny.chat"]})
    cell = run.cell_of(b, "phi3-tiny.chat")
    out = run.run_cell(b, cell, 9, 2.0, True, peaks=PEAKS,
                       t_start=time.perf_counter())
    assert out["correct"]
    assert out["metrics"]["engine.ticks.serve"]["value"] > 0
