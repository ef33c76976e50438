"""The ``granite-moe-1b.train4k`` cell's whole path on the CPU at a small
size (2 layers of width 64, 4 experts, top-2): set-up, window, the
comparison with ``reference/moe_decoder.py``, the comparison failing when
the step is broken or the reference runs in fp8, and the new metric
readers on a trace with the program's op names."""
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from benchmarks.chip import flops_moe, run
from benchmarks.chip.tests.small import PEAKS, bench
from benchmarks.chip.tests.test_chip_cells import _broken_step

CELL = "granite-moe-1b.train4k"
SIZES = {"num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 32,
         "num_local_experts": 4, "num_experts_per_tok": 2, "vocab_size": 256}
MIX = {"batch": 4, "seq": 32, "check_rows": 2}
# limits for these sizes on the CPU, between the program's readings and
# the fp8 control's at this seed; the committed ones are set on the chip
LIMITS = {"loss_gap": 0.02, "grad_norm_gap": 0.02, "update_norm_gap": 0.02}


def run_small(seed=2**31 + 77, trace=False, control=None):
    b = bench()
    return run.run_cell(b, run.cell_of(b, CELL), seed, 2.0, trace,
                        peaks=PEAKS, t_start=time.perf_counter(),
                        sizes=SIZES, mix_overrides=MIX, limits=LIMITS,
                        control=control)


def test_cell_runs_and_is_correct():
    out = run_small()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    names = {m["name"] for m in run.metrics_of(bench(), run.cell_of(
        bench(), CELL), False)}
    assert set(out["metrics"]) == names == {"train_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_traced_run_reads_host_metrics():
    out = run_small(trace=True)
    assert out["correct"]
    assert out["metrics"]["mfu.train_moe"]["value"] > 0


def test_control_is_not_correct():
    out = run_small(control="fp8")
    assert out["correct"], out["checks"]
    assert not out["control"]["fp8"]["correct"], out["control"]
    assert not out["control"]["half_batch"]["correct"], out["control"]


def test_step_returning_its_state_unchanged_fails(monkeypatch):
    def wrap(model, step):
        return jax.jit(lambda p, s, b: (p, s, {"loss": model.loss_fn(p, b)}))
    _broken_step(monkeypatch, wrap)
    out = run_small()
    assert not out["correct"]
    assert out["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_reference_draws_the_programs_weights():
    from benchmarks.chip.drivers.serve import program_config
    from repro.models.common import host_axis_env
    from repro.models.model_zoo import build_model
    cfg_file = run.read_json(run.HERE / "configs" / "granite-moe-1b-a400m.json")
    cfg_file.update(SIZES)
    ref = run.load_module(run.HERE / "reference" / "moe_decoder.py")
    ctx = SimpleNamespace(cfg_file=cfg_file, spec=ref.spec(cfg_file))
    cfg = program_config(ctx)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_multiplier,
            cfg.aux_loss_coef) == (12.0, 0.22, 6.0, 0.015625, 0.001)
    model = build_model(cfg, host_axis_env())
    seed = 2**31 + 5
    want = jax.tree_util.tree_flatten_with_path(
        model.init(jax.random.PRNGKey(seed))[0])[0]
    got = dict(jax.tree_util.tree_flatten_with_path(
        ref.init_params(ctx.spec, seed))[0])
    assert [k for k, _ in want] == list(got)
    for k, w in want:
        assert w.dtype == got[k].dtype and np.array_equal(w, got[k]), k


def test_grouped_product_readers():
    """``moe.gmm_ms.train`` sums the ragged-dot kernels (their tile
    schedules included) and nothing else; ``moe.gmm_roofline.train`` puts
    the routed rows' operations over that time."""
    ops = {"%ragged-dot-none.2 = bf16[131072,512]{1,0} custom-call(...)": 0.6,
           "%ragged-dot-metadata.1 = (s32[33]) custom-call(...)": 0.002,
           "%fusion.3 = bf16[4,4096,1024]{2,1,0} fusion(...)": 3.0,
           "%flash_attention_fwd_stats.1 = (bf16[4,4096,1024]) ...": 1.0}
    trace = SimpleNamespace(devices=[SimpleNamespace(ops=ops)],
                            program=lambda fn: (10.0, 20))
    ref = run.load_module(run.HERE / "reference" / "moe_decoder.py")
    cfg_file = run.read_json(run.HERE / "configs" / "granite-moe-1b-a400m.json")
    ctx = SimpleNamespace(spec=ref.spec(cfg_file),
                          peaks={"bf16_flops_per_s": 197e12})
    rec = SimpleNamespace(trace=trace, data={"tokens_per_step": 16384})
    gmm = run.load_module(run.HERE / "metrics" / "moe.gmm_ms.train.py")
    roof = run.load_module(run.HERE / "metrics" / "moe.gmm_roofline.train.py")
    ms = gmm.read(rec, ctx)
    assert ms == pytest.approx(1e3 * 0.602 / 20)
    flops = 16384 * 8 * 8 * 18 * 1024 * 512
    assert flops_moe.routed_train_flops_per_token(ctx.spec) * 16384 == flops
    assert roof.read(rec, ctx) == pytest.approx(
        100 * flops / (1e-3 * ms * 197e12))
    rec.trace = SimpleNamespace(devices=[SimpleNamespace(ops={})],
                                program=lambda fn: (10.0, 20))
    assert gmm.read(rec, ctx) is None and roof.read(rec, ctx) is None
