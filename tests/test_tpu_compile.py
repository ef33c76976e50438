"""Compile the device path for a described TPU v5e chip, with no chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described, not attached. This refuses what interpret mode accepts: Pallas
blocks that do not fit the (8, 128) tiling, lowerings Mosaic lacks, more
VMEM than a kernel may use, and a program larger than the chip's memory.
Each case compiles at real widths; nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import ops, ssd_scan, stream_matmul
from repro.models import attention
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model
from repro.optim import adamw
from repro.serving.tenant import _decode_step
from repro.train.train_step import TrainStepConfig, make_train_step

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _shapes(one_chip, *shapes, dtype=jnp.bfloat16):
    return [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip) for s in shapes]


def _compile_kernel(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# phi3-mini attention: 32 heads of 96, 2048 positions; the kernels take
# the blocks they pick from the shape, as the wrapper does
BH, S, HD = 32, 2048, 96


def test_flash_attention_fwd_compiles(one_chip):
    _compile_kernel(lambda q, k, v: fa.flash_attention_fwd(q, k, v),
                    _shapes(one_chip, *[(BH, S, HD)] * 3))


def test_flash_attention_fwd_stats_compiles(one_chip):
    _compile_kernel(lambda q, k, v: fa.flash_attention_fwd_stats(q, k, v),
                    _shapes(one_chip, *[(BH, S, HD)] * 3))


def _fwd_bwd(q, k, v, do, head_dim=None):
    out, lse = fa.flash_attention_fwd_stats(q, k, v, head_dim=head_dim)
    return fa.flash_attention_bwd(q, k, v, out, lse, do, head_dim=head_dim)


def test_flash_attention_bwd_compiles(one_chip):
    _compile_kernel(_fwd_bwd, _shapes(one_chip, *[(BH, S, HD)] * 4))


@pytest.mark.parametrize("block", [256, 512, 1024])
def test_flash_attention_compiles_at_gpt2_training_shape(one_chip, block,
                                                        monkeypatch):
    """Forward with stats and backward at gpt2-124m's training shape, 80
    rows of 1024 positions, 12 heads of 64 in place (blocks of two heads'
    128 lanes), at each block the shape may be given; a diagonal block
    that holds two or more bands is cut into them."""
    monkeypatch.setattr(fa, "MAX_BLOCK", block)
    assert fa.lanes(768, 64) == 128 and fa.pick_block(1024, 128, 2) == block
    assert (fa.sub_block(block, block) is None) == (block < 2 * fa.SUB_BLOCK)
    _compile_kernel(lambda *a: _fwd_bwd(*a, head_dim=64),
                    _shapes(one_chip, *[(80, 1024, 768)] * 4))


def test_flash_attention_compiles_at_granite_training_shape(one_chip):
    """Forward with stats and backward at granite-moe-1b-a400m's training
    shape, 4 rows of 4096 positions, 16 heads of 64 in place: blocks of
    1024, four a side, the diagonal ones cut into bands."""
    assert fa.pick_block(4096, 128, 2) == 1024
    assert fa.sub_block(1024, 1024) == fa.SUB_BLOCK
    _compile_kernel(lambda *a: _fwd_bwd(*a, head_dim=64),
                    _shapes(one_chip, *[(4, 4096, 1024)] * 4))


def test_gpt2_train_step_fused_attention_fits_one_chip(one_chip,
                                                       monkeypatch):
    """The gpt2-124m train step at 80 x 1024, float32 parameters and bf16
    compute, with attention dispatched as on one TPU device: the fused
    kernels are in the program, no f32 score matrix is, and the program
    fits a 16 GB v5e chip."""
    monkeypatch.setattr(attention, "_on_one_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    cfg = get_config("gpt2-124m").with_(param_dtype="float32",
                                        dtype="bfloat16", remat="layer")
    model = build_model(cfg, mesh)
    step, sh = make_train_step(model, mesh, TrainStepConfig(),
                               {"tokens": P(), "labels": P()})
    place = lambda tree, shard: jax.tree_util.tree_map(  # noqa: E731
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shard)
    params = place(model.init(None, abstract=True)[0], sh["params"])
    opt = place(jax.eval_shape(adamw.init, params), sh["opt"])
    tokens = jax.ShapeDtypeStruct((80, 1024), jnp.int32,
                                  sharding=sh["batch"]["tokens"])
    compiled = step.lower(params, opt, {"tokens": tokens,
                                        "labels": tokens}).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "flash_attention_bwd_dkv" in text
    assert "f32[80,12,1024,1024]" not in text
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-130m")
    nh, hp, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    assert (nh, hp, n) == (24, 64, 128)
    args = _shapes(one_chip, (1, S, nh, hp), (1, S, nh), (nh,), (1, S, n),
                   (1, S, n), dtype=jnp.float32)
    _compile_kernel(lambda *a: ssd_scan.ssd_scan(*a, chunk=128), args)


def test_grouped_matmul_compiles_at_granite_moe_widths(one_chip):
    """The MoE layer's grouped products at the training cell's widths,
    forward and backward: 4 x 4096 tokens routed to 8 of 32 experts, all
    in XLA's ragged-dot kernels."""
    cfg = get_config("granite-moe-1b-a400m")
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    rows = 4 * 4096 * cfg.experts_per_token
    sizes = jax.ShapeDtypeStruct((e,), jnp.int32, sharding=one_chip)

    def fwd_bwd(x, w, dy, sizes):
        out, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, sizes), x, w)
        return out, vjp(dy)
    compiled = _compile_kernel(
        fwd_bwd, [*_shapes(one_chip, (rows, d), (e, d, f), (rows, f)), sizes])
    assert compiled.as_text().count("ragged-dot") >= 3


def test_moe_permute_compiles_at_granite_moe_widths(one_chip, monkeypatch):
    """The MoE row permute at the training cell's widths, forward and
    backward: 4 x 4096 tokens of d = 1024 in bf16, each routed to 8 of 32
    experts; the combine, its transpose and the flattening it reads."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = get_config("granite-moe-1b-a400m")
    T, d, k, E = 4 * 4096, cfg.d_model, cfg.experts_per_token, cfg.num_experts

    def fwd_bwd(x, w, top_e, order, inv, dy):
        perm = ops.moe_route(top_e, order, inv, E, x.dtype)

        def layer(x, w):
            rows = ops.moe_dispatch(x, perm)
            return ops.moe_combine(rows * 2, w, perm)
        y, vjp = jax.vjp(layer, x, w)
        return y, vjp(dy)
    args = [*_shapes(one_chip, (T, d)), *_shapes(one_chip, (T, k),
                                                 dtype=jnp.float32),
            *_shapes(one_chip, (T, k), (T * k,), (T * k,), dtype=jnp.int32),
            *_shapes(one_chip, (T, d))]
    text = _compile_kernel(fwd_bwd, args).as_text()
    for kernel in ("flatten", "combine", "combine_bwd"):
        assert f"moe_permute_{kernel}" in text, kernel


def test_granite_train_step_fits_one_chip(one_chip, monkeypatch):
    """The granite-moe-1b-a400m train step at the benchmark cell's sizes,
    8 of 24 layers and 4 x 4096 tokens, float32 parameters and AdamW state
    with bf16 compute, attention dispatched as on one TPU device: the
    grouped products, the row permute kernels and the fused attention are
    in the program, and it fits a 16 GB v5e chip."""
    monkeypatch.setattr(attention, "_on_one_tpu", lambda: True)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    cfg = get_config("granite-moe-1b-a400m").with_(
        num_layers=8, param_dtype="float32", dtype="bfloat16", remat="layer")
    model = build_model(cfg, mesh)
    step, sh = make_train_step(model, mesh, TrainStepConfig(),
                               {"tokens": P(), "labels": P()})
    place = lambda tree, shard: jax.tree_util.tree_map(  # noqa: E731
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, shard)
    params = place(model.init(None, abstract=True)[0], sh["params"])
    opt = place(jax.eval_shape(adamw.init, params), sh["opt"])
    tokens = jax.ShapeDtypeStruct((4, 4096), jnp.int32,
                                  sharding=sh["batch"]["tokens"])
    compiled = step.lower(params, opt, {"tokens": tokens,
                                        "labels": tokens}).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text and "flash_attention_bwd_dkv" in text
    assert "moe_permute_" in text
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


def test_stream_matmul_compiles_at_phi3_mlp_widths(one_chip):
    cfg = get_config("phi3-mini-3.8b")
    _compile_kernel(stream_matmul.stream_matmul,
                    _shapes(one_chip, (512, cfg.d_model),
                            (cfg.d_model, cfg.d_ff)))


def test_phi3_decode_fits_one_chip(one_chip):
    """The serving decode step at published widths, bf16 weights, 4 slots
    of 1024 positions: arguments plus outputs fit a 16 GB v5e chip."""
    cfg = get_config("phi3-mini-3.8b").with_(param_dtype="bfloat16",
                                             remat="none")
    model = build_model(cfg, host_axis_env())
    place = lambda tree: jax.tree_util.tree_map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = place(model.init(None, abstract=True)[0])
    cache = place(jax.eval_shape(lambda: model.init_cache(4, 1024)))
    tokens, pos = _shapes(one_chip, (4, 1), (4,), dtype=jnp.int32)
    compiled = _decode_step.lower(model, params, cache, tokens,
                                  pos).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes
    assert 9 * 10**9 < used < V5E_HBM_BYTES, used
