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
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import moe_gmm, ssd_scan, stream_matmul
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model
from repro.serving.tenant import _decode_step

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


# phi3-mini attention: 32 heads of 96, 2048 positions
BH, S, HD = 32, 2048, 96


def test_flash_attention_fwd_compiles(one_chip):
    _compile_kernel(lambda q, k, v: fa.flash_attention_fwd(q, k, v),
                    _shapes(one_chip, *[(BH, S, HD)] * 3))


def test_flash_attention_fwd_stats_compiles(one_chip):
    _compile_kernel(lambda q, k, v: fa.flash_attention_fwd_stats(q, k, v),
                    _shapes(one_chip, *[(BH, S, HD)] * 3))


def test_flash_attention_bwd_compiles(one_chip):
    def fwd_bwd(q, k, v, do):
        out, lse = fa.flash_attention_fwd_stats(q, k, v)
        return fa.flash_attention_bwd(q, k, v, out, lse, do)
    _compile_kernel(fwd_bwd, _shapes(one_chip, *[(BH, S, HD)] * 4))


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-130m")
    nh, hp, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    assert (nh, hp, n) == (24, 64, 128)
    args = _shapes(one_chip, (1, S, nh, hp), (1, S, nh), (nh,), (1, S, n),
                   (1, S, n), dtype=jnp.float32)
    _compile_kernel(lambda *a: ssd_scan.ssd_scan(*a, chunk=128), args)


def test_grouped_matmul_compiles_at_granite_moe_widths(one_chip):
    cfg = get_config("granite-moe-1b-a400m")
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    _compile_kernel(moe_gmm.grouped_matmul,
                    _shapes(one_chip, (e, 256, d), (e, d, f)))


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
