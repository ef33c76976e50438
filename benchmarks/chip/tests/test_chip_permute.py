"""The row permute's readers (``moe.permute_ms.train``,
``moe.permute_roofline.train``) on a trace with the program's op names:
the kernels' time per step and the least bytes of the step's permutes."""
from types import SimpleNamespace

import pytest

from benchmarks.chip import run

CELL_CFG = run.HERE / "configs" / "granite-moe-1b-a400m.json"


def _ctx():
    ref = run.load_module(run.HERE / "reference" / "moe_decoder.py")
    cfg_file = run.read_json(CELL_CFG)
    return SimpleNamespace(spec=ref.spec(cfg_file), cfg_file=cfg_file,
                           peaks={"hbm_bytes_per_s": 819e9})


def _rec(ops):
    trace = SimpleNamespace(devices=[SimpleNamespace(ops=ops)],
                            program=lambda fn: (10.0, 20))
    return SimpleNamespace(trace=trace, data={"tokens_per_step": 16384})


def _reader(name):
    return run.load_module(run.HERE / "metrics" / f"{name}.py")


def test_permute_readers():
    """``moe.permute_ms.train`` sums the ``moe_permute_*`` kernels and
    nothing else; ``moe.permute_roofline.train`` puts the least bytes of the
    kernels' passes, 7.25 GB a step at the cell's sizes (three passes of
    the four a layer's permute makes: the dispatch forward is XLA's), over
    that time."""
    ops = {"%moe_permute_combine.3 = bf16[16384,1024]{1,0} custom-call(...)": 0.05,
           "%moe_permute_combine = bf16[16384,1024]{1,0} custom-call(...)": 0.04,
           "%moe_permute_flatten.12 = u32[65536,128]{1,0} custom-call(...)": 0.002,
           "%moe_permute_combine_bwd.1 = (bf16[131072,1024], f32[8,131072]) ...": 0.06,
           "%fusion.499 = bf16[131072,1024]{1,0} fusion(...)": 0.5,
           "%ragged-dot-none.2 = bf16[131072,512]{1,0} custom-call(...)": 0.6}
    ctx, rec = _ctx(), _rec(ops)
    ms = _reader("moe.permute_ms.train").read(rec, ctx)
    assert ms == pytest.approx(1e3 * 0.152 / 20)
    roof = _reader("moe.permute_roofline.train")
    least = roof.least_bytes(ctx.spec, 16384, 2)
    assert least == 8 * 3 * (16384 + 131072) * 1024 * 2
    assert least == pytest.approx(7.25e9, rel=1e-3)
    assert roof.read(rec, ctx) == pytest.approx(
        100 * least / (1e-3 * ms * 819e9))


def test_permute_readers_read_nothing_without_the_kernels():
    """The parent's program gathers with XLA fusions: no ``moe_permute_``
    op, so both readers return None, and so does a run with no trace."""
    ctx = _ctx()
    rec = _rec({"%fusion.499 = bf16[131072,1024]{1,0} fusion(...)": 0.5})
    for name in ("moe.permute_ms.train", "moe.permute_roofline.train"):
        assert _reader(name).read(rec, ctx) is None
        assert _reader(name).read(SimpleNamespace(trace=None, data={}),
                                  ctx) is None
