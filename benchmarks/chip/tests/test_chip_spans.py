"""The program's own spans and the metrics that read them: on small traces
recorded on a TPU v5e (``testdata/tiny_chat.xplane.pb``, from before the
program had spans of its own, and ``testdata/tiny_spill.xplane.pb``, the
spill cell's path at the sizes of ``small.py``, with them), and on the
small cells on the CPU."""
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from benchmarks.chip import run, spans, trace
from benchmarks.chip.tests import small, small_spans

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
CHAT = TESTDATA / "tiny_chat.xplane.pb"
SPILL = TESTDATA / "tiny_spill.xplane.pb"

# tok_embed (256 x 64 bf16) and the K cache's cold half (2 layers x 4 slots
# x 32 positions x 4 heads x 16, bf16) in host memory
EMBED, TAIL = 256 * 64 * 2, 2 * 4 * 32 * 4 * 16 * 2
COUNTS = {"offload.fetch": "h2d_bytes", "kv.materialize": "h2d_bytes",
          "kv.update": "d2h_bytes"}
NEW = [m["name"] for m in small_spans.PENDING["per_layer"]] + [
    "data.wait_ms.train"]


@pytest.fixture(scope="module")
def chat():
    return spans.read(str(CHAT))


@pytest.fixture(scope="module")
def spill():
    return spans.read(str(SPILL))


def _metric(name, tr):
    rec = SimpleNamespace(trace=tr, data={}, window_s=tr.window_s)
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read(rec, None)


# What trace.read reads on tiny_chat.xplane.pb.
CHAT_READS = {
    "window": (0.043558537, 0.34400060000000005),
    "busy_s": 0.0008676799999991353,
    "_decode_step": (0.0006102230000001041, 32),
    "_prefill_step": (0.00012568699999998545, 11),
    "ticks": 32, "spans": 38, "host": 9216,
    "decode_within_ticks": 0.0006102230000001041,
    "model.decode_ms.serve": 0.019069468750003253,
    "device.idle_share.serve": 99.71119889427762,
    "offload.host_ms_per_tick.spill": 4.643228062499998,
    "top_op": 0.00048253699999988714,
    "idle_gaps": [("(no host event)", 0.2086675360000007),
                  ("DeferredTpuAllocator::Allocate", 0.03128485699999996),
                  ("DoEnqueueProgram", 0.009569750000000037)],
}


@pytest.mark.parametrize("reader", ["trace", "spans"])
def test_existing_reductions_read_unchanged(reader):
    """Every reduction the admitted metrics use reads the same on a trace
    read with the program's span arguments as without."""
    tr = (spans.read if reader == "spans" else trace.read)(str(CHAT))
    approx = lambda x: pytest.approx(x, rel=1e-12, abs=0)  # noqa: E731
    assert tr.window == approx(CHAT_READS["window"])
    assert tr.busy_s == approx(CHAT_READS["busy_s"])
    for fn in ("_decode_step", "_prefill_step"):
        total, n = tr.program(fn)
        assert (total, n) == (approx(CHAT_READS[fn][0]), CHAT_READS[fn][1])
    ticks = tr.span_times("bench.tick")
    assert (len(ticks), len(tr.spans), len(tr.host)) == (
        CHAT_READS["ticks"], CHAT_READS["spans"], CHAT_READS["host"])
    assert sum(tr.program_time_within(ticks, ("_decode_step",))) == approx(
        CHAT_READS["decode_within_ticks"])
    for name in ("model.decode_ms.serve", "device.idle_share.serve",
                 "offload.host_ms_per_tick.spill"):
        assert _metric(name, tr) == approx(CHAT_READS[name]), name
    b = tr.breakdown()
    assert b["device_ops"][0][1] == approx(CHAT_READS["top_op"])
    assert [(k, approx(v)) for k, v in b["idle_gaps"][:3]] == (
        CHAT_READS["idle_gaps"])


@pytest.mark.parametrize("name", NEW)
def test_metrics_read_nothing_without_the_programs_spans(chat, name):
    """A program without the spans, as in a trace recorded before them,
    gives no value and no error."""
    assert chat.program_spans == []
    assert _metric(name, chat) is None
    assert _metric(name, trace.read(str(CHAT))) is None


def test_program_spans_with_their_arguments(spill):
    ticks = spill.span_times("bench.tick")
    admits = spans.with_args(spill, "engine.admit")
    fetch = spans.with_args(spill, "offload.fetch")
    mat = spans.with_args(spill, "kv.materialize")
    upd = spans.with_args(spill, "kv.update")
    assert ticks and admits
    for _, _, a in admits:
        assert set(a) == {"rid", "prompt_len", "wait_ms"}
        assert a["prompt_len"] in (24, 32, 40, 44) and a["wait_ms"] >= 0
    assert len({a["rid"] for _, _, a in admits}) == len(admits)
    assert [a for _, _, a in fetch] == [{"h2d_bytes": EMBED}] * len(fetch)
    assert [a for _, _, a in mat] == [{"h2d_bytes": TAIL}] * len(mat)
    assert [a for _, _, a in upd] == [{"d2h_bytes": TAIL}] * len(upd)
    # every tick decodes; every admission prefills and pastes
    assert len(fetch) == len(mat) == len(upd) == len(ticks) + len(admits)
    # each admission's copies nest inside its span
    for s, e, _ in admits:
        inside = [x for x in fetch + mat + upd if s <= x[0] and x[1] <= e]
        assert len(inside) == 3
    # the spans' times are those of the host events trace.read keeps
    for name in ("engine.admit", *COUNTS):
        assert spans.times(spill, name) == [
            (s, e) for s, e, _ in spans.with_args(spill, name)]
    # without their arguments, nothing of them is read
    assert spans.with_args(trace.read(str(SPILL)), "kv.update") is None


def test_decode_only_ticks_move_the_tiers_bytes(spill):
    """A tick that admits nothing moves the pool's host bytes in and out
    and the host-placed embedding in: exactly, in every such tick."""
    admits = spans.with_args(spill, "engine.admit")
    decode_only = 0
    for lo, hi in spill.span_times("bench.tick"):
        if any(lo <= s and e <= hi for s, e, _ in admits):
            continue
        decode_only += 1
        moved = sum(a[k] for n, k in COUNTS.items()
                    for s, e, a in spans.with_args(spill, n)
                    if lo <= s and e <= hi)
        assert moved == 2 * TAIL + EMBED
    assert decode_only > 0
    mb = _metric("offload.mb_per_tick.spill", spill)
    n_ticks = len(spill.span_times("bench.tick"))
    assert mb == pytest.approx((2 * TAIL + EMBED) * (n_ticks + len(admits))
                               / n_ticks / 1e6, rel=1e-12)


@pytest.mark.parametrize("name", ["offload.h2d_ms_per_tick.spill",
                                  "offload.d2h_ms_per_tick.spill",
                                  "engine.admit_ms.chat",
                                  "engine.queue_wait_ms.chat"])
def test_span_metrics_read_the_recorded_spill(spill, name):
    value = _metric(name, spill)
    assert value is not None and value >= 0
    if name.startswith("offload."):
        # inside the ticks, and less than the ticks' own length
        ticks = spill.span_times("bench.tick")
        assert 0 < value < 1e3 * sum(e - s for s, e in ticks) / len(ticks)


def test_span_time_within_counts_nested_spans_once():
    tr = trace.Trace(window=(0.0, 10.0), devices=[], spans=[],
                     host=[("kv.update", 1.0, 3.0), ("kv.update", 2.0, 2.5),
                           ("offload.fetch", 2.5, 4.0),
                           ("engine.admit", 0.0, 9.0)])
    got = spans.within(tr, [(0.0, 2.0), (2.0, 5.0)],
                       ("kv.update", "offload.fetch"))
    assert got == [pytest.approx(1.0), pytest.approx(2.0)]
    assert spans.times(tr, "kv.update") == [(1.0, 3.0), (2.0, 2.5)]


def test_program_spans_share_the_devices_clock(spill):
    """Each decode program runs on the device after its tick's
    ``kv.materialize`` span has dispatched the cache, and ends inside the
    ``kv.update`` span that waits for it."""
    lo, hi = spill.window
    decodes = [(s, e) for n, s, e in spill.devices[0].modules
               if trace.program_name(n) == "_decode_step" and lo <= s and e <= hi]
    mat = spans.times(spill, "kv.materialize")
    upd = spans.times(spill, "kv.update")
    assert len(decodes) == len(spill.span_times("bench.tick"))
    for s, e in decodes:
        assert any(me <= s for _, me in mat)
        assert any(us <= e <= ue for us, ue in upd)


def test_keeping_args_restores_the_plain_reader():
    plain = trace.read
    with spans.keeping_args():
        assert trace.read is spans.read
    assert trace.read is plain
    with pytest.raises(RuntimeError), spans.keeping_args():
        raise RuntimeError
    assert trace.read is plain


def test_pending_span_metrics_follow_the_contract():
    """Each pending metric: the keys and names of BENCHMARK.json's
    per-layer entries, a reader file, a layer named as the other metrics of
    it are, and an end-to-end metric it moves in each of its cells."""
    from benchmarks.chip.tests.test_chip_contract import NAME, UNIT
    b = small_spans.bench()
    e2e = {m["name"]: set(m.get("workloads", ())) for m in b["end_to_end"]}
    layers = {m["layer"] for m in small.bench()["per_layer"]}
    names = [m["name"] for m in b["per_layer"]]
    assert len(names) == len(set(names))
    for m in small_spans.PENDING["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] == "lower" and m["source"] == "device_trace"
        assert m["layer"] in layers
        assert set(m["workloads"]) <= e2e[m["moves"]]
        assert (run.HERE / "metrics" / f"{m['name']}.py").is_file()
    # the overlay leaves small's own entries as they are
    assert small_spans.bench()["per_layer"] == b["per_layer"]
    assert not {m["name"] for m in small_spans.PENDING["per_layer"]} & {
        m["name"] for m in small.bench()["per_layer"]}


@pytest.mark.parametrize("workload", ["phi3-mini.chat", "phi3-mini.spill",
                                      "gpt2-124m.train"])
def test_traced_run_reads_span_metrics(workload):
    """The small cells on the CPU, traced: each metric of the program's
    spans reads a number in its cell."""
    out = small_spans.run_small(workload, trace=True)
    assert out["correct"]
    new = {"phi3-mini.chat": ["engine.queue_wait_ms.chat",
                              "engine.admit_ms.chat"],
           "phi3-mini.spill": ["offload.h2d_ms_per_tick.spill",
                               "offload.d2h_ms_per_tick.spill",
                               "offload.mb_per_tick.spill"],
           "gpt2-124m.train": ["data.wait_ms.train"]}[workload]
    assert all(out["metrics"][m]["value"] > 0 for m in new)


def test_decode_tick_moves_the_pool_and_plan_host_bytes(tmp_path):
    """On the small spill cell, a tick that admits nothing reads, in
    ``offload.mb_per_tick.spill``, twice the pool's host bytes (each way)
    and the bytes of the parameters the plan put in host memory."""
    from jax.profiler import TraceAnnotation
    from benchmarks.chip.drivers.serve import program_config, spill_budget
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import start_multi
    from repro.serving import Request, TenantSpec
    cfg_file = run.read_json(run.HERE / "configs" / "phi3-mini-3.8b.json")
    cfg_file.update(small.SIZES["phi3-mini-3.8b"])
    ref = run.load_module(run.HERE / "reference" / "dense_decoder.py")
    cfg = program_config(SimpleNamespace(cfg_file=cfg_file,
                                         spec=ref.spec(cfg_file)))
    serving = small.MIXES["spill"]["serving"]
    slots, max_seq = serving["slots"], serving["max_seq"]
    mesh = make_host_mesh(1, 1)
    rt = start_multi([TenantSpec(
        "t", cfg, slots=slots, max_seq=max_seq,
        hbm_budget=spill_budget(cfg, slots, max_seq, mesh, serving["spill"]),
        spill_granule=serving["spill"]["granule"], seed=5)], {}, mesh=mesh)
    tenant = rt.tenants["t"]
    rt.submit("t", [Request(i, np.arange(1, 25, dtype=np.int32), 8)
                    for i in range(slots)])
    rt.step()                       # every slot admitted
    assert not tenant.engine.queue
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation(trace.WINDOW), TraceAnnotation("bench.tick"):
        rt.step()
    jax.profiler.stop_trace()
    tr = spans.read(trace.find_xplane(str(tmp_path)))
    assert not spans.with_args(tr, "engine.admit")
    plan = tenant.plan
    params_host = plan.host_bytes - sum(b for _, b in plan.partial)
    pool = tenant.engine.pool.host_bytes
    assert params_host > 0 and pool > 0
    assert _metric("offload.mb_per_tick.spill", tr) == (
        2 * pool + params_host) / 1e6
