"""Serving driver: one tenant on ``SliceRuntime``, as
``repro.launch.serve.start_multi`` builds it, driven tick by tick.

Set-up: the runtime draws the weights on the device from the seed, places
and plans the tenant (with an ``hbm_budget`` below its footprint where the
mix asks for a spill), then every (prompt length, slot) pair the window
can meet is admitted once with one output token, which compiles or loads
each prefill, paste and decode program.

Window, open loop: requests are submitted once they are due and the
runtime steps while any is queued or live. Closed loop: the queue is kept
non-empty, so every slot decodes every tick; set-up fills the slots.
Every output token is stamped with the host time at the end of the tick
that produced it.

Check: once the window has closed (and, open loop, every request due in it
has its first token), the program's state is freed and the reference
scores a seeded sample of the finished requests, the longest among them:
the widest gap by which a served token's logit lies below the reference's
best at its position.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time
from collections import deque
from typing import Dict, List

import numpy as np

from benchmarks.chip import stats
from benchmarks.chip import trace as trace_mod
from benchmarks.chip.traffic import loadgen

DRAIN_S = 60.0      # open loop: how long after the close first tokens may come


def program_config(ctx):
    """The program's ModelConfig: its registry entry with the sizes of the
    configuration file and the settings it is served with."""
    from repro.configs import get_config
    prog = ctx.cfg_file["program"]
    return get_config(prog["registry"]).with_(
        **{**ctx.spec.program_fields, **prog["with"]})


def spill_budget(cfg, slots, max_seq, mesh, spill: dict) -> int:
    """HBM budget of a slice too small for the tenant: its footprint less
    the embedding table and ``kv_fraction`` of the KV pool."""
    import jax
    from repro.models.model_zoo import build_model
    model = build_model(cfg, mesh)
    params, _ = model.init(None, abstract=True)
    cache = jax.eval_shape(lambda: model.init_cache(slots, max_seq))
    inv = model.serving_inventory(params, cache)
    total = sum(t.bytes for t in inv)
    embed = sum(t.bytes for t in inv if t.group == "embed")
    kv = sum(t.bytes for t in inv if t.group == "kv_cache")
    return (total - (embed if spill["embed"] else 0)
            - int(kv * spill["kv_fraction"]))


class Tracker:
    """Copies each followed request's new tokens and stamps them after
    every tick, and records what each tick computed."""

    def __init__(self):
        self.live: Dict[int, tuple] = {}     # rid -> (RequestSpec, Request)
        self.ticks: List[dict] = []
        self.truncated = 0

    def follow(self, spec, req) -> None:
        self.live[req.rid] = (spec, req)

    def tick(self, step) -> None:
        a = time.perf_counter()
        step()
        b = time.perf_counter()
        rec = {"start": a, "end": b, "prefill": [], "decode_ctx": []}
        for rid, (spec, req) in list(self.live.items()):
            n = len(spec.served)
            if n == 0 and req.generated:
                rec["prefill"].append(len(req.prompt))
            for j in range(n, len(req.generated)):
                # output j was decoded at position len(prompt) + j and
                # attended to every position up to it
                rec["decode_ctx"].append(len(req.prompt) + j + 1)
                spec.served.append(int(req.generated[j]))
                spec.stamps.append(b)
            if req.truncated:
                self.truncated += 1
            if req.done or req.truncated:
                del self.live[rid]
        self.ticks.append(rec)


def run(ctx):
    import jax
    from jax.profiler import TraceAnnotation
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import start_multi
    from repro.serving import Request, TenantSpec
    from benchmarks.chip.run import Record

    mix, seed, spec = ctx.mix, ctx.seed, ctx.spec
    serving = mix["serving"]
    slots, max_seq = serving["slots"], serving["max_seq"]
    cfg = program_config(ctx)
    mesh = make_host_mesh(1, 1)
    budget = (spill_budget(cfg, slots, max_seq, mesh, serving["spill"])
              if serving.get("spill") else None)
    granule = (serving["spill"] or {}).get("granule")
    rt = start_multi([TenantSpec("bench", cfg, slots=slots, max_seq=max_seq,
                                 hbm_budget=budget, spill_granule=granule,
                                 seed=seed)], {}, mesh=mesh)
    tenant = rt.tenants["bench"]
    engine = tenant.engine
    ctx.log(f"tenant: slots={slots} max_seq={max_seq} hbm_budget={budget} "
            f"offloaded={list(tenant.plan.offloaded)} "
            f"partial={[n for n, _ in tenant.plan.partial]} "
            f"kv_device_bytes={engine.pool.device_bytes} "
            f"kv_host_bytes={engine.pool.host_bytes}")

    # warm-up: every prompt length in every slot, one output token each
    rid = -1
    for prompt in loadgen.warmup_prompts(mix, spec.vocab, seed):
        for _ in range(slots):
            rt.submit("bench", [Request(rid, prompt, 1)])
            rid -= 1
        while not engine.idle:
            rt.step()

    tracker = Tracker()
    closed = mix["loop"] == "closed"
    sent: List[loadgen.RequestSpec] = []
    refused: List[loadgen.RequestSpec] = []

    def send(s) -> None:
        r = Request(s.rid, s.prompt, s.max_new)
        if rt.submit("bench", [r]):
            tracker.follow(s, r)
        else:
            refused.append(s)
        sent.append(s)

    if closed:
        source = loadgen.closed_loop(mix, spec.vocab, seed)
        for _ in range(slots):
            send(next(source))
        tracker.tick(rt.step)
    else:
        pending = deque(loadgen.open_loop(mix, spec.vocab, ctx.seconds, seed))
    gc.collect()
    jax.block_until_ready(jax.live_arrays())
    tracker.ticks.clear()

    if ctx.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    c0 = ctx.compiles.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    t_end = t0 + ctx.seconds
    lateness = []
    with TraceAnnotation(trace_mod.WINDOW):
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            if closed:
                if not engine.queue:
                    send(next(source))
            else:
                while pending and t0 + pending[0].due_s <= now:
                    s = pending.popleft()
                    send(s)
                    lateness.append(now - (t0 + s.due_s))
                if engine.idle:
                    nxt = t0 + pending[0].due_s if pending else t_end
                    with TraceAnnotation("bench.wait"):
                        time.sleep(max(0.0, min(nxt, t_end) - now))
                    continue
            with TraceAnnotation("bench.tick"):
                tracker.tick(rt.step)
    t1 = time.perf_counter()
    c1 = ctx.compiles.snapshot()
    window_ticks = list(tracker.ticks)
    backlog = len(engine.queue)

    trace = None
    if ctx.trace:
        jax.profiler.stop_trace()
        trace = trace_mod.read(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.log(f"trace: window {trace.window_s:.6f} s, busy {trace.busy_s:.6f} s, "
                f"decode executions {trace.program('_decode_step')[1]} "
                f"over {len(window_ticks)} ticks")

    if not closed:
        # requests due in the window get their first token before they are
        # judged: an answer that comes late is late, and its wait counts
        while pending:
            s = pending.popleft()
            send(s)
            lateness.append(time.perf_counter() - (t0 + s.due_s))
        deadline = time.perf_counter() + DRAIN_S
        while (any(not s.stamps for s, _ in tracker.live.values())
               and time.perf_counter() < deadline):
            tracker.tick(rt.step)

    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    del rt, tenant, engine
    tracker.live.clear()
    gc.collect()

    e2e = {"setup_s": setup_s}
    stamps = [s.stamps for s in sent]
    e2e["output_tok_s"] = stats.tokens_per_s(stamps, t0, t1)
    gaps = stats.token_gaps(stamps, t0, t1)
    if gaps:
        e2e["itl_p95_ms"] = 1e3 * stats.percentile(gaps, 95)
    no_first = 0
    if not closed:
        no_first = sum(1 for s in sent if not s.stamps)
        first = [s.stamps[0] if s.stamps else np.inf for s in sent]
        ttft = stats.ttft_s([t0 + s.due_s for s in sent], first)
        e2e["ttft_p95_ms"] = 1e3 * stats.percentile(ttft, 95)
    done = [s for s in sent if len(s.served) >= s.max_new]
    ctx.log(f"window: {t1 - t0:.6f} s, ticks={len(window_ticks)} "
            f"tokens={sum(len(t['decode_ctx']) for t in window_ticks)} "
            f"admitted={sum(len(t['prefill']) for t in window_ticks)}")
    if lateness:
        ctx.log(f"generator lateness: p50={np.percentile(lateness, 50):.6f} s "
                f"p95={np.percentile(lateness, 95):.6f} s "
                f"max={max(lateness):.6f} s over {len(lateness)} requests")
    ctx.log(f"requests: attempted={len(sent)} completed={len(done)} "
            f"queued_at_close={backlog} "
            f"refused={len(refused)} truncated={tracker.truncated} "
            f"without_first_token={no_first}")
    ctx.log(f"window compiles: lowerings={c1[0] - c0[0]} "
            f"backend_compiles={c1[1] - c0[1]}")

    gap, control = _logit_gap(ctx, sent, done, max_seq)
    checks = {"logit_gap": (gap, ctx.limits["logit_gap"])}
    return Record(setup_s=setup_s, window_s=t1 - t0, e2e=e2e,
                  attempted=len(sent),
                  failed=len(refused) + tracker.truncated + no_first,
                  checks=checks, memory_peak_bytes=peak, trace=trace,
                  data={"ticks": window_ticks, "control": control})


def _logit_gap(ctx, sent, done, max_seq):
    """The reference over a seeded sample of finished requests, the
    longest always among them, up to the mix's count of served tokens; a
    window that finishes fewer adds requests still decoding, with the
    tokens served so far. Returns the widest gap, and the control's
    where the run reads it."""
    target = ctx.mix["check"]["served_tokens"]
    longest = max(done, key=lambda s: len(s.prompt) + len(s.served),
                  default=None)
    rest = [s for s in done if s is not longest]
    partial = [s for s in sent if 0 < len(s.served) < s.max_new]
    picked = [longest] if longest is not None else []
    for s in loadgen.shuffled(rest, ctx.seed) + loadgen.shuffled(
            partial, ctx.seed):
        if sum(len(p.served) for p in picked) >= target:
            break
        picked.append(s)
    # the engine feeds the prompt's last token again at the first decode
    # step; the reference reads the sequence the program was fed
    seqs = [np.concatenate([s.prompt, s.prompt[-1:], s.served[:-1]])
            .astype(np.int32) for s in picked]
    served = [np.asarray(s.served, np.int32) for s in picked]
    params = ctx.ref.init_params(ctx.spec, ctx.seed)
    gap = ctx.ref.served_gaps(ctx.spec, params, seqs, served, max_seq)
    ctx.log(f"check: {len(seqs)} requests, {gap.size} served tokens, "
            f"widest gap below the reference's best logit {gap.max()!r}")
    if gap.size < target // 2:
        raise RuntimeError(f"only {gap.size} served tokens to compare; the "
                           f"check needs {target}")
    control = None
    if ctx.control:
        control = {ctx.control: {"logit_gap": float(ctx.ref.served_gaps(
            ctx.spec, params, seqs, served, max_seq,
            control=ctx.control).max())}}
        ctx.log(f"control ({ctx.control}): {control}")
    del params
    return float(gap.max()), control
