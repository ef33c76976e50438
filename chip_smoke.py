#!/usr/bin/env python3
"""Smoke run of the device path on a TPU, through the normal entry points.

    python chip_smoke.py             # phases (a)-(d) on one chip
    python chip_smoke.py --chips 4   # tensor-parallel serving on four chips

One chip, in this order, in this one process:

(a) serve — phi3-mini-3.8b at its published widths (all 32 layers, bf16
    weights drawn from a seed) through SliceRuntime/TenantEngine, as
    ``repro.launch.serve.start_multi`` builds it: 4 slots x 1024 positions,
    8 seeded requests of 32 new tokens with prompts of 128 and 512 tokens.
(b) offload — the same tenant and requests with ``TenantSpec.hbm_budget``
    set so the plan holds a cold tail of a quarter of the KV pool in
    ``pinned_host``. The planner ranks the embedding table (host traffic
    0.02 of its bytes per step) ahead of KV (0.05), so it spills that table
    first and the budget makes room for it. Every tick's logits must equal
    (a)'s: both run the same program on the same values.
(c) cache — one request's last decode logits, from prefill plus decode
    through the pool, against one full forward pass over the sequence the
    pool holds.
(d) train — gpt2-124m at full widths through ``repro.launch.train.train``
    (FaultTolerantRunner and its jitted step), batch 8 x seq 1024, 5 steps
    from a fresh checkpoint directory; every loss must be finite.

``--chips 4`` runs only this: the phi3-mini tenant tensor-parallel over a
(data=1, model=4) mesh against the same tenant on one device, same
requests; their logits must agree while their tokens agree.

Each phase prints its checks and its smoke figures (wall time, compile
seconds, peak device bytes): these are not benchmark numbers. The last line
of a passing run is one JSON object naming the device. Without a TPU, or
when any phase fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SERVE_ARCH = "phi3-mini-3.8b"
TRAIN_ARCH = "gpt2-124m"
SLOTS, MAX_SEQ, N_REQUESTS, MAX_NEW = 4, 1024, 8, 32
PROMPT_LENS = (128, 512)
# prefill+decode vs one forward pass differ only by bf16 KV rounding and
# reduction order; the same limit as tests/test_cache_equivalence.py
CACHE_REL_TOL = 0.02
# one device vs a 4-way tensor-parallel mesh: different reduction order
TP_REL_TOL = 0.02
SCRATCH = ROOT / ".smoke"


class Meter:
    """Compile seconds (XLA backend compiles) and wall time of one phase."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def start(self):
        self._wall0, self._compile0 = time.perf_counter(), self.compile_s

    def line(self) -> str:
        """Device 0's memory: peak since the process started, in use now,
        and the limit."""
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        return (f"wall_s={time.perf_counter() - self._wall0:.3f} "
                f"compile_s={self.compile_s - self._compile0:.3f} "
                f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
                f"bytes_in_use={stats.get('bytes_in_use')} "
                f"bytes_limit={stats.get('bytes_limit')}")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    print(f"  check passed: {what}", flush=True)


def rel_diff(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-9))


def serve_cfg():
    from repro.configs import get_config
    return get_config(SERVE_ARCH).with_(param_dtype="bfloat16", remat="none")


def serve(cfg, *, mesh, hbm_budget=None, on_tick=None):
    """Serve the seeded requests on one tenant through start_multi, stepping
    the runtime tick by tick. Returns (tenant, per-tick logits, requests)."""
    import numpy as np
    from repro.launch import serve as serve_mod
    from repro.serving import TenantSpec
    spec = TenantSpec("smoke", cfg, slots=SLOTS, max_seq=MAX_SEQ,
                      hbm_budget=hbm_budget)
    reqs = serve_mod.make_requests(cfg.vocab_size, N_REQUESTS,
                                   prompt_lens=PROMPT_LENS, max_new=MAX_NEW)
    rt = serve_mod.start_multi([spec], {"smoke": reqs}, mesh=mesh)
    tenant = rt.tenants["smoke"]
    logits = []
    while not tenant.engine.idle:
        rt.step()
        logits.append(np.asarray(tenant.engine.last_logits, np.float32))
        if on_tick is not None:
            on_tick(tenant)
    return tenant, logits, reqs


def final_logits(logits, reqs, rid):
    """Logits of request ``rid``'s last decode tick. Slots are recycled, so
    find the tick from the request's admit tick and token count."""
    r = reqs[rid]
    return logits[r.admit_tick + len(r.generated) - 1][r.slot]


def phase_serve_and_offload(meter, mesh):
    import numpy as np
    import jax
    from repro.core.offload import _flatten_with_paths, fetch_to_device
    cfg = serve_cfg()

    print(f"(a) serve {cfg.name}: {cfg.num_layers} layers, d_model="
          f"{cfg.d_model}, {cfg.param_dtype} weights, {SLOTS} slots x "
          f"{MAX_SEQ} positions, {N_REQUESTS} requests x {MAX_NEW} new "
          f"tokens, prompts {PROMPT_LENS}", flush=True)
    meter.start()
    tenant, want, reqs = serve(cfg, mesh=mesh)
    out = {r.rid: list(r.generated) for r in reqs}
    tokens = sum(len(v) for v in out.values())
    print(f"  tokens_out={tokens} ticks={len(want)} "
          f"kv_pool_bytes={tenant.engine.pool.device_bytes} {meter.line()}")
    check(tokens == N_REQUESTS * MAX_NEW and tenant.engine.stats.truncated == 0,
          f"all {N_REQUESTS} requests completed with {MAX_NEW} tokens")
    check(all(np.isfinite(x).all() for x in want), "every tick's logits finite")
    inv = tenant.model.serving_inventory(tenant.params, jax.eval_shape(
        lambda: tenant.model.init_cache(SLOTS, MAX_SEQ)))
    embed = sum(t.bytes for t in inv if t.group == "embed")
    kv = sum(t.bytes for t in inv if t.group == "kv_cache")
    budget = tenant.inventory_bytes - embed - kv // 4
    del tenant
    gc.collect()

    print(f"(b) offload: hbm_budget={budget} (footprint less the embedding "
          f"table and a quarter of the {kv}-byte KV pool)", flush=True)
    meter.start()

    def spilled_stay_on_host(t):
        kinds = t.engine.pool.spilled_kinds()
        assert kinds and set(kinds.values()) == {"pinned_host"}, kinds

    tenant, got, reqs_b = serve(cfg, mesh=mesh, hbm_budget=budget,
                                on_tick=spilled_stay_on_host)
    pool = tenant.engine.pool
    print(f"  plan offloaded={list(tenant.plan.offloaded)} "
          f"partial={[n for n, _ in tenant.plan.partial]} "
          f"split_leaves={pool.split_leaves} "
          f"pool_host_bytes={pool.host_bytes} "
          f"pool_device_bytes={pool.device_bytes} {meter.line()}")
    kinds = pool.spilled_kinds()
    check(bool(pool.split_leaves) and set(kinds.values()) == {"pinned_host"},
          f"cold KV leaves in pinned_host after every tick: {kinds}")
    check(pool.host_bytes > 0, f"pool.host_bytes={pool.host_bytes} > 0")
    check(0.2 <= pool.host_bytes / kv <= 0.3,
          f"the cold tail holds {pool.host_bytes / kv:.3f} of the KV pool")
    params = {f"params/{path}": x
              for path, x in _flatten_with_paths(tenant.params)}
    check(all(params[n].sharding.memory_kind == "pinned_host"
              for n in tenant.plan.offloaded if n in params),
          f"spilled params in pinned_host: {list(tenant.plan.offloaded)}")
    diff = max(float(np.max(np.abs(a - b))) for a, b in zip(want, got))
    print(f"  logits max_abs_diff vs (a) = {diff} over {len(got)} ticks")
    check(len(got) == len(want) and diff == 0.0,
          "every tick's logits equal phase (a)'s")
    check({r.rid: r.generated for r in reqs_b} == out,
          "every request's tokens equal phase (a)'s")

    rid = 0
    r = reqs_b[rid]
    seq = np.concatenate([r.prompt, r.prompt[-1:], r.generated[:-1]])
    print(f"(c) cache: request {rid} (prompt {len(r.prompt)}, "
          f"{len(r.generated)} generated) vs one forward pass over "
          f"{len(seq)} tokens", flush=True)
    meter.start()
    model = tenant.model
    fwd = jax.jit(lambda p, t: model.forward(
        p, {"tokens": t}, last_token_only=True)[0][0, -1])
    ref = fwd(fetch_to_device(tenant.params), seq[None, :].astype(np.int32))
    rel = rel_diff(final_logits(got, reqs_b, rid), ref)
    print(f"  rel_max_diff={rel} {meter.line()}")
    check(rel < CACHE_REL_TOL,
          f"rel max diff {rel:.4g} < {CACHE_REL_TOL} (bf16 KV rounding)")
    del tenant
    gc.collect()


def phase_train(meter, steps=5, batch=8, seq=1024, full_size=True):
    import math
    from repro.launch.train import train
    ckpt = SCRATCH / "train_ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    print(f"(d) train {TRAIN_ARCH} full_size={full_size}: batch {batch} x "
          f"seq {seq}, {steps} steps, fresh checkpoint dir", flush=True)
    meter.start()
    res = train(TRAIN_ARCH, steps=steps, batch=batch, seq=seq,
                full_size=full_size, ckpt_dir=str(ckpt))
    shutil.rmtree(ckpt, ignore_errors=True)
    losses = res["stats"].losses
    print(f"  losses={losses} {meter.line()}")
    check(res["stats"].steps_done == steps, f"{steps} steps done")
    check(all(math.isfinite(x) for x in losses), "every loss finite")


def phase_tensor_parallel(meter):
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    cfg = serve_cfg()
    print(f"(tp) {cfg.name} tensor-parallel over (data=1, model=4) vs one "
          f"device, same requests", flush=True)
    meter.start()
    tenant, want, reqs_1 = serve(cfg, mesh=make_host_mesh(1, 1))
    print(f"  one device: {meter.line()}", flush=True)
    del tenant
    gc.collect()
    meter.start()
    tenant, got, reqs_4 = serve(cfg, mesh=make_host_mesh(1, 4))
    print(f"  four devices: {meter.line()}")
    leaf = tenant.params["layers"]["wq"]
    check(len(leaf.sharding.device_set) == 4,
          f"wq sharded over 4 devices: {leaf.sharding.spec}")
    # compare ticks while both runs have fed the same tokens; once a near
    # tie flips an argmax the inputs differ and so may every later logit
    worst, compared = 0.0, 0
    for a, b in zip(want, got):
        worst = max(worst, rel_diff(b, a))
        compared += 1
        if not np.array_equal(a.argmax(-1), b.argmax(-1)):
            break
    same = sum(reqs_1[i].generated == reqs_4[i].generated
               for i in range(N_REQUESTS))
    print(f"  ticks compared={compared}/{len(want)} rel_max_diff={worst} "
          f"requests with identical tokens={same}/{N_REQUESTS}")
    check(worst < TP_REL_TOL,
          f"logits rel max diff {worst:.4g} < {TP_REL_TOL} "
          f"(reduction order)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"error: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"error: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    enable_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)} "
          f"jax {jax.__version__}", flush=True)
    meter = Meter()
    if args.chips == 4:
        phase_tensor_parallel(meter)
    else:
        phase_serve_and_offload(meter, make_host_mesh(1, 1))
        phase_train(meter)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
