"""Serving driver: single-tenant continuous batching, or the multi-tenant
SliceRuntime.

Single tenant (the original path):

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --requests 16

Multi-tenant — pack several archs onto one pod's slices, each with its own
offload plan, and drive them concurrently:

    PYTHONPATH=src python -m repro.launch.serve \
        --tenants llama3-8b:2s.32c,gpt2-124m:1s.16c --requests 8

``--hbm-budget BYTES`` pins the *first* tenant's plan budget below its
footprint so the offload path engages at reduced scale (see
examples/slice_runtime_demo.py for the scripted version).

The same paths are callable in-process: ``make_requests``, ``run_single``
(returns the outputs) and ``start_multi`` (returns a runtime with its
tenants placed and requests queued; ``.run()`` serves them and returns the
report, ``.step()`` advances one tick).
"""
from __future__ import annotations

import argparse
import time
from typing import Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model
from repro.serving import Request, SliceRuntime, TenantEngine, TenantSpec


PROMPT_LENS = (4, 8, 12, 16)   # the CLI's prompt lengths, cycled


def make_requests(vocab_size: int, n: int, *, prompt_lens: Sequence[int],
                  max_new: int, seed: int = 0) -> List[Request]:
    """``n`` seeded requests; request ``i`` has prompt length
    ``prompt_lens[i % len(prompt_lens)]``."""
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab_size,
                                    size=prompt_lens[i % len(prompt_lens)])
                    .astype(np.int32), max_new)
            for i in range(n)]


def run_single(cfg: ModelConfig, requests: List[Request], *, slots: int,
               max_seq: int, offload_kv: bool = False) -> dict:
    """One engine, no slice: drain ``requests``; returns outputs and stats."""
    model = build_model(cfg, host_axis_env())
    params, _ = model.init(jax.random.PRNGKey(0))
    engine = TenantEngine(model, params, slots=slots, max_seq=max_seq,
                          mesh=make_host_mesh(1, 1) if offload_kv else None,
                          offload_kv=offload_kv)
    t0 = time.perf_counter()
    outputs = engine.run(requests)
    return {"outputs": outputs, "engine": engine,
            "wall_s": time.perf_counter() - t0}


def start_multi(specs: Sequence[TenantSpec],
                requests: Dict[str, List[Request]],
                mesh=None) -> SliceRuntime:
    """Place and plan every tenant on ``mesh`` (default: one device) and
    queue its requests; the caller steps or runs the returned runtime."""
    rt = SliceRuntime(mesh=mesh if mesh is not None else make_host_mesh(1, 1))
    for spec in specs:
        rt.add_tenant(spec)
    for name, reqs in requests.items():
        rt.submit(name, reqs)
    return rt


def _tenant_specs(args) -> List[TenantSpec]:
    specs, names = [], set()
    for i, entry in enumerate(args.tenants.split(",")):
        arch, _, prof = entry.partition(":")
        cfg = get_config(arch)
        if not args.full_size:
            cfg = cfg.reduced().with_(remat="none")
        budget = args.hbm_budget if i == 0 and args.hbm_budget else None
        name = arch if arch not in names else f"{arch}-{i}"
        names.add(name)
        specs.append(TenantSpec(
            name=name, cfg=cfg, profile=prof or None,
            slots=args.slots, max_seq=args.max_seq,
            hbm_budget=budget,
            spill_granule=4096 if budget else None))
    return specs


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--tenants", default=None,
                    help="comma list of arch[:profile] — multi-tenant mode")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--offload-kv", action="store_true")
    ap.add_argument("--hbm-budget", type=int, default=None,
                    help="pin tenant 0's plan budget (bytes) to force offload")
    ap.add_argument("--full-size", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if not args.tenants:
        cfg = get_config(args.arch)
        if not args.full_size:
            cfg = cfg.reduced()
        reqs = make_requests(cfg.vocab_size, args.requests,
                             prompt_lens=PROMPT_LENS, max_new=args.max_new)
        res = run_single(cfg, reqs, slots=args.slots, max_seq=args.max_seq,
                         offload_kv=args.offload_kv)
        eng, wall = res["engine"], res["wall_s"]
        total = sum(len(v) for v in res["outputs"].values())
        print(f"arch={cfg.name} requests={len(res['outputs'])} "
              f"tokens={total} ticks={eng.ticks} "
              f"truncated={eng.stats.truncated} "
              f"rejected={eng.stats.rejected} wall={wall:.2f}s "
              f"tok/s={total / wall:.1f} offload_kv={args.offload_kv} "
              f"device={jax.devices()[0].device_kind}")
        return

    specs = _tenant_specs(args)
    rt = start_multi(specs, {
        spec.name: make_requests(spec.cfg.vocab_size, args.requests,
                                 prompt_lens=PROMPT_LENS,
                                 max_new=args.max_new, seed=k)
        for k, spec in enumerate(specs)})
    for t in rt.tenants.values():
        print(f"tenant {t.name}: slice={t.alloc.profile.name} "
              f"rect={t.alloc.rect} offloaded={list(t.plan.offloaded)} "
              f"partial={[n for n, _ in t.plan.partial]}")
    report = rt.run()
    for name, row in report["tenants"].items():
        print(f"{name}: profile={row['profile']} tokens={row['tokens_out']} "
              f"tok/s={row['tok_per_s']:.1f} completed={row['completed']} "
              f"truncated={row['truncated']}")
    print(f"device={jax.devices()[0].device_kind} "
          f"modeled_pod_utilization={report['pod_utilization']:.2f} "
          f"modeled_throttle={report['modeled']['throttle']:.2f}")


if __name__ == "__main__":
    main()
