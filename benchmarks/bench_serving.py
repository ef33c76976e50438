"""SliceRuntime serving benchmark — multi-tenant co-run on the live engine.

Rows (CSV: name,us_per_call,derived):
  serve/single.<arch>      one tenant alone, us per emitted token
  serve/corun.<arch>       same tenant co-run with a second tenant
  serve/corun.aggregate    both tenants' tokens over the co-run wall time
  serve/offload.<arch>     tenant under a forced offload plan (spill path)

Wall times on the CPU backend measure *engine overhead*, not TPU step
time; the modeled throttle/energy figures come from core.power and are
printed in the derived column.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.serving import Request, SliceRuntime, TenantSpec

ARCH_A = "llama3-8b"
ARCH_B = "gpt2-124m"
N_REQ = 4
MAX_NEW = 6


def _requests(cfg, n=N_REQ, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size, size=6).astype(np.int32),
                    MAX_NEW) for i in range(n)]


def _drive(rt, loads) -> dict:
    for name, reqs in loads.items():
        rt.submit(name, reqs)
    t0 = time.perf_counter()
    report = rt.run()
    report["wall_s"] = time.perf_counter() - t0
    return report


def run() -> None:
    mesh = make_host_mesh(1, 1)
    cfg_a = get_config(ARCH_A).reduced().with_(remat="none")
    cfg_b = get_config(ARCH_B).reduced().with_(remat="none")

    # single-tenant baseline
    rt = SliceRuntime(mesh=mesh)
    rt.add_tenant(TenantSpec(ARCH_A, cfg_a, profile="2s.32c",
                             slots=4, max_seq=48))
    rep = _drive(rt, {ARCH_A: _requests(cfg_a)})
    tok = rep["tenants"][ARCH_A]["tokens_out"]
    emit(f"serve/single.{ARCH_A}", rep["wall_s"] / max(tok, 1) * 1e6,
         f"tokens={tok}")

    # two tenants co-run on distinct slices
    rt = SliceRuntime(mesh=mesh)
    rt.add_tenant(TenantSpec(ARCH_A, cfg_a, profile="2s.32c",
                             slots=4, max_seq=48))
    rt.add_tenant(TenantSpec(ARCH_B, cfg_b, profile="1s.16c",
                             slots=4, max_seq=32))
    rep = _drive(rt, {ARCH_A: _requests(cfg_a), ARCH_B: _requests(cfg_b)})
    total = 0
    for arch in (ARCH_A, ARCH_B):
        row = rep["tenants"][arch]
        total += row["tokens_out"]
        emit(f"serve/corun.{arch}", rep["wall_s"] / max(row["tokens_out"], 1) * 1e6,
             f"tokens={row['tokens_out']},profile={row['profile']}")
    emit("serve/corun.aggregate", rep["wall_s"] / max(total, 1) * 1e6,
         f"tokens={total},pod_util={rep['pod_utilization']:.2f},"
         f"throttle={rep['modeled']['throttle_factor']:.2f}")

    # forced offload plan (budget below footprint -> spill path engaged)
    rt = SliceRuntime(mesh=mesh)
    t = rt.add_tenant(TenantSpec(ARCH_A, cfg_a, profile="2s.32c",
                                 slots=4, max_seq=48,
                                 hbm_budget=380_000, spill_granule=4096))
    rep = _drive(rt, {ARCH_A: _requests(cfg_a)})
    row = rep["tenants"][ARCH_A]
    emit(f"serve/offload.{ARCH_A}",
         rep["wall_s"] / max(row["tokens_out"], 1) * 1e6,
         f"tokens={row['tokens_out']},host_bytes={t.plan.host_bytes},"
         f"partial={len(t.plan.partial)}")
