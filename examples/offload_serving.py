"""Fine-grained CPU offloading for serving — paper §VI-A, executed for real.

A (reduced) Llama-3 is served twice: KV pool resident in device memory, then
placed in ``pinned_host`` memory via JAX memory kinds — the same mechanism a
real TPU runtime uses. Outputs must match exactly. On the CPU backend both
tiers are host RAM, so the wall-time difference there is not meaningful; it
is measured only on a chip.

    PYTHONPATH=src python examples/offload_serving.py
"""
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.offload import inventory_from_tree, plan_offload
from repro.launch.mesh import make_host_mesh
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model
from repro.serving.engine import Request, ServingEngine


def main() -> None:
    cfg = get_config("llama3-8b").reduced()
    model = build_model(cfg, host_axis_env())
    params, _ = model.init(jax.random.PRNGKey(0))
    mesh = make_host_mesh(1, 1)

    # what would the planner offload if the KV pool overflowed the slice?
    cache = model.init_cache(4, 128)
    inv = inventory_from_tree({"kv": cache})
    total = sum(t.bytes for t in inv)
    plan = plan_offload(inv, hbm_budget=total // 2)
    print(f"KV pool {total / 1024:.0f} KiB, budget {total // 2 / 1024:.0f} KiB "
          f"-> offloaded {plan.host_bytes / 1024:.0f} KiB "
          f"(fits={plan.fits}, traffic/step={plan.host_traffic_per_step / 1024:.1f} KiB)")

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(4)]

    results = {}
    for offload in (False, True):
        eng = ServingEngine(model, params, slots=2, max_seq=64,
                            mesh=mesh, offload_kv=offload)
        kinds = set(eng.pool.spilled_kinds().values()) or {"device"}
        t0 = time.time()
        out = eng.run([Request(i, p, 6) for i, p in enumerate(prompts)])
        dt = time.time() - t0
        results[offload] = out
        print(f"offload_kv={offload!s:5s} memory_kinds={kinds} "
              f"wall={dt:.2f}s tokens={sum(len(v) for v in out.values())}")

    assert results[False] == results[True], "offloading changed results!"
    print("outputs identical with and without KV offloading ✓")


if __name__ == "__main__":
    main()
