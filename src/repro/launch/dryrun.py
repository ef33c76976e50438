import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first initialization). Everything below may import jax.

_DOC = """Multi-pod dry-run: lower + compile every (arch × shape) on the
production meshes and extract the roofline inputs (deliverables e/f/g).

For each cell:
    with mesh:
        lowered  = jax.jit(step, in_shardings=…, out_shardings=…).lower(*specs)
        compiled = lowered.compile()
        memory_analysis / cost_analysis / HLO collective parse
Artifacts go to benchmarks/artifacts/dryrun/<mesh>/<arch>__<shape>.json.

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both [--subprocess]
"""
__doc__ = _DOC

import argparse
import gc
import json
import sys
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, ALL_ARCHS, get_config, get_shape
from repro.configs.shapes import DECODE, PREFILL, SHAPES, TRAIN, applicable
from repro.core.hw import GiB
from repro.core.roofline import analyze, model_flops_for
from repro.launch.mesh import make_production_mesh
from repro.models.common import AxisEnv
from repro.models.model_zoo import build_model
from repro.optim import adamw
from repro.train.train_step import TrainStepConfig, make_train_step

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "benchmarks", "artifacts", "dryrun")


def _sh(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def _auto_microbatches(cfg, shape, mesh, budget_bytes=3 * GiB) -> int:
    """Split the batch so per-device layer-boundary activations fit.

    Saved activations ≈ L × B_local × S × D × 2 bytes (bf16, replicated over
    the model axis — see DESIGN.md §5); family factors cover the extra live
    state of MoE capacity buffers and SSD intra-chunk tensors. Grows in
    powers of two while the local batch stays divisible."""
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    data_shards = axes.get("data", 1) * axes.get("pod", 1)
    if cfg.name in ("mamba2-130m", "gpt2-124m"):  # fsdp_only: joint batch
        data_shards *= axes.get("model", 1)
    b_local = max(1, shape.global_batch // data_shards)
    factor = {"moe": 2.0, "ssm": 3.0, "hybrid": 3.0}.get(cfg.family, 1.0)
    act = cfg.num_layers * b_local * shape.seq_len * cfg.d_model * 2 * factor
    mb = 1
    while act / mb > budget_bytes and b_local % (2 * mb) == 0:
        mb *= 2
    return mb


def lower_cell(arch: str, shape_name: str, mesh, *, remat: Optional[str] = None,
               compile_: bool = True, overrides: Optional[Dict] = None) -> Dict:
    """Lower (and compile) one cell; returns the roofline record."""
    cfg = get_config(arch)
    if remat:
        cfg = cfg.with_(remat=remat)
    forced_microbatches = None
    grad_compression = False
    if overrides:
        overrides = dict(overrides)
        forced_microbatches = overrides.pop("microbatches", None)
        grad_compression = bool(overrides.pop("grad_compression", False))
        cfg = cfg.with_(**overrides)
    shape = get_shape(shape_name)
    ok, reason = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": reason}

    model = build_model(cfg, mesh)
    t0 = time.time()
    with mesh:
        if shape.kind == TRAIN:
            microbatches = forced_microbatches or _auto_microbatches(cfg, shape, mesh)
            step_fn, shardings = make_train_step(
                model, mesh,
                TrainStepConfig(microbatches=microbatches,
                                grad_compression=grad_compression),
                {k: sp for k, (_, _, sp) in model.batch_specs(shape).items()})
            params, _ = model.abstract_params(mesh)
            opt = jax.eval_shape(adamw.init, params)
            opt = adamw.AdamWState(
                step=jax.ShapeDtypeStruct((), jnp.int32,
                                          sharding=NamedSharding(mesh, P())),
                mu=jax.tree_util.tree_map(
                    lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sh),
                    opt.mu, shardings["params"]),
                nu=jax.tree_util.tree_map(
                    lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                       sharding=sh),
                    opt.nu, shardings["params"]))
            batch = model.input_specs(shape, mesh)
            if grad_compression and "pod" in mesh.axis_names:
                err = jax.tree_util.tree_map(
                    lambda s, sh: jax.ShapeDtypeStruct(
                        s.shape, jnp.float32, sharding=sh),
                    params, shardings["params"])
                lowered = step_fn.lower(params, opt, batch, err)
            else:
                lowered = step_fn.lower(params, opt, batch)
            trip = cfg.num_layers
        elif shape.kind == PREFILL:
            params, specs = model.abstract_params(mesh)

            def prefill(p, b):
                logits, aux, cache = model.forward(p, b, return_cache=True,
                                                   last_token_only=True)
                return logits[:, 0, :], cache

            batch = model.input_specs(shape, mesh)
            env = model.env
            logits_spec = P(env.batch_axes(shape.global_batch),
                            env.tp if model.pol.vocab_sharded else None)
            cache_sh = _sh(mesh, model.cache_specs(shape.global_batch))
            lowered = jax.jit(
                prefill,
                in_shardings=(_sh(mesh, specs), None),
                out_shardings=(NamedSharding(mesh, logits_spec), cache_sh),
            ).lower(params, batch)
            trip = cfg.num_layers
        else:  # DECODE
            params, specs = model.abstract_params(mesh)
            cache = model.abstract_cache(shape.global_batch, shape.seq_len, mesh)
            batch = model.input_specs(shape, mesh)
            env = model.env
            logits_spec = P(env.batch_axes(shape.global_batch),
                            env.tp if model.pol.vocab_sharded else None)
            cache_sh = _sh(mesh, model.cache_specs(shape.global_batch))

            def decode(p, c, b):
                return model.decode(p, c, b)

            lowered = jax.jit(
                decode,
                in_shardings=(_sh(mesh, specs), None, None),
                out_shardings=(NamedSharding(mesh, logits_spec), cache_sh),
                donate_argnums=(1,)).lower(params, cache, batch)
            trip = cfg.num_layers
        t_lower = time.time() - t0

        rec = {"arch": arch, "shape": shape_name,
               "mesh": "x".join(map(str, mesh.devices.shape)),
               "n_devices": mesh.devices.size,
               "lower_s": round(t_lower, 2)}
        if not compile_:
            rec["compiled"] = False
            return rec

        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        mem = compiled.memory_analysis()
        rec["memory"] = {
            "argument_gib": mem.argument_size_in_bytes / GiB,
            "output_gib": mem.output_size_in_bytes / GiB,
            "temp_gib": mem.temp_size_in_bytes / GiB,
            "alias_gib": mem.alias_size_in_bytes / GiB,
            "host_temp_gib": mem.host_temp_size_in_bytes / GiB,
            "host_arg_gib": mem.host_argument_size_in_bytes / GiB,
            # per-device live estimate: args + temps (aliased args reused)
            "per_device_gib": (mem.argument_size_in_bytes
                               + mem.temp_size_in_bytes
                               + mem.output_size_in_bytes
                               - mem.alias_size_in_bytes) / GiB,
        }
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # older jax: one dict per device
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()
        terms = analyze(cost, hlo, mesh.devices.size,
                        model_flops_for(cfg, shape), loop_trip_count=trip)
        rec["roofline"] = terms.as_dict()
        rec["roofline"]["xla_cost_analysis"] = terms.xla_cost_analysis
        rec["collectives"] = {
            "bytes_by_op": terms.collectives.bytes_by_op,
            "count_by_op": terms.collectives.count_by_op,
            "loop_trips": terms.collectives.scaled_computations[:8],
        }
        if shape.kind == TRAIN:
            rec["microbatches"] = microbatches
        hc = terms.hlo_cost
        rec["top_sites"] = {
            "flops": [{"op": s.op_name[-120:], "value": s.value, "x": s.multiplier}
                      for s in hc.top_flops_sites[:8]],
            "collective": [{"op": s.op_name[-120:], "kind": s.kind,
                            "value": s.value, "x": s.multiplier}
                           for s in hc.top_collective_sites[:8]],
            "bytes": [{"op": s.op_name[-120:], "value": s.value, "x": s.multiplier}
                      for s in hc.top_bytes_sites[:10]],
        }
        rec["compiled"] = True
        del compiled, lowered
        gc.collect()
        return rec


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             remat: Optional[str] = None, overrides: Optional[Dict] = None,
             tag: str = "") -> Dict:
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    try:
        rec = lower_cell(arch, shape_name, mesh, remat=remat,
                         overrides=overrides)
    except Exception as e:  # noqa: BLE001 — report, don't crash the sweep
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
    if overrides:
        rec["overrides"] = {k: str(v) for k, v in overrides.items()}
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    path = os.path.join(out_dir, f"{arch}__{shape_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def summarize(rec: Dict) -> str:
    if rec.get("skipped"):
        return f"SKIP  {rec['arch']:24s} {rec['shape']:12s} ({rec['skipped'][:40]})"
    if rec.get("error"):
        return f"FAIL  {rec['arch']:24s} {rec['shape']:12s} {rec['error'][:80]}"
    r = rec["roofline"]
    m = rec["memory"]
    return (f"OK    {rec['arch']:24s} {rec['shape']:12s} "
            f"mem/dev={m['per_device_gib']:6.2f}GiB "
            f"dom={r['dominant']:10s} step={r['step_time_s']*1e3:8.2f}ms "
            f"mfu={r['roofline_mfu']*100:5.1f}% "
            f"useful={r['useful_flops_ratio']*100:5.1f}% "
            f"[lower {rec.get('lower_s')}s compile {rec.get('compile_s')}s]")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true",
                    help="sweep all assigned (arch × shape) cells")
    ap.add_argument("--include-paper-archs", action="store_true")
    ap.add_argument("--remat", default=None)
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (e.g. attn_chunk=512)")
    ap.add_argument("--tag", default="", help="artifact filename suffix")
    ap.add_argument("--out", default=ART_DIR)
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v in ("True", "true"):
            v = True
        if v in ("False", "false"):
            v = False
        overrides[k] = v

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        archs = ALL_ARCHS if args.include_paper_archs else ASSIGNED_ARCHS
        cells = [(a, s.name) for a in archs for s in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = 0
    for mk in meshes:
        out_dir = os.path.join(args.out, mk)
        for arch, shape in cells:
            rec = run_cell(arch, shape, mk, out_dir, remat=args.remat,
                           overrides=overrides or None, tag=args.tag)
            print(summarize(rec), flush=True)
            failures += 1 if rec.get("error") else 0
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
