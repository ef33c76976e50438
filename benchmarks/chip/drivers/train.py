"""Training driver: the repo's jitted AdamW step
(``repro.train.train_step.make_train_step``, buffers donated) on a
one-device ``make_host_mesh(1, 1)``, fed by the program's
``DataPipeline`` (a prefetch thread) from the benchmark's seeded batches.

Set-up builds the one step and its state, and drives it from the seed
through the mix's first ``check_steps`` steps, which compile it; the
window then goes on with the same step, state and feed, steps back to
back, each waited for (the loss comes back to the host every step, as a
training loop reads it).

Check: once the window has closed and the program's state is freed, the
reference runs the same first steps from the same seed on the same
batches, and three numbers are compared: the worst relative gap of a
step's loss, and by the worst leaf the gap between the program's and the
reference's norms of the first step's gradient as the optimizer got it
(worked out from AdamW's first moment after step 1) and of the
parameters' change over the checked steps.
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

from benchmarks.chip import trace as trace_mod
from benchmarks.chip.traffic import loadgen


def worst_leaf_gap(prog: dict, ref: dict, skip=()) -> float:
    """max over leaves of |prog - ref| / max(ref, median ref): the gap
    between two norms of a leaf, against the reference's norm of that leaf
    or of the median leaf, whichever is larger."""
    med = float(np.median([v for k, v in ref.items() if k not in skip]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med)
               for k in ref if k not in skip)


def compare(losses, grad, delta, ref, still) -> dict:
    """The three numbers compared with the reference's run ``ref``."""
    return {"loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(losses, ref["losses"])),
            "grad_norm_gap": worst_leaf_gap(grad, ref["grad"]),
            "update_norm_gap": worst_leaf_gap(delta, ref["delta"], still)}


def run(ctx):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.data.pipeline import DataPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.models.model_zoo import build_model
    from repro.optim import adamw
    from repro.train.train_step import TrainStepConfig, make_train_step
    from benchmarks.chip.drivers.serve import program_config
    from benchmarks.chip.run import Record

    mix, seed, spec = ctx.mix, ctx.seed, ctx.spec
    B, S, n_check = mix["batch"], mix["seq"], mix["check_steps"]
    opt = mix["optimizer"]
    cfg = program_config(ctx)
    mesh = make_host_mesh(1, 1)
    model = build_model(cfg, mesh)
    step, sh = make_train_step(model, mesh,
                               TrainStepConfig(opt=adamw.AdamWConfig(**opt)),
                               {"tokens": P(), "labels": P()})
    params = jax.jit(lambda k: model.init(k)[0],
                     out_shardings=sh["params"])(jax.random.PRNGKey(seed))
    state = jax.jit(adamw.init, out_shardings=sh["opt"])(params)
    norms = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
    diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))
    p0 = jax.device_get(params)     # on the host: the step needs the HBM
    source = loadgen.TokenBatches(mix, spec.vocab, seed)
    feed = iter(DataPipeline(source, B, S,
                             sharding=NamedSharding(mesh, P()), prefetch=2))

    losses = []
    for i in range(n_check):
        params, state, met = step(params, state, next(feed))
        losses.append(float(met["loss"]))
        if i == 0:
            # AdamW's first moment after one step is (1 - beta1) * g
            grad = _keyed(norms(state.mu), 1.0 / (1.0 - opt["beta1"]))
    delta = _keyed(norms(diff(params, jax.device_put(p0, sh["params"]))))
    del p0
    gc.collect()
    jax.block_until_ready((params, state))

    if ctx.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    c0 = ctx.compiles.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    t_end = t0 + ctx.seconds
    steps, bad = 0, 0
    with TraceAnnotation(trace_mod.WINDOW):
        while time.perf_counter() < t_end:
            with TraceAnnotation("bench.step"):
                batch = next(feed)
                params, state, met = step(params, state, batch)
                loss = float(met["loss"])
            steps += 1
            bad += not np.isfinite(loss)
    t1 = time.perf_counter()
    c1 = ctx.compiles.snapshot()
    feed.close()

    trace = None
    if ctx.trace:
        jax.profiler.stop_trace()
        trace = trace_mod.read(trace_mod.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.log(f"trace: window {trace.window_s:.6f} s, busy {trace.busy_s:.6f} s, "
                f"step executions {trace.program('step')[1]} over {steps} steps")
    in_use = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    need = footprint(step, params, state, batch)
    ctx.log(f"memory: peak_bytes_in_use={in_use} step_footprint={need}")
    peak = max((b for b in (in_use, need) if b is not None), default=None)
    del params, state, met
    gc.collect()

    tokens = steps * B * S
    ctx.log(f"window: {t1 - t0:.6f} s, steps={steps} tokens={tokens} "
            f"non_finite_losses={bad}")
    ctx.log(f"window compiles: lowerings={c1[0] - c0[0]} "
            f"backend_compiles={c1[1] - c0[1]}")
    ctx.log(f"program: losses={losses}")

    batches = [source.batch(i, B, S) for i in range(n_check)]
    ref = ctx.ref.adamw_steps(spec, seed, batches, opt, rows=mix["check_rows"])
    ctx.log(f"reference: losses={ref['losses']}")
    # leaves whose reference gradient is nought to rounding (a key bias
    # under softmax) move under Adam by round-off alone
    gmed = float(np.median(list(ref["grad"].values())))
    still = {k for k, v in ref["grad"].items() if v < 1e-3 * gmed}
    ctx.log(f"check: leaves left out of update_norm_gap: {sorted(still)}")
    got = compare(losses, grad, delta, ref, still)
    checks = {k: (v, ctx.limits[k]) for k, v in got.items()}
    control = None
    if ctx.control:
        # the reference in the program's place: in lower precision, and
        # with half of each batch left out (the mean over the rest)
        low = ctx.ref.adamw_steps(spec, seed, batches, opt,
                                  rows=mix["check_rows"], matmul=ctx.control)
        half = ctx.ref.adamw_steps(spec, seed, [b[:B // 2] for b in batches],
                                   opt, rows=mix["check_rows"])
        control = {ctx.control: compare(low["losses"], low["grad"],
                                        low["delta"], ref, still),
                   "half_batch": compare(half["losses"], half["grad"],
                                         half["delta"], ref, still)}
        ctx.log(f"control: {control}")
    return Record(setup_s=setup_s, window_s=t1 - t0,
                  e2e={"setup_s": setup_s, "train_tok_s": tokens / (t1 - t0)},
                  attempted=steps, failed=bad, checks=checks,
                  memory_peak_bytes=peak, trace=trace,
                  data={"steps": steps, "tokens_per_step": B * S, "seq": S,
                        "control": control})


def footprint(step, *args):
    """Bytes the compiled step holds on the device while it runs (its
    arguments, outputs and temporaries, less what it donates to its
    outputs), from the compile's memory analysis; ``None`` for a step that
    is no jitted function."""
    import jax
    if not hasattr(step, "lower"):
        return None
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        args)
    m = step.lower(*shapes).compile().memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _keyed(tree, scale: float = 1.0) -> dict:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): float(v) * scale for k, v in flat}
