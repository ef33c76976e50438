"""End-to-end training driver (runs for real on host devices).

    PYTHONPATH=src python -m repro.launch.train --arch gpt2-124m --steps 200

Composes: config → reduced-or-full model → slice allocation (partitioner) →
data pipeline → fault-tolerant runner (checkpoint/restart, straggler
tracking) → jitted AdamW train step. ``train()`` runs the same loop
in-process and returns its results.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.partitioner import StaticPartitioner
from repro.core.slices import get_profile
from repro.data.pipeline import ByteCorpusSource, DataPipeline, SyntheticSource
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model
from repro.optim import adamw
from repro.train import checkpoint as ckpt_mod
from repro.train.fault import FaultTolerantRunner, RunnerConfig, StepFailure


def train(arch: str = "gpt2-124m", *, steps: int = 200, batch: int = 8,
          seq: int = 256, lr: float = 3e-3, full_size: bool = False,
          corpus: Optional[str] = None,
          ckpt_dir: str = "/tmp/repro_train_ckpt", ckpt_every: int = 50,
          inject_failure_at: int = -1) -> dict:
    """Train ``arch`` for ``steps`` steps; returns the config, the runner's
    stats and the wall time. Resumes from ``ckpt_dir`` when it holds a
    checkpoint."""
    cfg = get_config(arch)
    if not full_size:
        cfg = cfg.reduced().with_(num_layers=min(cfg.num_layers, 4))
    env = host_axis_env()
    model = build_model(cfg, env)
    opt_cfg = adamw.AdamWConfig(lr=lr, warmup_steps=20, total_steps=steps)

    source = (ByteCorpusSource(corpus) if corpus
              else SyntheticSource(cfg.vocab_size, seed=0))
    pipe = DataPipeline(source, batch, seq)

    @jax.jit
    def jit_step(state, batch):
        loss, grads = jax.value_and_grad(model.loss_fn)(state["params"],
                                                        batch)
        p, o, met = adamw.update(opt_cfg, grads, state["opt"],
                                 state["params"])
        met["loss"] = loss
        return {"params": p, "opt": o}, met

    def step(state, batch):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        state, met = jit_step(state, batch)
        return state, {k: float(v) for k, v in met.items()}

    def build_step(profile):
        params, _ = model.init(jax.random.PRNGKey(0))
        state = {"params": params, "opt": adamw.init(params)}
        if ckpt_mod.latest_step(ckpt_dir) is not None:
            state, _ = ckpt_mod.restore(ckpt_dir, state)
        return step, state

    part = StaticPartitioner()
    profile = get_profile("1s.16c")
    part.allocate(profile, tag="train")

    pending_failure = [inject_failure_at]  # mutable: fire exactly once

    def fail_hook(step):
        if step == pending_failure[0]:
            pending_failure[0] = -1
            part.fail_chips([(0, 0)])
            raise StepFailure(f"injected chip failure at step {step}")

    runner = FaultTolerantRunner(
        RunnerConfig(ckpt_dir=ckpt_dir, ckpt_every=ckpt_every),
        part, profile, build_step,
        get_batch=pipe.batch_at,
        save_state=lambda s: s,
        fail_hook=fail_hook)

    t0 = time.perf_counter()
    stats = runner.run(steps)
    return {"cfg": cfg, "stats": stats, "wall_s": time.perf_counter() - t0}


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-124m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (default: reduced for CPU)")
    ap.add_argument("--corpus", default=None, help="byte-level corpus file")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--inject-failure-at", type=int, default=-1,
                    help="simulate a step failure (tests restart path)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    out = train(**vars(args))
    cfg, stats = out["cfg"], out["stats"]
    print(f"arch={cfg.name} steps={stats.steps_done} "
          f"wall={out['wall_s']:.1f}s "
          f"loss {stats.losses[0]:.3f} -> {np.mean(stats.losses[-10:]):.3f} "
          f"restarts={stats.restarts} stragglers={stats.straggler_events} "
          f"repartitions={stats.repartitions} "
          f"device={jax.devices()[0].device_kind}")


if __name__ == "__main__":
    main()
