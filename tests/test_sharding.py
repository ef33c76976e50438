"""Multi-device sharding tests — run in subprocesses so THIS process keeps a
single device (dry-run semantics demand the 512-device env var is only ever
set inside launch/dryrun.py)."""
import json
import subprocess
import sys
import textwrap

import pytest

REPO = "/root/repo"


def _run(prog: str, timeout: int = 560) -> str:
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, cwd=REPO, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-3000:])
    return out.stdout


def test_sharded_train_step_runs_and_matches_single_device():
    """Reduced llama3 on a 2×2 host mesh: the sharded loss must equal the
    single-device loss (GSPMD correctness end-to-end)."""
    prog = textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.configs.shapes import ShapeSuite, TRAIN
        from repro.launch.mesh import make_mesh
        from repro.models.model_zoo import build_model
        from repro.models.common import host_axis_env

        mesh = make_mesh((2, 2), ("data", "model"))
        cfg = get_config("llama3-8b").reduced().with_(
            num_heads=4, num_kv_heads=2, remat="none")
        shape = ShapeSuite("t", TRAIN, 64, 4)

        # single device reference
        m1 = build_model(cfg, host_axis_env())
        params, _ = m1.init(jax.random.PRNGKey(0))
        batch = m1.synthetic_batch(shape)
        ref = float(m1.loss_fn(params, batch))

        # sharded
        m = build_model(cfg, mesh)
        _, specs = m.init(None, abstract=True)
        sh = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))
        params_s = jax.tree_util.tree_map(jax.device_put, params, sh)
        bspec = {k: NamedSharding(mesh, sp)
                 for k, (_, _, sp) in m.batch_specs(shape).items()}
        batch_s = {k: jax.device_put(v, bspec[k]) for k, v in batch.items()}
        with mesh:
            got = float(jax.jit(m.loss_fn)(params_s, batch_s))
        assert abs(got - ref) / abs(ref) < 5e-3, (got, ref)
        print("LOSS_MATCH", got, ref)
        """)
    assert "LOSS_MATCH" in _run(prog)


def test_dryrun_single_cell_multi_pod():
    """One full dry-run cell on the 2×16×16 multi-pod mesh (512 devices):
    lower + compile must succeed and report roofline terms."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "gpt2-124m",
         "--shape", "train_4k", "--mesh", "multi"],
        capture_output=True, text=True, cwd=REPO, timeout=560,
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert out.returncode == 0, (out.stdout[-1000:], out.stderr[-2000:])
    assert "OK" in out.stdout


def test_compressed_grad_sync_reduces_dcn_bytes():
    """int8+EF cross-pod sync must cut cross-pod collective bytes vs fp32
    psum (measured from the compiled HLO, not claimed)."""
    prog = textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.hlo_analysis import analyze_hlo
        from repro.launch.mesh import make_mesh
        from repro.optim.compression import cross_pod_sync, init_error_feedback
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        grads = {"w": jnp.ones((256, 256), jnp.float32)}
        err = init_error_feedback(grads)

        def against(compress):
            def f(g, e):
                return cross_pod_sync(g, e, mesh, compress=compress)
            with mesh:
                c = jax.jit(f).lower(grads, err).compile()
            return analyze_hlo(c.as_text()).total_collective_bytes

        comp = against(True)
        plain = against(False)
        assert comp < plain, (comp, plain)
        print("BYTES", comp, plain)
        """)
    assert "BYTES" in _run(prog)
