"""Loop-aware HLO analyzer: exact flops on known programs, trip-count
recovery, collective accounting, slicing-aware traffic."""
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hlo_analysis import analyze_hlo, parse_module


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_matmul_flops_exact():
    x = jnp.zeros((64, 128), jnp.float32)
    w = jnp.zeros((128, 256), jnp.float32)
    cost = analyze_hlo(_compile_text(lambda a, b: a @ b, x, w))
    assert cost.flops == 2 * 64 * 128 * 256


def test_scan_trip_count_multiplies_flops():
    x = jnp.zeros((32, 64), jnp.float32)
    w = jnp.zeros((64, 64), jnp.float32)

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y
    cost = analyze_hlo(_compile_text(f, x, w))
    assert cost.flops == 7 * 2 * 32 * 64 * 64
    assert 7 in cost.trip_counts.values()


def test_nested_scans_multiply():
    x = jnp.zeros((16, 32), jnp.float32)
    w = jnp.zeros((32, 32), jnp.float32)

    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y
    cost = analyze_hlo(_compile_text(f, x, w))
    assert cost.flops == 5 * 3 * 2 * 16 * 32 * 32


def test_tuple_types_with_index_comments_parse():
    """Regression: /*index=N*/ comments inside while tuple types must not
    break op parsing (observed in large real modules)."""
    hlo = textwrap.dedent("""\
    HloModule m
    %body (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
      %p = (s32[], f32[4,4]) parameter(0)
      ROOT %t = (s32[], f32[4,4]) tuple(%p)
    }
    %cond (p: (s32[], f32[4,4])) -> pred[] {
      %p.1 = (s32[], f32[4,4]) parameter(0)
      %c = s32[] constant(11)
      ROOT %cmp = pred[] compare(%c, %c), direction=LT
    }
    ENTRY %main () -> f32[4,4] {
      %init = (s32[], f32[4,4], /*index=2*/f32[8,8]) tuple()
      %w = (s32[], f32[4,4]) while(%init), condition=%cond, body=%body
      ROOT %g = f32[4,4] get-tuple-element(%w), index=1
    }
    """)
    comps, ops = parse_module(hlo)
    whiles = [o for c in comps.values() for o in c.ops if o.opcode == "while"]
    assert len(whiles) == 1
    cost = analyze_hlo(hlo)
    assert cost.trip_counts.get("body") == 11


def test_slicing_traffic_counts_window_not_operand():
    big = jnp.zeros((1024, 256), jnp.float32)  # 1 MiB

    def f(x):
        return jax.lax.dynamic_slice(x, (0, 0), (8, 256)) * 2.0
    cost = analyze_hlo(_compile_text(f, big))
    # traffic must be ~KBs (window), not ~MBs (whole operand)
    assert cost.bytes_accessed < 200_000, cost.bytes_accessed


def test_collectives_counted_with_trips():
    """Runs in a subprocess with 8 host devices (this process must keep 1)."""
    prog = textwrap.dedent("""\
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.hlo_analysis import analyze_hlo
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("model",))
        def f(x, w):
            def body(c, _):
                # contraction over the model-sharded dim -> all-reduce that
                # depends on the carry (cannot be hoisted out of the loop)
                y = jnp.tanh(c @ w)
                y = jax.lax.with_sharding_constraint(
                    y, NamedSharding(mesh, P(None, "model")))
                return y, None
            y, _ = jax.lax.scan(body, x, None, length=6)
            return y.sum()
        xs = jax.ShapeDtypeStruct((32, 64), jnp.float32,
                                  sharding=NamedSharding(mesh, P(None, "model")))
        ws = jax.ShapeDtypeStruct((64, 64), jnp.float32,
                                  sharding=NamedSharding(mesh, P("model", None)))
        cost = analyze_hlo(jax.jit(f).lower(xs, ws).compile().as_text())
        counts = cost.collective_counts
        assert sum(counts.values()) >= 6, counts
        print("OK", counts)
        """)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, cwd="/root/repo", timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
