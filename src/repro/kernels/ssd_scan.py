"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

The XLA-level SSD in ``repro.models.ssm`` materializes (B, nc, Q, Q, nh)
decay/score tensors in HBM — the dominant memory-roofline cost of the SSM
archs. This kernel fuses the whole chunk computation in VMEM: the (Q, Q)
intra-chunk matrices never leave the core, and the recurrent (nh_b, hp, N)
state is carried in fp32 VMEM scratch across the sequential chunk dimension
of the grid (TPU grids iterate the last axis innermost, so scratch persists
chunk-to-chunk for a fixed (batch, head-block)).

Grid: (B, nh_blocks, n_chunks); each step loops over its heads with 2-D
matmuls. Per-step VMEM at (Q=128, nh_b=8, hp=64, N=128): x 256 KiB + B/C
128 KiB + per-head (Q,Q) fp32 64 KiB + state 256 KiB, plus the dt and
cum (Q, 1) columns, which pad to 128 lanes (512 KiB each), all
double-buffered. A whole-head block of mamba2-130m's 24 heads does not
fit VMEM (compile check), so 8 heads per block is the default.

Oracle: ``repro.kernels.ref.ssd_ref`` (naive sequential recurrence).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, y_ref,
                state_scr, *, chunk: int, nh_b: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    Bm = b_ref[0].astype(jnp.float32)                   # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)                   # (Q, N)
    # G[i, j] = C_i . B_j, shared by every head of the block
    G = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols

    for h in range(nh_b):                               # static unroll
        x = x_ref[0, h].astype(jnp.float32)             # (Q, hp)
        dt = dt_ref[0, h]                               # (Q, 1)
        cum_c = cumc_ref[0, h]                          # (Q, 1) in-chunk
        cum_r = cumr_ref[0, h]                          # (1, Q) decay
        seg_total = cum_r[:, chunk - 1:]                # (1, 1)

        # ---- intra-chunk (matmul form): L[i,j] = exp(cum_i - cum_j) ----
        L = jnp.exp(jnp.where(causal, cum_c - cum_r, -jnp.inf))
        y = jax.lax.dot_general(G * L, x * dt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

        # ---- inter-chunk: contribution of the carried state ----
        state = state_scr[h]                            # (hp, N)
        y += jnp.exp(cum_c) * jax.lax.dot_general(
            Cm, state, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (Q, hp)

        # ---- state update ----
        decay_to_end = jnp.exp(seg_total - cum_c) * dt  # (Q, 1)
        upd = jax.lax.dot_general(x * decay_to_end, Bm,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        state_scr[h] = state * jnp.exp(seg_total) + upd  # (hp, N)
        y_ref[0, h] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, A, B_, C_, *, chunk: int = 128, nh_block: int = 8,
             interpret: bool = False):
    """x: (B, S, nh, hp); dt: (B, S, nh) (softplus-ed); A: (nh,) negative;
    B_, C_: (B, S, N). Returns y: (B, S, nh, hp). S % chunk == 0.

    The kernel works head-major: per-head vectors enter as (chunk, 1)
    columns and (1, chunk) rows, so every block's last two dims fit the TPU
    tiling when ``nh_block`` is nh or a multiple of 8 and ``chunk`` is S or
    a multiple of 128. The in-chunk cumulative decay (a cumsum, which
    Mosaic does not lower) is computed here, outside the kernel."""
    Bb, S, nh, hp = x.shape
    N = B_.shape[-1]
    nh_block = min(nh_block, nh)
    assert S % chunk == 0 and nh % nh_block == 0, (S, chunk, nh, nh_block)
    nc = S // chunk
    dt = dt.astype(jnp.float32).transpose(0, 2, 1)              # (B, nh, S)
    dA = dt * A.astype(jnp.float32)[None, :, None]
    cum = jnp.cumsum(dA.reshape(Bb, nh, nc, chunk), axis=-1).reshape(
        Bb, nh, S)
    grid = (Bb, nh // nh_block, nc)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, nh_b=nh_block)
    col = pl.BlockSpec((1, nh_block, chunk, 1), lambda b, h, c: (b, h, c, 0))
    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, nh_block, chunk, hp),
                         lambda b, h, c: (b, h, c, 0)),
            col,                                                # dt
            col,                                                # cum
            pl.BlockSpec((1, nh_block, 1, chunk),
                         lambda b, h, c: (b, h, 0, c)),         # cum row
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, nh_block, chunk, hp),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb, nh, S, hp), x.dtype),
        scratch_shapes=[pltpu.VMEM((nh_block, hp, N), jnp.float32)],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt[..., None], cum[..., None],
      cum[:, :, None, :], B_, C_)
    return y.transpose(0, 2, 1, 3)
