"""Pallas TPU flash attention, forward and backward, MXU-tiled, online softmax.

Layout: q, k, v are (X, S, W) with the heads of a row side by side along
W, each ``head_dim`` wide: the model's (B, S, H * hd) as it leaves the
projections, or heads folded into X, (B * H, S, hd). A block is (1, b, L)
of ``L = n * head_dim`` lanes holding n whole heads: one head when it is
128 lanes or more, else as many as fill 128 lanes (two of gpt2's 64), so
the model's layout is read in place, with no transpose and no padding of
a 64-wide head to the 128 lanes. Inside a block each head is a lane mask:
its matmuls take the other heads' lanes as zeros, which costs the MXU
nothing at a contraction or output width of 128 or less.

Grids: forward and dq (X * G, q_blocks, kv_blocks), dk/dv (X * G,
kv_blocks, q_blocks), G = W / L lane groups; the last dim is innermost and
sequential, so the fp32 running max / sum / accumulators live in VMEM
scratch across it. The score and probability tiles exist only in VMEM;
HBM holds q, k, v, out, their gradients and the (X * G, n, S) fp32 rows of
logsumexp and of ``delta = rowsum(dO * O)``.

Precision: the MXU is fed the inputs' dtype (bf16 in training) with fp32
accumulation; ``p`` and ``ds`` are cast to it before their matmuls. The
running max and sum, ``lse``, ``delta`` and the accumulators stay fp32.

Causality: a block wholly above the diagonal is skipped, its compute by
``pl.when`` and its fetch by index maps that clamp a skipped step to the
block already resident (the pipeline issues no copy when a block index
repeats); only blocks crossing the diagonal build the element mask. A
crossing block of equal q and kv sizes (every one ``pick_block`` gives)
is cut into bands ``sub_block`` wide where it holds two or more: each
band runs from the block's edge to the diagonal and is masked whole
(``_diagonal_bands``), so only the sub-tiles on or below the diagonal are
computed: a single block of 1024 computes 36 of its 64 sub-tiles of 128.
One masked band a row of sub-tiles beat separate unmasked sub-tiles on
the chip: the forward's running max, sum and accumulator are then
updated once a band (PERF.md, "Findings").

Block sizes come from the shape (``pick_block``): the largest multiple of
128 dividing S, up to ``MAX_BLOCK``, whose VMEM estimate fits the budget.
At (block_q, block_k, L) = (1024, 1024, 128) with bf16 inputs the dk/dv
step, the largest, holds ~16 MiB of fp32 score-sized tiles, ~4 MiB of
their bf16 casts and ~4 MiB of double-buffered blocks and scratch: each
call sets the scoped VMEM limit to twice its estimate (at least 32 MiB),
well inside the v5e core's 128 MiB.

Validated against ``repro.kernels.ref.attention_ref`` and ``jax.vjp`` of
naive attention in interpret mode (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# largest block tried first; the chip chose it from 256, 512 and 1024 at
# the gpt2-124m training shape (PERF.md, "Findings")
MAX_BLOCK = 1024
VMEM_BUDGET = 48 * 2**20     # of the v5e core's 128 MiB VMEM
# width of the bands a diagonal block is cut into; the chip chose it
# from 128 and 256 at the gpt2-124m training shape (PERF.md, "Findings")
SUB_BLOCK = 128


def lanes(width: int, head_dim: int) -> int:
    """Lanes of a block over rows ``width`` wide: one head of 128 lanes or
    more, else as many whole heads as fill 128 lanes, where the row holds
    a whole number of such groups (else one head, the whole row)."""
    if head_dim < LANES and LANES % head_dim == 0 and width % LANES == 0:
        return LANES
    return head_dim


def _vmem_bytes(block_q: int, block_k: int, lanes_: int, itemsize: int) -> int:
    """Estimate of the dk/dv step's VMEM (the largest of the three
    kernels): four fp32 score-sized tiles and two casts of them, the six
    double-buffered input blocks, two outputs and two fp32 scratches."""
    lp = -(-lanes_ // LANES) * LANES
    tiles = block_q * block_k * (4 * 4 + 2 * itemsize)
    blocks = 2 * (2 * block_q + 4 * block_k) * lp * itemsize
    rows = 2 * 2 * 8 * block_q * 4
    return tiles + blocks + rows + 2 * block_k * lp * 4


def pick_block(S: int, lanes_: int, itemsize: int) -> int:
    """Block size along the sequence for S positions and blocks
    ``lanes_`` wide."""
    fits = [b for b in range(LANES, min(S, MAX_BLOCK) + 1, LANES)
            if S % b == 0
            and _vmem_bytes(b, b, lanes_, itemsize) <= VMEM_BUDGET]
    return fits[-1] if fits else min(S, LANES)


def sub_block(block_q: int, block_k: int, sub=None):
    """Width of the bands a block crossing the diagonal is cut into
    (``sub``, default ``SUB_BLOCK``), or None where it is computed whole:
    blocks of unequal sizes, and blocks that do not hold two or more
    bands."""
    sub = sub or SUB_BLOCK
    if block_q != block_k or block_q % sub or block_q < 2 * sub:
        return None
    return sub


def _geometry(shape_q, shape_k, dtype, head_dim, block_q, block_k, sub):
    X, S, W = shape_q
    Sk = shape_k[1]
    hd = head_dim or W
    L = lanes(W, hd)
    assert W % L == 0 and L % hd == 0, (W, hd, L)
    itemsize = jnp.dtype(dtype).itemsize
    block_q = min(block_q or pick_block(S, L, itemsize), S)
    block_k = min(block_k or pick_block(Sk, L, itemsize), Sk)
    assert S % block_q == 0 and Sk % block_k == 0, (S, Sk, block_q, block_k)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=max(32 * 2**20,
                             2 * _vmem_bytes(block_q, block_k, L, itemsize)))
    return (hd, L, W // L, block_q, block_k, sub_block(block_q, block_k, sub),
            params)


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------
def _kv_block_seen(iq, ik, block_q: int, block_k: int):
    """K/V block fetched at step (iq, ik) under causality: ik up to the last
    block that q block iq attends to, and that block for the steps after."""
    return jnp.minimum(ik, (iq * block_q + block_q - 1) // block_k)


def _q_block_seen(ik, iq, block_q: int, block_k: int):
    """Q-side block fetched at step (ik, iq) of the dk/dv kernel under
    causality: the first q block that attends to kv block ik for the steps
    before it, iq from there on."""
    return jnp.maximum(iq, (ik * block_k) // block_q)


def _diagonal_bands(block: int, sub: int, *, along_q: bool = False):
    """The bands of a diagonal block ``sub`` wide, as (q slice, kv slice)
    from the block's start: each row of sub-tiles from the block's left
    edge to the diagonal, or with ``along_q`` (the dk/dv kernel, which
    accumulates per kv row) each column from the diagonal to the bottom
    edge. Together they hold each sub-tile on or below the diagonal
    once."""
    bands = []
    for lo in range(0, block, sub):
        tile = slice(lo, lo + sub)
        bands.append((slice(lo, block), tile) if along_q
                     else (tile, slice(0, lo + sub)))
    return bands


def _run_blocks(piece, q_start, k_start, block_q: int, block_k: int,
                causal: bool, sub, *, along_q: bool = False):
    """Run ``piece(q slice, kv slice, masked)`` over one (q block, kv
    block) pair: not at all above the diagonal; where the block crosses
    it, with the element mask, the whole tile or, cut ``sub`` wide, each
    of ``_diagonal_bands``; below it the whole tile without the mask."""
    whole = (slice(0, block_q), slice(0, block_k))
    if not causal:
        piece(*whole, False)
        return

    def diagonal():
        bands = ([whole] if sub is None
                 else _diagonal_bands(block_q, sub, along_q=along_q))
        for qs, ks in bands:
            piece(qs, ks, True)

    needed = k_start <= q_start + block_q - 1
    crosses = k_start + block_k - 1 > q_start
    pl.when(needed & crosses)(diagonal)
    pl.when(needed & jnp.logical_not(crosses))(lambda: piece(*whole, False))


def _causal_mask(shape, q_start, k_start, *, q_axis: int):
    """True where a score tile's key does not follow its query."""
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    return qpos >= kpos


class _Heads:
    """The n heads of a block of L lanes, each a lane mask."""

    def __init__(self, n: int, hd: int):
        self.n, self.hd = n, hd

    def of(self, x, h: int):
        """x with every lane outside head h zeroed."""
        if self.n == 1:
            return x
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[1]), 1)
        return jnp.where(lane // self.hd == h, x, jnp.zeros_like(x))

    def put(self, x, h: int, value):
        """x with head h's lanes replaced by ``value``."""
        if self.n == 1:
            return value
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, x.shape[1]), 1)
        return jnp.where(lane // self.hd == h, value, x)


def _scaled(x, scale: float):
    """q times the softmax scale, rounded once to q's dtype for the MXU."""
    return (x.astype(jnp.float32) * scale).astype(x.dtype)


def _dot(a, b):
    """(m, k) @ (k, n), fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """(m, d) @ (n, d).T, fp32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _col_to_row(col):
    """(n, 1) -> (1, n), through a lane-aligned transpose."""
    return jnp.broadcast_to(col, (col.shape[0], LANES)).T[:1]


def _row_to_col(row):
    """(1, n) -> (n, 1), through a lane-aligned transpose."""
    return jnp.broadcast_to(row, (LANES, row.shape[1])).T[:, :1]


def _specs(G: int, L: int):
    """Block specs over (X, S, W) and the (X * G, n, S) rows, for a grid
    whose first axis runs over X * G and whose (position) block index the
    caller maps."""
    def block(b, pos):
        return pl.BlockSpec((1, b, L), lambda xg, i, j: (xg // G, pos(i, j),
                                                         xg % G))

    def row(b, n, pos):
        return pl.BlockSpec((1, n, b), lambda xg, i, j: (xg, 0, pos(i, j)))
    return block, row


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale: float, block_q: int,
                block_k: int, sub, causal: bool, kv_blocks: int,
                heads: _Heads, stats: bool):
    if stats:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        o_ref, m_scr, l_scr, acc_scr = refs
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    def piece(qs, ks, masked: bool):
        q = _scaled(q_ref[0, qs], scale)                   # (pq, L)
        k, v = k_ref[0, ks], v_ref[0, ks]                  # (pk, L)
        mask = (_causal_mask((q.shape[0], k.shape[0]), q_start + qs.start,
                             k_start + ks.start, q_axis=0)
                if masked else None)
        for h in range(heads.n):
            s = _dot_nt(heads.of(q, h), k)                 # (pq, pk) fp32
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_scr[h, qs]                          # (pq, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h, qs] = (l_scr[h, qs] * corr
                            + jnp.sum(p, axis=1, keepdims=True))
            acc = acc_scr[qs]
            acc_scr[qs] = (heads.put(acc, h, acc * corr)
                           + _dot(p.astype(v.dtype), heads.of(v, h)))
            m_scr[h, qs] = m_new

    _run_blocks(piece, q_start, k_start, block_q, block_k, causal, sub)

    @pl.when(ik == kv_blocks - 1)
    def _finish():
        acc = acc_scr[...]
        out = acc
        for h in range(heads.n):
            l = jnp.maximum(l_scr[h], 1e-30)
            out = heads.put(out, h, acc / l)
            if stats:
                lse_ref[0, h:h + 1, :] = _col_to_row(m_scr[h] + jnp.log(l))
        o_ref[0] = out.astype(o_ref.dtype)


def _fwd_call(q, k, v, *, causal, scale, head_dim, block_q, block_k, sub,
              interpret, stats: bool):
    X, S, W = q.shape
    Sk = k.shape[1]
    hd, L, G, block_q, block_k, sub, params = _geometry(
        q.shape, k.shape, q.dtype, head_dim, block_q, block_k, sub)
    n = L // hd
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block, row = _specs(G, L)
    kv = ((lambda iq, ik: _kv_block_seen(iq, ik, block_q, block_k))
          if causal else (lambda iq, ik: ik))
    out_specs = [block(block_q, lambda iq, ik: iq)]
    out_shape = [jax.ShapeDtypeStruct((X, S, W), q.dtype)]
    if stats:
        out_specs.append(row(block_q, n, lambda iq, ik: iq))
        out_shape.append(jax.ShapeDtypeStruct((X * G, n, S), jnp.float32))
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k,
        sub=sub, causal=causal, kv_blocks=Sk // block_k, heads=_Heads(n, hd),
        stats=stats)
    out = pl.pallas_call(
        kernel,
        grid=(X * G, S // block_q, Sk // block_k),
        in_specs=[block(block_q, lambda iq, ik: iq), block(block_k, kv),
                  block(block_k, kv)],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, block_q, 1), jnp.float32),
                        pltpu.VMEM((n, block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, L), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_fwd_stats" if stats else "flash_attention_fwd",
    )(q, k, v)
    return tuple(out) if stats else out[0]


def flash_attention_fwd(q, k, v, *, causal: bool = True, scale=None,
                        head_dim=None, block_q=None, block_k=None, sub=None,
                        interpret: bool = False):
    """q, k, v: (X, S, W), heads ``head_dim`` wide side by side along W
    (default: one head, W). Blocks default to ``pick_block`` of the
    shape, the bands of a diagonal block to ``sub_block``; scale to
    ``head_dim ** -0.5``."""
    return _fwd_call(q, k, v, causal=causal, scale=scale, head_dim=head_dim,
                     block_q=block_q, block_k=block_k, sub=sub,
                     interpret=interpret, stats=False)


def flash_attention_fwd_stats(q, k, v, *, causal: bool = True, scale=None,
                              head_dim=None, block_q=None, block_k=None,
                              sub=None, interpret: bool = False):
    """Forward + logsumexp stats (for the backward kernels).
    Returns (out (X, S, W), lse (X * G, n, S) fp32): per lane group of n
    heads, one lane-dense row per head, so a block of them is (n, block_q)."""
    return _fwd_call(q, k, v, causal=causal, scale=scale, head_dim=head_dim,
                     block_q=block_q, block_k=block_k, sub=sub,
                     interpret=interpret, stats=True)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float, block_q: int,
                block_k: int, sub, causal: bool, q_blocks: int,
                heads: _Heads):
    """dk, dv for one kv block, accumulated over the q blocks (innermost).
    Works on transposed tiles, (block_k, block_q), so lse and delta enter
    as rows and every matmul is a plain or an rhs-transposed one."""
    ik = pl.program_id(1)
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q_start = iq * block_q
    k_start = ik * block_k

    def piece(qs, ks, masked: bool):
        q = _scaled(q_ref[0, qs], scale)                  # (pq, L)
        k, v, do = k_ref[0, ks], v_ref[0, ks], do_ref[0, qs]
        mask = (_causal_mask((k.shape[0], q.shape[0]), q_start + qs.start,
                             k_start + ks.start, q_axis=1)
                if masked else None)
        for h in range(heads.n):
            st = _dot_nt(heads.of(k, h), q)               # (pk, pq) fp32
            if masked:
                st = jnp.where(mask, st, NEG_INF)
            pt = jnp.exp(st - lse_ref[0, h:h + 1, qs])    # lse: (1, pq)
            dv_scr[ks] += _dot(pt.astype(do.dtype), heads.of(do, h))
            dpt = _dot_nt(heads.of(v, h), do)             # (pk, pq)
            dst = pt * (dpt - delta_ref[0, h:h + 1, qs])
            dk_scr[ks] += _dot(dst.astype(q.dtype), heads.of(q, h))

    _run_blocks(piece, q_start, k_start, block_q, block_k, causal, sub,
                along_q=True)

    @pl.when(iq == q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, lse_scr, delta_scr, *, scale: float, block_q: int,
               block_k: int, sub, causal: bool, kv_blocks: int,
               heads: _Heads):
    """dq for one q block, accumulated over the kv blocks (innermost)."""
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        for h in range(heads.n):
            lse_scr[h] = _row_to_col(lse_ref[0, h:h + 1, :])
            delta_scr[h] = _row_to_col(delta_ref[0, h:h + 1, :])

    q_start = iq * block_q
    k_start = ik * block_k

    def piece(qs, ks, masked: bool):
        q = _scaled(q_ref[0, qs], scale)
        k, v, do = k_ref[0, ks], v_ref[0, ks], do_ref[0, qs]
        mask = (_causal_mask((q.shape[0], k.shape[0]), q_start + qs.start,
                             k_start + ks.start, q_axis=0)
                if masked else None)
        for h in range(heads.n):
            s = _dot_nt(heads.of(q, h), k)                # (pq, pk) fp32
            if masked:
                s = jnp.where(mask, s, NEG_INF)
            p = jnp.exp(s - lse_scr[h, qs])
            dp = _dot_nt(heads.of(do, h), v)
            ds = p * (dp - delta_scr[h, qs])
            dq_scr[qs] += _dot(ds.astype(k.dtype), heads.of(k, h))

    _run_blocks(piece, q_start, k_start, block_q, block_k, causal, sub)

    @pl.when(ik == kv_blocks - 1)
    def _finish():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, dout, *, causal: bool = True,
                        scale=None, head_dim=None, block_q=None, block_k=None,
                        sub=None, interpret: bool = False):
    """Flash backward: (dq, dk, dv), each shaped as q, k, v. ``lse`` from
    flash_attention_fwd_stats. Two pallas_calls: dk/dv with the q dim
    innermost, dq with the kv dim innermost, each accumulating in VMEM."""
    X, S, W = q.shape
    Sk = k.shape[1]
    hd, L, G, block_q, block_k, sub, params = _geometry(
        q.shape, k.shape, q.dtype, head_dim, block_q, block_k, sub)
    n = L // hd
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    delta = jnp.sum((dout.astype(jnp.float32) * out.astype(jnp.float32))
                    .reshape(X, S, G, n, hd), axis=-1)    # (X, S, G, n)
    delta = delta.transpose(0, 2, 3, 1).reshape(X * G, n, S)
    block, row = _specs(G, L)
    heads = _Heads(n, hd)
    if causal:
        qi = lambda ik, iq: _q_block_seen(ik, iq, block_q, block_k)  # noqa: E731
        ki = lambda iq, ik: _kv_block_seen(iq, ik, block_q, block_k)  # noqa: E731
    else:
        qi = lambda ik, iq: iq  # noqa: E731
        ki = lambda iq, ik: ik  # noqa: E731

    q_blk, k_blk = block(block_q, qi), block(block_k, lambda ik, iq: ik)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, sub=sub, causal=causal,
                          q_blocks=S // block_q, heads=heads),
        grid=(X * G, Sk // block_k, S // block_q),
        in_specs=[q_blk, k_blk, k_blk, q_blk, row(block_q, n, qi),
                  row(block_q, n, qi)],
        out_specs=[k_blk, k_blk],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, L), jnp.float32),
                        pltpu.VMEM((block_k, L), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, dout, lse, delta)

    q_blk, k_blk = block(block_q, lambda iq, ik: iq), block(block_k, ki)
    q_row = row(block_q, n, lambda iq, ik: iq)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, sub=sub, causal=causal,
                          kv_blocks=Sk // block_k, heads=heads),
        grid=(X * G, S // block_q, Sk // block_k),
        in_specs=[q_blk, k_blk, k_blk, q_blk, q_row, q_row],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, L), jnp.float32),
                        pltpu.VMEM((n, block_q, 1), jnp.float32),
                        pltpu.VMEM((n, block_q, 1), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv
