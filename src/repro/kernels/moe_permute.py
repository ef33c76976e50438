"""Pallas TPU kernels for the MoE layer's row permute (``models/moe.py``).

The layer sorts its N = T * k (token, expert) pairs by expert and gathers
each pair's token row into that order (XLA's gather: the source is small and
the gather runs near the bandwidth). What comes back is the expensive part:
the combine gathers the experts' N output rows from a large source and sums
each token's k of them with its routing weights, and the backward passes
move the same rows the other way. These kernels do those moves:

* ``combine``: y (T, d), y[t] = sum_j w[t, j] * src[inv[t, j]], summed in
  float32. Within an expert the sorted rows keep token order, so a block of
  tokens' rows lie in one contiguous run per expert. The kernel copies those
  runs by whole chunks of ``chunk_rows`` rows (the next block's while it sums
  this one), flattens them in VMEM and sums each token's k rows there, so
  no (N, d) array in token order is written. With unit weights it is the
  dispatch's transpose.
* ``combine_bwd``: the combine's transpose in sorted order, one pass:
  d_src[r] = w_s[r] * g[tok[r]] and dw_s[r] = <src[r], g[tok[r]]>, from a
  flat copy of g held in VMEM (``T * d * itemsize`` bytes, 32 MiB at the
  training cell).
* ``flatten``: (T, d) -> the flat words that ``combine_bwd`` holds.

Every output row is written once, by the grid step that owns it: nothing
scatters.

Rows and tiles. XLA keeps a (rows, d) array in (8, 128) tiles, 16-bit rows
packed in pairs, and a DMA moves whole tiles only, so one row cannot be
fetched by itself (Mosaic refuses the slice). The kernels gather inside VMEM
from a *flat* copy in which each row is one aligned (m, 128) run of 32-bit
words, m = d * itemsize / 512; for 16-bit data word i of a row holds its
lanes of chunk 2i in the low half and of chunk 2i + 1 in the high half.
Flattening 8 * pack rows is a few strided stores and integer operations.

Arithmetic: sums and products are float32 and the rows are cast to the
data's dtype, as the XLA formulation did.

Shapes: d a multiple of 128 * pack and token and row counts that fill whole
blocks go straight in (the benchmark's T = 16384, d = 1024); other shapes
are padded (``pad_dim``, and the row and token counts here).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
U32 = jnp.uint32
_LO, _HI = 0xFFFF, 0xFFFF0000
ROWS = 1024                 # combine_bwd block: sorted rows a grid step
TOKENS = 512                # combine block: tokens a grid step
UNROLL = 8                  # rows or tokens a loop turn
SMEM_BLOCK = 1024           # 1-D int blocks in SMEM are whole T(1024) tiles
VMEM_LIMIT = 100 * 2**20    # of the v5e core's 128 MiB


def pack(dtype) -> int:
    """Rows of ``dtype`` a 32-bit word holds: 1 for 32-bit, 2 for 16-bit."""
    return 4 // jnp.dtype(dtype).itemsize


def words(d: int, dtype) -> int:
    """Word rows (of 128 lanes) of one flat row."""
    return d * jnp.dtype(dtype).itemsize // (4 * LANES)


def _round(n: int, unit: int) -> int:
    return -(-n // unit) * unit


def pad_dim(d: int, dtype) -> int:
    """The width the kernels take for rows ``d`` wide."""
    return _round(d, LANES * pack(dtype))


def _u(v):
    return jnp.uint32(v)


def _params():
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=VMEM_LIMIT)


# ------------------------------------------------------------------ layouts
def _flatten_rows(src, dst, groups, m: int, p: int):
    """Rows of ``src`` (tiles) into flat words of ``dst``, 8 * p rows a
    group: row r's word i lands at dst row r * m + i."""
    rows = 8 * p

    def body(g, c):
        r0 = pl.multiple_of(g * rows, rows)
        base = g * rows * m
        for i in range(m):
            if p == 1:
                dst[pl.ds(base + i, 8, stride=m), :] = pltpu.bitcast(
                    src[pl.ds(r0, 8), pl.ds(i * LANES, LANES)], U32)
                continue
            a = pltpu.bitcast(src[pl.ds(r0, 16), pl.ds(2 * i * LANES, LANES)], U32)
            b = pltpu.bitcast(src[pl.ds(r0, 16), pl.ds((2 * i + 1) * LANES, LANES)],
                              U32)
            # a word of a holds rows (2s, 2s + 1) of chunk 2i: even rows
            # keep the low halves, odd rows the high ones
            dst[pl.ds(base + i, 8, stride=2 * m), :] = (a & _u(_LO)) | (b << _u(16))
            dst[pl.ds(base + m + i, 8, stride=2 * m), :] = (a >> _u(16)) | (b & _u(_HI))
        return c
    lax.fori_loop(0, groups, body, 0)


def _parts(v, p: int):
    """A flat row's words as float32: the chunks 0, p, 2p, ... then (for
    16-bit data) 1, 3, 5, ..., each (m, 128)."""
    if p == 1:
        return [lax.bitcast_convert_type(v, jnp.float32)]
    return [lax.bitcast_convert_type(v << _u(16), jnp.float32),
            lax.bitcast_convert_type(v & _u(_HI), jnp.float32)]


def _row(ref, r, m: int):
    """Flat row r of a word ref: (m, 128)."""
    return ref[pl.ds(pl.multiple_of(r * m, m), m), :]


def _store_f32_row(yf, r, parts, c: int):
    """Row r's float32 chunks into ``yf`` (rows * c, 128), chunk j of the
    row at yf row r * c + j."""
    p = len(parts)
    m = c // p
    for o, part in enumerate(parts):
        yf[pl.ds(r * c + o, m, stride=p), :] = part


def _f32_to_tiles(yf, out, c: int):
    """Float32 rows of ``yf`` into ``out``'s tiles, cast to its dtype, 16
    rows at a time."""
    def body(g, _):
        r0 = pl.multiple_of(g * 16, 16)
        for j in range(c):
            v = yf[pl.ds(g * 16 * c + j, 16, stride=c), :]
            out[pl.ds(r0, 16), pl.ds(j * LANES, LANES)] = v.astype(out.dtype)
        return _
    lax.fori_loop(0, out.shape[0] // 16, body, 0)


def _each(n: int, body):
    """body(i) for i in [0, n), ``UNROLL`` of them a loop turn (n a multiple
    of 16), so the scheduler can overlap one row's loads with another's
    stores."""
    u = UNROLL if n % UNROLL == 0 else 1

    def turn(t, c):
        for j in range(u):
            body(t * u + j)
        return c
    lax.fori_loop(0, n // u, turn, 0)


def _smem_blocks(a, nb: int):
    """A (nb * L,) int or float array as ``nb`` SMEM blocks of whole
    T(1024) tiles: (nb * Lp,), Lp = L rounded up to ``SMEM_BLOCK``."""
    a = a.reshape(nb, -1)
    lp = _round(a.shape[1], SMEM_BLOCK)
    return jnp.pad(a, ((0, 0), (0, lp - a.shape[1]))).reshape(-1), lp


# ------------------------------------------------------------------ flatten
def _flatten_kernel(x_ref, o_ref, *, m, p):
    _flatten_rows(x_ref, o_ref, x_ref.shape[0] // (8 * p), m, p)


def flatten(x, *, interpret=False):
    """(T, d) rows, d a multiple of ``pad_dim``, -> their flat words
    (Tp * m, 128), T padded to whole blocks of rows."""
    T, d = x.shape
    m, p = words(d, x.dtype), pack(x.dtype)
    rb = min(TOKENS, _round(T, 16))
    T = _round(T, rb)
    x = jnp.pad(x, ((0, T - x.shape[0]), (0, 0)))
    return pl.pallas_call(
        functools.partial(_flatten_kernel, m=m, p=p), grid=(T // rb,),
        in_specs=[pl.BlockSpec((rb, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb * m, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T * m, LANES), U32),
        name="moe_permute_flatten", interpret=interpret)(x)


# ------------------------------------------------------------------ combine
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["meta", "pos"],
                   meta_fields=["tokens", "max_chunks", "meta_len", "pos_len"])
@dataclasses.dataclass(frozen=True)
class CombinePlan:
    """Where a block of ``tokens`` tokens finds its rows in the sorted
    source: ``meta`` per block (SMEM blocks of ``meta_len``): the count of
    chunks to copy, then each chunk's index in the source; ``pos``: each
    (token, j) pair's row in the block's staging, token-major."""
    meta: jnp.ndarray
    pos: jnp.ndarray
    tokens: int
    max_chunks: int
    meta_len: int
    pos_len: int


def chunk_rows(dtype) -> int:
    """Rows of ``dtype`` a chunk copy moves: 8 rows of 32-bit words."""
    return 8 * pack(dtype)


def _pick(table, idx, n: int):
    """table[b, idx[b, i]] for a (nb, n) table and (nb, L) indices, as a
    one-hot sum. On a v5e XLA gathers single elements at ~8 ns each (1.1 ms
    for the training cell's 131,072 pairs); this is one elementwise
    fusion."""
    hit = idx[..., None] == jnp.arange(n)
    return jnp.sum(jnp.where(hit, table[:, None, :], 0), axis=-1)


def combine_plan(top_e, inv, num_experts: int, dtype) -> CombinePlan:
    """The combine's copy plan from the routing: ``top_e`` (T, k) expert
    ids, ``inv`` (T * k,) the sorted row of each pair (token-major)."""
    T, k = top_e.shape
    E = num_experts
    tb = min(TOKENS, _round(T, 16))
    nb = -(-T // tb)
    ch = chunk_rows(dtype)
    te = jnp.pad(top_e, ((0, nb * tb - T), (0, 0)),
                 constant_values=E).reshape(nb, tb * k)
    cnt = jnp.sum(te[..., None] == jnp.arange(E), axis=1,
                  dtype=jnp.int32)                       # (nb, E) pairs
    sizes = jnp.sum(cnt, axis=0)
    lo = (jnp.cumsum(sizes) - sizes)[None] + jnp.cumsum(cnt, 0) - cnt
    c_lo = lo // ch                                      # first chunk
    n = jnp.where(cnt > 0, (lo + cnt - 1) // ch - c_lo + 1, 0)
    slot = jnp.cumsum(n, 1) - n                          # first staging chunk
    max_chunks = tb * k // ch + 2 * E
    q = jnp.arange(max_chunks)
    owner = jnp.sum(slot[:, None, :] + n[:, None, :] <= q[:, None],
                    axis=-1)                             # (nb, max_chunks)
    src = _pick(c_lo - slot, owner, E) + q
    meta = jnp.concatenate([jnp.sum(n, 1, keepdims=True), src], 1)
    meta, meta_len = _smem_blocks(meta.astype(jnp.int32), nb)
    r = jnp.pad(inv, (0, nb * tb * k - T * k)).reshape(nb, tb * k)
    pos = jnp.where(te < E, _pick((slot - c_lo) * ch, te, E) + r, 0)
    pos, pos_len = _smem_blocks(pos.astype(jnp.int32), nb)
    return CombinePlan(meta, pos, tb, max_chunks, meta_len, pos_len)


def _combine_kernel(mcur, mnxt, pos_ref, w_ref, src_hbm, y_ref, stg, stgf, yf,
                    sem, *, m, p, k, ch):
    b = pl.program_id(0)
    nb = pl.num_programs(0)

    def issue(meta, slot):
        def body(q, c):
            pltpu.make_async_copy(
                src_hbm.at[pl.ds(pl.multiple_of(meta[1 + q] * ch, ch), ch)],
                stg.at[slot, pl.ds(pl.multiple_of(q * ch, ch), ch)],
                sem.at[slot]).start()
            return c
        lax.fori_loop(0, meta[0], body, 0)

    @pl.when(b == 0)
    def _():
        issue(mcur, 0)

    @pl.when(b + 1 < nb)
    def _():
        issue(mnxt, (b + 1) % 2)
    slot = b % 2

    def wait(q, c):
        pltpu.make_async_copy(src_hbm.at[pl.ds(0, ch)], stg.at[slot, pl.ds(0, ch)],
                              sem.at[slot]).wait()
        return c
    lax.fori_loop(0, mcur[0], wait, 0)
    _flatten_rows(stg.at[slot], stgf, mcur[0], m, p)
    c = m * p

    def body(t):
        acc = None
        for j in range(k):
            wt = w_ref[t * k + j]
            parts = [wt * x for x in _parts(_row(stgf, pos_ref[t * k + j], m), p)]
            acc = parts if acc is None else [a + x for a, x in zip(acc, parts)]
        _store_f32_row(yf, t, acc, c)
    _each(y_ref.shape[0], body)
    _f32_to_tiles(yf, y_ref, c)


def combine(src, w, plan: CombinePlan, *, interpret=False):
    """y[t] = sum_j w[t, j] * src[inv[t, j]] in float32, cast to src's
    dtype: ``src`` (N, d) sorted rows, d a multiple of ``pad_dim``, ``w``
    (T, k) float32, ``plan`` from ``combine_plan``."""
    N, d = src.shape
    T, k = w.shape
    m, p = words(d, src.dtype), pack(src.dtype)
    ch = chunk_rows(src.dtype)
    src = jnp.pad(src, ((0, _round(N, ch) - N), (0, 0)))
    tb = plan.tokens
    nb = -(-T // tb)
    w_b, _ = _smem_blocks(jnp.pad(w.astype(jnp.float32).reshape(-1),
                                  (0, (nb * tb - T) * k)), nb)
    staged = plan.max_chunks * ch
    y = pl.pallas_call(
        functools.partial(_combine_kernel, m=m, p=p, k=k, ch=ch), grid=(nb,),
        in_specs=[
            pl.BlockSpec((plan.meta_len,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((plan.meta_len,), lambda i: (jnp.minimum(i + 1, nb - 1),),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((plan.pos_len,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((plan.pos_len,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tb, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb * tb, d), src.dtype),
        scratch_shapes=[pltpu.VMEM((2, staged, d), src.dtype),
                        pltpu.VMEM((staged * m, LANES), U32),
                        pltpu.VMEM((tb * m * p, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))],
        compiler_params=_params(), name="moe_permute_combine",
        interpret=interpret)(plan.meta, plan.meta, plan.pos, w_b, src)
    return y[:T]


# ------------------------------------------------------------------ combine's transpose
def _combine_bwd_kernel(tok_ref, w_ref, gf_hbm, src_ref, dsrc_ref, dw_ref, gf,
                        sf, yf, dots, sem, *, m, p):
    @pl.when(pl.program_id(0) == 0)
    def _():
        cp = pltpu.make_async_copy(gf_hbm, gf, sem.at[0])    # held throughout
        cp.start()
        cp.wait()
    R = src_ref.shape[0]
    _flatten_rows(src_ref, sf, R // (8 * p), m, p)
    c = m * p

    def body(r):
        g = _parts(_row(gf, tok_ref[r], m), p)
        s = _parts(_row(sf, r, m), p)
        wr = w_ref[r]
        _store_f32_row(yf, r, [wr * x for x in g], c)
        dot = sum(a * b for a, b in zip(s, g))
        dots[pl.ds(r, 1), :] = jnp.sum(dot, axis=0, keepdims=True)
    _each(R, body)
    _f32_to_tiles(yf, dsrc_ref, c)
    dw = jnp.sum(dots[...].T, axis=0, keepdims=True)          # (1, R)
    dw_ref[...] = jnp.broadcast_to(dw, dw_ref.shape)


def combine_bwd(g, src, tok, w_s, *, interpret=False):
    """The combine's transpose in sorted order: for each sorted row r,
    d_src[r] = w_s[r] * g[tok[r]] (float32, cast to g's dtype) and
    dw_s[r] = <src[r], g[tok[r]]> (float32). ``g`` (T, d) the combine's
    output cotangent, ``src`` (N, d) its sorted rows."""
    _, d = g.shape
    N = src.shape[0]
    m, p = words(d, g.dtype), pack(g.dtype)
    R = min(ROWS, _round(N, 16))
    nb = -(-N // R)
    tok_b, lp = _smem_blocks(jnp.pad(tok, (0, nb * R - N)), nb)
    w_b, _ = _smem_blocks(jnp.pad(w_s.astype(jnp.float32), (0, nb * R - N)), nb)
    src = jnp.pad(src, ((0, nb * R - N), (0, 0)))
    gf = flatten(g, interpret=interpret)
    dsrc, dw = pl.pallas_call(
        functools.partial(_combine_bwd_kernel, m=m, p=p), grid=(nb,),
        in_specs=[pl.BlockSpec((lp,), lambda i: (i,), memory_space=pltpu.SMEM),
                  pl.BlockSpec((lp,), lambda i: (i,), memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec((R, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((R, d), lambda i: (i, 0)),
                   pl.BlockSpec((8, R), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((nb * R, d), g.dtype),
                   jax.ShapeDtypeStruct((8, nb * R), jnp.float32)],
        scratch_shapes=[pltpu.VMEM(gf.shape, U32),
                        pltpu.VMEM((R * m, LANES), U32),
                        pltpu.VMEM((R * m * p, LANES), jnp.float32),
                        pltpu.VMEM((R, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((1,))],
        compiler_params=_params(), name="moe_permute_combine_bwd",
        interpret=interpret)(tok_b, w_b, gf, src)
    return dsrc[:N], dw[0, :N]
