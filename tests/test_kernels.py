"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode,
plus hypothesis property tests on the numerics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    denom = np.max(np.abs(want)) + 1e-9
    return float(np.max(np.abs(got - want)) / denom)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,hd,bq,bk,sub", [
    pytest.param(1, 128, 2, 64, 128, 128, None, id="1-128-2-64-128-128"),
    pytest.param(2, 256, 4, 64, 128, 128, None, id="2-256-4-64-128-128"),
    pytest.param(1, 256, 1, 128, 64, 128, None, id="1-256-1-128-64-128"),
    pytest.param(2, 512, 2, 32, 128, 256, None, id="2-512-2-32-128-256"),
    # diagonal blocks cut into bands, two heads of 64 read in place: two
    # blocks of 256 (one on the diagonal, one below it), and the one
    # block of 1024 of gpt2's training rows
    pytest.param(1, 512, 2, 64, 256, 256, 128, id="in-place-512-256-sub-128"),
    pytest.param(1, 1024, 2, 64, 1024, 1024, 256,
                 id="in-place-1024-1024-sub-256"),
    pytest.param(1, 1024, 2, 64, 1024, 1024, 128,
                 id="in-place-1024-1024-sub-128"),
])
def test_flash_attention_matches_ref(B, S, H, hd, bq, bk, sub, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, H, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, H, hd), dtype)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    if sub is None:
        got = fa.flash_attention_fwd(fold(q), fold(k), fold(v), causal=True,
                                     block_q=bq, block_k=bk, interpret=True)
    else:
        assert fa.sub_block(bq, bk, sub) == sub
        flat = lambda t: t.reshape(B, S, H * hd)
        got = fold(fa.flash_attention_fwd(
            flat(q), flat(k), flat(v), causal=True, head_dim=hd, block_q=bq,
            block_k=bk, sub=sub, interpret=True).reshape(B, S, H, hd))
    want = ref.attention_ref(fold(q), fold(k), fold(v), causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert _rel_err(got, want) < tol


def test_flash_attention_non_causal():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 128, 2, 64), jnp.float32)
    k = jax.random.normal(ks[1], (2, 128, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (2, 128, 2, 64), jnp.float32)
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(4, 128, 64)
    got = fa.flash_attention_fwd(fold(q), fold(k), fold(v), causal=False,
                                 interpret=True)
    want = ref.attention_ref(fold(q), fold(k), fold(v), causal=False)
    assert _rel_err(got, want) < 2e-5


def _naive_attention(q, k, v, causal: bool):
    """Softmax attention in fp32, (B, S, H, hd)."""
    S, hd = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(hd)
    if causal:
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                      v.astype(jnp.float32))


# the block the custom-VJP wrapper picks for long sequences
_BLOCK = fa.pick_block(1 << 14, 128, 2)


@pytest.mark.parametrize("causal,bq,bk,sub,n_blocks,dtype,H", [
    pytest.param(True, 64, 64, None, None, jnp.float32, 1, id="True-64-64"),
    pytest.param(True, 128, 64, None, None, jnp.float32, 1,
                 id="True-128-64"),
    pytest.param(False, 64, 128, None, None, jnp.float32, 1,
                 id="False-64-128"),
    *[pytest.param(True, None, None, None, n, dt, 2,
                   id=f"wrapper-{n}-blocks-{jnp.dtype(dt).name}")
      for dt in (jnp.float32, jnp.bfloat16) for n in (1, 2, 4)],
    pytest.param(True, None, None, None, 2, jnp.float32, 3,
                 id="wrapper-2-blocks-float32-folded"),
    *[pytest.param(True, b, b, sub, n, dt, 2,
                   id=f"sub-{sub}-of-{n}-blocks-{b}-{jnp.dtype(dt).name}")
      for dt in (jnp.float32, jnp.bfloat16)
      for b, sub, n in ((256, 128, 2), (1024, 256, 1))],
])
def test_flash_backward_kernel_matches_autodiff(causal, bq, bk, sub,
                                                n_blocks, dtype, H):
    """The Pallas dq/dk/dv kernels against jax.vjp of naive attention: the
    kernels at explicit blocks, one head of 64 over 4 rows of 256 and,
    with diagonal blocks cut into bands, two heads of 64 read in place
    as one 128-lane block; and jax.grad through the custom-VJP wrapper
    ``causal_flash_attention`` in the model's (B, S, H, hd) layout at the
    blocks it picks, S at 1, 2 and 4 of them: two heads of 64 read in
    place, and three, whose 192 lanes the wrapper folds into the batch."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    wrapper = bq is None
    if not wrapper:
        B, S, hd = (4, 256, 64) if n_blocks is None else (1, n_blocks * bq,
                                                          64)
        assert fa.sub_block(bq, bk, sub) == sub
    else:
        B, S, hd = 1, n_blocks * _BLOCK, 64
        assert ops._in_place((B, S, H, hd)) == (H == 2)
        assert fa.pick_block(S, 128, jnp.dtype(dtype).itemsize) == _BLOCK
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), dtype) for kk in ks[:3])
    do = jax.random.normal(ks[3], (B, S, H, hd), jnp.float32)
    if not wrapper:
        flat = [t.reshape(B, S, H * hd) for t in (q, k, v, do)]
        kw = dict(causal=causal, head_dim=hd, block_q=bq, block_k=bk,
                  sub=sub, interpret=True)
        out, lse = fa.flash_attention_fwd_stats(*flat[:3], **kw)
        grads = fa.flash_attention_bwd(*flat[:3], out, lse, flat[3], **kw)
        got = [t.reshape(B, S, H, hd) for t in (out, *grads)]
    else:
        loss = lambda q, k, v: jnp.sum(  # noqa: E731
            ops.causal_flash_attention(q, k, v).astype(jnp.float32) * do)
        got = [ops.causal_flash_attention(q, k, v),
               *jax.grad(loss, argnums=(0, 1, 2))(q, k, v)]
    want_out, vjp = jax.vjp(lambda *a: _naive_attention(*a, causal), q, k, v)
    want = [want_out, *vjp(do)]
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype, name
        assert _rel_err(a, b) < tol, name


@pytest.mark.parametrize("along_q", [False, True])
@pytest.mark.parametrize("sub", [256, 128])
def test_diagonal_block_plan(sub, along_q):
    """A diagonal block of 1024 cut into bands of 256 (128) computes each
    of the 10 (36) sub-tile pairs on or below the diagonal once, in 4 (8)
    bands, each ending at (with ``along_q``, starting from) its diagonal
    sub-tile; a block under two bands, or of unequal q and kv sizes, is
    not cut."""
    n = 1024 // sub
    pairs = []
    bands = fa._diagonal_bands(1024, sub, along_q=along_q)
    assert len(bands) == n
    for qs, ks in bands:
        assert qs.start % sub == qs.stop % sub == ks.start % sub == 0
        assert ks.stop % sub == 0
        band = [(qi, ki) for qi in range(qs.start // sub, qs.stop // sub)
                for ki in range(ks.start // sub, ks.stop // sub)]
        diag = [(qi, ki) for qi, ki in band if qi == ki]
        assert len(diag) == 1
        assert diag[0] == (band[0] if along_q else band[-1])
        pairs += band
    assert sorted(pairs) == [(qi, ki) for qi in range(n)
                             for ki in range(qi + 1)]
    assert len(pairs) == n * (n + 1) // 2
    assert fa.sub_block(1024, 1024, 256) == 256
    assert fa.sub_block(512, 512, 128) == 128
    assert fa.sub_block(1024, 1024) == fa.SUB_BLOCK
    for b in (128, 256):
        assert fa.sub_block(b, b, 256) is None
    assert fa.sub_block(128, 128) is None
    assert fa.sub_block(256, 128, 128) is None
    assert fa.sub_block(1024, 512, 256) is None


@pytest.mark.parametrize("bq,bk", [(64, 64), (128, 64), (64, 128)])
def test_causal_clamp_leaves_results_unchanged(bq, bk, monkeypatch):
    """Under causality the index maps send a skipped step to the block
    already resident, so it issues no copy: every step that computes sees
    its own block, and out, lse, dq, dk and dv are bit-identical to the
    kernels' with unclamped maps."""
    for iq in range(512 // bq):
        for ik in range(512 // bk):
            needed = ik * bk <= iq * bq + bq - 1
            kv = int(fa._kv_block_seen(iq, ik, bq, bk))
            qs = int(fa._q_block_seen(ik, iq, bq, bk))
            assert (kv == ik) == needed and (qs == iq) == needed
            if not needed:  # the last block q block iq attends to, and
                # the first q block that attends to kv block ik
                assert kv == (iq * bq + bq - 1) // bk
                assert qs == (ik * bk) // bq
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q, k, v, do = (jax.random.normal(kk, (2, 512, 128), jnp.float32)
                   for kk in ks)

    def run():   # two heads of 64 in each 128-lane block
        kw = dict(causal=True, head_dim=64, block_q=bq, block_k=bk,
                  interpret=True)
        out, lse = fa.flash_attention_fwd_stats(q, k, v, **kw)
        return (out, lse, *fa.flash_attention_bwd(q, k, v, out, lse, do,
                                                  **kw))

    clamped = run()
    monkeypatch.setattr(fa, "_kv_block_seen", lambda iq, ik, bq, bk: ik)
    monkeypatch.setattr(fa, "_q_block_seen", lambda ik, iq, bq, bk: iq)
    for name, a, b in zip(("out", "lse", "dq", "dk", "dv"), clamped, run()):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


@pytest.mark.parametrize("cell,config,rows,seq", [
    ("gpt2-124m.train", "gpt2-124m", 80, 1024),
    ("granite-moe-1b.train4k", "granite-moe-1b-a400m", 4, 4096),
])
def test_attention_roofline_reader(cell, config, rows, seq):
    """``attn.roofline.train`` puts the causal attention operations of a
    step (3 x 4 layers H hd S(S+1)/2 a row) over the time of the ops that
    ``attn.kernel_ms.train`` reads, at 197 TFLOP/s, and reads nothing
    where the program has no such kernels."""
    from types import SimpleNamespace
    from benchmarks.chip import run
    cfg = run.read_json(run.HERE / "configs" / f"{config}.json")
    ref_mod = run.load_module(run.HERE / "reference" / f"{cfg['reference']}.py")
    spec = ref_mod.spec(cfg)
    ops_ = {"%flash_attention_bwd_dkv.10 = (bf16[80,1024,768]) ...": 0.9,
            "%flash_attention_fwd_stats.14 = (bf16[80,1024,768]) ...": 0.8,
            "%fusion.3 = bf16[80,1024,768]{2,1,0} fusion(...)": 3.0}
    trace = SimpleNamespace(devices=[SimpleNamespace(ops=ops_)],
                            program=lambda fn: (10.0, 10))
    rec = SimpleNamespace(trace=trace, data={"tokens_per_step": rows * seq,
                                             "seq": seq})
    ctx = SimpleNamespace(spec=spec, peaks={"bf16_flops_per_s": 197e12})
    roof = run.load_module(run.HERE / "metrics" / "attn.roofline.train.py")
    flops = (3 * rows * 4 * spec.layers * spec.heads * spec.head_dim
             * seq * (seq + 1) / 2)
    assert roof.read(rec, ctx) == pytest.approx(
        100 * flops / (1e-3 * 170.0 * 197e12))
    # the step's causal attention as PERF.md counts it: 4.64 and 3.30 TFLOP
    assert flops == pytest.approx({"gpt2-124m.train": 4.64e12,
                                   "granite-moe-1b.train4k": 3.30e12}[cell],
                                  rel=2e-3)
    rec.trace = SimpleNamespace(devices=[SimpleNamespace(ops={})],
                                program=lambda fn: (10.0, 10))
    assert roof.read(rec, ctx) is None


def _attention_inputs(B, Sq, Sk, H, hd, seed=11):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (B, Sq, H, hd), jnp.bfloat16),
            jax.random.normal(ks[1], (B, Sk, H, hd), jnp.bfloat16),
            jax.random.normal(ks[2], (B, Sk, H, hd), jnp.bfloat16))


@pytest.fixture
def on_tpu(monkeypatch):
    """attention_core as it dispatches on one TPU device (the kernels still
    run in interpret mode here); records the fused calls."""
    from repro.models import attention
    calls = []
    real = ops.causal_flash_attention

    def spy(*a):
        calls.append(a[0].shape)
        return real(*a)
    monkeypatch.setattr(attention, "_on_one_tpu", lambda: True)
    monkeypatch.setattr(ops, "causal_flash_attention", spy)
    return calls


def test_attention_core_sends_causal_self_attention_to_the_fused_path(
        on_tpu, monkeypatch):
    from repro.configs import get_config
    from repro.models import attention
    monkeypatch.setattr(attention, "FUSED_MIN_SCORE_BYTES", 0)
    cfg = get_config("gpt2-124m").reduced()
    q, k, v = _attention_inputs(2, 256, 256, cfg.num_heads, cfg.head_dim)
    got = attention.attention_core(cfg, q, k, v, causal=True)
    assert on_tpu == [q.shape]
    assert _rel_err(got, _naive_attention(q, k, v, True)) < 2e-2
    loss = lambda *a: jnp.sum(  # noqa: E731
        attention.attention_core(cfg, *a, causal=True).astype(jnp.float32))
    jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert len(on_tpu) == 2


@pytest.mark.parametrize("case", ["decode", "cross", "kv_len", "offset",
                                  "ragged_s", "small_scores"])
def test_attention_core_other_calls_keep_their_paths(case, on_tpu,
                                                     monkeypatch):
    """Decode, cross-attention, a ragged kv_len, a query offset, S off the
    128 grid and scores under the fused path's floor stay on their paths
    with the dispatch on a TPU: outputs bit-identical to those paths."""
    from repro.configs import get_config
    from repro.models import attention
    cfg = get_config("gpt2-124m").reduced()
    H, hd = cfg.num_heads, cfg.head_dim
    flash = lambda q, k, v, **kw: attention.flash_attention(  # noqa: E731
        q, k, v, chunk=cfg.attn_chunk, **kw)
    if case == "decode":
        q, k, v = _attention_inputs(2, 1, 256, H, hd)
        kw = dict(causal=False, kv_len=jnp.asarray([17, 200]))
        want = attention.decode_attention(q, k, v, kv_len=kw["kv_len"])
    elif case == "cross":
        q, k, v = _attention_inputs(2, 128, 256, H, hd)
        kw = dict(causal=False)
        want = flash(q, k, v, causal=False)
    elif case == "kv_len":
        q, k, v = _attention_inputs(2, 256, 256, H, hd)
        kw = dict(causal=True, kv_len=jnp.asarray(200))
        want = flash(q, k, v, **kw)
    elif case == "offset":
        q, k, v = _attention_inputs(2, 128, 128, H, hd)
        kw = dict(causal=True, q_offset=jnp.asarray(3))
        want = flash(q, k, v, **kw)
    elif case == "ragged_s":
        q, k, v = _attention_inputs(2, 200, 200, H, hd)
        kw = dict(causal=True)
        want = flash(q, k, v, **kw)
    else:
        q, k, v = _attention_inputs(2, 256, 256, H, hd)
        kw = dict(causal=True)
        want = flash(q, k, v, **kw)
    if case != "small_scores":   # every other case would pass the floor
        monkeypatch.setattr(attention, "FUSED_MIN_SCORE_BYTES", 0)
    got = attention.attention_core(cfg, q, k, v, **kw)
    assert on_tpu == []
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("arch,B,S,fused", [
    ("phi3-mini-3.8b", 1, 768, False),    # chat prefill: 75 MB of scores
    ("phi3-mini-3.8b", 1, 1024, True),    # 134 MB
    ("gpt2-124m", 1, 1024, False),        # 50 MB
    ("gpt2-124m", 80, 1024, True),        # the training cell: 4 GB
])
def test_fused_path_takes_scores_from_its_floor(arch, B, S, fused,
                                                monkeypatch):
    """The fused path takes causal self-attention from
    FUSED_MIN_SCORE_BYTES of fp32 scores up; below, the XLA scan."""
    from repro.configs import get_config
    from repro.models import attention
    monkeypatch.setattr(attention, "_on_one_tpu", lambda: True)
    cfg = get_config(arch)
    q = jax.ShapeDtypeStruct((B, S, cfg.num_heads, cfg.head_dim),
                             jnp.bfloat16)
    assert attention._fused(q, q, causal=True, q_offset=0,
                            kv_len=None) == fused


def test_fused_path_needs_one_tpu_device(monkeypatch):
    """A Pallas kernel is not partitioned: under a context mesh of several
    devices the dispatch keeps off the fused path, on one device it takes
    it."""
    from jax.sharding import AbstractMesh, use_abstract_mesh
    from repro.models import attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention._on_one_tpu()
    with use_abstract_mesh(AbstractMesh((1, 1), ("data", "model"))):
        assert attention._on_one_tpu()
    with use_abstract_mesh(AbstractMesh((2, 2), ("data", "model"))):
        assert not attention._on_one_tpu()


def test_attention_core_off_the_tpu_keeps_the_xla_scan(monkeypatch):
    """Off the TPU (the CPU here) causal self-attention stays on the XLA
    scan, bit-identical to it."""
    from repro.configs import get_config
    from repro.models import attention
    monkeypatch.setattr(ops, "causal_flash_attention", None)
    cfg = get_config("gpt2-124m").reduced()
    q, k, v = _attention_inputs(2, 256, 256, cfg.num_heads, cfg.head_dim)
    assert not attention._on_one_tpu()
    got = attention.attention_core(cfg, q, k, v, causal=True)
    want = attention.flash_attention(q, k, v, causal=True,
                                     chunk=cfg.attn_chunk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_flash_custom_vjp_matches_autodiff():
    """The XLA scan's gradient — the attention of every call off the TPU
    and of every ragged or offset one on it — vs autodiff of naive causal
    attention."""
    from repro.models import attention
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    B, S, H, hd = 2, 256, 2, 64
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), jnp.float32) for kk in ks)

    def naive(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    f_scan = lambda *a: jnp.sum(jnp.sin(attention.flash_attention(  # noqa: E731
        *a, causal=True, chunk=64, scale=hd ** -0.5)))
    f_nv = lambda *a: jnp.sum(jnp.sin(naive(*a)))
    g1 = jax.grad(f_scan, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_nv, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert _rel_err(a, b) < 1e-4


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,nh,hp,N,chunk,nhb", [
    (1, 128, 4, 32, 64, 64, 4),
    (2, 256, 8, 32, 64, 128, 4),
    (1, 128, 2, 64, 128, 32, 2),
])
def test_ssd_matches_ref(B, S, nh, hp, N, chunk, nhb):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = 0.5 * jax.random.normal(ks[0], (B, S, nh, hp), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (nh,)))
    B_ = 0.3 * jax.random.normal(ks[3], (B, S, N))
    C_ = 0.3 * jax.random.normal(ks[4], (B, S, N))
    got = ops.ssd(x, dt, A, B_, C_, chunk=chunk, nh_block=nhb)
    want = ref.ssd_ref(x, dt, A, B_, C_)
    assert _rel_err(got, want) < 1e-4


def test_ssd_kernel_agrees_with_model_ssd():
    """The Pallas kernel and the XLA-level chunked SSD in the model zoo
    implement the same recurrence."""
    from repro.models.ssm import ssd_chunked
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    B, S, nh, hp, N = 2, 128, 4, 32, 64
    x = 0.5 * jax.random.normal(ks[0], (B, S, nh, hp), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[2], (nh,)))
    B_ = 0.3 * jax.random.normal(ks[3], (B, S, N))
    C_ = 0.3 * jax.random.normal(ks[4], (B, S, N))
    y_kernel = ops.ssd(x, dt, A, B_, C_, chunk=64, nh_block=4)
    y_model, _ = ssd_chunked(x, dt, A, B_, C_, chunk=64)
    assert _rel_err(y_kernel, y_model) < 1e-4


# ---------------------------------------------------------------------------
# grouped products / stream matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("E,C,d,f", [(2, 128, 128, 128), (4, 256, 128, 384),
                                     (1, 128, 256, 128)])
def test_gmm_matches_ref(E, C, d, f):
    """The MoE layer's grouped products (``jax.lax.ragged_dot``) over E * C
    rows in groups of uneven size, the last group left empty where there
    are several, against the loop over the groups."""
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(ks[0], (E * C, d), jnp.float32)
    w = jax.random.normal(ks[1], (E, d, f), jnp.float32)
    sizes = [E * C] if E == 1 else [C + C // 2] + [C // 2] * (E - 2) + [0]
    sizes[0] += E * C - sum(sizes)
    got = jax.lax.ragged_dot(x, w, jnp.asarray(sizes, jnp.int32))
    assert _rel_err(got, ref.gmm_ref(x, w, sizes)) < 1e-5


@pytest.mark.parametrize("M,K,N,bk", [(128, 512, 128, 256), (256, 1024, 384, 512)])
def test_stream_matmul_matches_ref(M, K, N, bk):
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (M, K), jnp.float32)
    w = jax.random.normal(ks[1], (K, N), jnp.float32)
    got = ops.stream_matmul(x, w, block_k=bk)
    assert _rel_err(got, ref.matmul_ref(x, w)) < 1e-5


# ---------------------------------------------------------------------------
# property tests
# ---------------------------------------------------------------------------
@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       scale=st.floats(0.1, 4.0))
def test_flash_attention_rows_sum_to_convex_combination(seed, scale):
    """Attention output is a convex combination of V rows → bounded by V's
    row-wise min/max (fp32, causal)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = scale * jax.random.normal(ks[0], (1, 128, 1, 64), jnp.float32)
    k = scale * jax.random.normal(ks[1], (1, 128, 1, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 1, 64), jnp.float32)
    out = np.asarray(fa.flash_attention_fwd(q[:, :, 0], k[:, :, 0],
                                            v[:, :, 0], causal=True,
                                            interpret=True))
    vmax = float(np.max(v)) + 1e-4
    vmin = float(np.min(v)) - 1e-4
    assert out.max() <= vmax and out.min() >= vmin


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_ssd_zero_input_is_zero(seed):
    B, S, nh, hp, N = 1, 64, 2, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jnp.zeros((B, S, nh, hp), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, nh)))
    A = -jnp.exp(0.3 * jax.random.normal(ks[1], (nh,)))
    B_ = jax.random.normal(ks[2], (B, S, N))
    out = ops.ssd(x, dt, A, B_, B_, chunk=32, nh_block=2)
    assert np.allclose(np.asarray(out), 0.0, atol=1e-6)
