"""Serving engine: continuous batching correctness vs naive per-request
decode; offloaded-KV (pinned_host) produces identical tokens."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models.common import host_axis_env
from repro.models.model_zoo import build_model
from repro.serving.engine import Request, ServingEngine

ENV = host_axis_env()


def _model(arch="llama3-8b"):
    cfg = get_config(arch).reduced().with_(remat="none")
    model = build_model(cfg, ENV)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _reference_decode(model, params, prompt, n_new, max_seq=64):
    """Single-request greedy decode, step by step."""
    cache = model.init_cache(1, max_seq)
    _, _, pc = model.forward(params, {"tokens": jnp.asarray(prompt)[None, :]},
                             return_cache=True)
    L = len(prompt)
    cache = jax.tree_util.tree_map(
        lambda d, s: (d.at[:, :, :L].set(s.astype(d.dtype))
                      if d.ndim >= 3 and d.shape[2] == max_seq else
                      s.astype(d.dtype)),
        cache, pc)
    out = []
    tok = int(prompt[-1])
    pos = L
    for _ in range(n_new):
        logits, cache = model.decode(params, cache, {
            "tokens": jnp.asarray([[tok]], jnp.int32),
            "pos": jnp.asarray(pos, jnp.int32)})
        tok = int(jnp.argmax(logits[0]))
        out.append(tok)
        pos += 1
    return out


def test_engine_matches_reference_single():
    cfg, model, params = _model()
    prompt = np.arange(1, 9, dtype=np.int32) % cfg.vocab_size
    want = _reference_decode(model, params, prompt, 6)
    eng = ServingEngine(model, params, slots=1, max_seq=64)
    out = eng.run([Request(0, prompt, 6)])
    assert out[0] == want


def test_engine_concurrent_requests_match_reference():
    cfg, model, params = _model()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 9, 7)]
    want = [_reference_decode(model, params, p, 5) for p in prompts]
    eng = ServingEngine(model, params, slots=2, max_seq=64)
    out = eng.run([Request(i, p, 5) for i, p in enumerate(prompts)])
    for i in range(3):
        assert out[i] == want[i], f"request {i}"


def test_offloaded_kv_same_tokens():
    """KV pool in host memory (the paper's offload scheme applied to
    serving) must not change results."""
    from repro.core.offload import host_memory_kind
    from repro.launch.mesh import make_host_mesh
    cfg, model, params = _model()
    mesh = make_host_mesh(1, 1)
    prompt = np.arange(2, 10, dtype=np.int32)
    base = ServingEngine(model, params, slots=1, max_seq=64)
    off = ServingEngine(model, params, slots=1, max_seq=64, mesh=mesh,
                        offload_kv=True)
    # every pool leaf is held in pinned_host between steps
    assert host_memory_kind(mesh) == "pinned_host"
    assert set(off.pool.spilled_kinds().values()) == {"pinned_host"}
    assert off.pool.device_bytes == 0 and off.pool.host_bytes > 0
    out_a = base.run([Request(0, prompt, 5)])
    out_b = off.run([Request(0, prompt, 5)])
    assert out_a[0] == out_b[0]
    assert set(off.pool.spilled_kinds().values()) == {"pinned_host"}


def test_slots_are_recycled():
    cfg, model, params = _model("gpt2-124m")
    rng = np.random.default_rng(2)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=4).astype(np.int32), 3)
            for i in range(5)]
    eng = ServingEngine(model, params, slots=2, max_seq=32)
    out = eng.run(reqs)
    assert len(out) == 5
    assert all(len(v) == 3 for v in out.values())


def test_latency_stamps_under_queue_backlog():
    """Crafted backlog: one slot, three 2-token requests submitted at
    tick 0. Each request waits for its predecessor's two decode ticks,
    so the queue waits step 0/2/4 and end-to-end 2/4/6: the stamps pin
    the order of admission."""
    cfg, model, params = _model("gpt2-124m")
    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=3)
                    .astype(np.int32), 2) for i in range(3)]
    eng = ServingEngine(model, params, slots=1, max_seq=32)
    for r in reqs:
        assert eng.submit(r)
    while not eng.idle:
        eng.tick()
    assert [r.submit_tick for r in reqs] == [0, 0, 0]
    assert [r.admit_tick for r in reqs] == [0, 2, 4]
    assert [r.finish_tick for r in reqs] == [2, 4, 6]
