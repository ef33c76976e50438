"""The one general traffic generator: it reads a traffic mix (a JSON file
beside this one) and the run's seed, and makes every request or batch.

The arrival arithmetic (``ConstantCurve``, ``BurstyCurve`` and Lewis
thinning in ``arrival_times``) is copied from the program's
``repro.cluster.loadgen`` so that a change to the program cannot change the
yardstick.

Mixes give each length as a distribution, and open loops a rate. A run
of ``n`` requests takes the distribution's ``n`` quantiles at
``(i + 0.5) / n`` and the seed only shuffles them: every seed offers the
same work in another order, so runs differ by the system and not by the
draw. Token ids always come from the seed.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

HERE = Path(__file__).resolve().parent

# independent random streams of one seed
_ARRIVALS, _PROMPT_LEN, _OUTPUT_LEN, _TOKENS, _SAMPLE = range(5)


def load_mix(name: str) -> dict:
    path = HERE / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


# ---------------------------------------------------------------------------
# load curves and Lewis thinning (copied from repro.cluster.loadgen)
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ConstantCurve:
    rps: float

    def rate(self, t: float) -> float:
        return self.rps


class BurstyCurve:
    """Flash crowds over a quiet floor: burst onsets are a seeded Poisson
    process (mean gap ``mean_gap_s``); each burst adds ``burst_rps`` that
    decays as ``exp(-(t - onset) / decay_s)``."""

    def __init__(self, base_rps: float, burst_rps: float, *,
                 mean_gap_s: float, decay_s: float, seed=0,
                 horizon_s: float = 86400.0):
        self.base_rps = base_rps
        self.burst_rps = burst_rps
        self.decay_s = decay_s
        r = np.random.default_rng(seed)
        onsets: List[float] = []
        t = float(r.exponential(mean_gap_s))
        while t < horizon_s:
            onsets.append(t)
            t += float(r.exponential(mean_gap_s))
        self.onsets = np.asarray(onsets, dtype=float)

    def rate(self, t: float) -> float:
        active = self.onsets[self.onsets <= t]
        if active.size == 0:
            return self.base_rps
        return self.base_rps + self.burst_rps * float(
            np.exp(-(t - active) / self.decay_s).sum())


def arrival_times(curve, horizon_s: float, seed=0,
                  max_rate: float = None) -> np.ndarray:
    """Seeded arrival instants of an inhomogeneous Poisson process by
    Lewis thinning; ``max_rate`` bounds the proposal process."""
    if max_rate is None:
        grid = np.linspace(0.0, horizon_s, 512)
        max_rate = max(curve.rate(float(g)) for g in grid) * 1.1
    if max_rate <= 0.0:
        return np.empty(0, dtype=float)
    r = np.random.default_rng(seed)
    out: List[float] = []
    t = 0.0
    while True:
        t += float(r.exponential(1.0 / max_rate))
        if t >= horizon_s:
            break
        if r.uniform() * max_rate <= curve.rate(t):
            out.append(t)
    return np.asarray(out, dtype=float)


# ---------------------------------------------------------------------------
# lengths and arrivals of one run
# ---------------------------------------------------------------------------
def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, r: np.random.Generator) -> np.ndarray:
    """``n`` lengths from ``spec``: a ``"choice"`` of values with weights,
    or a ``"lognormal"`` (median, sigma) or ``"uniform"`` range, each
    clipped to [min, max]. Stratified: the same multiset for every seed."""
    kind = spec["dist"]
    if kind == "choice":
        values = np.asarray(spec["values"], dtype=np.int64)
        w = np.asarray(spec["weights"], dtype=float)
        w = w / w.sum()
        counts = np.floor(w * n).astype(int)
        rest = np.argsort(-(w * n - counts), kind="stable")
        counts[rest[:n - counts.sum()]] += 1
        out = np.repeat(values, counts)
    elif kind == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(q) for q in _quantiles(n)])
        out = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    elif kind == "uniform":
        out = np.rint(spec["min"] + (spec["max"] - spec["min"])
                      * _quantiles(n))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    out = np.clip(out, spec.get("min", 1), spec.get("max", np.inf))
    return r.permutation(out.astype(np.int64))


def arrivals(spec: dict, horizon_s: float, seed: int) -> np.ndarray:
    """Due times in [0, horizon_s) of an open loop."""
    kind = spec["process"]
    if kind == "poisson_stratified":
        # exponential gaps at the rate's quantiles, shuffled: the count is
        # exactly rate * horizon for every seed
        n = max(1, int(round(spec["rate_rps"] * horizon_s)))
        gaps = -np.log1p(-_quantiles(n)) / spec["rate_rps"]
        gaps = rng(seed, _ARRIVALS).permutation(gaps)
        gaps *= horizon_s / gaps.sum()
        return np.cumsum(gaps) - gaps[0]
    if kind == "thinning":
        c = spec["curve"]
        if c["shape"] == "constant":
            curve = ConstantCurve(c["rps"])
        elif c["shape"] == "bursty":
            curve = BurstyCurve(c["base_rps"], c["burst_rps"],
                                mean_gap_s=c["mean_gap_s"],
                                decay_s=c["decay_s"],
                                seed=[int(seed), _ARRIVALS],
                                horizon_s=horizon_s)
        else:
            raise ValueError(f"unknown curve {c['shape']!r}")
        return arrival_times(curve, horizon_s, seed=[int(seed), _ARRIVALS])
    raise ValueError(f"unknown arrival process {kind!r}")


@dataclass
class RequestSpec:
    """One generated request: what the program receives, and when."""
    rid: int
    prompt: np.ndarray
    max_new: int
    due_s: float = 0.0          # open loop: offset from the window's start
    # filled in while it is served: each output token and its host time
    served: List[int] = field(default_factory=list)
    stamps: List[float] = field(default_factory=list)


def open_loop(mix: dict, vocab: int, horizon_s: float,
              seed: int) -> List[RequestSpec]:
    due = arrivals(mix["arrivals"], horizon_s, seed)
    return _requests(mix, vocab, seed, len(due), due, first_rid=0)


def closed_loop(mix: dict, vocab: int, seed: int,
                block: int = 64) -> Iterator[RequestSpec]:
    """Endless requests in blocks of ``block``; each block is stratified."""
    k = 0
    while True:
        yield from _requests(mix, vocab, [int(seed), k], block,
                             np.zeros(block), first_rid=k * block)
        k += 1


def _requests(mix, vocab, seed, n, due, first_rid) -> List[RequestSpec]:
    seed = list(np.atleast_1d(seed))
    p = lengths(mix["prompt_len"], n, np.random.default_rng(seed + [_PROMPT_LEN]))
    o = lengths(mix["output_len"], n, np.random.default_rng(seed + [_OUTPUT_LEN]))
    tok = np.random.default_rng(seed + [_TOKENS])
    return [RequestSpec(first_rid + i,
                        tok.integers(0, vocab, size=int(p[i])).astype(np.int32),
                        int(o[i]), float(due[i]))
            for i in range(n)]


def warmup_prompts(mix: dict, vocab: int, seed: int) -> List[np.ndarray]:
    """One prompt of every length the mix can send."""
    spec = mix["prompt_len"]
    if spec["dist"] != "choice":
        raise ValueError("warm-up needs a finite set of prompt lengths")
    r = rng(seed, _TOKENS + 100)
    return [r.integers(0, vocab, size=int(v)).astype(np.int32)
            for v in spec["values"]]


# ---------------------------------------------------------------------------
# training batches
# ---------------------------------------------------------------------------
class TokenBatches:
    """Seeded (batch, seq + 1) token rows; ``batch(step, ...)`` is the
    ``source`` interface of the program's data pipeline. Zipf-distributed
    ids (as llm.c-style text is), folded into the vocabulary."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.a = float(mix["tokens"]["zipf_a"])
        self.vocab = vocab
        self.seed = int(seed)

    def batch(self, step: int, batch: int, seq: int) -> np.ndarray:
        r = np.random.default_rng([self.seed, _TOKENS, int(step)])
        ranks = r.zipf(self.a, size=(batch, seq + 1)).astype(np.int64)
        return (ranks % self.vocab).astype(np.int32)


def shuffled(items: list, seed: int) -> list:
    """``items`` in an order drawn from the seed."""
    return [items[i] for i in rng(seed, _SAMPLE).permutation(len(items))]

