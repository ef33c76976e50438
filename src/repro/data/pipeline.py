"""Deterministic sharded token pipeline with host-side prefetch.

Two sources:
  * SyntheticSource — seeded Zipf-ish token stream (default for benches/tests;
    fully deterministic per (seed, step) so restarts resume exactly);
  * ByteCorpusSource — byte-level LM over any file (the paper's llm.c
    tinystories/shakespeare workload shape).

``DataPipeline`` yields {tokens, labels} of (global_batch, seq+1) split into
next-token pairs, placed with the train batch sharding; a background thread
keeps ``prefetch`` batches ready so input never serializes the step
(host-side analogue of overlapping data movement with compute).
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass
from typing import Dict, Iterator, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation


class SyntheticSource:
    def __init__(self, vocab_size: int, seed: int = 0):
        self.vocab = vocab_size
        self.seed = seed

    def batch(self, step: int, batch: int, seq: int) -> np.ndarray:
        rng = np.random.default_rng(self.seed * 1_000_003 + step)
        # Zipf-ish marginal — more realistic logits than uniform
        ranks = rng.zipf(1.3, size=(batch, seq + 1)).astype(np.int64)
        return (ranks % self.vocab).astype(np.int32)


class ByteCorpusSource:
    def __init__(self, path: str, seed: int = 0):
        with open(path, "rb") as f:
            self.data = np.frombuffer(f.read(), dtype=np.uint8)
        if self.data.size < 2:
            raise ValueError(f"corpus {path} too small")
        self.seed = seed

    def batch(self, step: int, batch: int, seq: int) -> np.ndarray:
        rng = np.random.default_rng(self.seed * 7_777_777 + step)
        starts = rng.integers(0, max(1, self.data.size - seq - 1), size=batch)
        rows = [self.data[s:s + seq + 1].astype(np.int32) for s in starts]
        return np.stack(rows)


@dataclass
class DataPipeline:
    source: object
    global_batch: int
    seq_len: int
    sharding: Optional[jax.sharding.Sharding] = None
    prefetch: int = 2
    start_step: int = 0

    def __iter__(self) -> Iterator[Dict[str, jax.Array]]:
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()

        def producer():
            step = self.start_step
            while not stop.is_set():
                q.put(self.source.batch(step, self.global_batch, self.seq_len))
                step += 1

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            step = self.start_step
            while True:
                # the step loop's wait for its batch, on the profiler's clock
                with TraceAnnotation("data.next", step=step):
                    arr = q.get()
                    tokens, labels = arr[:, :-1], arr[:, 1:]
                    if self.sharding is not None:
                        tokens = jax.device_put(tokens, self.sharding)
                        labels = jax.device_put(labels, self.sharding)
                yield {"tokens": tokens, "labels": labels}
                step += 1
        finally:
            stop.set()

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        """Deterministic random access — exact restart after failure."""
        arr = self.source.batch(step, self.global_batch, self.seq_len)
        return {"tokens": arr[:, :-1], "labels": arr[:, 1:]}
