"""Data pipeline: determinism, exact-restart, corpus source, prefetch."""
import os
import tempfile

import numpy as np

from repro.data.pipeline import ByteCorpusSource, DataPipeline, SyntheticSource


def test_synthetic_deterministic_per_step():
    s = SyntheticSource(1000, seed=7)
    a = s.batch(3, 4, 16)
    b = s.batch(3, 4, 16)
    c = s.batch(4, 4, 16)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.dtype == np.int32 and a.shape == (4, 17)
    assert a.min() >= 0 and a.max() < 1000


def test_batch_at_matches_iterator():
    """Restart semantics: batch_at(step) must equal the live stream."""
    s = SyntheticSource(500, seed=1)
    pipe = DataPipeline(s, 2, 8)
    it = iter(pipe)
    streamed = [next(it) for _ in range(3)]
    for step, got in enumerate(streamed):
        want = pipe.batch_at(step)
        np.testing.assert_array_equal(np.asarray(got["tokens"]),
                                      want["tokens"])
        np.testing.assert_array_equal(np.asarray(got["labels"]),
                                      want["labels"])


def test_labels_are_shifted_tokens():
    s = SyntheticSource(500, seed=2)
    pipe = DataPipeline(s, 2, 8)
    b = pipe.batch_at(0)
    raw = s.batch(0, 2, 8)
    np.testing.assert_array_equal(b["tokens"], raw[:, :-1])
    np.testing.assert_array_equal(b["labels"], raw[:, 1:])


def test_byte_corpus_source():
    with tempfile.NamedTemporaryFile(delete=False) as f:
        f.write(bytes(range(256)) * 20)
        path = f.name
    try:
        src = ByteCorpusSource(path, seed=0)
        b = src.batch(0, 3, 32)
        assert b.shape == (3, 33)
        assert b.min() >= 0 and b.max() <= 255
        np.testing.assert_array_equal(b, src.batch(0, 3, 32))
    finally:
        os.unlink(path)


def test_each_batch_handed_over_in_a_data_next_span(tmp_path):
    """The step loop's wait for a batch is the ``data.next`` span, numbered
    by the step it feeds, on the profiler's clock."""
    import jax
    from jax.profiler import ProfileData
    pipe = DataPipeline(SyntheticSource(500, seed=3), 2, 8, start_step=5,
                        sharding=jax.sharding.SingleDeviceSharding(
                            jax.devices()[0]))
    it = iter(pipe)
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            next(it)
    it.close()
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = ProfileData.from_file(str(path)).find_plane_with_name("/host:CPU")
    spans = [dict(e.stats) for line in host.lines for e in line.events
             if e.name == "data.next"]
    assert spans == [{"step": 5}, {"step": 6}, {"step": 7}]
