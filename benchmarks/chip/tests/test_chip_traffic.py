"""The traffic generator and the end-to-end arithmetic."""
import numpy as np
import pytest

from benchmarks.chip import stats
from benchmarks.chip.traffic import loadgen

BIG = 2**31 + 12345


@pytest.mark.parametrize("name", ["chat", "spill"])
def test_same_seed_same_requests_other_seed_same_work(name):
    mix = loadgen.load_mix(name)
    if mix["loop"] == "open":
        make = lambda s: loadgen.open_loop(mix, 32064, 20.0, s)
    else:
        src = lambda s: loadgen.closed_loop(mix, 32064, s)
        make = lambda s: [next(g) for g in [src(s)] for _ in range(3 * 64)]
    a, b, c = make(BIG), make(BIG), make(BIG + 1)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another seed: the same multiset of sizes (and count), in another order
    assert len(a) == len(c)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    assert set(len(r.prompt) for r in a) <= set(mix["prompt_len"]["values"])
    assert all(mix["output_len"]["min"] <= r.max_new <= mix["output_len"]["max"]
               for r in a)


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    mix = loadgen.load_mix("chat")
    due = [r.due_s for r in loadgen.open_loop(mix, 100, 50.0, 3)]
    assert len(due) == round(mix["arrivals"]["rate_rps"] * 50.0)
    assert due[0] == 0.0 and due[-1] < 50.0 and np.all(np.diff(due) > 0)


def test_bursty_thinning_is_seeded_and_bounded():
    spec = {"process": "thinning",
            "curve": {"shape": "bursty", "base_rps": 1.0, "burst_rps": 6.0,
                      "mean_gap_s": 10.0, "decay_s": 3.0}}
    a = loadgen.arrivals(spec, 60.0, BIG)
    assert np.array_equal(a, loadgen.arrivals(spec, 60.0, BIG))
    assert len(a) > 0 and a.min() >= 0 and a.max() < 60.0
    flat = loadgen.arrivals({"process": "thinning",
                             "curve": {"shape": "constant", "rps": 2.0}},
                            1000.0, 5)
    assert abs(len(flat) / 1000.0 - 2.0) < 0.2


def test_training_rows_are_seeded_and_differ():
    mix = loadgen.load_mix("train")
    src = loadgen.TokenBatches(mix, 50257, BIG)
    a = src.batch(0, 8, 64)
    assert np.array_equal(a, loadgen.TokenBatches(mix, 50257, BIG).batch(0, 8, 64))
    assert a.shape == (8, 65) and a.max() < 50257
    rows = np.concatenate([a, src.batch(1, 8, 64)])
    assert len({r.tobytes() for r in rows}) == len(rows)


def test_rates_and_tails_over_the_whole_window_with_a_stall():
    # two requests decode every 10 ms over a 3 s window, except for a 1 s
    # stall in the middle: the rate counts the stall's lost time, and the
    # tail holds the stall's gap
    t = np.concatenate([np.arange(0.0, 1.0, 0.01), np.arange(2.0, 3.0, 0.01)])
    stamps = [t, t + 0.005]
    assert stats.tokens_per_s(stamps, 0.0, 3.0) == pytest.approx(400 / 3.0)
    gaps = stats.token_gaps(stamps, 0.0, 3.0)
    assert len(gaps) == 2 * 199
    assert max(gaps) == pytest.approx(1.01)
    # a chunked median of rates would read 200 tokens/s and miss the stall
    assert stats.percentile(gaps, 100) == pytest.approx(1.01)
    assert stats.percentile(gaps, 95) == pytest.approx(0.01)
    # gaps are counted where they end: none before the window opens
    assert len(stats.token_gaps(stamps, 2.4975, 3.0)) == 2 * 50
    due = [0.0, 0.5, 1.5]
    first = [0.02, 2.0, 2.01]
    assert stats.ttft_s(due, first) == pytest.approx([0.02, 1.5, 0.51])

