"""The trace reduction, on a small trace recorded on a TPU v5e: a traced
window of the chat cell's path at a small model size
(``testdata/tiny_chat.xplane.pb``)."""
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import trace

TRACE = Path(__file__).resolve().parents[1] / "testdata" / "tiny_chat.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.read(str(TRACE))


def test_device_and_window(tr):
    assert [d.name for d in tr.devices] == ["/device:TPU:0"]
    lo, hi = tr.window
    assert 0.2 < tr.window_s < 1.0
    assert 0 < tr.busy_s < tr.window_s
    busy = tr.busy(tr.devices[0])
    assert np.all(busy[:, 0] >= lo) and np.all(busy[:, 1] <= hi)
    assert np.all(busy[1:, 0] > busy[:-1, 1])       # disjoint, sorted


def test_one_decode_per_tick(tr):
    ticks = tr.span_times("bench.tick")
    total, n = tr.program("_decode_step")
    assert n == len(ticks) > 0 and total > 0
    per_tick = tr.program_time_within(ticks, ("_decode_step",))
    assert all(d < e - s for d, (s, e) in zip(per_tick, ticks))
    # host and device clocks agree to ~0.1 ms: a few of these 2 ms ticks
    # see their decode lean into the neighbour
    assert sum(d > 0 for d in per_tick) >= 0.8 * len(ticks)
    assert sum(per_tick) == pytest.approx(total, rel=0.05)


def test_breakdown(tr):
    b = tr.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    ops = [v for _, v in b["device_ops"]]
    assert ops == sorted(ops, reverse=True)
    idle = sum(v for _, v in tr.breakdown(top=10**6)["idle_gaps"])
    assert idle == pytest.approx(tr.window_s - tr.busy_s, rel=1e-6)


def test_union_and_names():
    iv = np.array([[0.0, 1.0], [0.5, 2.0], [3.0, 4.0], [3.5, 3.6]])
    assert trace.union(iv).tolist() == [[0.0, 2.0], [3.0, 4.0]]
    assert trace.program_name("jit__decode_step(12)") == "_decode_step"
    assert trace.program_name("jit_step") == "step"
