"""The per-layer metrics read from the program's own spans that wait, with
the serving cells, for BENCHMARK.json to admit them: ``small.bench()``
with them added, and the small cells run with the traces keeping the
spans' arguments (``spans.keeping_args``)."""
import copy
import time

from benchmarks.chip import spans
from benchmarks.chip.tests import small

PENDING = {
    "per_layer": [
        {"name": "engine.queue_wait_ms.chat", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "runtime / engine",
         "moves": "ttft_p95_ms", "workloads": ["phi3-mini.chat"]},
        {"name": "engine.admit_ms.chat", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "runtime / engine",
         "moves": "ttft_p95_ms", "workloads": ["phi3-mini.chat"]},
        {"name": "offload.h2d_ms_per_tick.spill", "unit": "ms",
         "better": "lower", "source": "device_trace", "layer": "offload tier",
         "moves": "output_tok_s", "workloads": ["phi3-mini.spill"]},
        {"name": "offload.d2h_ms_per_tick.spill", "unit": "ms",
         "better": "lower", "source": "device_trace", "layer": "offload tier",
         "moves": "output_tok_s", "workloads": ["phi3-mini.spill"]},
        {"name": "offload.mb_per_tick.spill", "unit": "MB",
         "better": "lower", "source": "device_trace", "layer": "offload tier",
         "moves": "output_tok_s", "workloads": ["phi3-mini.spill"]},
    ],
}


def bench() -> dict:
    """``small.bench()``, with the pending span metrics it does not hold."""
    b = copy.deepcopy(small.bench())
    for key, entries in PENDING.items():
        have = {e["name"] for e in b[key]}
        b[key] += [copy.deepcopy(e) for e in entries if e["name"] not in have]
    return b


def run_small(workload: str, seed: int = 2**31 + 77, seconds: float = 2.0,
              trace: bool = False) -> dict:
    from benchmarks.chip import run
    b = bench()
    cell = run.cell_of(b, workload)
    with spans.keeping_args():
        return run.run_cell(b, cell, seed, seconds, trace, peaks=small.PEAKS,
                            t_start=time.perf_counter(),
                            sizes=small.SIZES.get(cell["config"], {}),
                            mix_overrides=small.MIXES.get(cell["traffic"], {}),
                            limits=small.LIMITS.get(workload))
