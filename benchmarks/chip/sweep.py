#!/usr/bin/env python3
"""Rate sweep of an open-loop serving cell, to find the highest arrival
rate the system sustains without a growing backlog (the knee).

    python3 benchmarks/chip/sweep.py --workload phi3-mini.chat \
        --seconds 20 --seed 5 --rates 1.5 2.5 3.5 4.5

Runs the cell once per rate in this one process, as the benchmark runs
it, with the mix's rate replaced; prints one JSON line per rate with the
offered and served token rates and the tails. A cell's traffic file then
fixes its rate as a number (about four fifths of the knee).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
from benchmarks.chip.run import (HERE, ROOT, cell_of,  # noqa: E402
                                 enable_compile_cache, read_json, run_cell)
from benchmarks.chip.traffic import loadgen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("error: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    bench = read_json(ROOT / "BENCHMARK.json")
    cell = cell_of(bench, args.workload)
    peaks = read_json(HERE / "peaks.json")[dev.device_kind]
    mix = loadgen.load_mix(cell["traffic"])
    for rate in args.rates:
        arrivals = {**mix["arrivals"], "rate_rps": rate}
        n = int(round(rate * args.seconds))
        out_len = loadgen.lengths(mix["output_len"], n, loadgen.rng(0, 0))
        out = run_cell(bench, cell, args.seed, args.seconds, False,
                       peaks=peaks, t_start=time.perf_counter(),
                       mix_overrides={"arrivals": arrivals})
        m = {k: v["value"] for k, v in out["metrics"].items()}
        print(json.dumps({"rate_rps": rate,
                          "offered_tok_s": float(out_len.sum()) / args.seconds,
                          **m, "failed": out["failed"],
                          "correct": out["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
