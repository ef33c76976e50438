#!/usr/bin/env python3
"""Readings that the limits of ``limits/<workload>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload phi3-mini.chat \
        --seconds 12 --seeds 101 102 ... --control-seeds 101 102 103

Runs the cell once per seed in this one process (compiled programs are
shared), each run as the benchmark runs it but with a short window, and
prints the numbers compared. On the control seeds it also reads the
control: the reference computed in fp8 put in the program's place, and
for training the reference with half of each batch left out. The lower
reading of a number is the largest over the seeds; the upper one the
smallest the control gives. The last line is a JSON summary.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
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
    rows = []
    for seed in args.seeds:
        ctl = "fp8" if seed in args.control_seeds else None
        out = run_cell(bench, cell, seed, args.seconds, False, peaks=peaks,
                       t_start=time.perf_counter(), control=ctl)
        row = {"seed": seed, "checks": {k: c["value"] for k, c in
                                        out["checks"].items()},
               "control": out.get("control"),
               "metrics": {k: m["value"] for k, m in out["metrics"].items()},
               "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    names = rows[0]["checks"]
    summary = {"lower": {k: max(r["checks"][k] for r in rows) for k in names}}
    ctl_rows = [r["control"] for r in rows if r["control"]]
    if ctl_rows:
        summary["upper"] = {f"{kind}.{k}": min(c[kind]["checks"][k]
                                               for c in ctl_rows)
                            for kind in ctl_rows[0]
                            for k in ctl_rows[0][kind]["checks"]}
        summary["control_correct"] = {
            kind: [c[kind]["correct"] for c in ctl_rows] for kind in ctl_rows[0]}
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
