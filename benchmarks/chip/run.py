#!/usr/bin/env python3
"""On-chip benchmark: runs one cell of ``BENCHMARK.json`` and prints one
JSON result line.

    python3 benchmarks/chip/run.py --workload phi3-mini.chat --seed 7 \
        --seconds 51 --trace 0

Everything a cell needs is found by name, so a cell or a metric is added
with files and entries only:

* ``configs/<config>.json`` — the model's published sizes, the program
  entry that serves them, and the name of its plain reference;
* ``reference/<reference>.py`` — that reference (weights from the seed,
  forward pass, training steps), importing nothing of the program;
* ``traffic/<traffic>.json`` — the mix, read by ``traffic/loadgen.py``,
  naming the driver that runs it;
* ``drivers/<driver>.py`` — set-up, warm-up, the measured window and the
  comparison with the reference;
* ``metrics/<metric>.py`` — one reader per per-layer metric;
* ``limits/<workload>.json`` — the limit of each number compared.

Set-up time (``setup_s``) runs from the start of ``main`` to the start of
the window, JAX's start-up on the chip included. With ``--trace 1`` the
window runs under the profiler and the line carries the per-layer
metrics; with ``--trace 0`` the end-to-end ones. Without a TPU, or with
fewer chips than the cell asks for, nothing runs and the exit code is 2.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, Optional, Tuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CACHE_DIR = ROOT / ".jax_cache"


def read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"{path.relative_to(ROOT)} not found")
    return json.loads(path.read_text())


def load_module(path: Path):
    """Import a file by path (names may hold dots, as metric names do)."""
    if not path.is_file():
        raise FileNotFoundError(f"{path.relative_to(ROOT)} not found")
    name = "bench_" + "_".join(path.relative_to(HERE).with_suffix("").parts)
    name = name.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts XLA lowerings (a compile or a load from the persistent cache)
    and backend compiles, so that the window can show it did neither."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.lowerings = 0
        self.compiles = 0
        self._lower = dispatch.JAXPR_TO_MLIR_MODULE_EVENT
        self._compile = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self._lower:
            self.lowerings += 1
        elif event == self._compile:
            self.compiles += 1

    def snapshot(self) -> Tuple[int, int]:
        return self.lowerings, self.compiles


@dataclass
class Ctx:
    """What a driver gets: the cell, its files, and the run's arguments."""
    cell: dict
    seed: int
    seconds: float
    trace: bool
    cfg_file: dict
    mix: dict
    spec: object                 # the reference's Spec of cfg_file
    ref: object                  # the reference module
    peaks: Optional[dict]
    limits: dict
    t_start: float
    compiles: CompileCounter
    log: Callable = log
    # calibration only (calibrate.py): also read the lower-precision control
    # and the planted faults, which the benchmark's own runs never do
    control: Optional[str] = None


@dataclass
class Record:
    """What a driver returns."""
    setup_s: float
    window_s: float
    e2e: Dict[str, float]
    attempted: int
    failed: int
    checks: Dict[str, Tuple[float, float]]     # name -> (value, limit)
    memory_peak_bytes: Optional[int]
    trace: object = None                        # trace.Trace of the window
    data: dict = field(default_factory=dict)    # what metric readers read


def cell_of(bench: dict, name: str) -> dict:
    for c in bench["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[c['name'] for c in bench['workloads']]}")


def metrics_of(bench: dict, cell: dict, trace: bool) -> list:
    """The metrics this cell reports: end-to-end ones without a trace,
    per-layer ones with it."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell["name"] in m.get("workloads", [cell["name"]])
            and m["moves"] in reported]


def enable_compile_cache() -> None:
    """JAX's persistent cache at a fixed path inside the checkout; every
    program is kept, the eager ones that compile in under a second too."""
    import jax
    CACHE_DIR.mkdir(exist_ok=True)    # JAX writes no entry into a missing one
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(bench: dict, cell: dict, seed: int, seconds: float, trace: bool,
             *, peaks: Optional[dict], t_start: float,
             sizes: Optional[dict] = None, mix_overrides: Optional[dict] = None,
             limits: Optional[dict] = None, control: Optional[str] = None
             ) -> dict:
    """Run one cell and return its result object. ``sizes`` and
    ``mix_overrides`` replace keys of the configuration and the traffic
    mix, and ``limits`` the cell's limits; the CPU tests use them to run
    the same path at a small size."""
    from benchmarks.chip.traffic import loadgen
    cfg_file = read_json(HERE / "configs" / f"{cell['config']}.json")
    cfg_file.update(sizes or {})
    mix = loadgen.load_mix(cell["traffic"])
    mix.update(mix_overrides or {})
    ref = load_module(HERE / "reference" / f"{cfg_file['reference']}.py")
    driver = load_module(HERE / "drivers" / f"{mix['driver']}.py")
    ctx = Ctx(cell=cell, seed=seed, seconds=seconds, trace=trace,
              cfg_file=cfg_file, mix=mix, spec=ref.spec(cfg_file), ref=ref,
              peaks=peaks,
              limits=limits or read_json(HERE / "limits" / f"{cell['name']}.json"),
              t_start=t_start, compiles=CompileCounter(), control=control)
    rec: Record = driver.run(ctx)

    metrics = {}
    for m in metrics_of(bench, cell, trace):
        if trace:
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(
                rec, ctx)
        else:
            value = rec.e2e.get(m["name"])
            if value is None:
                raise KeyError(f"driver {mix['driver']!r} gives no "
                               f"{m['name']!r}")
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": rec.memory_peak_bytes}
    out = {"correct": all(v <= lim for v, lim in rec.checks.values()),
           "attempted": rec.attempted, "failed": rec.failed,
           "metrics": metrics, "device": device}
    if trace and rec.trace is not None:
        device["busy_s"] = rec.trace.busy_s
        device["window_s"] = rec.trace.window_s
        out["breakdown"] = rec.trace.breakdown()
    if control:
        # each control in the program's place, judged by the same limits
        out["control"] = {
            kind: {"correct": all(v <= rec.checks[k][1] for k, v in got.items()),
                   "checks": got}
            for kind, got in rec.data["control"].items()}
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in rec.checks.items()}
    return out


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = read_json(ROOT / "BENCHMARK.json")
    cell = cell_of(bench, args.workload)

    import repro  # noqa: F401  (the system under test; without it, nothing runs)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"error: JAX found no TPU (platform {devices[0].platform!r}); "
            f"nothing was run")
        return 2
    if len(devices) < cell["chips"]:
        log(f"error: {cell['name']} needs {cell['chips']} chips, JAX found "
            f"{len(devices)}")
        return 2
    kind = devices[0].device_kind
    peaks = read_json(HERE / "peaks.json")
    if kind not in peaks:
        log(f"error: no peaks for device kind {kind!r} in peaks.json")
        return 2
    enable_compile_cache()
    out = run_cell(bench, cell, args.seed, args.seconds, bool(args.trace),
                   peaks=peaks[kind], t_start=t_start)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
