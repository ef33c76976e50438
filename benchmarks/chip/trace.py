"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy and idle time over the traced window, device time per
compiled program, the host spans the benchmark put around its own calls,
and the ``breakdown`` (top device operations, idle gaps by host activity).

Device planes are ``/device:TPU:<n>``. On each, the ``XLA Modules`` line
holds one event per program execution and the ``XLA Ops`` line one per
operation; busy time is the union of the operation intervals. The host
plane's events are the benchmark's ``bench.*`` annotations and the
runtime's own activity, on the same clock.
"""
from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np

WINDOW = "bench.window"        # host span around the measured window
_MODULE_ID = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]   # (start_s, end_s)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def program_name(module: str) -> str:
    """``jit__decode_step(12)`` -> ``_decode_step``: the jitted function."""
    name = _MODULE_ID.sub("", module)
    return name[4:] if name.startswith("jit_") else name


def union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    grp = np.cumsum(new) - 1
    merged_end = np.zeros(len(starts))
    np.maximum.at(merged_end, grp, ends)
    return np.stack([starts, merged_end], 1)


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


@dataclass
class Device:
    name: str
    ops: Dict[str, float] = field(default_factory=dict)   # op -> seconds
    op_iv: np.ndarray = field(default_factory=lambda: np.zeros((0, 2)))
    op_names: List[str] = field(default_factory=list)
    modules: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    window: Interval
    devices: List[Device]
    spans: List[Tuple[str, float, float]]        # bench.* host spans
    host: List[Tuple[str, float, float]]         # every other host event

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, dev: Device) -> np.ndarray:
        return clip(union(dev.op_iv), *self.window)

    @property
    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over the devices
        that ran any."""
        used = [d for d in self.devices if len(d.op_iv)]
        if not used:
            return 0.0
        return float(np.mean([np.sum(np.diff(self.busy(d), axis=1))
                              for d in used]))

    def program(self, fn: str) -> Tuple[float, int]:
        """(device seconds, executions) of the program jitted from ``fn``
        inside the window, summed over devices."""
        total, n = 0.0, 0
        lo, hi = self.window
        for d in self.devices:
            for name, s, e in d.modules:
                if program_name(name) == fn and s >= lo and e <= hi:
                    total += e - s
                    n += 1
        return total, n

    def program_time_within(self, spans: Sequence[Tuple[float, float]],
                            fns: Sequence[str]) -> List[float]:
        """Per span, the device seconds of programs ``fns`` that overlap
        it (first device). Host and device clocks of one trace agree to
        about a tenth of a millisecond, so a program may lean over a
        span's edge by that much."""
        if not self.devices:
            return [0.0] * len(spans)
        mods = np.asarray([(s, e) for name, s, e in self.devices[0].modules
                           if program_name(name) in fns]).reshape(-1, 2)
        return [float(np.sum(np.clip(np.minimum(mods[:, 1], hi)
                                     - np.maximum(mods[:, 0], lo), 0, None)))
                for lo, hi in spans]

    def span_times(self, name: str) -> List[Tuple[float, float]]:
        lo, hi = self.window
        return [(s, e) for n, s, e in self.spans
                if n == name and s >= lo and e <= hi]

    def breakdown(self, top: int = 10) -> dict:
        """Top device operations by time, and idle time inside the window
        by the innermost host event running at each gap's midpoint."""
        ops: Dict[str, float] = {}
        for d in self.devices:
            for k, v in d.ops.items():
                ops[k] = ops.get(k, 0.0) + v
        device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        # an operation's name is its HLO text: keep its name and shape
        device_ops = [(k[:160], v) for k, v in device_ops]
        idle: Dict[str, float] = {}
        used = [d for d in self.devices if len(d.op_iv)]
        if used:
            busy = self.busy(used[0])
            lo, hi = self.window
            edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
            gaps = edges[edges[:, 1] > edges[:, 0]]
            host = np.asarray([(s, e) for _, s, e in self.host]).reshape(-1, 2)
            names = [n for n, _, _ in self.host]
            for g0, g1 in gaps:
                mid = 0.5 * (g0 + g1)
                cover = np.nonzero((host[:, 0] <= mid) & (host[:, 1] >= mid))[0]
                if len(cover):
                    inner = cover[np.argmin(host[cover, 1] - host[cover, 0])]
                    label = names[inner]
                else:
                    label = "(no host event)"
                idle[label] = idle.get(label, 0.0) + float(g1 - g0)
        idle_gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in device_ops],
                "idle_gaps": [[k, v] for k, v in idle_gaps]}


def read(path: str, window_span: str = WINDOW) -> Trace:
    """Reduce one ``.xplane.pb``. The window is the host span named
    ``window_span``; without one, the whole trace."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: List[Device] = []
    spans, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in plane.name:
            dev = Device(plane.name)
            names, ivs = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [(e.name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9)
                                   for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        s = e.start_ns * 1e-9
                        names.append(e.name)
                        ivs.append((s, s + e.duration_ns * 1e-9))
            dev.op_iv = np.asarray(ivs, dtype=float).reshape(-1, 2)
            dev.op_names = names
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    s = e.start_ns * 1e-9
                    item = (e.name, s, s + e.duration_ns * 1e-9)
                    (spans if e.name.startswith("bench.") else host).append(item)
    win = [(s, e) for n, s, e in spans if n == window_span]
    if win:
        window = (min(s for s, _ in win), max(e for _, e in win))
    else:
        ends = [iv for d in devices for iv in d.op_iv.tolist()]
        ends += [(s, e) for _, s, e in spans + host]
        window = ((min(s for s, _ in ends), max(e for _, e in ends))
                  if ends else (0.0, 0.0))
    lo, hi = window
    for d in devices:
        # operation time inside the window, by operation name
        if not len(d.op_iv):
            continue
        keep = np.nonzero((d.op_iv[:, 1] > lo) & (d.op_iv[:, 0] < hi))[0]
        for i, (s, e) in zip(keep, np.clip(d.op_iv[keep], lo, hi)):
            d.ops[d.op_names[i]] = d.ops.get(d.op_names[i], 0.0) + (e - s)
    return Trace(window, devices, spans, host)
