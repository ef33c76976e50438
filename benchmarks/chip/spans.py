"""The program's own spans in a profiler trace: the
``jax.profiler.TraceAnnotation`` that the program puts at its layer
boundaries (serving engine, KV pool, offload tier, training feed), with
their counts as keyword arguments, on the clock of the device planes.

``trace.read`` keeps every host event but the benchmark's own ``bench.*``
spans in ``Trace.host`` as (name, start, end), so the spans' times need
nothing more. Their arguments it does not keep: ``read`` here keeps them
too, as ``program_spans`` (name, start, end, arguments) on the ``Trace``,
and ``keeping_args`` has the drivers read their traces so. A metric of an
argument reads nothing from a trace read without them, as it reads
nothing from a program that has no such span.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from benchmarks.chip import trace

# name prefixes of the program's spans, whose arguments ``read`` keeps
PROGRAM = ("engine.", "kv.", "offload.", "data.")

_plain_read = trace.read


def times(tr, name: str) -> List[Tuple[float, float]]:
    """(start, end) of the host events named ``name`` inside the window."""
    lo, hi = tr.window
    return [(s, e) for n, s, e in tr.host if n == name and s >= lo and e <= hi]


def within(tr, spans: Sequence[Tuple[float, float]],
           names: Sequence[str]) -> List[float]:
    """Per span, the host seconds inside the union of the host events named
    ``names`` (nested ones count once)."""
    iv = trace.union(np.asarray([(s, e) for n, s, e in tr.host
                                 if n in names]).reshape(-1, 2))
    return [float(np.sum(np.diff(trace.clip(iv, lo, hi), axis=1)))
            for lo, hi in spans]


def with_args(tr, name: str) -> Optional[List[Tuple[float, float, Dict]]]:
    """(start, end, arguments) of the program's spans named ``name`` inside
    the window; None where the trace was read without their arguments."""
    kept = getattr(tr, "program_spans", None)
    if kept is None:
        return None
    lo, hi = tr.window
    return [(s, e, a) for n, s, e, a in kept
            if n == name and s >= lo and e <= hi]


def read(path: str, window_span: str = trace.WINDOW):
    """``trace.read``, and the program's spans with their arguments."""
    from jax.profiler import ProfileData
    tr = _plain_read(path, window_span)
    kept = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0 and e.name.startswith(PROGRAM):
                    s = e.start_ns * 1e-9
                    kept.append((e.name, s, s + e.duration_ns * 1e-9,
                                 dict(e.stats)))
    tr.program_spans = kept
    return tr


@contextlib.contextmanager
def keeping_args():
    """Within it, the drivers' traces keep the program's span arguments."""
    trace.read = read
    try:
        yield
    finally:
        trace.read = _plain_read
