"""Time a request waited in the engine's queue: the mean ``wait_ms`` of the
window's ``engine.admit`` spans (``TenantEngine.prefill``), from its
``submit`` to the start of its admission, on ``time.perf_counter``. Reads
the spans' arguments, which a trace keeps when read by ``spans.read``."""
from benchmarks.chip import spans


def read(rec, ctx):
    if rec.trace is None:
        return None
    admits = spans.with_args(rec.trace, "engine.admit")
    if not admits:
        return None
    return sum(args["wait_ms"] for _, _, args in admits) / len(admits)
