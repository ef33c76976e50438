"""Host time of one admission: the mean length of the window's
``engine.admit`` spans (``TenantEngine.prefill``: a slot claimed, the
prompt's prefill, its paste into the pool)."""
from benchmarks.chip import spans


def read(rec, ctx):
    if rec.trace is None:
        return None
    admits = spans.times(rec.trace, "engine.admit")
    if not admits:
        return None
    return 1e3 * sum(e - s for s, e in admits) / len(admits)
