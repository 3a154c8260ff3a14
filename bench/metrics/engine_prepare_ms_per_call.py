"""Host time per engine call before its program is invoked (ms): the
``repro.qrd.prepare`` span — operand checks, tuned-config lookup, cache
key, callable LRU, mesh placement (`QRDEngine._prepare`)."""
from bench.lib import engine_trace


def read(ctx):
    eng = engine_trace.of(ctx)
    if eng is None or not eng.calls:
        return None
    return 1e3 * eng.span_s("repro.qrd.prepare") / eng.calls
