"""Host time per engine call in the jitted program's dispatch into the
runtime (ms): the ``repro.qrd.launch`` span, which holds PJRT's
``Execute`` (argument handling, output buffers, enqueue)."""
from bench.lib import engine_trace


def read(ctx):
    eng = engine_trace.of(ctx)
    if eng is None or not eng.calls:
        return None
    return 1e3 * eng.span_s("repro.qrd.launch") / eng.calls
