"""Device idle time per engine call from the call's ``repro.qrd.call``
span until its program ends (ms): the host's prepare and launch, the
runtime's enqueue, and the gaps between the program's ops.  With
`idle_after_program_ms` it makes up all the window's idle time
(`bench/lib/engine_trace.py`); the two can trade the host-device clock
offset between them."""
from bench.lib import engine_trace


def read(ctx):
    eng = engine_trace.of(ctx)
    if eng is None or not eng.pairs:
        return None
    return 1e3 * eng.idle_before_s / len(eng.pairs)
