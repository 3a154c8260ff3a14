"""Device idle time per engine call from its program's end until the next
call's ``repro.qrd.call`` span starts (ms): the runtime's completion
notice, the host's wake-up from its wait and the caller's own work.  See
`idle_before_program_ms`."""
from bench.lib import engine_trace


def read(ctx):
    eng = engine_trace.of(ctx)
    if eng is None or not eng.pairs:
        return None
    return 1e3 * eng.idle_after_s / len(eng.pairs)
