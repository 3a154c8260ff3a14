"""Device time per engine call in the named scope ``decode`` (ms): the
rows-first transpose back, the block-FP rescale to float64 and the split
into Q and R (`kernels/ops._blockfp_qr`, `core/qrd._split_qr`).  The
float64 emulation's ``X64Combine`` calls carry no scope and are left
out."""
from bench.lib import engine_trace


def read(ctx):
    eng = engine_trace.of(ctx)
    if eng is None or not eng.calls or "decode" not in eng.scopes:
        return None
    return 1e3 * eng.scope_s("decode") / eng.calls
