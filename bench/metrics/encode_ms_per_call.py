"""Device time per engine call in the named scope ``encode`` (ms): the
identity augmentation, the block-FP exponent and rounding, the rows-first
transpose (`core/qrd.py`, `kernels/ops._blockfp_qr`).  The float64
emulation's ``X64Split*`` calls carry no scope and are left out."""
from bench.lib import engine_trace


def read(ctx):
    eng = engine_trace.of(ctx)
    if eng is None or not eng.calls or "encode" not in eng.scopes:
        return None
    return 1e3 * eng.scope_s("encode") / eng.calls
