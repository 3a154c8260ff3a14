"""Host time per engine call from the call until it returns, before the
wait for the device (ms): `QRDEngine` validation, config and callable
lookup, and the jitted program's dispatch (`qrd/engine.py`)."""


def read(ctx):
    n = ctx.spans.count.get("engine_call")
    if not n:
        return None
    return 1e3 * ctx.spans.seconds["engine_call"] / n
