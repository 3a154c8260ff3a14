"""Device time per engine call outside the Pallas kernels (ms): the
float64 encode and decode around the kernel (`kernels/ops.py`,
`core/formats.py`), the identity augmentation, layout copies and the
float64 emulation's split and combine calls."""


def read(ctx):
    if not ctx.trace.other_s or not ctx.traced_calls():
        return None
    return 1e3 * ctx.trace.other_s / ctx.traced_calls()
