"""Share of its roofline that the block-FP QR kernel reaches (%).

The least time the chip could take for one engine call's kernel work —
the larger of its lower-bound int32 operations over the chip's int32
vector peak and its operand bytes over HBM bandwidth (`bench/work`,
`bench/peaks.json`) — over the Pallas kernels' device time per call in
the trace.  A lower-bound count over upper-bound peaks: it cannot pass
100 % unless the trace misses kernel time.
"""


def read(ctx):
    if not ctx.trace.kernel_s or not ctx.traced_calls():
        return None
    ops, nbytes = ctx.work()
    least = max(ops / ctx.peaks["int32_vector_ops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least * ctx.traced_calls() / ctx.trace.kernel_s
