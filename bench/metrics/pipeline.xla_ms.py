"""Device milliseconds per job in XLA operations outside Mosaic kernels and
collectives: pads, the neighbor cube's gather and lane pad, weights,
scatter_dense.  Averaged over devices."""


def read(ctx):
    if not ctx.jobs or not ctx.trace.ops:
        return None
    secs = sum(ctx.trace.op_seconds("xla").values())
    return 1e3 * ctx.trace.per_device(secs) / ctx.jobs
