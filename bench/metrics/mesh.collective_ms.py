"""Device milliseconds per job in collective operations (all-gather,
all-reduce, collective-permute, reduce-scatter, all-to-all, with their
``-start`` and ``-done`` halves), averaged over the cell's devices.
Nothing when no collective ran."""


def read(ctx):
    secs = sum(ctx.trace.op_seconds("collective").values())
    if secs <= 0 or not ctx.jobs:
        return None
    return 1e3 * ctx.trace.per_device(secs) / ctx.jobs
