"""Device milliseconds per job inside Mosaic kernels (``tpu_custom_call``
operations), averaged over devices.  Nothing when no kernel ran."""


def read(ctx):
    secs = sum(ctx.trace.op_seconds("kernel").values())
    if secs <= 0 or not ctx.jobs:
        return None
    return 1e3 * ctx.trace.per_device(secs) / ctx.jobs
