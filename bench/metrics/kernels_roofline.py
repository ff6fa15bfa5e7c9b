"""Share of the Mosaic kernels' roofline, in %: the least time the chip
could take for the algorithm's own work in the kernels a job runs (each
kernel's work model in ``bench/kernels``), over the device time they took.
Nothing when no modelled kernel ran."""


def read(ctx):
    rows = ctx.kernel_rows()
    if not rows:
        return None
    return 100.0 * sum(r["least_ms"] for r in rows) / sum(
        r["ms_per_job"] for r in rows)
