"""Milliseconds from the start of a job's ``job.call`` span to the job's
first operation on the device, averaged over jobs: planning, validation
and dispatch on the host before the device has work."""


def read(ctx):
    tr = ctx.trace
    dev = min(tr.ops, default=None)
    if dev is None:
        return None
    starts = sorted(s for _, s, _, _ in tr.ops[dev])
    delays = []
    for s, e in tr.spans_named("job.call"):
        first = next((t for t in starts if s <= t < e), None)
        if first is not None:
            delays.append(first - s)
    return 1e3 * sum(delays) / len(delays) if delays else None
