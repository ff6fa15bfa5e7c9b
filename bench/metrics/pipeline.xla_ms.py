"""Device milliseconds per job in XLA operations outside Mosaic kernels and
collectives: pads, the neighbor cube's gather and lane pad, weights,
scatter_dense, and on a mesh the sharded selection, merge and values.
Averaged over devices.  An operation that holds others on its device (a
``while`` loop, whose body's operations the trace lists as well) is counted
through them and not beside them."""


def _holders(ops):
    """The operations inside whose interval another one of nonzero length
    lies, on one device."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    open_, held = [], set()
    for i, (_, s, e, _) in enumerate(ops):
        while open_ and ops[open_[-1]][2] <= s:
            open_.pop()
        if open_ and s < e <= ops[open_[-1]][2]:
            held.add(open_[-1])
        open_.append(i)
    return [ops[i] for i in sorted(held)]


def read(ctx):
    tr = ctx.trace
    if not ctx.jobs or not tr.ops:
        return None
    secs = sum(tr.op_seconds("xla").values()) - sum(
        e - s for ops in tr.ops.values() for _, s, e, k in _holders(ops)
        if k == "xla")
    return 1e3 * tr.per_device(secs) / ctx.jobs
