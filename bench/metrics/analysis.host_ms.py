"""Host milliseconds per job in the harness's ``job.communities`` span: the
host copy of the result and the strong-tie communities.  Nothing for a job
without that stage."""


def read(ctx):
    spans = ctx.trace.spans_named("job.communities")
    if not spans or not ctx.jobs:
        return None
    return 1e3 * sum(e - s for s, e in spans) / ctx.jobs
