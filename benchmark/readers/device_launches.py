"""Executable launches on a chip per traced query (events of the trace's
`XLA Modules` line): the mean over chips, the median over the queries."""


def read(ctx):
    return ctx.per_query(lambda r, i: sum(
        ctx.reduction.launches_per_query[i].values())
        / len(ctx.reduction.launches_per_query[i]))
