"""Seconds in which an operation ran on the chip, per traced query: the
union of the device-op intervals inside the query, the mean over chips, the
median over the traced queries."""


def read(ctx):
    return ctx.per_query(lambda r, i: sum(
        ctx.reduction.busy_per_query_s[i].values())
        / len(ctx.reduction.busy_per_query_s[i]))
