"""Seconds in which an operation ran on the least busy chip, per traced
query (median over the traced queries). Beside `device_busy_s`, the mean
over chips, it says whether every chip of a mesh works or one does."""


def read(ctx):
    if len(ctx.reduction.busy_s) < 2:
        return None
    return ctx.per_query(
        lambda r, i: min(ctx.reduction.busy_per_query_s[i].values()))
