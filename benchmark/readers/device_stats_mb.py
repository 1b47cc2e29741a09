"""A byte counter of the program's `DEVICE_STATS`, per traced query, in MB."""


def read(ctx, key):
    return ctx.per_query(lambda r, i: r.device_stats[key] / 1e6)
