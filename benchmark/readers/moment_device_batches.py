"""Aggregation batches per traced query, partial or merge, whose STDDEV_SAMP
moments a device kernel reduced (`jit(agg_dense_partial)`, `jit(agg_partial)`,
`jit(agg_merge)`): the program's `DEVICE_STATS.moment_device_batches`. The
batches the generic table reduced are `moment_host_batches` beside it.
Nothing to read from a program that does not count them."""


def read(ctx):
    if not all("moment_device_batches" in r.device_stats for r in ctx.records):
        return None
    return ctx.per_query(lambda r, i: r.device_stats["moment_device_batches"])
