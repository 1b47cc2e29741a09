"""PARTIAL aggregation batches per traced query that the slot-table kernel
answered (`jit(agg_dense_partial)`, chosen from the stream's probed key
range): the program's `DEVICE_STATS.agg_dense_batches`. The batches the sort
kernel answered are `agg_sort_batches` beside it. Nothing to read from a
program that does not count them."""


def read(ctx):
    if not all("agg_dense_batches" in r.device_stats for r in ctx.records):
        return None
    return ctx.per_query(lambda r, i: r.device_stats["agg_dense_batches"])
