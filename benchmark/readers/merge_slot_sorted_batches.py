"""FINAL / PARTIAL_MERGE merges per traced query that reduced by ONE sort of
their packed key id (`jit(agg_merge_sorted)`, planned from the keys'
observed ranges, in place of `jit(agg_merge)`'s ranking of every key word):
the program's `DEVICE_STATS.merge_slot_sorted_batches`. Nothing to read from
a program that does not count them."""


def read(ctx):
    if not all("merge_slot_sorted_batches" in r.device_stats
               for r in ctx.records):
        return None
    return ctx.per_query(
        lambda r, i: r.device_stats["merge_slot_sorted_batches"])
