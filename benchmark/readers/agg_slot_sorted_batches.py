"""Slot-table batches per traced query that reduced in the slot-sorted form
(`jit(agg_dense_partial)` of a table past its crossover: one sort of the
packed slot id and prefix scans, in place of a masked vector reduction a
slot): the program's `DEVICE_STATS.agg_slot_sorted_batches`. They are
counted among `agg_dense_batches` too. Nothing to read from a program that
does not count them."""


def read(ctx):
    if not all("agg_slot_sorted_batches" in r.device_stats
               for r in ctx.records):
        return None
    return ctx.per_query(
        lambda r, i: r.device_stats["agg_slot_sorted_batches"])
