"""Batches per traced query that a join, an Expand or an aggregation stage
worked through with a var-width column as dictionary codes on the device:
the delta of the program's `coded_key_batches` counter (`host_key_batches`
beside it counts those whose coded column was turned into a host column).
Nothing to read where the configuration does not name the counter or the
program does not count it."""


def read(ctx):
    if not all("coded_key_batches" in r.counters for r in ctx.records):
        return None
    return ctx.per_query(lambda r, i: r.counters["coded_key_batches"])
