"""Sort-merge join partitions per traced query that the device programs
joined: the delta of the program's `smj_device_joins` counter (one a shuffle
partition; `smj_host_joins` beside it counts those that took the host's key
interning). Nothing to read where the configuration does not name the
counter or the program does not count it."""


def read(ctx):
    if not all("smj_device_joins" in r.counters for r in ctx.records):
        return None
    return ctx.per_query(lambda r, i: r.counters["smj_device_joins"])
