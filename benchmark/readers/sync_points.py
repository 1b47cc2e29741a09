"""Blocking host-device syncs per traced query: the program's
`DEVICE_STATS.sync_calls` (every `int()` of a device scalar and every pull to
the host; each one drains the launch queue). Nothing to read from a program
that does not count them."""


def read(ctx):
    if not all("sync_calls" in r.device_stats for r in ctx.records):
        return None
    return ctx.per_query(lambda r, i: r.device_stats["sync_calls"])
