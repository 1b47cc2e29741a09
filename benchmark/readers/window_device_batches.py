"""Window batches per traced query that the device program computed: the
delta of the program's `window_device_batches` counter (one a batch of a
`WindowExec`; `window_host_batches` beside it counts those that took a host
path). Nothing to read where the configuration does not name the counter or
the program does not count it."""


def read(ctx):
    if not all("window_device_batches" in r.counters for r in ctx.records):
        return None
    return ctx.per_query(lambda r, i: r.counters["window_device_batches"])
