"""Bytes the mesh all_to_all moved per traced query (the program's
`collective_bytes` counter), in MB."""


def read(ctx):
    return ctx.per_query(lambda r, i: r.counters["collective_bytes"] / 1e6)
