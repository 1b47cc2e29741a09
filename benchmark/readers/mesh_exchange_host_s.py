"""The mesh exchange's own Python: seconds inside the program's
`mesh:exchange` spans (the lowering thread's part of a mesh exchange after the map
tasks: the send buffers cut on each chip, the all_to_all enqueued, each
reducer's rows gathered on its chip) less what the same thread spent inside
them waiting for the device or copying (`sync:*`, `transfer:*`), summed over
the threads, the median over the traced queries. Nothing to read where the
program records no such span (one without the task-a-chip exchange, or a
cell whose session has no mesh)."""

from benchlib import spans as sp
from readers.operator_host_s import host_seconds

WAITS = ("sync:*", "transfer:*")


def read(ctx):
    spans = sp.load()
    exchanges = sp.matching(spans, ["mesh:exchange"])
    if not any(sp.of_query(exchanges, r) for r in ctx.records):
        return None
    blocked = sp.matching(spans, WAITS)
    return ctx.per_query(lambda r, i: host_seconds(
        sp.of_query(exchanges, r), sp.of_query(blocked, r)))
