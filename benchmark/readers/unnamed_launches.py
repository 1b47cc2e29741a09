"""Executable launches of a traced query that no `kernel:<fn>` span timed: the
events of the trace's `XLA Modules` line (`device_launches`) less the
program's `DEVICE_STATS.kernel_calls` delta (one a call through
`core/kernels._dispatch` or `fused_dispatch`, each a `kernel:` span under
tracing). What is left are eager `jnp` operations and jitted functions called
directly: their enqueue lies inside an `op` segment with no name of its own.
The mean over chips, as `device_launches`, the median over the queries: it
holds for a cell on one chip (the count of dispatches is the process's, and
on a mesh one dispatch launches on every chip). A dispatch that launches
more than one executable leaves the others counted here though its span
timed them. Nothing to read from a program that does not count its
dispatches, or from a trace without that line (a rehearsal without a
chip)."""


def read(ctx):
    launches = [sum(by_chip.values()) / len(by_chip)
                for by_chip in ctx.reduction.launches_per_query]
    if not any(launches) or not all(
            "kernel_calls" in r.device_stats for r in ctx.records):
        return None
    return ctx.per_query(
        lambda r, i: launches[i] - r.device_stats["kernel_calls"])
