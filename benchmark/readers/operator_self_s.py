"""Self time (`elapsed_compute_time_ns`, host clock) of the operators of the
named classes, summed over a query's tasks; the median over the traced
queries. Tasks run side by side, so the sum can exceed the query's time; and
on an asynchronous device the wait for a kernel lands on whichever operator
synchronises, not on the one that launched it. Nothing to read where no
operator of these classes ran."""


def read(ctx, classes):
    if not any(c in r.self_ns for r in ctx.records for c in classes):
        return None
    return ctx.per_query(lambda r, i: sum(
        r.self_ns.get(c, 0) for c in classes) / 1e9)
