"""Array operands a traced query handed to the runtime through
`core/kernels._dispatch` / `fused_dispatch`: the sum of `args.operands` (the
array leaves of a call's arguments, which the call flattens one by one in
Python) over the query's `kernel:*` spans, the median over the traced
queries. `enqueue_s` beside it is the same spans' seconds: a concat of fewer,
larger windows brings both down. Nothing to read from a program whose enqueue
spans do not say what they enqueued."""

from benchlib import spans as sp


def read(ctx):
    calls = [s for s in sp.matching(sp.load(), ["kernel:*"])
             if "operands" in s.args]
    per_query = [sp.of_query(calls, r) for r in ctx.records]
    if not any(per_query):
        return None
    return ctx.per_query(
        lambda r, i: sum(s.args["operands"] for s in per_query[i]))
