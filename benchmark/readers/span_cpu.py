"""CPU seconds a traced query's threads ran inside the program's spans named
by ``spans`` (patterns over `"<cat>:<name>"`): the sum of `args.cpu_us`, the
recording thread's own CPU clock across the span, over the threads, the
median over the traced queries. Of spans nested in one another on a thread
only the outermost counts: its CPU holds theirs. `span_sum` beside it gives
the same spans' wall seconds; the difference is time the threads did not run.
Nothing to read where no traced query has such a span with `cpu_us` (a
program that does not stamp it)."""

from benchlib import spans as sp


def outermost(spans):
    """Of each thread's spans those that lie in no other of them."""
    out = []
    for group in sp.by_thread(spans).values():
        end = float("-inf")
        for s in sorted(group, key=lambda s: (s.start, -s.end)):
            if s.end > end:
                out.append(s)
                end = s.end
    return out


def cpu_seconds(spans):
    return sum(s.args["cpu_us"] for s in outermost(spans)) / 1e6


def stamped(spans, patterns):
    """The spans matching ``patterns`` that carry the CPU stamp."""
    return [s for s in sp.matching(spans, patterns) if "cpu_us" in s.args]


def read(ctx, spans):
    wanted = stamped(sp.load(), spans)
    per_query = [sp.of_query(wanted, r) for r in ctx.records]
    if not any(per_query):
        return None
    return ctx.per_query(lambda r, i: cpu_seconds(per_query[i]))
