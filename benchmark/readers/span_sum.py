"""Seconds a traced query's threads spent inside the program's spans named
by ``spans`` (patterns over `"<cat>:<name>"`), summed over the threads, the
median over the traced queries. With ``per`` it is a rate instead: the sum of
that argument over the same spans (`rows`, `bytes`), times ``scale``, divided
by those seconds. Nothing to read where no traced query has such a span (a
program that does not record them)."""

from benchlib import spans as sp


def read(ctx, spans, per=None, scale=1.0):
    wanted = sp.matching(sp.load(), spans)
    per_query = [sp.of_query(wanted, r) for r in ctx.records]
    if not any(per_query):
        return None

    def value(record, i):
        seconds = sp.thread_seconds(per_query[i])
        if per is None:
            return seconds
        total = sum(s.args.get(per, 0) for s in per_query[i])
        return scale * total / seconds if seconds else 0.0

    return ctx.per_query(value)
