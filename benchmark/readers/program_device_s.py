"""Device seconds per traced query of the programs named by ``programs``
(shell-style patterns over the launch's name as `breakdown` prints it,
`jit(<fn>)`): the durations of their events on the trace's `XLA Modules`
line that start inside the query's `bench_query` annotation, the mean over
chips, the median over the traced queries. Nothing to read where no such
program was launched (another program, or a rehearsal without a chip)."""

import fnmatch

from benchlib import xplane


def seconds_per_query(trace, programs):
    """One number per `bench_query` annotation of ``trace``, in time order."""
    queries = [(s, s + d) for s, d, name in trace.annotations
               if name == "bench_query"]
    chips = trace.chips
    out = [0.0] * len(queries)
    for chip in chips:
        for start, dur, name in trace.launches.get(chip, ()):
            module = xplane.module_name(name)
            if not any(fnmatch.fnmatchcase(module, p) for p in programs):
                continue
            for i, (lo, hi) in enumerate(queries):
                if lo <= start < hi:
                    out[i] += dur / 1e9 / len(chips)
    return out


def read(ctx, programs):
    per_query = seconds_per_query(ctx.trace, programs)
    if not any(per_query):
        return None
    return ctx.per_query(lambda r, i: per_query[r.index])
