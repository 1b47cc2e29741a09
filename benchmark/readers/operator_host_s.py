"""An operator class's self time with the waiting taken out: its Python and
host compute. The program records every stretch it charges to an operator's
`elapsed_compute_time_ns` as an `op:<class>` span, so the `op` spans of the
classes that ``classes_of`` names (another reader's file, `agg_self_s`) add
up to that reader's number. From each is taken what the same thread spent,
inside it, blocked or copying: ``waits`` (`sync:*` on a device scalar,
`transfer:*` to and from the device, `scan:decode_wait` and
`shuffle:fetch_wait` on a prefetch thread); waits nested in one another count
once, and another thread's waits are not this operator's. Summed over a
query's tasks, the median over the traced queries. Nothing to read where the
program records no `op` span of these classes."""

import json
import os

from benchlib import intervals as iv
from benchlib import spans as sp

WAITS = ("sync:*", "transfer:*", "scan:decode_wait", "shuffle:fetch_wait")


def host_seconds(segments, waits):
    """Seconds of ``segments`` not covered by the same thread's ``waits``."""
    waits_of = {tid: iv.union((w.start, w.end) for w in group)
                for tid, group in sp.by_thread(waits).items()}
    return sum((s.end - s.start)
               - iv.covered(waits_of.get(s.tid, ()), s.start, s.end)
               for s in segments)


def read(ctx, classes_of, waits=WAITS):
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           classes_of + ".json")) as f:
        classes = json.load(f)["params"]["classes"]
    spans = sp.load()
    segments = sp.matching(spans, [f"op:{c}" for c in classes])
    if not any(sp.of_query(segments, r) for r in ctx.records):
        return None
    blocked = sp.matching(spans, waits)
    return ctx.per_query(lambda r, i: host_seconds(
        sp.of_query(segments, r), sp.of_query(blocked, r)))
