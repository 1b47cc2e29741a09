"""What the operators' own time (`operator_host_s`, every class) is made of:
CPU the task threads used, and time they did not run. The program stamps a
task thread's whole run (`task:task`) and every upload and pull on it
(`transfer:*`) with the recording thread's CPU clock (`args.cpu_us`) beside
the wall clock; a task thread is in an `op:<class>` segment all but a
hundredth of that run. ``part`` `cpu`: the CPU seconds of the `task` spans
less the CPU inside the same thread's stamped waits among them (of
`operator_host_s`'s ``WAITS`` the program stamps the transfers, which copy;
in a `sync:*`, `scan:decode_wait` or `shuffle:fetch_wait` a thread stands,
and the few per cent of them it runs stay the operators'; a wait nested in
another counts once, a wait on a thread that runs no task is nobody's),
summed over a query's tasks, the median over the traced queries. ``part``
`offcpu`: the `op` segments' host seconds, as `operator_host_s.host_seconds`
computes them, less that CPU: the operators' time in which the thread waited
for the interpreter, was blocked in a call that let it go, or was
descheduled. Nothing to read where the program stamps no `task` span."""

from benchlib import intervals as iv
from benchlib import spans as sp
from readers import span_cpu
from readers.operator_host_s import WAITS, host_seconds

PARTS = ("cpu", "offcpu")


def cpu_seconds(tasks, waits):
    """CPU seconds of ``tasks`` outside the same thread's ``waits``. A wait
    gives up the share of its CPU that its thread's tasks cover of its wall
    time: all of it, where it lies inside one."""
    total = sum(s.args["cpu_us"] for s in tasks) / 1e6
    running = {tid: iv.union((s.start, s.end) for s in group)
               for tid, group in sp.by_thread(tasks).items()}
    for w in span_cpu.outermost(waits):
        wall = w.end - w.start
        inside = iv.covered(running.get(w.tid, ()), w.start, w.end)
        if wall > 0 and inside > 0:
            total -= w.args["cpu_us"] / 1e6 * inside / wall
    return total


def read(ctx, part):
    if part not in PARTS:
        raise ValueError(f"part {part!r} is not one of {PARTS}")
    spans = sp.load()
    tasks = span_cpu.stamped(spans, ["task:task"])
    if not any(sp.of_query(tasks, r) for r in ctx.records):
        return None
    copying = span_cpu.stamped(spans, WAITS)
    segments, blocked = sp.matching(spans, ["op:*"]), sp.matching(spans, WAITS)

    def value(record, i):
        cpu = cpu_seconds(sp.of_query(tasks, record),
                          sp.of_query(copying, record))
        if part == "cpu":
            return cpu
        return host_seconds(sp.of_query(segments, record),
                            sp.of_query(blocked, record)) - cpu

    return ctx.per_query(value)
