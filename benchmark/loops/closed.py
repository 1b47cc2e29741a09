"""Closed loop, one client, through `Session.execute_to_table`: a Spark
driver submits a query and waits for its answer. No query starts once the
window has passed; the one in flight finishes and counts."""

import time

from benchlib import stats


def run(system, next_query, seconds, max_queries, probe):
    """Issues queries until ``seconds`` have passed or ``max_queries`` ran.

    ``next_query()`` gives ``(class name, plan)``. ``probe(index, name)`` is
    a context manager yielding the query's record; what it does on entry and
    exit is outside the timed span, which is the host clock around plan in,
    Arrow table on the host out."""
    records = []
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline and \
            (max_queries is None or len(records) < max_queries):
        name, plan = next_query()
        with probe(len(records), name) as record:
            record.t0 = time.perf_counter()
            try:
                record.table = system.session.execute_to_table(plan)
            except Exception as exc:  # counted as failed, reported by run.py
                record.error = exc
            record.seconds = time.perf_counter() - record.t0
        records.append(record)
    return records


def end_to_end(records, seconds):
    """What this loop's users see, from the queries that answered rightly:
    the median time a driver waited for one."""
    return {"query_s": stats.median([r.seconds for r in records])}
