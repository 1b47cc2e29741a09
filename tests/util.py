"""Test helpers: in-memory sources and operator runners (the analogue of the
reference's MemoryExec-based JVM-free operator tests, SURVEY.md §4.1)."""

import pyarrow as pa

from blaze_tpu.core import ColumnarBatch
from blaze_tpu.ir import types as T
from blaze_tpu.ops.base import ExecContext, Operator
from blaze_tpu.ops.basic import MemoryScanExec


def mem_scan(data_or_batches, schema=None, num_batches=1):
    """Build a MemoryScanExec from a pydict (optionally split into batches)
    or a list of per-partition batch lists."""
    if isinstance(data_or_batches, dict):
        big = ColumnarBatch.from_pydict(data_or_batches, schema)
        n = big.num_rows
        if num_batches <= 1 or n == 0:
            batches = [big]
        else:
            per = max(1, (n + num_batches - 1) // num_batches)
            batches = [big.slice(i, per) for i in range(0, n, per)]
        return MemoryScanExec(big.schema, [batches])
    partitions = data_or_batches
    return MemoryScanExec(schema, partitions)


def jaxpr_eqns(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs (pjit, cond,
    while bodies) included."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from jaxpr_eqns(sub)


def run_op(op: Operator, partition=0, ctx=None):
    ctx = ctx or ExecContext()
    return list(op.execute(partition, ctx))


def collect(op: Operator, ctx=None):
    """All partitions -> single arrow table."""
    ctx = ctx or ExecContext()
    batches = []
    for p in range(op.num_partitions()):
        for b in op.execute(p, ctx):
            if b.num_rows:
                batches.append(b.to_arrow())
    if not batches:
        return T.schema_to_arrow(op.schema).empty_table()
    return pa.Table.from_batches(batches)


def collect_pydict(op: Operator, ctx=None):
    return collect(op, ctx).to_pydict()


class CrashOnce:
    """Worker-crash fixture UDF: hard-kills the hosting process on the first
    call (marker file absent), passes through afterwards. Module-level class
    so it pickles by reference across the driver->worker boundary."""

    def __init__(self, marker_path):
        self.marker_path = marker_path

    def __call__(self, x):
        import os

        if not os.path.exists(self.marker_path):
            with open(self.marker_path, "w") as f:
                f.write("attempt")
            os._exit(9)
        return x


class CrashAlways:
    """Worker-crash fixture UDF: hard-kills the hosting WORKER process on
    every call (retry-budget exhaustion tests). Guarded by the failpoint
    salt, which WorkerPool.spawn exports to every pool worker and the driver
    never sets on itself, so in-driver fallback attempts survive and only
    pool workers die."""

    def __call__(self, x):
        import os

        if os.environ.get("BLAZE_TPU_FAILPOINT_SALT") is not None:
            os._exit(9)
        raise RuntimeError("CrashAlways ran outside a pool worker")
