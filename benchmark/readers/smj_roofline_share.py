"""The least time the chip's HBM could take over what a merge join must read
and write once, as a share of the device time of the join's programs
(`smj_device_s`). Bound: memory bandwidth (a join compares and copies; it
computes next to nothing a byte). The bytes are counted from the rows joined
and the pairs found, whatever implements the join: `join_bytes`. Nothing to
read where the traced queries' class declares no merge join, the program
does not count `smj_matched_pairs`, or no join program ran on the device."""

from benchlib import device
from readers import program_device_s

PLANE_BYTES = 8 + 1  # an int64 data plane and its validity plane, a row


def join_bytes(left_rows, right_rows, pairs, keys, left_columns, right_columns,
               plane_bytes=PLANE_BYTES):
    """Bytes an inner merge join cannot avoid: every row's key planes of both
    sides read once to match them, and for every pair found all columns of
    both sides read once (the gather) and written once (the output)."""
    match = (left_rows + right_rows) * keys * plane_bytes
    move = pairs * (left_columns + right_columns) * plane_bytes
    return match + 2 * move


def read(ctx):
    device_s = program_device_s.seconds_per_query(ctx.trace, ["jit(smj_*)"])
    joins = {name: getattr(cls, "MERGE_JOIN", None)
             for name, cls in ctx.classes.items()}
    if not any(device_s) or not all(
            joins[r.name] and "smj_matched_pairs" in r.counters
            for r in ctx.records):
        return None
    bandwidth = device.peaks(ctx.system.devices[0].device_kind)["hbm_bytes_per_s"]

    def share(record, i):
        join = joins[record.name]
        (left, left_columns), (right, right_columns) = join["left"], join["right"]
        nbytes = join_bytes(
            ctx.system.data.rows(left), ctx.system.data.rows(right),
            record.counters["smj_matched_pairs"], join["keys"],
            len(left_columns), len(right_columns))
        return 100.0 * (nbytes / bandwidth) / device_s[record.index]

    return ctx.per_query(share)
