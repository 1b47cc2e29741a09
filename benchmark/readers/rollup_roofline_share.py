"""The least time the chip's HBM could take over what a rollup must read and
write once, as a share of the device time of the programs Expand and the
aggregation over it launch (`rollup_device_s`). Bound: memory bandwidth (an
Expand copies and nulls; the aggregation over it orders and adds; neither
computes much a byte). The bytes are counted from the rows Expand put out,
whatever implements it: `rollup_bytes`. Nothing to read where the traced
queries' class declares no rollup, the program does not count `rollup_rows`,
or none of those programs ran on the device."""

import json
import os

from benchlib import device
from readers import program_device_s

CODE_BYTES = 4 + 1     # an int32 code plane and its validity plane, a row
MEASURE_BYTES = 8 + 1  # an int64 plane and its validity plane, a row


def rollup_bytes(rollup_rows, rollup, code_bytes=CODE_BYTES,
                 measure_bytes=MEASURE_BYTES):
    """Bytes a rollup cannot avoid. ``rollup_rows`` is what Expand put out:
    ``sets`` rows a joined row. Each joined row's ``keys`` code planes and
    its ``measures`` are read once; every row put out is written once: its
    ``keys`` coded keys, the grouping id (an int64 plane) and its
    measures."""
    rows_in = rollup_rows / rollup["sets"]
    read = rows_in * (rollup["keys"] * code_bytes
                      + rollup["measures"] * measure_bytes)
    written = rollup_rows * (rollup["keys"] * code_bytes
                             + (1 + rollup["measures"]) * measure_bytes)
    return read + written


def programs():
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "rollup_device_s.json")) as f:
        return json.load(f)["params"]["programs"]


def read(ctx):
    device_s = program_device_s.seconds_per_query(ctx.trace, programs())
    rollups = {name: getattr(cls, "ROLLUP", None)
               for name, cls in ctx.classes.items()}
    if not any(device_s) or not all(
            rollups[r.name] and "rollup_rows" in r.counters
            for r in ctx.records):
        return None
    bandwidth = device.peaks(ctx.system.devices[0].device_kind)["hbm_bytes_per_s"]

    def share(record, i):
        nbytes = rollup_bytes(record.counters["rollup_rows"],
                              rollups[record.name])
        return 100.0 * (nbytes / bandwidth) / device_s[record.index]

    return ctx.per_query(share)
