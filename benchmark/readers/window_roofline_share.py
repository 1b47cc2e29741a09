"""The least time the chip's HBM could take over what a window must read and
write once, as a share of the device time of the window's programs
(`window_device_s`). Bound: memory bandwidth (a running sum adds once a
byte-pair; a window computes next to nothing a byte). The bytes are counted
from the rows the windows saw, whatever implements them: `window_bytes`.
Nothing to read where the traced queries' class declares no windows, the
program does not count `window_rows`, or no window program ran on the
device."""

from benchlib import device
from readers import program_device_s

PLANE_BYTES = 8 + 1  # an int64 data plane and its validity plane, a row


def window_bytes(rows, windows, plane_bytes=PLANE_BYTES):
    """Bytes a chain of windows cannot avoid: each reads its key and argument
    planes of every row once and writes its result planes once. ``rows`` is
    what the windows saw in all (a row that passes two windows counts
    twice), so each window's share of them is ``rows / len(windows)``."""
    planes = sum(w["keys"] + w["arguments"] + w["results"] for w in windows)
    return rows / len(windows) * planes * plane_bytes


def read(ctx):
    device_s = program_device_s.seconds_per_query(ctx.trace, ["jit(window_*)"])
    windows = {name: getattr(cls, "WINDOWS", None)
               for name, cls in ctx.classes.items()}
    if not any(device_s) or not all(
            windows[r.name] and "window_rows" in r.counters
            for r in ctx.records):
        return None
    bandwidth = device.peaks(ctx.system.devices[0].device_kind)["hbm_bytes_per_s"]

    def share(record, i):
        nbytes = window_bytes(record.counters["window_rows"],
                              windows[record.name])
        return 100.0 * (nbytes / bandwidth) / device_s[record.index]

    return ctx.per_query(share)
