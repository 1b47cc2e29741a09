"""The least time the chips' HBM could take to read what the plan must read
once (the query class's bytes per row times the rows it scans, spread over
the chips at the published bandwidth), as a share of the time the chip was
busy in that query. Bound: memory bandwidth (these plans do a handful of
integer operations per byte). Nothing to read where no operation ran."""

from benchlib import device


def read(ctx):
    chips = len(ctx.reduction.busy_s)
    busy = [sum(per_chip.values()) / chips
            for per_chip in ctx.reduction.busy_per_query_s]
    if not all(busy):
        return None
    bandwidth = device.peaks(ctx.system.devices[0].device_kind)["hbm_bytes_per_s"]

    def share(record, i):
        cls = ctx.classes[record.name]
        nbytes = cls.BYTES_PER_ROW * ctx.system.data.rows(cls.SCANNED)
        return 100.0 * (nbytes / (chips * bandwidth)) / busy[i]

    return ctx.per_query(share)
