"""The least time the interchip links could take to move what the mesh
all_to_all sends off a chip in a traced query, as a share of the time a
chip ran collective operations in it. The program's `collective_bytes`
counts every chip's send buffers, n of them, and a chip keeps its own chunk
(one n-th) of its buffer, so the bytes that leave a chip are

    collective_bytes * (n - 1) / n / n

and the share is those bytes over the published interchip bandwidth
(`ici_bytes_per_s` in `benchlib/device.py`) over `collective_s` (the union of
a chip's collective operations, the mean over chips, per query), in %. The
bytes are what the links must carry and the collective's intervals cover
the carrying, so the share cannot pass 100% while the peak is right. Bound:
the interchip bandwidth. Nothing to read on one chip, or where no
collective operation ran (a rehearsal without a chip)."""

from benchlib import device


def read(ctx):
    per_chip = ctx.reduction.collective_s
    n = len(per_chip)
    if n < 2 or not sum(per_chip.values()):
        return None
    seconds = sum(per_chip.values()) / n / len(ctx.records)
    link = device.peaks(ctx.system.devices[0].device_kind)["ici_bytes_per_s"]
    return ctx.per_query(lambda r, i: 100.0 * (
        r.counters["collective_bytes"] * (n - 1) / n / n) / link / seconds)
