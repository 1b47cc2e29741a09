"""Device time of the collective operations in the trace (union of their
intervals on a chip), the mean over chips, per traced query."""


def read(ctx):
    per_chip = ctx.reduction.collective_s
    if len(per_chip) < 2:
        return None
    return sum(per_chip.values()) / len(per_chip) / len(ctx.records)
