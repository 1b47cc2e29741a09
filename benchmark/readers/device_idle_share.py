"""Share of the traced window in which no operation ran on the chip: 1 minus
the union of the device-op intervals over the window; the mean over chips."""


def read(ctx):
    return 100.0 * ctx.reduction.idle_share
