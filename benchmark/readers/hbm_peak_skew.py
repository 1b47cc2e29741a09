"""The fullest chip's memory peak over the mean of the chips' peaks."""


def read(ctx):
    seen = [p for p in ctx.peak_bytes if p is not None]
    if len(seen) < 2 or not sum(seen):
        return None
    return max(seen) / (sum(seen) / len(seen))
