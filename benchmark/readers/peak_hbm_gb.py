"""Peak device memory of the fullest chip after the window
(`memory_stats()["peak_bytes_in_use"]`)."""


def read(ctx):
    seen = [p for p in ctx.peak_bytes if p is not None]
    return max(seen) / 1e9 if seen else None
