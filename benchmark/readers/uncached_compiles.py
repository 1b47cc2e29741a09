"""Executables XLA compiled in set-up although the persistent cache was warm:
those under the program's 0.5 s floor for writing an entry. Nothing to read
on a run that began with an empty cache, where everything compiles."""


def read(ctx):
    return ctx.setup_compiles if ctx.cache_was_warm else None
