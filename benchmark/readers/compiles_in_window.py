"""Executables JAX asked its backend for after warm-up (`jax.monitoring`
compile events inside the window). Expected 0."""


def read(ctx):
    return len(ctx.window_compiles)
