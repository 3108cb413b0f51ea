"""Seconds JAX spent tracing, lowering and compiling during set-up (or
loading compiled programs from the persistent cache), from its
``jax.monitoring`` duration events.  Layer: entry (set-up); moves ``setup_s``."""


def read(ctx):
    return ctx.compile_s
