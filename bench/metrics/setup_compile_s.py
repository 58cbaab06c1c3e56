"""Seconds of JAX tracing, lowering and backend compilation during set-up
(jax.monitoring durations)."""


def read(ctx):
    return ctx["setup_compile"]["seconds"]
