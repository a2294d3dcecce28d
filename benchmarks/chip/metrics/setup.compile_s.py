"""Seconds of set-up JAX spent making device programs: tracing, lowering,
backend compiles and persistent-cache reads (``jax.monitoring``)."""


def read(ctx):
    return ctx["setup_compile_s"]
