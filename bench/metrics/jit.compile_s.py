"""Seconds JAX spent tracing, lowering, compiling or loading programs
from its cache while the window was served (``jax.monitoring``);
0 when warm-up met every shape."""


def read(ctx):
    return float(ctx["compile_s"])
