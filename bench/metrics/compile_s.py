"""Seconds of XLA compilation during set-up, summed from JAX's
``/jax/core/compile/backend_compile_duration`` events.  Reads of the
persistent cache compile nothing and add nothing."""


def read(run):
    return run.compile_s
