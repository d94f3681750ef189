"""Seconds of backend compilation during set-up, from JAX's
``/jax/core/compile/backend_compile_duration`` events (programs read
back from the persistent cache take none)."""


def read(run):
    return run.setup["compile_s"]
