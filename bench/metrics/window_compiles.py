"""jit cache: XLA compilations, or loads from the persistent cache, inside
the measured window (a ``jax.monitoring`` listener on the backend-compile
event).  Set-up warms every program, so this should read 0."""


def read(run):
    return run["compiles"]
