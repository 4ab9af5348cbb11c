"""Executables produced inside the window, compiled or loaded from the
persistent cache (``jax.monitoring`` backend-compile events counted by
the harness).  Set-up warms every shape the traffic uses, so this is 0
unless the window runs a shape it did not warm.  Layer: executors."""


def read(run):
    return run.compiles
