"""Seconds from the process's start until the window opens: JAX's start,
data generation, the service, and the warm passes with their compiles or
compile-cache loads (host clock)."""


def read(run):
    return run.setup_s
