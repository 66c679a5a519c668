"""Executables built inside the window (``jax.monitoring`` backend-compile
events, a compile-cache load included); 0 when set-up warmed every shape."""


def read(run):
    return run.compiles
