"""engine.compiles_in_window: executables built inside the window (XLA
compiles and loads from the persistent cache, seen by a ``jax.monitoring``
listener the harness registers); set-up should leave none."""


def read(run):
    return run.compiles
