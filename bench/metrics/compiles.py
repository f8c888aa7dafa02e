"""Programs built inside the measured window: jit lowerings counted by a
``jax.monitoring`` listener that the harness registers.  Each is a new
executable, compiled or loaded from the persistent cache; set-up warms
every shape, so the count should be 0."""


def read(run):
    return float(run.compiles)
