"""Process start to the first timed submit: JAX and chip start-up, the
program's data from the seed, the fleet and session, and the warm-up of
every shape the cell's traffic uses (with its compiles, or their loads
from the persistent cache)."""


def read(run):
    return run.setup_s
