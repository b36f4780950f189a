"""Process start to the opening of the measured window: JAX start-up, ring
allocation, programs from the compile cache, election, payload pool and
warm-up."""


def read(run):
    return run.setup_s
