"""Process start to the first timed operation: imports, weights and inputs
from the seed, the system's construction and the warm-up of every shape."""


def read(run):
    return run.setup_s
