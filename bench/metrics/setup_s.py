"""Set-up: process start to the first timed step (host clock).  JAX's
start, the weights, compiles or cache reads, the warm call and the first
steps that the reference follows."""


def read(run):
    return run.setup_s
