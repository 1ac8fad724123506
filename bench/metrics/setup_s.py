"""Process start to the first scheduled arrival: weights, compiles or cache reads, warm-up."""


def read(run):
    return run.setup_s
