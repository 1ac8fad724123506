"""Live decode rows per step, weighted by the step's wall time."""
from bench import readers


def read(run):
    return readers.decode_batch_mean(run)
