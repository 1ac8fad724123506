"""The backend's fenced prefill and chunk seconds over the window's wall time."""
from bench import readers


def read(run):
    return readers.prefill_share(run)
