"""Median of the backend's fenced decode-step seconds in the window."""
from bench import readers


def read(run):
    return readers.step_ms(run)
