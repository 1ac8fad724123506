"""Median over window requests of (wall time of the step that served the first token - scheduled arrival)."""
from bench import readers


def read(run):
    return readers.ttft_p(run, 50)
