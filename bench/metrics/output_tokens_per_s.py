"""Tokens served by steps returning inside the window, per window second."""
from bench import readers


def read(run):
    return readers.output_tokens_per_s(run)
