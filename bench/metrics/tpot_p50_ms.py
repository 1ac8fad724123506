"""Median over finished window requests of (last token - first token) / (tokens - 1)."""
from bench import readers


def read(run):
    return readers.tpot_p(run, 50)
