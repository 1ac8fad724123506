"""Committed tokens per live row per step over the window's steps."""
from bench import readers


def read(run):
    return readers.commit_per_row(run)
