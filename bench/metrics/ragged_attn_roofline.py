"""Roofline least time of the ragged paged-attention calls over their device time."""
from bench import readers


def read(run):
    return readers.ragged_attn_roofline(run)
