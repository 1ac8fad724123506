"""Target flops of the window's prefilled and committed tokens over fenced step and prefill seconds at the bf16 peak."""
from bench import readers


def read(run):
    return readers.mfu(run)
