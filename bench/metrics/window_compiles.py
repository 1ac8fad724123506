"""Backend compiles (persistent-cache reads included) in the program's
compile log inside the traced window; logs the jit that compiled."""
from bench import program_spans as PS


def read(run):
    names = PS.window_compiles(run)
    if names:
        print(f"compiled inside the traced window: {names}", flush=True)
    return None if names is None else len(names)
