"""Device-idle seconds whose innermost host span is the engine step call's
own (``repro/step.*`` or its dispatch ``repro/step[...]``), as a share of
the traced window.  Also logs how the window's idle time splits by span."""
from bench import program_spans as PS


def read(run):
    line = PS.describe(run)
    if line is not None:
        print(line, flush=True)
    return PS.idle_share(run, PS.STEP, PS.STEP_HOST)
