"""Device-idle seconds whose innermost host span is the scheduler's own
(``repro/sched.*``), as a share of the traced window."""
from bench import program_spans as PS


def read(run):
    return PS.idle_share(run, PS.SCHED, PS.SCHED)
