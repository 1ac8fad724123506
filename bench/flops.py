"""Operations and bytes the served work needs: the yardstick for ``mfu``
and for the paged-attention kernel's roofline.  What a token and an
attention call cost is the architecture's (``tokens_flops`` and
``attn_call`` of its module in ``bench/archs/``); the roofline is here.

Counts are of the algorithm, not of the program: a token attends to every
key up to its own position, rejected draft tokens and the draft model are
not counted, and padding the program adds to a bucket is not counted.
"""
from __future__ import annotations

from typing import Dict, Tuple

BF16 = 2


def least_time(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
