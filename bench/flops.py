"""Operations and bytes the served work needs, from a configuration's
shapes: the yardstick for ``mfu`` and for the ragged kernel's roofline.

Counts are of the algorithm, not of the program: a token attends to every
key up to its own position, rejected draft tokens and the draft model are
not counted, and padding the program adds to a bucket is not counted.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Tuple

BF16 = 2


def layer_matmul_flops(m: Dict) -> float:
    """Multiply-add flops of one token through one layer's projections."""
    d, ff = m["d_model"], m["d_ff"]
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return 2.0 * (d * H * hd + 2 * d * KVH * hd + H * hd * d + 3 * d * ff)


def attn_flops(m: Dict, keys: float) -> float:
    """One query of one layer against ``keys`` keys (scores and values)."""
    return 4.0 * m["n_heads"] * m["head_dim"] * keys


def tokens_flops(m: Dict, first_pos: int, n: int, logits: bool) -> float:
    """``n`` consecutive tokens at positions ``first_pos ..``, each
    attending to every key up to itself, through all layers (and the
    output head when ``logits``)."""
    if n <= 0:
        return 0.0
    keys = n * first_pos + n * (n + 1) / 2.0      # sum of (pos + 1)
    per = m["n_layers"] * (n * layer_matmul_flops(m) + attn_flops(m, keys))
    if logits:
        per += n * 2.0 * m["d_model"] * m["vocab_size"]
    return per


def ragged_call(m: Dict, rows: Iterable[Tuple[int, int]],
                block: int) -> Tuple[float, float]:
    """(flops, bytes) of one layer's ragged paged-attention call.

    ``rows`` holds ``(queries, keys)`` per live slot: ``queries`` new
    positions attending to ``keys`` cached positions.  Bytes are the slot's
    live K and V blocks, their position rows, and the queries in and
    outputs out, all at the served bf16 width.
    """
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = nbytes = 0.0
    for q, k in rows:
        blocks = math.ceil(k / block)
        flops += 4.0 * H * hd * q * k
        nbytes += blocks * block * (2 * KVH * hd * BF16 + 4)
        nbytes += 2 * q * H * hd * BF16
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    tc = flops / peaks["bf16_flops_per_s"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
