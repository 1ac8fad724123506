"""Launch planning for the ragged fused paged-attention kernel: host-side
``cu_blocks`` construction, grid-step accounting, and the per-cell
autotuned-config cache.

The ragged kernel (kernels/paged_verify_attn.py) sizes its grid by the
*real* number of allocated blocks — ``sum_b max(live_blocks(b), 1)`` steps
instead of the dense ``B * MAXB`` — and exposes two launch knobs
(``num_buffers`` manual-DMA depth, ``vmem_limit_bytes``).  This module
owns the three host/trace-boundary pieces around it:

* :func:`host_cu_blocks` — build the ``[B + 1]`` cumulative step array
  from the host block tables (the engine's ``PagedKVTables`` accounting
  already lives on host, so this costs no device round-trip; the array
  rides into the registered jits as one tiny int32 operand).
* :func:`grid_steps_ragged` / :func:`grid_steps_dense` /
  :func:`dead_tile_fraction` — the shared step-count arithmetic used by
  the dispatch layer, the microbenchmark's per-cell records, the
  ``--check`` regression gate, and the serving telemetry's grid-occupancy
  gauge.  One definition keeps all four honest with the kernel's actual
  grid (``ragged_plan`` gives every empty slot one dead step so its
  output row still finalizes to zeros).
* :func:`lookup_config` — dispatch-time lookup of the autotuned launch
  config for a ``(batch, T, max_blocks)`` cell.  ``benchmarks/
  kernel_bench.py --autotune`` searches the knob space per cell and
  caches the winners into ``results/BENCH_kernels.json`` under
  ``"autotune"``; the lookup loads that file lazily (once per process),
  falls back to :data:`DEFAULT_CONFIG` when the file or cell is missing,
  and otherwise picks the nearest recorded cell by log-distance — so an
  unmeasured shape inherits the config of its closest measured neighbour.
  A table applies only on the device kind it was timed on (``meta``
  ``device_kind`` against ``jax.devices()[0].device_kind``): winners timed
  in interpret mode on the CPU say nothing about a TPU, so there — and for
  a table that records no device kind — the lookup returns the default.
  The lookup runs at *trace* time (shapes are static), never per step.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import Dict, Optional, Tuple

import jax
import numpy as np

# results/BENCH_kernels.json relative to the repo root (three dirs up)
RESULTS_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    "results", "BENCH_kernels.json")


@dataclasses.dataclass(frozen=True)
class RaggedConfig:
    """Launch knobs for one ragged-kernel call.

    ``num_buffers = 0`` keeps the standard BlockSpec auto-pipeline;
    ``>= 2`` switches to the explicit manual-DMA ring of that depth.
    ``vmem_limit_bytes`` bounds the TPU compiler's VMEM budget for the
    launch (None = compiler default; ignored in interpret mode).
    """
    num_buffers: int = 0
    vmem_limit_bytes: Optional[int] = None

    def to_json(self) -> dict:
        return {"num_buffers": self.num_buffers,
                "vmem_limit_bytes": self.vmem_limit_bytes}

    @classmethod
    def from_json(cls, d: dict) -> "RaggedConfig":
        return cls(num_buffers=int(d.get("num_buffers", 0)),
                   vmem_limit_bytes=(None if d.get("vmem_limit_bytes")
                                     is None
                                     else int(d["vmem_limit_bytes"])))


DEFAULT_CONFIG = RaggedConfig()

# the autotuner's search space: manual-DMA depths (0 = auto pipeline,
# then double/triple/quad buffering) x VMEM budgets (None = default)
SEARCH_NUM_BUFFERS = (0, 2, 3, 4)
SEARCH_VMEM_LIMITS = (None, 32 << 20, 64 << 20)


# ---------------------------------------------------------------------------
# host-side grid arithmetic (np only — callers hold host block tables)


def host_cu_blocks(tables: np.ndarray) -> np.ndarray:
    """Cumulative ragged grid-step counts ``[B + 1]`` from host block
    tables ``[B, MAXB]`` (physical ids, -1 unused): per-slot steps =
    ``max(live, 1)`` — every slot keeps at least one (dead) step so its
    accumulators initialize and its output row finalizes to zeros."""
    live = (tables >= 0).sum(axis=1)
    steps = np.maximum(live, 1)
    return np.concatenate([np.zeros(1, np.int32),
                           np.cumsum(steps).astype(np.int32)])


def grid_steps_ragged(tables: np.ndarray) -> int:
    """Total ragged grid steps for these tables: ``sum max(live, 1)``."""
    return int(host_cu_blocks(tables)[-1])


def grid_steps_dense(tables: np.ndarray) -> int:
    """Total dense grid steps: ``B * MAXB``, raggedness notwithstanding."""
    return int(tables.shape[0] * tables.shape[1])


def dead_tile_fraction(tables: np.ndarray) -> float:
    """Fraction of the dense grid that is dead tiles — the share of grid
    steps the ragged kernel simply does not launch."""
    dense = grid_steps_dense(tables)
    return 1.0 - grid_steps_ragged(tables) / float(dense) if dense else 0.0


# ---------------------------------------------------------------------------
# per-cell autotuned-config cache


def cell_key(batch: int, t: int, max_blocks: int) -> str:
    """The JSON key for one autotune cell: q batch x q length (s+1 for
    verify, chunk width for prefix extension) x table width."""
    return f"B{int(batch)}_T{int(t)}_MAXB{int(max_blocks)}"


_cache: Optional[Dict[str, RaggedConfig]] = None
_cache_path: Optional[str] = None


def clear_config_cache() -> None:
    """Drop the lazily-loaded autotune table (tests; after re-tuning)."""
    global _cache, _cache_path
    _cache = None
    _cache_path = None


def _load(path: str) -> Dict[str, RaggedConfig]:
    """The autotune table at ``path`` if it was timed on this process's
    device kind, else empty."""
    global _cache, _cache_path
    if _cache is not None and _cache_path == path:
        return _cache
    table: Dict[str, RaggedConfig] = {}
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        kind = (data.get("meta") or {}).get("device_kind")
        if kind == jax.devices()[0].device_kind:
            for key, rec in (data.get("autotune") or {}).items():
                table[key] = RaggedConfig.from_json(rec.get("config", rec))
    except (OSError, ValueError):
        table = {}
    _cache, _cache_path = table, path
    return table


def _parse_key(key: str) -> Optional[Tuple[int, int, int]]:
    try:
        b, t, m = key.split("_")
        return int(b[1:]), int(t[1:]), int(m[4:])
    except (ValueError, IndexError):
        return None


def lookup_config(batch: int, t: int, max_blocks: int,
                  path: Optional[str] = None) -> RaggedConfig:
    """The autotuned launch config for a ``(batch, T, max_blocks)`` cell.

    Exact cell if measured; else the nearest measured cell by summed
    log2-distance over the three dims (shapes scale geometrically, so log
    distance matches how configs generalize); else the safe default — also
    whenever the table was timed on another device kind.
    """
    table = _load(path or RESULTS_PATH)
    if not table:
        return DEFAULT_CONFIG
    key = cell_key(batch, t, max_blocks)
    if key in table:
        return table[key]
    want = (batch, t, max_blocks)

    def dist(key: str) -> float:
        dims = _parse_key(key)
        if dims is None:
            return math.inf
        return sum(abs(math.log2(max(a, 1)) - math.log2(max(b, 1)))
                   for a, b in zip(want, dims))

    best = min(table, key=dist)
    return table[best] if math.isfinite(dist(best)) else DEFAULT_CONFIG
