"""Paged-attention kernel microbenchmark: materialized gather vs the fused
block-table-streaming Pallas kernel vs the RAGGED real-length-grid kernel,
across a (batch, s, blocks) grid.

Measures, per shape:

* ``fused_us``          — the dense fused kernel (``B * MAXB`` grid;
                          kernels/paged_verify_attn.py); native on TPU,
                          interpret mode elsewhere
* ``ragged_us``         — the ragged kernel: grid sized by the REAL
                          allocated blocks via the scalar-prefetched
                          ``cu_blocks`` plan (kernels/tuning.py)
* ``grid_steps_dense`` / ``grid_steps_ragged`` / ``dead_tile_fraction``
                        — the launch-grid accounting for the case's block
                          tables: how many tiles the dense grid wastes on
                          ``-1`` entries and how many the ragged grid
                          actually launches
* ``gather_pallas_us``  — gather the logical view, then the shared Pallas
                          verify kernel at the *matched* tile size
                          (``block_k = block_size``) — the apples-to-apples
                          "same tiles, plus the copy" baseline
* ``gather_ref_us``     — gather + the pure-XLA reference attention (the
                          CPU serving path)
* ``gather_view_bytes`` — the transient ``[B, MAXB*bs, KVH, hd]`` k+v copy
                          the gather path materializes per call (and per
                          layer, per step, on the serving path) — the
                          fused path's figure is 0 by construction
* ``*_temp_bytes``      — XLA's compiled temp-allocation sizes where the
                          backend reports them
* ``fused/gather_materializes`` — jaxpr inspection: does any op output a
                          ``MAXB*bs``-row logical view?  Must be False for
                          the fused path (the kernel's whole point) and
                          True for the gather path (keeps the check
                          honest).

``--autotune`` searches the ragged kernel's launch knobs (``num_buffers``
manual-DMA depth x ``vmem_limit_bytes``) per grid cell and caches the
winners under ``"autotune"`` in results/BENCH_kernels.json — the serving
dispatch (kernels/tuning.py ``lookup_config``) reads exactly that section,
so re-tuning here retunes serving.  ``--profile-dma`` additionally times
the manual-DMA path's ``profile='dma'`` / ``profile='compute'`` variants
(each skips the other half of the loop body), splitting tile-stream time
from flash-tile compute time per cell.

``--check`` is the CI smoke mode: on the reference shape it exits nonzero
if the fused path materializes a gathered view, if the gather path
mysteriously stops materializing one (the check would be vacuous), if the
fused kernel is slower than gather+verify at matched tiles, or if the
ragged grid regresses — its step count must stay strictly below the dense
``B * MAXB`` count on the (deterministically ragged) reference shape AND
match the block tables' ``sum(max(live, 1))`` exactly, so the real-length
grid failing back to dense launches fails loudly.  Off-TPU both paths
execute in interpret mode, which prices grid steps rather than HBM, so the
matched-tile and grid-step comparisons are the meaningful ones there; on
TPU the same code compares the native kernels.  Results land in
results/BENCH_kernels.json.

  PYTHONPATH=src python benchmarks/kernel_bench.py \
      [--quick] [--check] [--autotune] [--profile-dma]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:           # `python benchmarks/kernel_bench.py`
    sys.path.insert(0, _ROOT)       # puts benchmarks/ first, not the root

from repro.kernels.paged import gather_verify_attn, paged_verify_attn
from repro.kernels.tuning import (RaggedConfig, SEARCH_NUM_BUFFERS,
                                  SEARCH_VMEM_LIMITS, cell_key,
                                  clear_config_cache, dead_tile_fraction,
                                  grid_steps_dense, grid_steps_ragged,
                                  host_cu_blocks)
from tools.graphlint.passes.materialize import find_gathered_views

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")
OUT_PATH = os.path.join(RESULTS, "BENCH_kernels.json")

# model-ish head geometry (bench-scale): 4 q heads over 2 kv heads, hd=64
H, KVH, HD = 4, 2, 64
BLOCK_SIZE = 16
CHECK_SHAPE = (4, 3, 8)                  # (batch, s, max_blocks) for --check


def build_case(B: int, s: int, MAXB: int, bs: int = BLOCK_SIZE,
               seed: int = 0):
    """A ragged paged pool + verify-step inputs for one grid point.  The
    pool holds one layer, ``[1, NB, bs, KVH, HD]``, read at layer 0."""
    rng = np.random.default_rng(seed)
    T = s + 1
    NB = B * MAXB + 4                    # slack blocks (unowned => garbage)
    k = jnp.asarray(rng.normal(size=(1, NB, bs, KVH, HD)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, NB, bs, KVH, HD)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, T, H, HD)), jnp.float32)
    bt = np.full((B, MAXB), -1, np.int32)
    pos = np.full((NB, bs), -1, np.int32)
    order = rng.permutation(NB)
    nxt = 0
    lens = rng.integers(max(1, (MAXB - 2) * bs), MAXB * bs - T, size=B)
    for b, L in enumerate(lens):
        for j in range(-(-int(L) // bs)):
            pb = int(order[nxt]); nxt += 1
            bt[b, j] = pb
            rows = np.arange(bs) + j * bs
            write = rows < L
            pos[pb, write[: bs].nonzero()[0]] = rows[write]
    qp = jnp.asarray(np.stack([np.arange(T, dtype=np.int32) + int(L) - 1
                               for L in lens]))
    return q, k, v, qp, jnp.asarray(pos), jnp.asarray(bt)


def best_us(fn, args, repeats: int = 7, inner: int = 10) -> float:
    """Best-of-N timing: the min over repeats is the standard noise-robust
    microbenchmark estimator (scheduler contention only ever adds time)."""
    fn(*args).block_until_ready()        # warmup/compile
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = fn(*args)
        out.block_until_ready()
        ts.append((time.perf_counter() - t0) / inner)
    return float(np.min(ts) * 1e6)


def materializes_view(fn, args, B: int, MAXB: int, bs: int) -> bool:
    """True iff the traced computation builds a [.., MAXB*bs, ..] logical
    view (the gathered copy the fused kernel exists to eliminate).

    Detection lives in tools/graphlint (the engine-level
    no-materialization pass uses the same ``find_gathered_views`` over
    every registered step/chunk jit); here the bare kernel call is the
    whole trace, so no trailing-dims narrowing is needed."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return bool(find_gathered_views(jaxpr.jaxpr, MAXB * bs))


def temp_bytes(fn, args) -> Optional[int]:
    try:
        ma = jax.jit(fn).lower(*args).compile().memory_analysis()
        return int(ma.temp_size_in_bytes)
    except Exception:
        return None


def _profiled_ragged(q, k, v, qp, pos, bt, layer, cu, *, config, profile):
    from repro.kernels.paged_verify_attn import ragged_paged_verify_attn_pallas
    return ragged_paged_verify_attn_pallas(
        q, k, v, qp, pos, bt, layer, cu,
        num_buffers=config.num_buffers,
        vmem_limit_bytes=config.vmem_limit_bytes,
        profile=profile, interpret=jax.default_backend() != "tpu")


def _ragged_fn(config: RaggedConfig, profile: Optional[str] = None):
    """A jitted ragged-kernel closure with the launch knobs pinned (the
    explicit ``config`` bypasses the autotune-cache lookup, so the bench
    measures exactly the knobs it thinks it measures)."""
    if profile is None:
        return jax.jit(lambda *a: paged_verify_attn(
            *a[:7], use_pallas=True, cu_blocks=a[7], config=config))
    return jax.jit(lambda *a: _profiled_ragged(*a, config=config,
                                               profile=profile))


def bench_case(B: int, s: int, MAXB: int, bs: int = BLOCK_SIZE,
               config: Optional[RaggedConfig] = None,
               profile_dma: bool = False) -> Dict:
    q, k, v, qp, pos, bt = build_case(B, s, MAXB, bs)
    fused = jax.jit(lambda *a: paged_verify_attn(*a, use_pallas=True))
    gpal = jax.jit(lambda *a: gather_verify_attn(*a, use_pallas=True,
                                                 block_k=bs))
    gref = jax.jit(lambda *a: gather_verify_attn(*a, use_pallas=False))
    args = (q, k, v, qp, pos, bt, 0)
    tables = np.asarray(bt)
    cu = jnp.asarray(host_cu_blocks(tables))
    config = config or RaggedConfig()
    ragged = _ragged_fn(config)
    rargs = args + (cu,)

    # parity first: a microbenchmark of a wrong kernel is worse than none
    ref_out = np.asarray(gref(*args))
    np.testing.assert_allclose(np.asarray(fused(*args)), ref_out,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(ragged(*rargs)), ref_out,
                               rtol=2e-4, atol=2e-4)

    itemsize = np.dtype(np.float32).itemsize
    view_bytes = 2 * B * MAXB * bs * KVH * HD * itemsize   # k + v copies
    rec = {
        "batch": B, "s": s, "max_blocks": MAXB, "block_size": bs,
        "kv_heads": KVH, "q_heads": H, "head_dim": HD,
        "fused_us": best_us(fused, args),
        "ragged_us": best_us(ragged, rargs),
        "ragged_config": config.to_json(),
        "grid_steps_dense": grid_steps_dense(tables),
        "grid_steps_ragged": grid_steps_ragged(tables),
        "dead_tile_fraction": dead_tile_fraction(tables),
        "gather_pallas_us": best_us(gpal, args),
        "gather_ref_us": best_us(gref, args),
        "gather_view_bytes": view_bytes,
        "fused_view_bytes": 0,
        "fused_temp_bytes": temp_bytes(
            lambda *a: paged_verify_attn(*a, use_pallas=True), args),
        "gather_ref_temp_bytes": temp_bytes(
            lambda *a: gather_verify_attn(*a, use_pallas=False), args),
        "fused_materializes": materializes_view(
            lambda *a: paged_verify_attn(*a, use_pallas=True),
            args, B, MAXB, bs),
        "gather_materializes": materializes_view(
            lambda *a: gather_verify_attn(*a, use_pallas=False),
            args, B, MAXB, bs),
    }
    rec["fused_vs_gather_pallas"] = (
        rec["gather_pallas_us"] / max(rec["fused_us"], 1e-9))
    rec["ragged_vs_fused"] = rec["fused_us"] / max(rec["ragged_us"], 1e-9)
    if profile_dma:
        # DMA-vs-compute split: each profile variant skips the OTHER half
        # of the manual-DMA loop body, so the pair brackets where the
        # per-tile time goes.  Needs the manual-DMA path (depth >= 2).
        pcfg = (config if config.num_buffers >= 2
                else RaggedConfig(num_buffers=2,
                                  vmem_limit_bytes=config.vmem_limit_bytes))
        rec["profile_config"] = pcfg.to_json()
        rec["ragged_dma_us"] = best_us(
            _ragged_fn(pcfg, profile="dma"), rargs, repeats=3, inner=3)
        rec["ragged_compute_us"] = best_us(
            _ragged_fn(pcfg, profile="compute"), rargs, repeats=3, inner=3)
    return rec


def autotune_case(B: int, s: int, MAXB: int, bs: int = BLOCK_SIZE) -> Dict:
    """Search the ragged launch knobs for one ``(B, T, MAXB)`` cell; the
    winner is what ``lookup_config`` hands the serving dispatch."""
    q, k, v, qp, pos, bt = build_case(B, s, MAXB, bs)
    cu = jnp.asarray(host_cu_blocks(np.asarray(bt)))
    rargs = (q, k, v, qp, pos, bt, 0, cu)
    vmem_limits = (SEARCH_VMEM_LIMITS if jax.default_backend() == "tpu"
                   else (None,))   # interpret mode ignores the VMEM budget
    trials = []
    for nbuf in SEARCH_NUM_BUFFERS:
        for vmem in vmem_limits:
            cfg = RaggedConfig(num_buffers=nbuf, vmem_limit_bytes=vmem)
            us = best_us(_ragged_fn(cfg), rargs, repeats=3, inner=3)
            trials.append((us, cfg))
    best = min(trials, key=lambda t: t[0])
    return {
        "config": best[1].to_json(),
        "us": best[0],
        "searched": len(trials),
        "trials": [{"config": c.to_json(), "us": u} for u, c in trials],
    }


def run(quick: bool = False, check: bool = False, autotune: bool = False,
        profile_dma: bool = False) -> Dict:
    on_tpu = jax.default_backend() == "tpu"
    if check or quick:
        grid: List[Tuple[int, int, int]] = [CHECK_SHAPE]
        if quick and not check:
            grid += [(1, 1, 4)]
    else:
        grid = [(B, s, MAXB)
                for B in (1, 4, 8)
                for s in (1, 3)
                for MAXB in (4, 8, 16)]

    # autotune first, bench each cell at its tuned knobs (what serving runs)
    tuned: Dict[str, Dict] = {}
    if autotune:
        for (B, s, MAXB) in grid:
            tuned[cell_key(B, s + 1, MAXB)] = autotune_case(B, s, MAXB)
    records = []
    for (B, s, MAXB) in grid:
        rec_cfg = tuned.get(cell_key(B, s + 1, MAXB))
        cfg = (RaggedConfig.from_json(rec_cfg["config"])
               if rec_cfg is not None else None)
        records.append(bench_case(B, s, MAXB, config=cfg,
                                  profile_dma=profile_dma))

    payload = {
        "meta": {
            "backend": jax.default_backend(),
            # kernels/tuning.py applies the autotune table only on this kind
            "device_kind": jax.devices()[0].device_kind,
            "execution": "native" if on_tpu else "interpret",
            "note": ("off-TPU the Pallas kernels run in interpret mode, "
                     "which prices grid steps rather than HBM traffic; "
                     "gather_pallas_us uses the matched tile size "
                     "block_k=block_size so fused-vs-gather compares the "
                     "same tiles with and without the materialized copy; "
                     "ragged_us runs the real-length-grid kernel at the "
                     "autotuned (or default) launch knobs"),
            "block_size": BLOCK_SIZE,
            "check_shape": list(CHECK_SHAPE),
        },
        "grid": records,
    }
    # the autotune section IS the serving dispatch table
    # (kernels/tuning.py lookup_config) — keep the existing one when this
    # invocation did not re-tune, so a smoke run can't drop tuned configs
    if tuned:
        payload["autotune"] = tuned
    elif os.path.exists(OUT_PATH):
        try:
            with open(OUT_PATH, encoding="utf-8") as f:
                prev = json.load(f).get("autotune")
            if prev:
                payload["autotune"] = prev
        except (OSError, ValueError):
            pass

    problems = []
    ref = next(r for r in records
               if (r["batch"], r["s"], r["max_blocks"]) == CHECK_SHAPE)
    if ref["fused_materializes"]:
        problems.append("fused path materializes a gathered KV view")
    if not ref["gather_materializes"]:
        problems.append("gather path no longer materializes a view — the "
                        "no-materialization check is vacuous")
    # native TPU timings are stable: 10% headroom over best-of-N.  Interpret
    # mode prices Python grid steps, not HBM, and is contention-sensitive,
    # so off-TPU the gate only trips at the >=2x an actual regression (the
    # fused path re-growing a gather, tiling collapse) actually produces —
    # the materialization checks above stay hard either way
    factor = 1.10 if on_tpu else 2.0
    if ref["fused_us"] > factor * ref["gather_pallas_us"]:
        problems.append(
            f"fused kernel slower than gather+verify on the reference "
            f"shape: {ref['fused_us']:.0f}us vs "
            f"{ref['gather_pallas_us']:.0f}us")
    # ragged-grid gates: the reference shape is deterministically ragged
    # (seeded lens), so the real-length grid must launch strictly fewer
    # steps than the dense B*MAXB grid — and exactly the tables'
    # sum(max(live, 1)), else the cu_blocks plan drifted from the kernel
    if ref["grid_steps_ragged"] >= ref["grid_steps_dense"]:
        problems.append(
            f"ragged grid launches {ref['grid_steps_ragged']} steps, not "
            f"below the dense {ref['grid_steps_dense']} — the real-length "
            f"grid regressed to dense launches")
    _, _, _, _, _, chk_bt = build_case(*CHECK_SHAPE)
    expect = int(np.maximum((np.asarray(chk_bt) >= 0).sum(axis=1), 1).sum())
    if ref["grid_steps_ragged"] != expect:
        problems.append(
            f"ragged grid-step count {ref['grid_steps_ragged']} does not "
            f"match the block tables' live count {expect} — the cu_blocks "
            f"plan drifted from the tables")
    if ref["ragged_us"] > factor * ref["fused_us"]:
        problems.append(
            f"ragged kernel slower than the dense fused kernel on the "
            f"reference shape: {ref['ragged_us']:.0f}us vs "
            f"{ref['fused_us']:.0f}us — fewer grid steps should never "
            f"cost more")
    payload["check"] = {"ok": not problems, "problems": problems}

    # --check / --quick are smoke gates, not the artifact: never clobber an
    # existing full-grid BENCH_kernels.json with their 1-2 point grids
    os.makedirs(RESULTS, exist_ok=True)
    if not (check or quick) or not os.path.exists(OUT_PATH):
        with open(OUT_PATH, "w") as f:
            json.dump(payload, f, indent=1, default=float)
        print(f"wrote {os.path.relpath(OUT_PATH)} "
              f"({len(records)} grid points, backend={jax.default_backend()})")
    elif tuned:
        # smoke grid + --autotune: merge the newly tuned cells into the
        # existing full-grid artifact instead of clobbering it
        with open(OUT_PATH, encoding="utf-8") as f:
            existing = json.load(f)
        existing.setdefault("autotune", {}).update(tuned)
        with open(OUT_PATH, "w") as f:
            json.dump(existing, f, indent=1, default=float)
        print(f"merged {len(tuned)} autotuned cell(s) into "
              f"{os.path.relpath(OUT_PATH)} (smoke mode)")
    else:
        print(f"kept existing {os.path.relpath(OUT_PATH)} "
              f"(smoke mode, {len(records)} grid points measured)")
    for r in records:
        extra = ""
        if "ragged_dma_us" in r:
            extra = (f"  dma {r['ragged_dma_us']:.0f}us / compute "
                     f"{r['ragged_compute_us']:.0f}us")
        print(f"  B={r['batch']} s={r['s']} blocks={r['max_blocks']}: "
              f"fused {r['fused_us']:.0f}us  ragged {r['ragged_us']:.0f}us "
              f"(grid {r['grid_steps_ragged']}/{r['grid_steps_dense']}, "
              f"dead {r['dead_tile_fraction']:.2f})  gather+pallas "
              f"{r['gather_pallas_us']:.0f}us  gather-ref "
              f"{r['gather_ref_us']:.0f}us  view {r['gather_view_bytes']}B"
              + extra)
    if tuned:
        # new configs are live for the NEXT lookup in this process too
        clear_config_cache()
        for key, rec_cfg in sorted(tuned.items()):
            print(f"  autotune {key}: {rec_cfg['config']} "
                  f"({rec_cfg['us']:.0f}us over {rec_cfg['searched']} "
                  f"trials)")
    if problems:
        for p in problems:
            print(f"CHECK FAILED: {p}")
    return payload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="reference shape + one small point only")
    ap.add_argument("--check", action="store_true",
                    help="smoke mode: reference shape only; exit nonzero "
                         "if the fused path regresses (slower than gather, "
                         "materializes the view, or the ragged grid stops "
                         "tracking real block counts)")
    ap.add_argument("--autotune", action="store_true",
                    help="search the ragged kernel's launch knobs per grid "
                         "cell and cache the winners into "
                         "results/BENCH_kernels.json (the serving dispatch "
                         "table)")
    ap.add_argument("--profile-dma", action="store_true",
                    help="also time the manual-DMA path's profile='dma' / "
                         "'compute' variants (DMA-vs-compute split)")
    args = ap.parse_args(argv)
    payload = run(quick=args.quick, check=args.check,
                  autotune=args.autotune, profile_dma=args.profile_dma)
    if args.check and not payload["check"]["ok"]:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
