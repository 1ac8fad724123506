"""Block-table attention for the paged KV pool: fused streaming kernel on
TPU, materialized gather as the reference / CPU fallback.

The paged pool stores KV rows in fixed-size blocks shared by every slot:

    k_pool / v_pool : [n_layers, num_blocks, block_size, KVH, hd]
    pos             : [num_blocks, block_size]   absolute position, -1 unwritten
    block_tables    : [B, max_blocks]            physical block ids, -1 unused

Every entry point takes the whole pool and a ``layer`` index: the model's
layer scan carries the pool and writes it in place, so no per-layer slice
of it ever exists, and the kernels read ``(layer, block)`` tiles directly.
A caller holding one layer's ``[NB, ...]`` pool passes ``k[None]`` and
layer 0.

Two execution paths with identical masking semantics:

* **fused** (:mod:`repro.kernels.paged_verify_attn`, TPU native or
  ``interpret=True``): the Pallas kernel's BlockSpec index maps read
  k/v/pos tiles straight from the pool through the scalar-prefetched block
  table — no ``[B, MAXB*bs, ...]`` logical view ever exists, the pool's
  HBM rows move exactly once per step, and the transient footprint no
  longer grows with batch size.  ``-1`` table entries skip their tile in
  the kernel (``@pl.when``), which is numerically the same as gathering a
  key-position of ``-1``.
* **gather** (:func:`gather_verify_attn`, the ``use_pallas=False``
  reference and non-TPU fallback): rebuild each slot's logical contiguous
  view with one XLA gather, then run the shared
  :func:`~repro.kernels.ops.spec_verify_attn` wrapper over the copy.
  Ownership is by construction (a slot only gathers its own blocks), and
  rows behind a ``-1`` table entry surface with key position ``-1``, which
  the shared position mask treats as unattendable.

Either way the *persistent* footprint win of paging stands: the pool holds
``num_blocks * block_size`` KV rows total instead of ``capacity *
cache_len`` worst-case rows, so short requests stop paying for the longest
one (BASS-style ragged per-request KV, PAPERS.md).  The fused path
additionally removes the gather's transient double-buffering of the hot
verify step — the largest single-lever perf win on the serving path.

int8 pools (kv_quant) pass per-(row, kv-head) ``k_scale``/``v_scale``
``[nL, NB, bs, KVH]``; both paths dequantize with them (the fused kernel in
VMEM after a 1 B/elem stream, the gather path before the shared wrapper).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.ops import kernel_mode, spec_verify_attn
from repro.kernels.paged_verify_attn import (paged_verify_attn_pallas,
                                             ragged_paged_verify_attn_pallas)
from repro.kernels.tuning import RaggedConfig, lookup_config


def gather_kv_blocks(k: jax.Array, v: jax.Array, block_tables: jax.Array,
                     layer: int | jax.Array,
                     ) -> Tuple[jax.Array, jax.Array]:
    """Gather per-slot logical KV views of one layer from the shared block
    pool.

    k/v: [nL, NB, bs, KVH, hd]; block_tables: [B, MAXB] (-1 = unallocated);
    ``layer``: int or int32 scalar.  One gather of ``(layer, block)`` pairs,
    with no slice of the layer first.  Returns (k_slot, v_slot) of shape
    [B, MAXB * bs, KVH, hd].  Rows behind -1 table entries contain arbitrary
    pool data — callers must mask them via :func:`gather_key_positions`
    (which reports their position as -1).

    Fast path: with a one-block-per-slot table (MAXB == 1 — short-prompt
    traces sized to a single block) the gather+reshape collapses to a
    direct row index, keeping this reference path honest in the
    microbenchmark's smallest shapes.
    """
    B, MAXB = block_tables.shape
    bs = k.shape[2]
    safe = jnp.where(block_tables < 0, 0, block_tables)
    if MAXB == 1:
        return k[layer, safe[:, 0]], v[layer, safe[:, 0]]
    kg = k[layer, safe].reshape(B, MAXB * bs, *k.shape[3:])
    vg = v[layer, safe].reshape(B, MAXB * bs, *v.shape[3:])
    return kg, vg


def gather_key_positions(pos: jax.Array, block_tables: jax.Array) -> jax.Array:
    """Per-slot logical key positions [B, MAXB * bs]; -1 where the table has
    no block (or the pool row is unwritten), i.e. never attendable."""
    B, MAXB = block_tables.shape
    bs = pos.shape[1]
    safe = jnp.where(block_tables < 0, 0, block_tables)
    if MAXB == 1:
        return jnp.where((block_tables < 0), -1, pos[safe[:, 0]])
    kp = jnp.where((block_tables < 0)[:, :, None], -1, pos[safe])
    return kp.reshape(B, MAXB * bs)


def gather_scales(scale: jax.Array, block_tables: jax.Array,
                  layer: int | jax.Array) -> jax.Array:
    """Gather one layer's int8 dequant scales [nL, NB, bs, KVH] -> per-slot
    [B, MAXB*bs, KVH], as ``(layer, block)`` pairs like
    :func:`gather_kv_blocks`.

    Rows behind -1 table entries carry arbitrary pool scales; they are
    harmless because their key positions gather as -1 (never attendable).
    """
    B, MAXB = block_tables.shape
    bs = scale.shape[2]
    safe = jnp.where(block_tables < 0, 0, block_tables)
    if MAXB == 1:
        return scale[layer, safe[:, 0]]
    return scale[layer, safe].reshape(B, MAXB * bs, scale.shape[3])


def gather_verify_attn(q: jax.Array, k: jax.Array, v: jax.Array,
                       q_pos: jax.Array, pos: jax.Array,
                       block_tables: jax.Array,
                       layer: int | jax.Array,
                       window: Optional[int] = None, prefix_len: int = 0,
                       scale: Optional[float] = None,
                       k_scale: Optional[jax.Array] = None,
                       v_scale: Optional[jax.Array] = None,
                       use_pallas: Optional[bool] = None,
                       block_k: int = 512) -> jax.Array:
    """Gather + the shared verify kernel: the paged reference path.

    Materializes each slot's [MAXB * bs] logical view, then runs
    :func:`~repro.kernels.ops.spec_verify_attn` over the copy — identical
    masking semantics to the contiguous ring at logical length MAXB * bs.
    ``use_pallas`` is forwarded to the shared wrapper (the microbenchmark
    times gather+Pallas-verify against the fused kernel with it).
    """
    kg, vg = gather_kv_blocks(k, v, block_tables, layer)
    kpos = gather_key_positions(pos, block_tables)
    ks = vs = None
    if k_scale is not None:
        ks = gather_scales(k_scale, block_tables, layer)
        vs = gather_scales(v_scale, block_tables, layer)
    return spec_verify_attn(q, kg, vg, q_pos, kpos, window=window,
                            prefix_len=prefix_len, scale=scale,
                            k_scale=ks, v_scale=vs, use_pallas=use_pallas,
                            block_k=block_k)


def paged_verify_attn(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_pos: jax.Array, pos: jax.Array,
                      block_tables: jax.Array,
                      layer: int | jax.Array,
                      window: Optional[int] = None, prefix_len: int = 0,
                      scale: Optional[float] = None,
                      k_scale: Optional[jax.Array] = None,
                      v_scale: Optional[jax.Array] = None,
                      use_pallas: Optional[bool] = None,
                      cu_blocks: Optional[jax.Array] = None,
                      config: Optional[RaggedConfig] = None) -> jax.Array:
    """Verify-step attention against the paged pool.

    q: [B, T, H, hd]; k/v: [nL, NB, bs, KVH, hd], the whole pool;
    q_pos: [B, T]; pos: [NB, bs]; block_tables: [B, MAXB]; ``layer``: the
    layer attended (int or int32 scalar — inside the model's layer scan,
    the scan index).  Optional k_scale/v_scale [nL, NB, bs, KVH] for int8
    pools.  Returns [B, T, H, hd].

    Dispatch (:func:`~repro.kernels.ops.kernel_mode` policy): a fused
    streaming kernel natively on TPU (or interpreted when forced with
    ``use_pallas=True`` off-TPU — tests and the microbenchmark), the
    gather path otherwise.  ``use_pallas`` here selects *which paged path*
    runs; the gather path's inner verify kernel keeps its own auto policy
    (Pallas on TPU, reference on CPU), so forcing the gather — e.g. the
    sharded-pool pin — never silently downgrades a TPU run to the pure-jnp
    attention.  Both paths are numerically parity-checked in
    tests/test_paged_fused_kernel.py.

    ``cu_blocks [B + 1]`` (host-computed cumulative grid-step counts,
    ``kernels/tuning.py host_cu_blocks``) upgrades the fused path to the
    **ragged** kernel: grid steps = sum of live blocks instead of
    ``B * MAXB``, launch knobs resolved per ``(B, T, MAXB)`` cell from the
    autotune cache (``config`` overrides the lookup — tests and the
    benchmark pin exact knobs with it).  Without ``cu_blocks`` the dense
    fused kernel runs; the gather reference ignores both (its semantics
    are already length-exact).  All three agree bit-for-bit per row
    across every raggedness pattern (tests/test_ragged_paged_attn.py).
    """
    m = kernel_mode(use_pallas)
    if m == "ref":
        return gather_verify_attn(q, k, v, q_pos, pos, block_tables, layer,
                                  window=window, prefix_len=prefix_len,
                                  scale=scale, k_scale=k_scale,
                                  v_scale=v_scale, use_pallas=None)
    if cu_blocks is not None:
        if config is None:
            # static shapes -> one cache lookup per trace, never per step
            config = lookup_config(q.shape[0], q.shape[1],
                                   block_tables.shape[1])
        return ragged_paged_verify_attn_pallas(
            q, k, v, q_pos, pos, block_tables, layer, cu_blocks,
            window=window, prefix_len=prefix_len, scale=scale,
            k_scale=k_scale, v_scale=v_scale,
            num_buffers=config.num_buffers,
            vmem_limit_bytes=config.vmem_limit_bytes,
            interpret=(m == "interpret"))
    return paged_verify_attn_pallas(q, k, v, q_pos, pos, block_tables,
                                    layer, window=window,
                                    prefix_len=prefix_len,
                                    scale=scale, k_scale=k_scale,
                                    v_scale=v_scale,
                                    interpret=(m == "interpret"))
