"""Fused paged verify-attention Pallas kernels: stream KV straight through
the block tables, never materializing a gathered logical view.

The gather path (kernels/paged.py ``gather_verify_attn``) rebuilds each
slot's contiguous ``[B, MAXB*bs, KVH, hd]`` KV view before running the
verify kernel over the copy — every paged verify step pays the pool's HBM
traffic twice (gather write + kernel read) and the transient view grows
linearly with batch size, exactly the regime where the paper's batching x
speculation synergy lives.  These kernels remove the copy: the k/v/pos
BlockSpec index maps read each tile *directly* from the shared pool
through the slot's block-table row, prefetched as a scalar
(``PrefetchScalarGridSpec``) so the index maps can consume it before the
kernel body runs.

The kernels take the whole ``[nL, NB, bs, KVH, hd]`` pool and the layer
index, which rides the scalar-prefetch channel as an ``int32[1]`` operand:
the k/v (and int8 scale) index maps return ``(layer, block, ...)`` with
the layer dimension squeezed, and the manual-DMA ring copies
``k_hbm.at[layer, block]``.  The model's layer scan carries the pool and
writes it in place, so no per-layer slice of it exists anywhere.  A caller
holding one layer's pool passes ``k[None]`` and layer 0.

Two grid strategies over the same tile math:

* **dense** (:func:`paged_verify_attn_pallas`): grid ``(batch,
  max_blocks_per_slot)``; ``-1`` table entries (unallocated logical
  blocks — ragged slots, empty rows, mid-chunked-prefill pending slots)
  contribute nothing: the index map clips them to physical block 0 so the
  DMA address is always valid — consecutive dead entries then revisit the
  same block, which the Pallas pipeline recognizes and skips re-fetching —
  and the body skips the tile entirely (``@pl.when``), which is
  numerically identical to every key in it carrying position ``-1`` (the
  gather path's convention).  Dead tiles still cost grid steps.
* **ragged** (:func:`ragged_paged_verify_attn_pallas`): the grid is a
  flat run of ``cu_blocks[B]`` steps — the *sum of live blocks* (each
  empty slot keeps exactly one dead step so its accumulators still
  initialize and its output row still finalizes to zeros), host-computed
  from the same block accounting that owns the tables and prefetched
  alongside them.  Step ``i`` serves slot ``ss[i]`` and its ``sb[i]``-th
  live logical block, both derived in-trace from ``cu_blocks`` and the
  table (stable argsort packs each row's live entries first, in ascending
  logical order — so a slot's blocks are visited in exactly the dense
  kernel's order and the online-softmax accumulation is bit-identical).
  Accumulators init at ``i == cu[b]`` and the output row finalizes at
  ``i == cu[b+1]-1``.  Dead tiles simply do not exist in the grid:
  raggedness costs nothing.

The ragged kernel additionally offers **explicit multi-buffered DMA**
(``num_buffers >= 2``): the k/v pool tiles live in ``ANY`` memory space
and the kernel drives its own ``make_async_copy`` ring — ``num_buffers``
VMEM landing buffers per stream, one DMA semaphore lane each, warm-up
fetch of the first ``num_buffers - 1`` tiles at step 0 and a steady-state
fetch of tile ``i + num_buffers - 1`` each step — so the fetch horizon
(how far DMA runs ahead of compute) is a tunable knob instead of the
pipeline default.  The small pos (and int8 scale) tiles keep riding the
BlockSpec pipeline.  ``num_buffers = 0`` keeps the standard
BlockSpec auto-pipeline.  ``profile='dma'`` / ``profile='compute'`` skip
the compute or the copies respectively so the benchmark can price the two
halves of the pipeline separately (benchmarks/kernel_bench.py
``--profile-dma``).

TPU block layout: every block's last two dimensions must be multiples of
(8, 128) or span the whole array axis.  So the position maps travel as
``[B, GT, 1]`` (query positions, a column per slot) and ``[NB, 1, bs]``
(pool positions, a row per block), and the online-softmax max/sum
scratch is ``[KVH, GT, 1]``: the score tile ``[GT, bs]`` is then masked by
a plain column-vs-row broadcast, with no relayout in the kernel.

Masking (q_pos/k_pos arithmetic, ``window``, ``prefix_len``) is the shared
position-mask contract of kernels/ref.py, evaluated against the pool's
per-row ``pos`` map — identical to gathering first, because a slot only
ever reaches its own blocks (ownership by construction of the table).
Mixed launches ride the same contract: a batch row may carry verify
queries (positions ``seq-1 .. seq+s-1``) or a chunk-prefill prefix
extension (positions ``start .. start+n``) — per-query-row masking plus
per-row block tables make the kernel agnostic to which is which, and
``q_pos = -1`` rows (padding in heterogeneous launches) match nothing.

GQA: the pool keeps its ``[nL, NB, bs, KVH, hd]`` layout (one DMA per owned
block covers every kv head — blocks are owned by exactly one slot, so each
pool row is read exactly once per step, the HBM floor), and the kernel
loops the kv heads as an unrolled static loop of 2D MXU dots.  The q block
is pre-folded to ``[B, KVH, G*Tq, hd]`` host-side (tiny) and stays VMEM-
resident across the whole block stream.

int8 KV (kv_quant): per-(row, kv-head) ``k_scale``/``v_scale`` pool arrays
ride the same block-table index maps (or DMA ring); tiles stream from HBM
at 1 B/elem and dequantize in VMEM — the contiguous kernel's quant path,
carried over.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _flash_tile(q_ref, kt, vt, qp, kp, acc_ref, m_ref, l_ref, *,
                scale: float, window: Optional[int], prefix_len: int,
                kvh: int, ks=None, vs=None):
    """Fold one ``[bs, KVH, hd]`` KV tile into the online-softmax
    accumulators — the shared tile math of the dense and ragged grids.

    ``kt``/``vt`` are tile *values* (read from a BlockSpec ref or a manual
    DMA landing buffer); ``ks``/``vs`` are the int8 dequant scale tiles
    ``[bs, KVH]`` when the pool is quantized.  ``qp`` is the ``[GT, 1]``
    query-position column, ``kp`` the ``[1, bs]`` pool-position row.
    """
    ok = (kp >= 0) & (kp <= qp)                          # [GT, bs]
    if window is not None:
        ok &= kp > qp - window
    if prefix_len:
        ok |= (kp >= 0) & (kp < prefix_len)
    for h in range(kvh):                             # unrolled 2D dots
        q = q_ref[0, h].astype(jnp.float32)          # [GT, hd]
        k = kt[:, h, :].astype(jnp.float32)          # [bs, hd]
        v = vt[:, h, :].astype(jnp.float32)
        if ks is not None:
            # int8 pool tiles: moved at 1 B/elem, dequantized in VMEM
            k = k * ks[:, h:h + 1].astype(jnp.float32)
            v = v * vs[:, h:h + 1].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = jnp.where(ok, s, -jnp.inf)
        m_prev = m_ref[h]                                # [GT, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(ok, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isneginf(m_prev), 0.0,
                         jnp.exp(m_prev - m_safe))
        l_ref[h] = l_ref[h] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[h] = m_new


def _tile_visible(qp, kp, window: Optional[int], prefix_len: int):
    """Tile-level visibility (flash-decode early exit): is any pool row in
    this tile attendable by any query?  Dead tiles report False outright —
    identical to every row carrying position -1.  ``qp`` is ``[GT, 1]``,
    ``kp`` is ``[1, bs]``."""
    q_hi = qp.max()
    vis = (kp >= 0) & (kp <= q_hi)
    if window is not None:
        q_lo = jnp.where(qp < 0, jnp.iinfo(jnp.int32).max, qp).min()
        vis &= kp > q_lo - window
    if prefix_len:
        vis |= (kp >= 0) & (kp < prefix_len)
    return vis.any()


def _fold_q(q: jax.Array, q_pos: jax.Array, kvh: int):
    """Fold q per kv head: ``[B, T, H, hd] -> [B, KVH, G*T, hd]`` (rows
    (g, t), matching ops._fold_gqa's ordering), repeat q_pos per group
    row, and pad the row dim to the TPU sublane multiple (8) with
    ``q_pos = -1`` rows that match nothing.  Returns ``(qf, qpf, GT,
    unfold)``: ``qpf`` is the ``[B, GT, 1]`` position column, and unfold
    maps ``[B, KVH, GT, hd]`` back to ``[B, T, H, hd]``.
    """
    B, T, H, hd = q.shape
    G = H // kvh
    qf = (q.reshape(B, T, kvh, G, hd).transpose(0, 2, 3, 1, 4)
           .reshape(B, kvh, G * T, hd))
    qpf = jnp.broadcast_to(q_pos[:, None, :], (B, G, T)).reshape(B, G * T)
    rows = G * T
    pad = (-rows) % 8                       # TPU sublane multiple
    if pad:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        qpf = jnp.pad(qpf, ((0, 0), (0, pad)), constant_values=-1)
    GT = rows + pad
    qpf = qpf[:, :, None]

    def unfold(o: jax.Array) -> jax.Array:
        ot = o[:, :, :rows] if pad else o
        return (ot.reshape(B, kvh, G, T, hd).transpose(0, 3, 1, 2, 4)
                  .reshape(B, T, H, hd))

    return qf, qpf, GT, unfold


def _layer_operand(layer: int | jax.Array) -> jax.Array:
    """The layer index as the ``int32[1]`` scalar-prefetch operand the
    index maps (and the DMA ring) address the ``[nL, NB, ...]`` pool with."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _acc_scratch(kvh: int, gt: int, hd: int):
    """Online-softmax VMEM scratch: accumulator, running max, running sum."""
    return [pltpu.VMEM((kvh, gt, hd), jnp.float32),
            pltpu.VMEM((kvh, gt, 1), jnp.float32),
            pltpu.VMEM((kvh, gt, 1), jnp.float32)]


# ---------------------------------------------------------------------------
# dense grid: (batch, max_blocks_per_slot), @pl.when skipping dead tiles


def _fused_kernel(bt_ref, ly_ref, q_ref, k_ref, v_ref, qp_ref, pp_ref, *rest,
                  scale: float, window: Optional[int], prefix_len: int,
                  nb: int, kvh: int, quant: bool):
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    qp = qp_ref[0]                                       # [GT, 1]
    kp = pp_ref[0]                                       # [1, bs]
    owned = bt_ref[b, j] >= 0

    @pl.when(owned & _tile_visible(qp, kp, window, prefix_len))
    def _compute():
        _flash_tile(q_ref, k_ref[0], v_ref[0], qp, kp,
                    acc_ref, m_ref, l_ref, scale=scale, window=window,
                    prefix_len=prefix_len, kvh=kvh,
                    ks=None if ks_ref is None else ks_ref[0],
                    vs=None if vs_ref is None else vs_ref[0])

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_verify_attn_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                             q_pos: jax.Array, pos: jax.Array,
                             block_tables: jax.Array,
                             layer: int | jax.Array,
                             window: Optional[int] = None,
                             prefix_len: int = 0,
                             scale: Optional[float] = None,
                             k_scale: Optional[jax.Array] = None,
                             v_scale: Optional[jax.Array] = None,
                             interpret: bool = False) -> jax.Array:
    """Verify-step attention against the paged pool, fused, dense grid.

    q: [B, T, H, hd] (tiny T = s+1, or a prefill chunk); k/v:
    [nL, NB, bs, KVH, hd], the whole pool of every layer; q_pos: [B, T];
    pos: [NB, bs] (absolute position, -1 unwritten); block_tables: [B, MAXB]
    (physical block ids, -1 unused); ``layer``: the layer whose tiles are
    read (int or int32 scalar).  Optional k_scale/v_scale: [nL, NB, bs, KVH]
    per-(row, kv-head) dequant scales for an int8 pool.  Returns
    [B, T, H, hd].

    No ``[B, MAXB*bs, ...]`` logical view is ever built: tiles stream from
    the pool through the prefetched block table (module docstring).  The
    grid is the dense ``(B, MAXB)`` — dead tiles are skipped but still
    cost grid steps; :func:`ragged_paged_verify_attn_pallas` removes them.
    """
    B, T, H, hd = q.shape
    bs, KVH = k.shape[2], k.shape[3]
    MAXB = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf, qpf, GT, unfold = _fold_q(q, q_pos, KVH)

    # index maps receive the prefetched block table and layer; dead entries
    # clip to physical block 0 (valid address, body skips the tile — and
    # repeated dead entries revisit the same block, so the pipeline elides
    # the DMA)
    def _kv_map(b, j, bt, ly):
        return (ly[0], jnp.maximum(bt[b, j], 0), 0, 0, 0)

    def _pos_map(b, j, bt, ly):
        return (jnp.maximum(bt[b, j], 0), 0, 0)

    def _scale_map(b, j, bt, ly):
        return (ly[0], jnp.maximum(bt[b, j], 0), 0, 0)

    quant = k_scale is not None
    in_specs = [
        pl.BlockSpec((1, KVH, GT, hd), lambda b, j, bt, ly: (b, 0, 0, 0)),
        pl.BlockSpec((None, 1, bs, KVH, hd), _kv_map),
        pl.BlockSpec((None, 1, bs, KVH, hd), _kv_map),
        pl.BlockSpec((1, GT, 1), lambda b, j, bt, ly: (b, 0, 0)),
        pl.BlockSpec((1, 1, bs), _pos_map),
    ]
    args = [block_tables, _layer_operand(layer), qf, k, v, qpf,
            pos[:, None, :]]
    if quant:
        in_specs += [pl.BlockSpec((None, 1, bs, KVH), _scale_map),
                     pl.BlockSpec((None, 1, bs, KVH), _scale_map)]
        args += [k_scale, v_scale]
    kern = functools.partial(_fused_kernel, scale=scale, window=window,
                             prefix_len=prefix_len, nb=MAXB, kvh=KVH,
                             quant=quant)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MAXB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KVH, GT, hd),
                               lambda b, j, bt, ly: (b, 0, 0, 0)),
        scratch_shapes=_acc_scratch(KVH, GT, hd),
    )
    o = pl.pallas_call(
        kern,
        name="paged_verify_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, GT, hd), q.dtype),
        interpret=interpret,
    )(*args)
    return unfold(o)


# ---------------------------------------------------------------------------
# ragged grid: one flat run of sum(max(live_blocks, 1)) steps


def ragged_plan(block_tables: jax.Array, cu_blocks: jax.Array):
    """Derive the step->(slot, logical block) maps for the ragged grid.

    ``cu_blocks`` is the host-computed cumulative step count ``[B + 1]``
    (per-slot steps = max(live blocks, 1); see kernels/tuning.py
    ``host_cu_blocks``).  Returns ``(ss, sb, pbs)`` of static length
    ``B * MAXB`` (the grid only visits the first ``cu_blocks[B]``):

    * ``ss[i]``  — the slot served by step ``i``;
    * ``sb[i]``  — the *logical* block index within that slot's table row
      (its ``(i - cu[ss[i]])``-th live entry, in ascending logical order —
      the dense kernel's visit order, so accumulation is bit-identical);
    * ``pbs[i]`` — the physical pool block (dead entries clipped to 0 so
      the address is always valid; the body's ``owned`` check skips them).

    All three are cheap in-trace int32 ops over ``[B, MAXB]``; they ride
    the scalar-prefetch channel into the index maps.
    """
    B, MAXB = block_tables.shape
    cu = cu_blocks.astype(jnp.int32)
    ar = jnp.arange(B * MAXB, dtype=jnp.int32)
    ss = jnp.clip(jnp.searchsorted(cu, ar, side="right") - 1,
                  0, B - 1).astype(jnp.int32)
    # stable argsort of the dead mask packs each row's live logical
    # indices first, in ascending order (interior -1 holes included)
    order = jnp.argsort(jnp.where(block_tables >= 0, 0, 1),
                        axis=1, stable=True).astype(jnp.int32)
    sb = order[ss, jnp.minimum(ar - cu[ss], MAXB - 1)]
    pbs = jnp.maximum(block_tables[ss, sb], 0).astype(jnp.int32)
    return ss, sb, pbs


def _ragged_kernel(bt_ref, ly_ref, ss_ref, sb_ref, cu_ref, q_ref, k_ref,
                   v_ref, qp_ref, pp_ref, *rest,
                   scale: float, window: Optional[int], prefix_len: int,
                   kvh: int, quant: bool, profile: Optional[str]):
    if quant:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, acc_ref, m_ref, l_ref = rest
    i = pl.program_id(0)
    b = ss_ref[i]

    @pl.when(i == cu_ref[b])
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    qp = qp_ref[0]                                       # [GT, 1]
    kp = pp_ref[0]                                       # [1, bs]
    owned = bt_ref[b, sb_ref[i]] >= 0

    if profile != "dma":
        @pl.when(owned & _tile_visible(qp, kp, window, prefix_len))
        def _compute():
            _flash_tile(q_ref, k_ref[0], v_ref[0], qp, kp,
                        acc_ref, m_ref, l_ref, scale=scale, window=window,
                        prefix_len=prefix_len, kvh=kvh,
                        ks=None if ks_ref is None else ks_ref[0],
                        vs=None if vs_ref is None else vs_ref[0])

    @pl.when(i == cu_ref[b + 1] - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def _ragged_dma_kernel(bt_ref, ly_ref, ss_ref, sb_ref, cu_ref, pbs_ref,
                       q_ref, qp_ref, pp_ref, *rest,
                       scale: float, window: Optional[int], prefix_len: int,
                       kvh: int, quant: bool, nbuf: int,
                       profile: Optional[str]):
    """Ragged grid with an explicit ``nbuf``-deep manual DMA ring.

    The k/v pools stay in ANY memory space; each gets ``nbuf`` VMEM
    landing buffers and a DMA semaphore lane per buffer.  Step 0
    warm-starts the first ``nbuf - 1`` tile fetches; every step then
    starts tile ``i + nbuf - 1`` and waits on its own — generalized
    double/quad buffering with the fetch horizon as a knob.  The pos (and
    int8 scale) tiles are a few hundred bytes per block, and their
    sub-128-lane rows are not a legal manual-DMA slice on the TPU, so they
    ride the BlockSpec pipeline through the same ``pbs`` block map.
    """
    if quant:
        (ks_ref, vs_ref, k_hbm, v_hbm, o_ref, acc_ref, m_ref, l_ref,
         kbuf, vbuf, ksem, vsem) = rest
    else:
        (k_hbm, v_hbm, o_ref, acc_ref, m_ref, l_ref,
         kbuf, vbuf, ksem, vsem) = rest
        ks_ref = vs_ref = None
    i = pl.program_id(0)
    n = pl.num_programs(0)
    b = ss_ref[i]
    layer = ly_ref[0]

    def _copies(t, slot):
        blk = pbs_ref[t]
        return [pltpu.make_async_copy(k_hbm.at[layer, blk], kbuf.at[slot],
                                      ksem.at[slot]),
                pltpu.make_async_copy(v_hbm.at[layer, blk], vbuf.at[slot],
                                      vsem.at[slot])]

    @pl.when(i == cu_ref[b])
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    slot = i % nbuf
    if profile != "compute":
        # warm-up: tiles 0 .. nbuf-2 land in buffers 0 .. nbuf-2
        @pl.when(i == 0)
        def _warmup():
            for d in range(nbuf - 1):
                @pl.when(d < n)
                def _start(d=d):
                    for op in _copies(d, d):
                        op.start()

        # steady state: keep the ring full nbuf-1 tiles ahead of compute
        nxt = i + nbuf - 1

        @pl.when(nxt < n)
        def _ahead():
            for op in _copies(nxt, nxt % nbuf):
                op.start()

        for op in _copies(i, slot):
            op.wait()

    qp = qp_ref[0]                                       # [GT, 1]
    kp = pp_ref[0]                                       # [1, bs]
    owned = bt_ref[b, sb_ref[i]] >= 0

    if profile != "dma":
        @pl.when(owned & _tile_visible(qp, kp, window, prefix_len))
        def _compute():
            _flash_tile(q_ref, kbuf[slot], vbuf[slot], qp, kp,
                        acc_ref, m_ref, l_ref, scale=scale, window=window,
                        prefix_len=prefix_len, kvh=kvh,
                        ks=None if ks_ref is None else ks_ref[0],
                        vs=None if vs_ref is None else vs_ref[0])

    @pl.when(i == cu_ref[b + 1] - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def ragged_paged_verify_attn_pallas(q: jax.Array, k: jax.Array,
                                    v: jax.Array, q_pos: jax.Array,
                                    pos: jax.Array,
                                    block_tables: jax.Array,
                                    layer: int | jax.Array,
                                    cu_blocks: jax.Array,
                                    window: Optional[int] = None,
                                    prefix_len: int = 0,
                                    scale: Optional[float] = None,
                                    k_scale: Optional[jax.Array] = None,
                                    v_scale: Optional[jax.Array] = None,
                                    num_buffers: int = 0,
                                    vmem_limit_bytes: Optional[int] = None,
                                    profile: Optional[str] = None,
                                    interpret: bool = False) -> jax.Array:
    """Verify-step attention against the paged pool, fused, *ragged* grid.

    Same operands (the whole ``[nL, NB, ...]`` pool plus ``layer``) and
    masking contract as :func:`paged_verify_attn_pallas` plus ``cu_blocks
    [B + 1]`` — the host-computed cumulative grid-step counts (per-slot
    steps = ``max(live blocks, 1)``; see
    ``kernels/tuning.py host_cu_blocks``).  The grid is one flat run of
    ``cu_blocks[B]`` steps, so dead table entries cost nothing; per-slot
    blocks are visited in ascending logical order, making the output
    bit-identical to the dense kernel (and the gather reference) for every
    raggedness pattern.

    Launch knobs (autotuned per (batch, s, blocks) cell — see
    ``kernels/tuning.py`` and ``benchmarks/kernel_bench.py --autotune``):

    * ``num_buffers = 0`` — standard BlockSpec auto-pipelining;
      ``>= 2`` — explicit manual DMA with that many landing buffers per
      k/v stream (double/quad/... buffering).
    * ``vmem_limit_bytes`` — TPU compiler VMEM budget for the launch
      (ignored in interpret mode).
    * ``profile`` — ``'dma'`` skips the tile compute, ``'compute'`` skips
      the copies (manual-DMA variant only): the benchmark's
      DMA-vs-compute split.  Output is garbage in either mode.
    """
    B, T, H, hd = q.shape
    bs, KVH = k.shape[2], k.shape[3]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qf, qpf, GT, unfold = _fold_q(q, q_pos, KVH)
    ss, sb, pbs = ragged_plan(block_tables, cu_blocks)
    ly = _layer_operand(layer)
    total = cu_blocks.astype(jnp.int32)[block_tables.shape[0]]
    quant = k_scale is not None

    # index maps see the grid index plus every scalar-prefetch operand,
    # in positional order
    def _q_map(i, bt, ly, ss, sb, cu):
        return (ss[i], 0, 0, 0)

    def _qp_map(i, bt, ly, ss, sb, cu):
        return (ss[i], 0, 0)

    kwargs = {}
    if vmem_limit_bytes is not None and not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem_limit_bytes))
    pos3 = pos[:, None, :]                               # [NB, 1, bs] rows

    if num_buffers >= 2:
        def _q_map_d(i, bt, ly, ss, sb, cu, pbs):
            return (ss[i], 0, 0, 0)

        def _qp_map_d(i, bt, ly, ss, sb, cu, pbs):
            return (ss[i], 0, 0)

        def _pos_map_d(i, bt, ly, ss, sb, cu, pbs):
            return (pbs[i], 0, 0)

        def _scale_map_d(i, bt, ly, ss, sb, cu, pbs):
            return (ly[0], pbs[i], 0, 0)

        in_specs = [
            pl.BlockSpec((1, KVH, GT, hd), _q_map_d),
            pl.BlockSpec((1, GT, 1), _qp_map_d),
            pl.BlockSpec((1, 1, bs), _pos_map_d),
        ]
        args = [block_tables, ly, ss, sb, cu_blocks.astype(jnp.int32), pbs,
                qf, qpf, pos3]
        if quant:
            in_specs += [pl.BlockSpec((None, 1, bs, KVH), _scale_map_d),
                         pl.BlockSpec((None, 1, bs, KVH), _scale_map_d)]
            args += [k_scale, v_scale]
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        args += [k, v]
        D = num_buffers
        scratch = _acc_scratch(KVH, GT, hd) + [
            pltpu.VMEM((D, bs, KVH, hd), k.dtype),
            pltpu.VMEM((D, bs, KVH, hd), v.dtype),
            pltpu.SemaphoreType.DMA((D,)),
            pltpu.SemaphoreType.DMA((D,)),
        ]
        kern = functools.partial(_ragged_dma_kernel, scale=scale,
                                 window=window, prefix_len=prefix_len,
                                 kvh=KVH, quant=quant, nbuf=D,
                                 profile=profile)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(total,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KVH, GT, hd), _q_map_d),
            scratch_shapes=scratch,
        )
    else:
        def _kv_map(i, bt, ly, ss, sb, cu):
            return (ly[0], jnp.maximum(bt[ss[i], sb[i]], 0), 0, 0, 0)

        def _pos_map(i, bt, ly, ss, sb, cu):
            return (jnp.maximum(bt[ss[i], sb[i]], 0), 0, 0)

        def _scale_map(i, bt, ly, ss, sb, cu):
            return (ly[0], jnp.maximum(bt[ss[i], sb[i]], 0), 0, 0)

        in_specs = [
            pl.BlockSpec((1, KVH, GT, hd), _q_map),
            pl.BlockSpec((None, 1, bs, KVH, hd), _kv_map),
            pl.BlockSpec((None, 1, bs, KVH, hd), _kv_map),
            pl.BlockSpec((1, GT, 1), _qp_map),
            pl.BlockSpec((1, 1, bs), _pos_map),
        ]
        args = [block_tables, ly, ss, sb, cu_blocks.astype(jnp.int32),
                qf, k, v, qpf, pos3]
        if quant:
            in_specs += [pl.BlockSpec((None, 1, bs, KVH), _scale_map),
                         pl.BlockSpec((None, 1, bs, KVH), _scale_map)]
            args += [k_scale, v_scale]
        kern = functools.partial(_ragged_kernel, scale=scale, window=window,
                                 prefix_len=prefix_len, kvh=KVH,
                                 quant=quant, profile=profile)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(total,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KVH, GT, hd), _q_map),
            scratch_shapes=_acc_scratch(KVH, GT, hd),
        )
    o = pl.pallas_call(
        kern,
        name="ragged_paged_verify_attn",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, GT, hd), q.dtype),
        interpret=interpret,
        **kwargs,
    )(*args)
    return unfold(o)
