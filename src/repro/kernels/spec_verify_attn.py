"""Speculative-verify attention Pallas kernel (the paper's hot spot).

One verify step scores q_len = s+1 draft positions against a long ragged KV
cache (decode_32k: 32k rows; long_500k: a ring-buffered window).  This is a
flash-decode-style kernel: the *whole* tiny q block (s+1 rows, padded to the
8-row sublane multiple) stays resident in VMEM while the kernel streams the
cache in ``block_k`` tiles; grid = (batch, k_blocks).

TPU adaptation of the paper's GPU attention-mask trick: rejection masking is
position arithmetic on the ring buffer's absolute-position row map (k_pos),
so "discarding" mis-speculated tokens costs nothing — stale rows simply stay
masked until overwritten.  Cache tiles whose positions are all outside the
(q - window, q] visibility range are *skipped* (@pl.when) — on a 512k-row
cache with an 8k window that's a 64x reduction in touched tiles, the
structural equivalent of flash-decode's early exit.

TPU block layout: a block's last two dimensions must be multiples of
(8, 128) or span the whole array axis, and a ``block_k`` tile is only a
sublane multiple.  So query positions travel as a ``[N, Tq, 1]`` column,
key positions as ``[N, nk, bk]`` (the whole row of one batch entry stays
resident across its k sweep; step ``j`` reads row ``j``, a ``[1, bk]``
lane vector), and int8 scales as ``[N, L, 1]`` columns that scale the
``[bk, hd]`` tile row-wise.  The score tile ``[Tq, bk]`` is then masked by a
plain column-vs-row broadcast.  The resident key positions cost ``4 * L``
bytes of VMEM per pipeline buffer (128 KiB at L = 32k).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _verify_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref,
                   acc_ref, m_ref, l_ref, *,
                   scale: float, window: Optional[int], prefix_len: int,
                   nk: int, ks_ref=None, vs_ref=None):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    qp = qp_ref[0]                                       # [Tq, 1]
    kp = kp_ref[0, pl.ds(j, 1), :]                       # [1, bk]

    # tile-level visibility: any cache row in this tile attendable by any query?
    q_hi = qp.max()
    vis = (kp >= 0) & (kp <= q_hi)
    if window is not None:
        q_lo = jnp.where(qp < 0, jnp.iinfo(jnp.int32).max, qp).min()
        vis &= kp > q_lo - window
    if prefix_len:
        vis |= (kp >= 0) & (kp < prefix_len)

    @pl.when(vis.any())
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # [Tq, hd]
        k = k_ref[0].astype(jnp.float32)                 # [bk, hd]
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:
            # int8 cache tiles: HBM moved them at 1 B/elem; dequantize in
            # VMEM with the per-row scales (the beyond-paper kv_quant path)
            k = k * ks_ref[0].astype(jnp.float32)        # [bk, 1] column
            v = v * vs_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        ok = (kp >= 0) & (kp <= qp)                      # [Tq, bk]
        if window is not None:
            ok &= kp > qp - window
        if prefix_len:
            ok |= (kp >= 0) & (kp < prefix_len)
        s = jnp.where(ok, s, -jnp.inf)
        m_prev = m_ref[...]                              # [Tq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(ok, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def choose_block_k(L: int, block_k: int):
    """Sublane-aligned cache tiling: ``(bk, Lp)`` with ``Lp % bk == 0``.

    The old policy (``while L % bk: bk -= 1``) silently degraded to tiny —
    even 1-row — tiles whenever L had no large divisor (prime-ish cache
    lengths), collapsing MXU utilisation.  Policy now:

    1. prefer a *divisor* tile — the largest multiple-of-8 divisor of L
       that is <= requested and >= the 64-row floor — because it needs no
       padding and therefore no physical copy of the cache operands (e.g.
       L=640, block_k=512 picks 320 exactly as before; L=520 picks 104
       where the old loop picked the unaligned 260 — smaller, but
       sublane-aligned and still zero-copy);
    2. otherwise keep the requested tile (rounded to the 8-row sublane
       multiple) and pad the cache *tail* to the next multiple: padded
       rows carry ``k_pos = -1`` and are never attendable, so numerics
       are unchanged and the tile never collapses.  Padding copies the
       cache operands, so it is reserved for lengths with no
       MXU-reasonable divisor (any non-multiple-of-8 L necessarily pads —
       there is no sublane-aligned divisor to find).

    Known trade-off: a length with *no* divisor tile >= 64 (e.g. 8*prime)
    pays the pad copy every call.  Serving cache lengths are chosen by the
    caller, and every config in this repo uses lengths with good divisors;
    callers picking exotic lengths should round up to a multiple of 64 at
    cache-allocation time to get the zero-copy path.
    """
    req = max(8, min(block_k, L + (-L) % 8))
    req -= req % 8                      # sublane multiple, never a tiny tile
    if L % 8 == 0:
        # 64-row floor: a divisor tile below it is the old degradation
        # failure mode (tiny tiles), worse than one padded copy
        for bk in range(req, min(64, req) - 1, -8):
            if L % bk == 0:
                return bk, L            # divisor tile: zero-copy
    assert req % 8 == 0 and req >= 8, (L, block_k, req)
    Lp = L + (-L) % req
    return req, Lp


def spec_verify_attn_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            q_pos: jax.Array, k_pos: jax.Array,
                            window: Optional[int] = None, prefix_len: int = 0,
                            scale: Optional[float] = None,
                            block_k: int = 512,
                            k_scale: Optional[jax.Array] = None,
                            v_scale: Optional[jax.Array] = None,
                            interpret: bool = False) -> jax.Array:
    """q: [B, Tq, hd] with tiny Tq (s+1, padded to a multiple of 8 by ops.py;
    padded rows carry q_pos = -1); k/v: [B, L, hd]; k_pos: [B, L].
    Optional k_scale/v_scale: [B, L] per-row dequant scales for int8 k/v
    (the kv_quant cache — tiles stream from HBM at 1 B/elem and are
    dequantized in VMEM).  Returns [B, Tq, hd]."""
    B, Tq, hd = q.shape
    L = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    bk, Lp = choose_block_k(L, block_k)
    if Lp != L:
        # pad the cache tail with k_pos = -1 rows (never attendable) so the
        # tile stays a sublane multiple instead of degrading for prime-ish L
        ext = ((0, 0), (0, Lp - L), (0, 0))
        k = jnp.pad(k, ext)
        v = jnp.pad(v, ext)
        k_pos = jnp.pad(k_pos, ((0, 0), (0, Lp - L)), constant_values=-1)
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, Lp - L)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, Lp - L)))
    nk = Lp // bk
    quant = k_scale is not None
    in_specs = [
        pl.BlockSpec((1, Tq, hd), lambda b, j: (b, 0, 0)),
        pl.BlockSpec((1, bk, hd), lambda b, j: (b, j, 0)),
        pl.BlockSpec((1, bk, hd), lambda b, j: (b, j, 0)),
        pl.BlockSpec((1, Tq, 1), lambda b, j: (b, 0, 0)),
        pl.BlockSpec((1, nk, bk), lambda b, j: (b, 0, 0)),
    ]
    args = [q, k, v, q_pos[:, :, None], k_pos.reshape(B, nk, bk)]
    kern = functools.partial(_verify_kernel, scale=scale, window=window,
                             prefix_len=prefix_len, nk=nk)
    if quant:
        in_specs += [pl.BlockSpec((1, bk, 1), lambda b, j: (b, j, 0)),
                     pl.BlockSpec((1, bk, 1), lambda b, j: (b, j, 0))]
        args += [k_scale[:, :, None], v_scale[:, :, None]]

        def kern(q_ref, k_ref, v_ref, qp_ref, kp_ref, ks_ref, vs_ref, o_ref,
                 acc_ref, m_ref, l_ref):
            return _verify_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref,
                                  acc_ref, m_ref, l_ref, scale=scale,
                                  window=window, prefix_len=prefix_len,
                                  nk=nk, ks_ref=ks_ref, vs_ref=vs_ref)
    return pl.pallas_call(
        kern,
        name="spec_verify_attn",
        grid=(B, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Tq, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((Tq, hd), jnp.float32),
            pltpu.VMEM((Tq, 1), jnp.float32),
            pltpu.VMEM((Tq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
