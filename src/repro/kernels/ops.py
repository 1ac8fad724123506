"""jit'd public wrappers for the Pallas kernels.

Dispatch policy: on TPU backends the Pallas kernels compile natively; on CPU
(this container) callers either use ``interpret=True`` (tests — executes the
kernel body in Python for bit-faithful validation) or fall back to the
pure-jnp reference (fast path for CPU benchmarks).  Models call these
wrappers, so swapping the implementation never touches model code.

GQA head folding: the attention kernel operates on one kv-head per grid row.
``spec_verify_attn`` folds (batch, kv_head) into the kernel batch dim and
the q-head group into the q rows, so a 32-head/4-kv-head GQA layer becomes
4 kernel batches of 8x-longer q blocks — dense MXU tiles instead of 8
strided passes.

Meshes: XLA cannot partition a Pallas TPU kernel, so a jit compiled for a
multi-device mesh must run each kernel inside ``shard_map``.  The engine
traces its sharded jits under :func:`partition_mesh`, and
:func:`batch_partitioned` then runs the verify kernel per device on its
slice of the batch (or on the whole batch, replicated, where the batch
does not divide over the data axes — the B=1 prefill forwards).
"""
from __future__ import annotations

import contextlib
import math
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as _ref
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.spec_verify_attn import spec_verify_attn_pallas
from repro.kernels.ssd_chunk import ssd_chunk_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mode(use_pallas: Optional[bool]) -> str:
    """'pallas' | 'interpret' | 'ref'."""
    if use_pallas is None:
        return "pallas" if _on_tpu() else "ref"
    if use_pallas:
        return "pallas" if _on_tpu() else "interpret"
    return "ref"


# public alias: kernels/paged.py routes its fused-vs-gather dispatch through
# the exact same policy (None -> native on TPU / reference on CPU;
# True -> native on TPU / interpret elsewhere; False -> reference)
kernel_mode = _mode


# ---------------------------------------------------------------------------
# kernels under a mesh

_MESH: list = []


@contextlib.contextmanager
def partition_mesh(mesh):
    """Trace the enclosed code for ``mesh`` (None: a single device): kernel
    calls made while it is active run per device (:func:`batch_partitioned`)."""
    _MESH.append(mesh)
    try:
        yield
    finally:
        _MESH.pop()


def batch_partitioned(fn: Callable, batched: Sequence) -> jax.Array:
    """``fn(*batched)``, run inside ``shard_map`` when a partition mesh is
    active.  Every operand and the result lead with the batch axis, and a
    kernel row reads only its own batch entry, so each device computes its
    slice of the batch over the mesh's data axes; a batch that does not
    divide over them (B=1 prefill) is computed whole on every device."""
    mesh = _MESH[-1] if _MESH else None
    if mesh is None or mesh.size == 1:
        return fn(*batched)
    axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    n = math.prod(mesh.shape[a] for a in axes)
    spec = P(axes) if axes and batched[0].shape[0] % n == 0 else P()
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * len(batched),
                         out_specs=spec, check_vma=False)(*batched)


# ---------------------------------------------------------------------------
# rmsnorm


def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-6,
            use_pallas: Optional[bool] = None) -> jax.Array:
    m = _mode(use_pallas)
    if m == "ref":
        return _ref.rmsnorm_ref(x, gamma, eps)
    return rmsnorm_pallas(x, gamma, eps, interpret=(m == "interpret"))


# ---------------------------------------------------------------------------
# GQA head folding helpers


def _fold_gqa(q: jax.Array, k: jax.Array, v: jax.Array,
              q_pos: jax.Array, k_pos: jax.Array):
    """[B,T,H,hd] x [B,L,KVH,hd] -> per-kv-head folded batches.

    Returns (qf [B*KVH, G*T, hd], kf [B*KVH, L, hd], vf, qpf [B*KVH, G*T],
    kpf [B*KVH, L], unfold) where unfold maps [B*KVH, G*T, vd] back to
    [B, T, H, vd].
    """
    B, T, H, hd = q.shape
    L, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    # q: [B,T,KVH,G,hd] -> [B,KVH,G,T,hd] -> [B*KVH, G*T, hd]
    qf = (q.reshape(B, T, KVH, G, hd).transpose(0, 2, 3, 1, 4)
           .reshape(B * KVH, G * T, hd))
    kf = k.transpose(0, 2, 1, 3).reshape(B * KVH, L, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KVH, L, v.shape[-1])
    qpf = jnp.broadcast_to(q_pos[:, None, None, :], (B, KVH, G, T)).reshape(
        B * KVH, G * T)
    kpf = jnp.broadcast_to(k_pos[:, None, :], (B, KVH, L)).reshape(B * KVH, L)

    def unfold(o: jax.Array) -> jax.Array:
        vd = o.shape[-1]
        return (o.reshape(B, KVH, G, T, vd).transpose(0, 3, 1, 2, 4)
                 .reshape(B, T, H, vd))

    return qf, kf, vf, qpf, kpf, unfold


# ---------------------------------------------------------------------------
# speculative verify attention (decode hot path)


def spec_verify_attn(q: jax.Array, k: jax.Array, v: jax.Array,
                     q_pos: jax.Array, k_pos: jax.Array,
                     window: Optional[int] = None, prefix_len: int = 0,
                     scale: Optional[float] = None, block_k: int = 512,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     use_pallas: Optional[bool] = None) -> jax.Array:
    """Verify-step attention.  q: [B,T,H,hd] with tiny T (s+1, or a prefill
    chunk); k/v: [B,L,KVH,hd]; q_pos/k_pos: [B,T]/[B,L]; returns
    [B,T,H,hd].  q rows are padded to a multiple of 8 for TPU sublanes, padded rows
    carry q_pos = -1 and are sliced off the output.

    int8 caches (kv_quant): pass the int8 k/v plus per-(row, kv-head)
    ``k_scale``/``v_scale`` [B, L, KVH].  The Pallas kernel streams 1 B/elem
    from HBM and dequantizes in VMEM; the CPU reference dequantizes up front
    (numerically identical, HBM accounting differs — launch/costs.py models
    the kernel behaviour)."""
    m = _mode(use_pallas)
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if m == "ref":
        if k_scale is not None:
            k = (k.astype(jnp.float32) * k_scale.astype(jnp.float32)[..., None]
                 ).astype(q.dtype)
            v = (v.astype(jnp.float32) * v_scale.astype(jnp.float32)[..., None]
                 ).astype(q.dtype)
        # unfolded layout: keeps the model-axis sharding of the cache intact
        return _ref.gqa_masked_ref(q, k, v, q_pos, k_pos, window, prefix_len,
                                   scale)

    def kernel(q, k, v, q_pos, k_pos, k_scale=None, v_scale=None):
        qf, kf, vf, qpf, kpf, unfold = _fold_gqa(q, k, v, q_pos, k_pos)
        ksf = vsf = None
        if k_scale is not None:
            B, L, KVH = k_scale.shape
            ksf = k_scale.transpose(0, 2, 1).reshape(B * KVH, L)
            vsf = v_scale.transpose(0, 2, 1).reshape(B * KVH, L)
        rows = qf.shape[1]
        pad = (-rows) % 8
        if pad:
            qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
            qpf = jnp.pad(qpf, ((0, 0), (0, pad)), constant_values=-1)
        o = spec_verify_attn_pallas(qf, kf, vf, qpf, kpf, window, prefix_len,
                                    scale, block_k, k_scale=ksf, v_scale=vsf,
                                    interpret=(m == "interpret"))
        if pad:
            o = o[:, :rows]
        return unfold(o)

    scales = () if k_scale is None else (k_scale, v_scale)
    return batch_partitioned(kernel, (q, k, v, q_pos, k_pos, *scales))


# ---------------------------------------------------------------------------
# SSD chunk


def ssd_chunk(x: jax.Array, b: jax.Array, c: jax.Array, dt: jax.Array,
              l: jax.Array, h0: jax.Array,
              use_pallas: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Batched SSD chunk.  x: [BH,Q,P]; b/c: [BH,Q,N]; dt/l: [BH,Q];
    h0: [BH,P,N] -> (y [BH,Q,P], h_new [BH,P,N]) fp32."""
    m = _mode(use_pallas)
    if m == "ref":
        ys, hs = jax.vmap(_ref.ssd_chunk_ref)(x, b, c, dt, l, h0)
        return ys, hs
    return ssd_chunk_pallas(x, b, c, dt, l, h0, interpret=(m == "interpret"))
