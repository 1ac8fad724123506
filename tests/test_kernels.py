"""Per-kernel validation: Pallas (interpret=True on CPU — executes the real
kernel body) vs the pure-jnp oracles in kernels/ref.py, swept over shapes,
dtypes, masking modes, and block sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.spec_verify_attn import spec_verify_attn_pallas
from repro.kernels.ssd_chunk import ssd_chunk_pallas

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, k=0):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape).astype(dtype)


# ---------------------------------------------------------------------------
# rmsnorm


@pytest.mark.parametrize("shape", [(4, 128), (2, 7, 128), (3, 5, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    x = _rand(shape, dtype, 1)
    g = _rand(shape[-1:], jnp.float32, 2)
    got = rmsnorm_pallas(x, g, interpret=True, block_rows=4)
    want = R.rmsnorm_ref(x, g)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# spec-verify kernel


@pytest.mark.parametrize("Tq,Tk,hd,bk", [
    (16, 32, 32, 8), (32, 32, 64, 16), (8, 64, 128, 32)])
def test_verify_kernel_causal_chunk(Tq, Tk, hd, bk):
    """Prefill-chunk-sized q blocks (a chunk's prefix extension rides the
    same kernel): causal over the whole key range."""
    B = 2
    q, k, v = _rand((B, Tq, hd), k=1), _rand((B, Tk, hd), k=2), _rand((B, Tk, hd), k=3)
    qp = jnp.broadcast_to(jnp.arange(Tq, dtype=jnp.int32) + Tk - Tq, (B, Tq))
    kp = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32), (B, Tk))
    got = spec_verify_attn_pallas(q, k, v, qp, kp, block_k=bk, interpret=True)
    want = R.flash_attn_ref(q, k, v, qp, kp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window,prefix", [(None, 0), (8, 0), (None, 6), (8, 6)])
def test_verify_kernel_masking_modes(window, prefix):
    B, Tq, Tk, hd = 2, 16, 48, 32
    q, k, v = _rand((B, Tq, hd), k=4), _rand((B, Tk, hd), k=5), _rand((B, Tk, hd), k=6)
    qp = jnp.broadcast_to(jnp.arange(Tq, dtype=jnp.int32) + 20, (B, Tq))
    kp = jnp.where(jnp.arange(Tk) < 40, jnp.arange(Tk, dtype=jnp.int32), -1)
    kp = jnp.broadcast_to(kp, (B, Tk))
    got = spec_verify_attn_pallas(q, k, v, qp, kp, window=window,
                                  prefix_len=prefix, block_k=16,
                                  interpret=True)
    want = R.flash_attn_ref(q, k, v, qp, kp, window=window, prefix_len=prefix)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("Tq", [1, 2, 5, 8])
@pytest.mark.parametrize("L,bk", [(64, 16), (128, 128)])
def test_verify_kernel_vs_ref(Tq, L, bk):
    B, hd = 3, 64
    q, k, v = _rand((B, Tq, hd), k=7), _rand((B, L, hd), k=8), _rand((B, L, hd), k=9)
    seq = 37
    qp = jnp.broadcast_to(jnp.arange(Tq, dtype=jnp.int32) + seq, (B, Tq))
    kp = jnp.where(jnp.arange(L) < seq + Tq, jnp.arange(L, dtype=jnp.int32), -1)
    kp = jnp.broadcast_to(kp, (B, L))
    got = spec_verify_attn_pallas(q, k, v, qp, kp, block_k=bk, interpret=True)
    want = R.spec_verify_ref(q, k, v, qp, kp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_verify_kernel_tile_skip_matches_windowed_ref():
    """Sliding window: skipped tiles must not change the result."""
    B, Tq, L, hd, w = 2, 4, 256, 32, 32
    q, k, v = _rand((B, Tq, hd), k=10), _rand((B, L, hd), k=11), _rand((B, L, hd), k=12)
    qp = jnp.broadcast_to(jnp.arange(Tq, dtype=jnp.int32) + 200, (B, Tq))
    kp = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
    got = spec_verify_attn_pallas(q, k, v, qp, kp, window=w, block_k=32,
                                  interpret=True)
    want = R.spec_verify_ref(q, k, v, qp, kp, window=w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# ops wrappers: GQA folding + ragged positions


@pytest.mark.parametrize("H,KVH", [(8, 8), (8, 2), (4, 1)])
def test_ops_gqa_folding(H, KVH):
    B, T, L, hd = 2, 6, 64, 32
    q = _rand((B, T, H, hd), k=13)
    k = _rand((B, L, KVH, hd), k=14)
    v = _rand((B, L, KVH, hd), k=15)
    lens = jnp.array([50, 33])
    qp = lens[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    kp = jnp.where(jnp.arange(L)[None] < (lens + T)[:, None],
                   jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L)), -1)
    want = ops.spec_verify_attn(q, k, v, qp, kp, use_pallas=False)
    got = ops.spec_verify_attn(q, k, v, qp, kp, use_pallas=True, block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# SSD chunk kernel


@pytest.mark.parametrize("Q,P,N", [(8, 8, 16), (16, 64, 128), (32, 16, 32)])
def test_ssd_chunk_vs_ref(Q, P, N):
    BH = 3
    x = _rand((BH, Q, P), k=16)
    b = _rand((BH, Q, N), k=17) * 0.3
    c = _rand((BH, Q, N), k=18) * 0.3
    dt = jax.nn.softplus(_rand((BH, Q), k=19))
    l = -jax.nn.softplus(_rand((BH, Q), k=20))
    h0 = _rand((BH, P, N), k=21)
    y_p, h_p = ssd_chunk_pallas(x, b, c, dt, l, h0, interpret=True)
    y_r, h_r = jax.vmap(R.ssd_chunk_ref)(x, b, c, dt, l, h0)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_r), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_r), rtol=2e-4, atol=2e-4)


def test_ssd_chunk_chains_like_sequential_scan():
    """Two chained chunk calls == one call over the concatenated sequence
    (the inter-chunk recurrence contract the model relies on)."""
    BH, Q, P, N = 2, 8, 8, 16
    x = _rand((BH, 2 * Q, P), k=22)
    b = _rand((BH, 2 * Q, N), k=23) * 0.3
    c = _rand((BH, 2 * Q, N), k=24) * 0.3
    dt = jax.nn.softplus(_rand((BH, 2 * Q), k=25))
    l = -jax.nn.softplus(_rand((BH, 2 * Q), k=26))
    h0 = jnp.zeros((BH, P, N))
    y_full, h_full = ops.ssd_chunk(x, b, c, dt, l, h0, use_pallas=False)
    y1, h1 = ops.ssd_chunk(x[:, :Q], b[:, :Q], c[:, :Q], dt[:, :Q], l[:, :Q],
                           h0, use_pallas=False)
    y2, h2 = ops.ssd_chunk(x[:, Q:], b[:, Q:], c[:, Q:], dt[:, Q:], l[:, Q:],
                           h1, use_pallas=False)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# model-level attention helpers agree with each other


def test_train_tri_and_ref_attention_agree():
    from repro.models import common as cm
    B, T, H, KVH, hd = 2, 24, 4, 2, 16
    q = _rand((B, T, H, hd), k=27)
    k = _rand((B, T, KVH, hd), k=28)
    v = _rand((B, T, KVH, hd), k=29)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    a = cm.flash_attention_tri(q, k, v, pos, pos, block_q=8, block_k=8)
    b_ = cm.flash_attention_train(q, k, v, pos, pos, block_q=8)
    c_ = R.gqa_masked_ref(q, k, v, pos, pos, None, 0, 1.0 / np.sqrt(hd))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c_), rtol=2e-5, atol=2e-5)


def test_verify_kernel_int8_dequant_in_vmem():
    """kv_quant path: int8 cache tiles + per-row scales dequantized inside
    the kernel must match the dequantized-reference attention."""
    B, Tq, L, hd = 2, 8, 64, 32
    q = _rand((B, Tq, hd), k=30)
    k = _rand((B, L, hd), k=31)
    v = _rand((B, L, hd), k=32)
    ks = jnp.max(jnp.abs(k), -1) / 127.0
    vs = jnp.max(jnp.abs(v), -1) / 127.0
    kq = jnp.clip(jnp.round(k / ks[..., None]), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(v / vs[..., None]), -127, 127).astype(jnp.int8)
    qp = jnp.broadcast_to(jnp.arange(Tq, dtype=jnp.int32) + 40, (B, Tq))
    kp = jnp.broadcast_to(
        jnp.where(jnp.arange(L) < 48, jnp.arange(L, dtype=jnp.int32), -1), (B, L))
    got = spec_verify_attn_pallas(q, kq, vq, qp, kp, k_scale=ks, v_scale=vs,
                                  block_k=16, interpret=True)
    kd = kq.astype(jnp.float32) * ks[..., None]
    vd = vq.astype(jnp.float32) * vs[..., None]
    want = R.spec_verify_ref(q, kd, vd, qp, kp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
