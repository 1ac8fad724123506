"""Fused paged-attention kernel: interpret-mode parity vs kernels/ref.py and
vs the materialized gather+verify path — ragged block tables with -1 holes,
sliding window, bidirectional prefix, int8 pool scales, the ≤1-block gather
fast path, the sublane block-size fix, and a scheduler-level
serve_continuous_live run that must be token- and StepTrace-identical with
the fused kernel on vs off.  All fast tier (citier `kernels` runs the
kernel-parity subset)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry as R
from repro.core.adaptive import AdaptiveController, SpeculationLUT
from repro.core.spec_decode import SpecDecodeEngine
from repro.kernels import ref as KR
from repro.kernels.paged import (gather_key_positions, gather_kv_blocks,
                                 gather_scales, gather_verify_attn,
                                 paged_verify_attn)
from repro.kernels.paged_verify_attn import paged_verify_attn_pallas
from repro.kernels.spec_verify_attn import choose_block_k
from repro.serving.request import Request
from repro.serving.scheduler import (ContinuousEngineBackend,
                                     PrefillBudgetAdmit,
                                     serve_continuous_live)

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, k=0):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape).astype(dtype)


def _pool(B, lens, NB, bs, MAXB, KVH, hd, seed=0, holes=()):
    """Build a ragged paged pool: per-slot block tables (optionally with
    interior -1 holes — e.g. a preempted slot's partially-rebuilt table),
    the pool pos map, and k/v pool arrays with garbage in unowned blocks.
    k/v hold one layer, ``[1, NB, bs, KVH, hd]``: the kernels read it at
    layer 0."""
    rng = np.random.default_rng(seed)
    k = _rand((1, NB, bs, KVH, hd), k=seed + 1)
    v = _rand((1, NB, bs, KVH, hd), k=seed + 2)
    bt = np.full((B, MAXB), -1, np.int32)
    pos = np.full((NB, bs), -1, np.int32)
    order = rng.permutation(NB)
    nxt = 0
    for b, L in enumerate(lens):
        nblk = -(-L // bs) if L else 0
        for j in range(nblk):
            if (b, j) in holes:
                continue
            pb = int(order[nxt]); nxt += 1
            bt[b, j] = pb
            for o in range(bs):
                p = j * bs + o
                if p < L:
                    pos[pb, o] = p
    return k, v, jnp.asarray(bt), jnp.asarray(pos)


def _qpos(lens, T):
    return jnp.asarray(np.stack([
        np.arange(T, dtype=np.int32) + (L - 1) if L else
        np.full(T, -1, np.int32) for L in lens]))


# ---------------------------------------------------------------------------
# kernel-level parity (interpret mode executes the real kernel body)


@pytest.mark.parametrize("T,H,KVH", [(1, 2, 2), (4, 4, 2), (6, 4, 1)])
def test_fused_matches_gather_and_ref(T, H, KVH):
    B, hd, NB, bs, MAXB = 3, 32, 12, 8, 3
    lens = [13, 24, 7]
    k, v, bt, pos = _pool(B, lens, NB, bs, MAXB, KVH, hd, seed=3)
    q = _rand((B, T, H, hd), k=9)
    qp = _qpos(lens, T)
    got = paged_verify_attn_pallas(q, k, v, qp, pos, bt, 0, interpret=True)
    via_gather = gather_verify_attn(q, k, v, qp, pos, bt, 0,
                                    use_pallas=False)
    kg, vg = gather_kv_blocks(k, v, bt, 0)
    want = KR.gqa_masked_ref(q, kg, vg, qp, gather_key_positions(pos, bt))
    np.testing.assert_allclose(np.asarray(got), np.asarray(via_gather),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_ragged_tables_with_holes_and_empty_slot():
    """-1 entries anywhere in the table (trailing raggedness, interior
    holes, a fully empty slot) must contribute nothing — exactly the
    gather path's k_pos = -1 convention."""
    B, T, H, KVH, hd, NB, bs, MAXB = 4, 3, 4, 2, 32, 16, 8, 4
    lens = [30, 9, 0, 17]
    # slot 3 has an interior hole at logical block 1: its rows are simply
    # not attendable (the gather path surfaces them as k_pos = -1)
    k, v, bt, pos = _pool(B, lens, NB, bs, MAXB, KVH, hd, seed=5,
                          holes={(3, 1)})
    assert int(np.asarray(bt)[3, 1]) == -1 and int(np.asarray(bt)[3, 2]) >= 0
    q = _rand((B, T, H, hd), k=11)
    qp = _qpos(lens, T)
    got = paged_verify_attn_pallas(q, k, v, qp, pos, bt, 0, interpret=True)
    want = gather_verify_attn(q, k, v, qp, pos, bt, 0, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # the empty slot's rows are fully masked -> exact zeros on both paths
    assert np.all(np.asarray(got)[2] == 0)


@pytest.mark.parametrize("window,prefix", [(11, 0), (None, 5), (11, 5)])
def test_fused_window_and_prefix_masking(window, prefix):
    B, T, H, KVH, hd, NB, bs, MAXB = 2, 4, 4, 2, 32, 10, 8, 3
    lens = [22, 15]
    k, v, bt, pos = _pool(B, lens, NB, bs, MAXB, KVH, hd, seed=7)
    q = _rand((B, T, H, hd), k=13)
    qp = _qpos(lens, T)
    got = paged_verify_attn_pallas(q, k, v, qp, pos, bt, 0, window=window,
                                   prefix_len=prefix, interpret=True)
    want = gather_verify_attn(q, k, v, qp, pos, bt, 0, window=window,
                              prefix_len=prefix, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_fused_int8_scales_dequant_in_kernel():
    B, T, H, KVH, hd, NB, bs, MAXB = 2, 4, 4, 2, 32, 10, 8, 3
    lens = [19, 8]
    k, v, bt, pos = _pool(B, lens, NB, bs, MAXB, KVH, hd, seed=17)
    ks = jnp.max(jnp.abs(k), -1) / 127.0 + 1e-8          # [1, NB, bs, KVH]
    vs = jnp.max(jnp.abs(v), -1) / 127.0 + 1e-8
    kq = jnp.clip(jnp.round(k / ks[..., None]), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(v / vs[..., None]), -127, 127).astype(jnp.int8)
    q = _rand((B, T, H, hd), k=19)
    qp = _qpos(lens, T)
    got = paged_verify_attn_pallas(q, kq, vq, qp, pos, bt, 0,
                                   k_scale=ks, v_scale=vs, interpret=True)
    want = gather_verify_attn(q, kq, vq, qp, pos, bt, 0, k_scale=ks,
                              v_scale=vs, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_paged_verify_attn_dispatch_modes_agree():
    """The public dispatcher: forced-ref, forced-pallas (interpret on CPU),
    and the gather+Pallas-verify combination all agree."""
    B, T, H, KVH, hd, NB, bs, MAXB = 2, 3, 4, 2, 32, 8, 8, 2
    lens = [12, 10]
    k, v, bt, pos = _pool(B, lens, NB, bs, MAXB, KVH, hd, seed=23)
    q = _rand((B, T, H, hd), k=29)
    qp = _qpos(lens, T)
    ref = paged_verify_attn(q, k, v, qp, pos, bt, 0, use_pallas=False)
    fused = paged_verify_attn(q, k, v, qp, pos, bt, 0, use_pallas=True)
    gather_pallas = gather_verify_attn(q, k, v, qp, pos, bt, 0,
                                       use_pallas=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gather_pallas), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# gather fast path (MAXB == 1) and the sublane block-size fix


def test_gather_single_block_fast_path_matches_general():
    KVH, hd, NB, bs = 2, 16, 6, 8
    k = _rand((NB, bs, KVH, hd), k=31)
    v = _rand((NB, bs, KVH, hd), k=32)
    scale = jnp.abs(_rand((NB, bs, KVH), k=33)) + 0.1
    pos = jnp.where(_rand((NB, bs), k=34) > 0,
                    jnp.arange(bs, dtype=jnp.int32)[None, :], -1)
    bt1 = jnp.asarray([[3], [-1], [0]], jnp.int32)       # MAXB == 1
    kg, vg = gather_kv_blocks(k[None], v[None], bt1, 0)
    kp = gather_key_positions(pos, bt1)
    sg = gather_scales(scale[None], bt1, 0)
    assert kg.shape == (3, bs, KVH, hd) and kp.shape == (3, bs)
    safe = np.maximum(np.asarray(bt1)[:, 0], 0)
    np.testing.assert_array_equal(np.asarray(kg), np.asarray(k)[safe])
    np.testing.assert_array_equal(np.asarray(vg), np.asarray(v)[safe])
    np.testing.assert_array_equal(np.asarray(sg), np.asarray(scale)[safe])
    # the empty slot's positions are forced to -1 despite aliasing block 0
    assert np.all(np.asarray(kp)[1] == -1)
    np.testing.assert_array_equal(np.asarray(kp)[0], np.asarray(pos)[3])


def test_choose_block_k_never_degrades_to_tiny_tiles():
    for L, bk_req in [(97, 512), (97, 32), (100, 64), (8, 512), (3, 16),
                      (512, 512), (96, 16), (640, 512), (202, 512)]:
        bk, Lp = choose_block_k(L, bk_req)
        assert bk % 8 == 0 and bk >= 8, (L, bk_req, bk)
        assert Lp % bk == 0 and Lp >= L and Lp - L < bk, (L, bk_req, bk, Lp)
    # the old failure mode: prime L forced 1-row tiles; now the tail pads
    assert choose_block_k(97, 32)[0] == 32
    assert choose_block_k(512, 512) == (512, 512)        # aligned unchanged
    # a large divisor beats padding (zero-copy): 640 keeps the old bk=320,
    # and the 64-row search floor keeps 520/1000 on zero-copy divisor
    # tiles (104/200 — the old loop's 260/500 were not sublane-aligned)
    assert choose_block_k(640, 512) == (320, 640)
    assert choose_block_k(520, 512) == (104, 520)
    assert choose_block_k(1000, 512) == (200, 1000)
    assert choose_block_k(96, 16) == (16, 96)            # exact divisor
    # but a divisor below the 64-row floor is rejected in favor of
    # full-size padded tiles (the anti-degradation half of the policy)
    assert choose_block_k(136, 128) == (128, 256)        # not bk=8


@pytest.mark.parametrize("L", [97, 100, 37])
def test_verify_kernel_padded_tail_matches_ref(L):
    """Prime-ish cache lengths run with full-size padded tiles and still
    match the reference bit-for-bit on the unpadded rows."""
    from repro.kernels.spec_verify_attn import spec_verify_attn_pallas
    B, Tq, hd = 2, 4, 32
    q = _rand((B, Tq, hd), k=41)
    k = _rand((B, L, hd), k=42)
    v = _rand((B, L, hd), k=43)
    seq = L - Tq - 1
    qp = jnp.broadcast_to(jnp.arange(Tq, dtype=jnp.int32) + seq, (B, Tq))
    kp = jnp.where(jnp.arange(L) < seq + Tq, jnp.arange(L, dtype=jnp.int32), -1)
    kp = jnp.broadcast_to(kp, (B, L))
    got = spec_verify_attn_pallas(q, k, v, qp, kp, block_k=32, interpret=True)
    want = KR.spec_verify_ref(q, k, v, qp, kp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# sharded paged pools pin auto routing to the gather path


def test_sharded_paged_pool_pins_gather_unless_forced():
    """A mesh-sharded paged pool cannot run the fused kernel's prefetched
    block table through GSPMD (blocks are not shard-local), so auto routing
    (paged_fused=None) must pin the gather path — and restore auto on the
    next unsharded pool.  Forcing True is respected."""
    from repro.launch.mesh import make_serving_mesh
    tcfg = R.get_smoke_config("yi-9b")
    eng = SpecDecodeEngine(tcfg, None, max_new=8)
    mesh = make_serving_mesh(1)
    eng.init_slots(2, cache_len=32, block_size=8, mesh=mesh)
    assert eng.tcfg.paged_fused is False          # pinned for the mesh pool
    eng.init_slots(2, cache_len=32, block_size=8)
    assert eng.tcfg.paged_fused is None           # restored off-mesh
    forced = SpecDecodeEngine(tcfg, None, max_new=8, paged_fused=True)
    forced.init_slots(2, cache_len=32, block_size=8, mesh=mesh)
    assert forced.tcfg.paged_fused is True        # explicit force respected


# ---------------------------------------------------------------------------
# engine-level: the paged int8 (kv_quant) pool, fused vs gather vs solo


def test_engine_paged_kv_quant_matches_solo_both_kernels():
    """The paged pool's new int8 cache: a paged run (scale leaves injected
    block-wise, dequant in the kernel) must match the solo contiguous
    kv_quant run token-for-token on BOTH kernel paths."""
    tcfg = R.get_smoke_config("yi-9b").with_(kv_quant=True)
    d = R.get_draft_config("yi-9b")
    dcfg = dataclasses.replace(
        d, n_layers=1, d_model=64, d_ff=128, vocab_size=tcfg.vocab_size,
        dtype="float32",
        attn=dataclasses.replace(d.attn, n_heads=2, n_kv_heads=2,
                                 head_dim=32))
    rng = np.random.default_rng(3)
    p = rng.integers(0, tcfg.vocab_size, (9,)).astype(np.int32)
    outs = {}
    ref = None
    for fused in (False, True):
        eng = SpecDecodeEngine(tcfg, dcfg, max_new=12, paged_fused=fused)
        tp = eng.target.init(jax.random.PRNGKey(0))
        dp = eng.draft.init(jax.random.PRNGKey(1))
        if ref is None:
            ref, _, _ = eng.generate(tp, dp, p[None, :],
                                     np.array([9], np.int32), s=3,
                                     cache_len=64)
        state = eng.init_slots(2, cache_len=64, block_size=8)
        assert "k_scale" in state.tcache and state.tcache["k"].dtype == jnp.int8
        state = eng.prefill_into(tp, dp, state, 0, p, len(p), 64)
        for _ in range(12):
            state, _ = eng.step(tp, dp, state, 3)
            if bool(np.asarray(state.done)[0]):
                break
        outs[fused] = np.asarray(state.out)[0, :12].copy()
    np.testing.assert_array_equal(outs[False], ref[0])
    np.testing.assert_array_equal(outs[False], outs[True])


# ---------------------------------------------------------------------------
# scheduler-level: fused on vs off must be token- and trace-identical


@pytest.fixture(scope="module")
def smoke_pair():
    tcfg = R.get_smoke_config("yi-9b")
    d = R.get_draft_config("yi-9b")
    dcfg = dataclasses.replace(
        d, n_layers=1, d_model=64, d_ff=128, vocab_size=tcfg.vocab_size,
        dtype="float32",
        attn=dataclasses.replace(d.attn, n_heads=2, n_kv_heads=2,
                                 head_dim=32))
    eng = SpecDecodeEngine(tcfg, dcfg, max_new=10)
    tp = eng.target.init(jax.random.PRNGKey(0))
    dp = eng.draft.init(jax.random.PRNGKey(1))
    return tcfg, dcfg, tp, dp


def _trace(tcfg, n=5):
    rng = np.random.default_rng(11)
    reqs = []
    for rid in range(n):
        L = int(rng.integers(5, 12))
        toks = rng.integers(0, tcfg.vocab_size, (L,)).astype(np.int32)
        reqs.append(Request(rid=rid, arrival=0.0, tokens=toks, prompt_len=L,
                            max_new=int(rng.integers(4, 9))))
    return reqs


@pytest.mark.parametrize("chunked", [False, True])
def test_serve_paged_fused_token_and_trace_identical(smoke_pair, chunked):
    """A full serve_continuous_live paged run with the fused kernel on vs
    off: token- and StepTrace-identical, with (``chunked=True``) the over-
    budget prompts admitted chunk-by-chunk so the fused prefix-extension
    chunk forward is on the measured path too."""
    tcfg, dcfg, tp, dp = smoke_pair
    ctrl = lambda: AdaptiveController(lut=SpeculationLUT({1: 3, 2: 2, 4: 2}))
    runs = {}
    for fused in (False, True):
        # the backend plumb (engine.set_paged_fused before init_slots) is
        # the serving-layer entry point; the engine ctor kwarg is covered
        # by the engine-level parity below
        eng = SpecDecodeEngine(tcfg, dcfg, max_new=10)
        be = ContinuousEngineBackend(eng, tp, dp, capacity=3, cache_len=32,
                                     warm_s=[2, 3], block_size=8,
                                     collect_outputs=True, paged_fused=fused)
        assert eng.tcfg.paged_fused is fused
        policy = PrefillBudgetAdmit(token_budget=6) if chunked else None
        res = serve_continuous_live(_trace(tcfg), eng, tp, dp, ctrl(),
                                    backend=be, policy=policy)
        runs[fused] = (res, be)
    (r0, b0), (r1, b1) = runs[False], runs[True]
    t0, t1 = r0.trace, r1.trace
    assert [t.admitted for t in t0] == [t.admitted for t in t1]
    assert [t.occupancy for t in t0] == [t.occupancy for t in t1]
    assert [t.committed for t in t0] == [t.committed for t in t1]
    assert [t.preempted for t in t0] == [t.preempted for t in t1]
    assert [t.done_rids for t in t0] == [t.done_rids for t in t1]
    assert [t.chunked for t in t0] == [t.chunked for t in t1]
    if chunked:
        assert sum(len(t.chunked) for t in t0) > 0   # chunk path exercised
    assert set(b0.outputs) == set(b1.outputs) and len(b0.outputs) == 5
    for rid in b0.outputs:
        np.testing.assert_array_equal(b0.outputs[rid], b1.outputs[rid],
                                      err_msg=f"rid {rid}")
