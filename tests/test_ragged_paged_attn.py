"""Ragged fused paged attention: the real-length-grid kernel must be
BIT-identical to the dense fused kernel (and allclose to the gather
reference) across extreme raggedness patterns — one max-length slot among
1-block slots, all-dead rows, interior table holes, pending
(mid-chunked-prefill) slots, int8 pools, every manual-DMA depth — plus the
launch-planning arithmetic (kernels/tuning.py), the autotune-cache lookup,
and the mixed verify+chunk launch: ``step_with_chunk`` equals
``flush_chunk`` + ``step`` state-for-state on the interpret-mode ragged
kernel, and ``serve_continuous_live(mixed_launch=True)`` is token- and
StepTrace-identical to the unfused run, chunked admission and preemption
included.  Fast tier; citier ``kernels`` runs the kernel-parity subset."""
import dataclasses
import json
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry as R
from repro.core.adaptive import AdaptiveController, SpeculationLUT
from repro.core.spec_decode import SpecDecodeEngine
from repro.kernels.paged import gather_verify_attn, paged_verify_attn
from repro.kernels.paged_verify_attn import (paged_verify_attn_pallas,
                                             ragged_paged_verify_attn_pallas)
from repro.kernels.tuning import (DEFAULT_CONFIG, RaggedConfig, cell_key,
                                  clear_config_cache, dead_tile_fraction,
                                  grid_steps_dense, grid_steps_ragged,
                                  host_cu_blocks, lookup_config)
from repro.serving.scheduler import (ContinuousEngineBackend,
                                     PrefillBudgetAdmit,
                                     serve_continuous_live)
from repro.serving.traffic import TrafficPhase, make_requests

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, k=0):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape).astype(dtype)


def _pool(B, lens, NB, bs, MAXB, KVH, hd, seed=0, holes=()):
    """Ragged paged pool (same construction as test_paged_fused_kernel):
    block tables with optional interior -1 holes, pool pos map, and k/v
    pools whose unowned blocks hold garbage.  k/v hold one layer,
    ``[1, NB, bs, KVH, hd]``: the kernels read it at layer 0."""
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


# raggedness matrix: (lens, MAXB, bs, NB, holes) per pattern.  "extreme" is
# the worst case the dense grid pays for: one near-max slot among 1-block
# slots plus an empty (pending / mid-chunked-prefill: device table row all
# -1) slot; "all_dead" has no live query row at all.
_PATTERNS = {
    "basic": ([13, 24, 7], 3, 8, 14, ()),
    "extreme": ([115, 3, 5, 2, 7, 0], 15, 8, 24, ()),
    "all_dead": ([0, 0, 0], 3, 8, 6, ()),
    "holes": ([22, 15, 9], 3, 8, 12, ((0, 1), (2, 0))),
}


def _case(name, T=3, H=4, KVH=2, hd=32):
    lens, MAXB, bs, NB, holes = _PATTERNS[name]
    B = len(lens)
    k, v, bt, pos = _pool(B, lens, NB, bs, MAXB, KVH, hd,
                          seed=len(name), holes=holes)
    q = _rand((B, T, H, hd), k=29 + len(name))
    qp = _qpos(lens, T)
    cu = jnp.asarray(host_cu_blocks(np.asarray(bt)))
    return q, k, v, qp, pos, bt, cu


# ---------------------------------------------------------------------------
# kernel-level parity (interpret mode executes the real kernel body)


@pytest.mark.parametrize("pattern", sorted(_PATTERNS))
def test_ragged_bit_identical_to_dense_fused(pattern):
    """The ragged grid visits a (sub)set of the dense grid's live tiles in
    the same per-slot order, so its output must be BIT-identical to the
    dense fused kernel — and allclose to the gather reference — on every
    raggedness pattern."""
    q, k, v, qp, pos, bt, cu = _case(pattern)
    ragged = ragged_paged_verify_attn_pallas(q, k, v, qp, pos, bt, 0, cu,
                                             interpret=True)
    dense = paged_verify_attn_pallas(q, k, v, qp, pos, bt, 0, interpret=True)
    np.testing.assert_array_equal(np.asarray(ragged), np.asarray(dense))
    want = np.asarray(gather_verify_attn(q, k, v, qp, pos, bt, 0,
                                         use_pallas=False))
    got = np.asarray(ragged)
    live = np.asarray(qp) >= 0                    # dead rows: ragged/dense
    np.testing.assert_allclose(got[live], want[live],  # give 0, gather NaN
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("nbuf", [2, 3, 4])
def test_manual_dma_depths_bit_identical(nbuf):
    """Every manual-DMA ring depth must reproduce the auto-pipelined
    (num_buffers=0) output bit-for-bit on the extreme pattern — buffering
    is a schedule, never a numeric."""
    q, k, v, qp, pos, bt, cu = _case("extreme")
    base = ragged_paged_verify_attn_pallas(q, k, v, qp, pos, bt, 0, cu,
                                           interpret=True)
    dma = ragged_paged_verify_attn_pallas(q, k, v, qp, pos, bt, 0, cu,
                                          num_buffers=nbuf, interpret=True)
    np.testing.assert_array_equal(np.asarray(dma), np.asarray(base))


@pytest.mark.parametrize("nbuf", [0, 2])
def test_ragged_int8_window_prefix(nbuf):
    """int8 pool scales (dequant in-kernel, including through the manual-DMA
    scale stream) plus sliding-window and bidirectional-prefix masking."""
    q, k, v, qp, pos, bt, cu = _case("holes", T=4)
    ks = jnp.max(jnp.abs(k), -1) / 127.0 + 1e-8          # [1, NB, bs, KVH]
    vs = jnp.max(jnp.abs(v), -1) / 127.0 + 1e-8
    kq = jnp.clip(jnp.round(k / ks[..., None]), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(v / vs[..., None]), -127, 127).astype(jnp.int8)
    for kw in ({}, {"window": 10, "prefix_len": 5}):
        got = ragged_paged_verify_attn_pallas(
            q, kq, vq, qp, pos, bt, 0, cu, k_scale=ks, v_scale=vs,
            num_buffers=nbuf, interpret=True, **kw)
        dense = paged_verify_attn_pallas(q, kq, vq, qp, pos, bt, 0,
                                         k_scale=ks, v_scale=vs,
                                         interpret=True, **kw)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(dense))
        want = gather_verify_attn(q, kq, vq, qp, pos, bt, 0, k_scale=ks,
                                  v_scale=vs, use_pallas=False, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_dispatcher_routes_ragged_on_cu_blocks():
    """paged_verify_attn with cu_blocks + forced pallas runs the ragged
    kernel (same numbers as calling it directly); without cu_blocks the
    dense kernel answers; forced-ref ignores cu_blocks entirely."""
    q, k, v, qp, pos, bt, cu = _case("basic")
    via_dispatch = paged_verify_attn(q, k, v, qp, pos, bt, 0,
                                     use_pallas=True, cu_blocks=cu,
                                     config=RaggedConfig(num_buffers=2))
    direct = ragged_paged_verify_attn_pallas(q, k, v, qp, pos, bt, 0, cu,
                                             num_buffers=2, interpret=True)
    np.testing.assert_array_equal(np.asarray(via_dispatch),
                                  np.asarray(direct))
    ref = paged_verify_attn(q, k, v, qp, pos, bt, 0, use_pallas=False,
                            cu_blocks=cu)
    np.testing.assert_allclose(np.asarray(via_dispatch), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the layer axis: the model's layer scan hands every kernel the whole
# [nL, NB, ...] pool and the layer index


N_LAYERS = 3


def _layered_case(quant):
    """The "holes" case over an N_LAYERS-layer pool with distinct data in
    every layer: bf16 tiles, or int8 tiles with their dequant scales."""
    q, k, _, qp, pos, bt, cu = _case("holes", T=4)
    shape = (N_LAYERS,) + k.shape[1:]
    k, v = _rand(shape, k=101), _rand(shape, k=102)
    if not quant:
        bf = jnp.bfloat16
        return q.astype(bf), k.astype(bf), v.astype(bf), None, None, \
            qp, pos, bt, cu
    ks = jnp.max(jnp.abs(k), -1) / 127.0 + 1e-8          # [nL, NB, bs, KVH]
    vs = jnp.max(jnp.abs(v), -1) / 127.0 + 1e-8
    kq = jnp.clip(jnp.round(k / ks[..., None]), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(v / vs[..., None]), -127, 127).astype(jnp.int8)
    return q, kq, vq, ks, vs, qp, pos, bt, cu


def _attend(kernel, q, k, v, qp, pos, bt, layer, cu, ks, vs):
    kw = dict(k_scale=ks, v_scale=vs)
    if kernel == "gather":
        return gather_verify_attn(q, k, v, qp, pos, bt, layer,
                                  use_pallas=False, **kw)
    if kernel == "dense":
        return paged_verify_attn_pallas(q, k, v, qp, pos, bt, layer,
                                        interpret=True, **kw)
    nbuf = {"ragged": 0, "dma2": 2, "dma4": 4}[kernel]
    return ragged_paged_verify_attn_pallas(q, k, v, qp, pos, bt, layer, cu,
                                           num_buffers=nbuf, interpret=True,
                                           **kw)


@pytest.mark.parametrize("layer", [0, N_LAYERS // 2, N_LAYERS - 1])
@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kernel",
                         ["ragged", "dma2", "dma4", "dense", "gather"])
def test_layer_of_pool_equals_that_layer_alone(kernel, dtype, layer):
    """Every paged attention path reading ``layer`` of a multi-layer pool
    (the index a traced int32, as inside the layer scan) is bit-identical
    to the same path on that layer's pool alone, read at layer 0.  The
    layers hold distinct data, so reading any other layer would differ."""
    q, k, v, ks, vs, qp, pos, bt, cu = _layered_case(dtype == "int8")
    got = _attend(kernel, q, k, v, qp, pos, bt, jnp.int32(layer), cu, ks, vs)
    alone = lambda a: None if a is None else a[layer][None]
    want = _attend(kernel, q, alone(k), alone(v), qp, pos, bt, 0, cu,
                   alone(ks), alone(vs))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# launch planning: grid arithmetic + autotune-cache lookup


def test_grid_step_accounting():
    tables = np.array([[3, 7, -1, -1],      # 2 live
                       [-1, -1, -1, -1],    # empty slot still gets 1 step
                       [1, 2, 5, 9]])       # full
    cu = host_cu_blocks(tables)
    np.testing.assert_array_equal(cu, [0, 2, 3, 7])
    assert grid_steps_ragged(tables) == 7
    assert grid_steps_dense(tables) == 12
    assert dead_tile_fraction(tables) == pytest.approx(5 / 12)
    # interior holes count live entries, not prefix length
    holey = np.array([[4, -1, 8]])
    np.testing.assert_array_equal(host_cu_blocks(holey), [0, 2])


def test_lookup_config_exact_nearest_default(tmp_path):
    path = str(tmp_path / "bench.json")
    clear_config_cache()
    assert lookup_config(4, 4, 8, path=path) == DEFAULT_CONFIG  # no file
    table = {
        # a table applies only on the device kind it was timed on
        "meta": {"device_kind": jax.devices()[0].device_kind},
        "autotune": {
            cell_key(4, 4, 8): {"config": {"num_buffers": 2,
                                           "vmem_limit_bytes": None}},
            cell_key(8, 4, 16): {"config": {"num_buffers": 4,
                                            "vmem_limit_bytes": 33554432}},
        }
    }
    with open(path, "w") as f:
        json.dump(table, f)
    clear_config_cache()
    assert lookup_config(4, 4, 8, path=path) == RaggedConfig(num_buffers=2)
    # nearest-by-log-distance: (7, 4, 14) is closest to the B8/MAXB16 cell
    assert lookup_config(7, 4, 14, path=path) == RaggedConfig(
        num_buffers=4, vmem_limit_bytes=32 << 20)
    clear_config_cache()


@pytest.mark.parametrize("meta", [{"device_kind": "TPU v5 lite"}, {}],
                         ids=["other-kind", "no-kind"])
def test_lookup_config_ignores_table_of_other_device(tmp_path, meta):
    """Winners timed on one device kind (or on an unrecorded one) never
    steer launches on another: the lookup falls back to the default."""
    path = str(tmp_path / "bench.json")
    with open(path, "w") as f:
        json.dump({"meta": meta, "autotune": {cell_key(4, 4, 8): {
            "config": {"num_buffers": 2, "vmem_limit_bytes": None}}}}, f)
    clear_config_cache()
    assert lookup_config(4, 4, 8, path=path) == DEFAULT_CONFIG
    clear_config_cache()


def test_committed_autotune_table_is_tagged_with_its_device():
    """The committed table was timed in interpret mode on the CPU and says
    so, so a TPU process never picks up its launch knobs."""
    from repro.kernels.tuning import RESULTS_PATH
    with open(RESULTS_PATH, encoding="utf-8") as f:
        meta = json.load(f)["meta"]
    assert meta["device_kind"] == "cpu" and meta["execution"] == "interpret"


# ---------------------------------------------------------------------------
# the mixed verify+chunk launch


CACHE_LEN = 96
BLOCK = 8


@pytest.fixture(scope="module")
def engine():
    tcfg = R.get_smoke_config("yi-9b")
    d = R.get_draft_config("yi-9b")
    dcfg = dataclasses.replace(
        d, n_layers=1, d_model=64, d_ff=128, vocab_size=tcfg.vocab_size,
        dtype="float32",
        attn=dataclasses.replace(d.attn, n_heads=2, n_kv_heads=2,
                                 head_dim=32))
    eng = SpecDecodeEngine(tcfg, dcfg, max_new=24)
    tp = eng.target.init(jax.random.PRNGKey(0))
    dp = eng.draft.init(jax.random.PRNGKey(1))
    return eng, tp, dp, tcfg


def _mixed_setup(eng, tp, dp, tcfg):
    """Two live decode slots plus one deferred (pending) prefill chunk."""
    rng = np.random.default_rng(5)
    p0 = rng.integers(0, tcfg.vocab_size, (9,)).astype(np.int32)
    p1 = rng.integers(0, tcfg.vocab_size, (13,)).astype(np.int32)
    long_p = rng.integers(0, tcfg.vocab_size, (22,)).astype(np.int32)
    state = eng.init_slots(3, cache_len=CACHE_LEN, block_size=BLOCK)
    state = eng.prefill_into(tp, dp, state, 0, p0, len(p0), CACHE_LEN)
    state = eng.prefill_into(tp, dp, state, 1, p1, len(p1), CACHE_LEN)
    toks = np.ones((8,), np.int32)
    toks[:8] = long_p[:8]
    state, chunk = eng.prefill_chunk_into(tp, dp, state, 2, toks, 0, 8,
                                          len(long_p), defer=True)
    return state, chunk


def test_step_with_chunk_matches_flush_then_step(engine):
    """On the interpret-mode ragged kernel, the ONE mixed verify+chunk
    launch must leave bit-identical row state and step stats to the
    two-launch order (standalone chunk dispatch, then the plain step)."""
    eng, tp, dp, tcfg = engine
    eng.set_paged_fused(True)        # interpret-mode ragged kernel on CPU
    try:
        state_a, chunk_a = _mixed_setup(eng, tp, dp, tcfg)
        state_a = eng.flush_chunk(tp, dp, state_a, chunk_a)
        state_a, st_a = eng.step(tp, dp, state_a, 2)

        state_b, chunk_b = _mixed_setup(eng, tp, dp, tcfg)
        state_b, st_b = eng.step_with_chunk(tp, dp, state_b, 2, chunk_b)
    finally:
        eng.set_paged_fused(None)

    np.testing.assert_array_equal(st_a.accepted, st_b.accepted)
    np.testing.assert_array_equal(st_a.committed, st_b.committed)
    for name in ("seq_lens", "last2", "out", "n_generated", "done"):
        np.testing.assert_array_equal(
            np.asarray(getattr(state_a, name)),
            np.asarray(getattr(state_b, name)), err_msg=name)
    for key in state_a.tcache:
        np.testing.assert_array_equal(np.asarray(state_a.tcache[key]),
                                      np.asarray(state_b.tcache[key]),
                                      err_msg=f"tcache[{key}]")
    for key in state_a.dcache:
        np.testing.assert_array_equal(np.asarray(state_a.dcache[key]),
                                      np.asarray(state_b.dcache[key]),
                                      err_msg=f"dcache[{key}]")


def _trace(tcfg, n=8, seed=11):
    reqs = make_requests(n, [TrafficPhase(0.0005, 1.0, float("inf"))],
                         tcfg.vocab_size, seed=seed, max_new=16)
    rng = np.random.default_rng(3)
    for i, r in enumerate(reqs):
        # arrivals pinned to 0: the schedule must not depend on wall time,
        # or the faster mixed run would admit on a different iteration
        r.arrival = 0.0
        r.max_new = int(rng.integers(10, 17))
        if i % 2 == 0:
            L = int(rng.integers(24, 40))
            r.tokens = rng.integers(0, tcfg.vocab_size, (L,)).astype(np.int32)
            r.prompt_len = L
    return reqs


def _serve(engine, mixed, num_blocks):
    eng, tp, dp, tcfg = engine
    backend = ContinuousEngineBackend(eng, tp, dp, capacity=4,
                                      cache_len=CACHE_LEN, block_size=BLOCK,
                                      num_blocks=num_blocks,
                                      collect_outputs=True, warm_s=(2, 3, 4),
                                      mixed_launch=mixed)
    ctrl = AdaptiveController(lut=SpeculationLUT({1: 4, 2: 3, 4: 2}))
    res = serve_continuous_live(_trace(tcfg), eng, tp, dp, ctrl,
                                backend=backend,
                                policy=PrefillBudgetAdmit(token_budget=16,
                                                          chunk=8))
    return backend, res


@pytest.mark.parametrize("num_blocks,needs_preempt",
                         [(40, False), (20, True)],
                         ids=["chunked", "chunked+preempt"])
def test_serve_mixed_launch_token_and_trace_parity(engine, num_blocks,
                                                   needs_preempt):
    """serve_continuous_live with mixed_launch on vs off: token outputs and
    every non-duration StepTrace field identical, across chunked admission
    and (undersized pool) preemption."""
    b_off, r_off = _serve(engine, False, num_blocks)
    b_on, r_on = _serve(engine, True, num_blocks)
    per_rid = Counter(rid for t in r_on.trace for rid, _ in t.chunked)
    assert per_rid and max(per_rid.values()) >= 3
    if needs_preempt:
        assert any(t.preempted for t in r_on.trace), \
            "pool was not under pressure; the preemption leg lost its bite"
    assert set(b_off.outputs) == set(b_on.outputs)
    for rid in b_off.outputs:
        np.testing.assert_array_equal(b_off.outputs[rid], b_on.outputs[rid],
                                      err_msg=f"rid {rid}")
    assert len(r_off.trace) == len(r_on.trace)
    for t0, t1 in zip(r_off.trace, r_on.trace):
        for f in ("occupancy", "s", "rids", "committed", "admitted",
                  "preempted", "done_rids", "chunked", "cache_hits"):
            assert getattr(t0, f) == getattr(t1, f), f


def test_mixed_launch_needs_paged_pool(engine):
    eng, tp, dp, _ = engine
    with pytest.raises(ValueError, match="paged KV pool"):
        ContinuousEngineBackend(eng, tp, dp, capacity=2,
                                cache_len=CACHE_LEN, mixed_launch=True)
