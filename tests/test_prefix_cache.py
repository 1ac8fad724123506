"""Copy-on-write prefix cache over the paged BlockPool.

Correctness backbone of cross-request prefix sharing
(serving/prefix_cache.py + the refcount/COW extensions of serving/slots.py):

  * unit tests of the refcount lifecycle, the radix index, LRU eviction,
    and the sharing-aware fragmentation/occupancy accounting;
  * a property-based campaign driving hundreds of random interleavings of
    admit / share / COW-write / insert / retire / evict against a shadow
    reference model — no double-free, no leaked block, no in-place write
    to a shared block, radix round-trips (fast; pure host accounting);
  * end-to-end parity on the live engine: shared-prefix runs are token-
    AND StepTrace-identical to cold runs (fused kernel on/off), chunked
    admission of a partially-cached prompt, preempt-then-restore via the
    surviving shared prefix, and sim-vs-live replay with the cache on;
  * eviction under pressure on an undersized pool.
"""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # fallback shim, see tests/_hypothesis_fallback.py
    from _hypothesis_fallback import given, settings, st

from repro.core.adaptive import AdaptiveController, SpeculationLUT, fixed_controller
from repro.core.analytical import LatencyModel
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import Request
from repro.serving.scheduler import (ContinuousEngineBackend,
                                     ContinuousScheduler, ImmediateAdmit,
                                     PrefillBudgetAdmit, SimStepBackend,
                                     replay_sources)
from repro.serving.slots import BlockPool, PagedKVTables

BS = 4                                   # block size used by the host tests


def _kv(num_blocks=24, capacity=4, max_blocks=8, cache=True):
    kv = PagedKVTables(num_blocks, BS, capacity, max_blocks)
    pc = None
    if cache:
        pc = PrefixCache(kv.pool)
        kv.attach_cache(pc)
    return kv, pc


# ---------------------------------------------------------------------------
# refcount lifecycle (BlockPool)


def test_refcount_lifecycle():
    pool = BlockPool(6, BS)
    a, b = pool.alloc(2)
    assert pool.refcount(a) == pool.refcount(b) == 1
    pool.incref(a)
    assert pool.refcount(a) == 2 and pool.shared_count == 1
    assert pool.exclusive_count == 1
    assert pool.decref(a) is False       # still held once
    assert pool.decref(a) is True        # now actually freed
    assert pool.refcount(a) == 0 and a in pool._free
    pool.check_invariants()


def test_double_free_raises():
    pool = BlockPool(4, BS)
    (a,) = pool.alloc(1)
    pool.decref(a)
    with pytest.raises(RuntimeError):
        pool.decref(a)
    with pytest.raises(RuntimeError):
        pool.free([a])
    with pytest.raises(RuntimeError):
        pool.incref(a)                   # incref of a free block is a bug too


def test_bulk_free_returns_only_actually_freed():
    pool = BlockPool(6, BS)
    a, b, c = pool.alloc(3)
    pool.incref(b)                       # b shared with a second owner
    freed = pool.free([a, b, c])
    assert freed == [a, c]               # b survives at refcount 1
    assert pool.refcount(b) == 1
    assert pool.free([b]) == [b]
    pool.check_invariants()
    assert pool.free_count == 6


# ---------------------------------------------------------------------------
# radix index (PrefixCache)


def _toks(*vals):
    return np.asarray(vals, np.int32)


def test_radix_insert_match_roundtrip():
    kv, pc = _kv()
    kv.prefill(0, 3 * BS)
    tokens = np.arange(3 * BS, dtype=np.int32)
    added = pc.insert(tokens, kv.table(0))
    assert added == 3 and pc.size == 3
    assert pc.match(tokens) == kv.table(0)[:3]
    # partial-block tails never match (block granularity)
    assert pc.match(tokens[:2 * BS + 1]) == kv.table(0)[:2]
    # diverging tokens stop the walk at the shared prefix
    div = tokens.copy()
    div[2 * BS] += 1
    assert pc.match(div) == kv.table(0)[:2]
    assert pc.match(np.arange(100, 100 + BS, dtype=np.int32)) == []


def test_radix_first_writer_wins_and_rejects_double_index():
    kv, pc = _kv()
    tokens = np.arange(2 * BS, dtype=np.int32)
    kv.prefill(0, 2 * BS)
    kv.prefill(1, 2 * BS)
    pc.insert(tokens, kv.table(0))
    # same prefix from another slot: existing nodes keep the first blocks
    assert pc.insert(tokens, kv.table(1)) == 0
    assert pc.match(tokens) == kv.table(0)[:2]
    # a block id cannot back two different trie nodes
    with pytest.raises(RuntimeError):
        pc.insert(np.arange(50, 50 + BS, dtype=np.int32), [kv.table(0)[0]])
    with pytest.raises(ValueError):
        pc.insert(tokens, kv.table(0)[:1])   # fewer blocks than token blocks


def test_reclaimable_counts_only_what_reclaim_can_free():
    """A slot that copied-on-write a shared parent block but still holds
    its child leaves the parent cache-only yet unevictable (eviction is
    leaf-first): admission feasibility must not count it."""
    kv, pc = _kv(num_blocks=4, capacity=2, max_blocks=3)
    tokens = np.arange(0, 2 * BS, dtype=np.int32)
    kv.prefill(0, 2 * BS)
    pc.insert(tokens, kv.table(0))
    kv.release(0)
    kv.attach(1, pc.lock(tokens), 2 * BS)
    pc.unlock(kv.table(1))
    parent, child = pc.match(tokens)
    kv.cow_for_range(1, 0, 1)                # slot 1 now owns a copy of 0
    assert kv.pool.refcount(parent) == 1 and kv.pool.refcount(child) == 2
    assert pc.reclaimable() == 0 == len(pc.reclaim(10))
    # what is counted is exactly what an allocation can obtain
    assert kv.available_blocks == kv.pool.free_count == 1


def test_lock_pins_against_reclaim():
    kv, pc = _kv()
    t_a = np.arange(0, 2 * BS, dtype=np.int32)
    t_b = np.arange(100, 100 + BS, dtype=np.int32)
    kv.prefill(0, 2 * BS)
    kv.prefill(1, BS)
    pc.insert(t_a, kv.table(0))
    pc.insert(t_b, kv.table(1))
    b_unlocked = kv.table(1)[0]
    kv.release(0), kv.release(1)         # cache is now the only owner
    assert pc.reclaimable() == 3
    locked = pc.lock(t_a)
    assert len(locked) == 2
    # locked blocks are not evictable — only t_b's block goes
    evicted = pc.reclaim(10)
    assert evicted == [b_unlocked]
    assert set(evicted).isdisjoint(locked)
    assert pc.size == 2
    pc.unlock(locked)
    assert pc.reclaim(10) != [] and pc.size == 0
    kv.pool.check_invariants()
    assert kv.pool.free_count == kv.num_blocks


def test_reclaim_is_lru_and_leaf_first():
    kv, pc = _kv()
    t_a = np.arange(0, 3 * BS, dtype=np.int32)      # chain of 3
    kv.prefill(0, 3 * BS)
    pc.insert(t_a, kv.table(0))
    blocks = list(kv.table(0))
    kv.release(0)
    # the deepest node is the only leaf: eviction drains leaf-first even
    # though the root of the chain is older
    assert pc.reclaim(1) == [blocks[2]]
    assert pc.reclaim(1) == [blocks[1]]
    assert pc.reclaim(1) == [blocks[0]]
    # LRU across independent entries: older last_used goes first
    kv.prefill(0, BS)
    pc.insert(np.arange(100, 100 + BS, dtype=np.int32), kv.table(0))
    old = kv.table(0)[0]
    kv.release(0)
    kv.prefill(1, BS)
    pc.insert(np.arange(200, 200 + BS, dtype=np.int32), kv.table(1))
    new = kv.table(1)[0]
    kv.release(1)
    assert pc.reclaim(1) == [old]
    assert pc.reclaim(1) == [new]


# ---------------------------------------------------------------------------
# attach / COW over the slot tables


def test_attach_shares_blocks_and_cow_isolates_writes():
    kv, pc = _kv()
    tokens = np.arange(2 * BS, dtype=np.int32)
    kv.prefill(0, 2 * BS + 2)            # donor: 2 full blocks + a tail
    pc.insert(tokens, kv.table(0))
    locked = pc.lock(tokens)
    kv.attach(1, locked, 2 * BS)
    pc.unlock(locked)
    assert kv.table(1) == kv.table(0)[:2]
    assert all(kv.pool.refcount(b) == 3 for b in locked)  # donor+cache+slot1
    assert kv.shared_blocks == 2
    # slot 1 writes into the shared range: COW swaps in fresh copies
    pairs = kv.cow_for_range(1, 0, 2 * BS)
    assert [src for src, _ in pairs] == locked
    assert kv.table(1) != kv.table(0)[:2]
    assert all(kv.pool.refcount(dst) == 1 for _, dst in pairs)
    assert all(kv.pool.refcount(src) == 2 for src, _ in pairs)
    # donor's own table is untouched and still cache-indexed
    assert pc.match(tokens) == kv.table(0)[:2]
    kv.release(0), kv.release(1)
    kv.pool.check_invariants()


def test_attach_rejects_bad_geometry():
    kv, pc = _kv()
    kv.prefill(0, BS)
    pc.insert(np.arange(BS, dtype=np.int32), kv.table(0))
    locked = pc.lock(np.arange(BS, dtype=np.int32))
    kv.prefill(1, 2)
    with pytest.raises(RuntimeError):
        kv.attach(1, locked, BS)         # non-empty slot
    with pytest.raises(ValueError):
        kv.attach(2, locked, BS + 1)     # tokens not block-aligned
    pc.unlock(locked)


def test_alloc_reclaims_cache_blocks_on_demand():
    kv, pc = _kv(num_blocks=4, capacity=2, max_blocks=4)
    kv.prefill(0, 3 * BS)
    pc.insert(np.arange(3 * BS, dtype=np.int32), kv.table(0))
    kv.release(0)                        # 3 blocks cache-only, 1 free
    assert kv.free_blocks == 1 and kv.available_blocks == 4
    kv.prefill(1, 3 * BS)                # needs 3: evicts 2 from the cache
    assert kv.evicted_pending and kv.evicted_total == 2
    assert pc.size == 1
    kv.pool.check_invariants()


# ---------------------------------------------------------------------------
# sharing-aware fragmentation / occupancy accounting (satellite bugfix)


def test_fragmentation_counts_reclaimable_blocks():
    kv, pc = _kv(num_blocks=8, capacity=4, max_blocks=4)
    kv.prefill(0, BS)                    # block 0
    kv.prefill(1, BS)                    # block 1
    kv.prefill(2, BS)                    # block 2
    pc.insert(np.arange(100, 100 + BS, dtype=np.int32), kv.table(1))
    kv.release(1)                        # block 1: cache-only (reclaimable)
    # free list is [3..7]; naive free-list-only accounting would report the
    # 5-run as largest over 5 free => 0.0 fragmentation, hiding that block
    # 1 splits the *reclaimable* space. Sharing-aware accounting scans
    # free ∪ reclaimable = {1,3,4,5,6,7}: largest run 5 of 6.
    assert kv.available_blocks == 6
    assert kv.fragmentation == pytest.approx(1 - 5 / 6)
    # a cache-held block that is also slot-shared is NOT reclaimable and
    # must not count as available space
    locked = pc.lock(np.arange(100, 100 + BS, dtype=np.int32))
    assert kv.available_blocks == 5
    assert kv.fragmentation == pytest.approx(0.0)
    pc.unlock(locked)


def test_shared_vs_exclusive_gauges():
    kv, pc = _kv()
    kv.prefill(0, 2 * BS)
    tokens = np.arange(2 * BS, dtype=np.int32)
    pc.insert(tokens, kv.table(0))
    assert kv.shared_blocks == 2         # slot 0 + cache
    assert kv.cached_blocks == 2
    locked = pc.lock(tokens)
    kv.attach(1, locked, 2 * BS)
    pc.unlock(locked)
    assert kv.shared_blocks == 2 and kv.pool.exclusive_count == 0
    kv.release(0), kv.release(1)
    assert kv.shared_blocks == 0 and kv.cached_blocks == 2
    assert kv.pool.exclusive_count == 2  # cache is now the only owner


# ---------------------------------------------------------------------------
# property-based campaign: random interleavings vs a shadow reference model


class _Machine:
    """Drives PagedKVTables + PrefixCache with randomized operations and
    checks the standing invariants against a shadow model after each one.

    Shadow model: the expected refcount of every block is (number of slot
    tables containing it) + (1 if the cache indexes it).  No block leaks:
    blocks with expected refcount 0 are exactly the free list.
    """

    PREFIXES = 3                          # shared system-prompt vocabulary

    def __init__(self, num_blocks=16, capacity=4, max_blocks=6):
        self.kv, self.pc = _kv(num_blocks, capacity, max_blocks)
        self.capacity = capacity
        self.max_rows = max_blocks * BS
        self.slots = {}                   # slot -> (tokens, tainted)
        self.rid = 0

    # -- op helpers --------------------------------------------------------

    def _prompt(self, seed):
        rng = np.random.default_rng(seed)
        pfx = int(rng.integers(self.PREFIXES))
        n_pre = int(rng.integers(1, 3))          # 1-2 shared blocks
        tail = rng.integers(0, 5)
        sys = np.arange(1000 * pfx, 1000 * pfx + n_pre * BS, dtype=np.int32)
        tl = rng.integers(0, 30, (int(tail),)).astype(np.int32) + 5000
        return np.concatenate([sys, tl])

    def admit(self, seed):
        free = [s for s in range(self.capacity) if s not in self.slots]
        if not free:
            return
        slot = free[0]
        prompt = self._prompt(seed)
        total = len(prompt) + 1                  # +1: the first decode row
        locked = self.pc.lock(prompt)
        P = len(locked) * BS
        need = (self.kv.blocks_for(total) - P // BS
                + (1 if P == total else 0))
        if need > self.kv.available_blocks:
            self.pc.unlock(locked)               # admission abort
            return
        if P:
            self.kv.attach(slot, locked, P)
            self.pc.unlock(locked)
            self.kv.ensure(slot, total)
            self.kv.commit(slot, total - P)
        else:
            self.pc.unlock(locked)
            self.kv.prefill(slot, total)
        self.kv.evicted_pending.clear()
        self.slots[slot] = [prompt, False]

    def write(self, seed):
        """COW-write a random row range of a random slot.  The standing
        invariant: after cow_for_range, every block covering the range is
        exclusively owned — an in-place write would have been illegal on
        any block the cow pass had to copy."""
        if not self.slots:
            return
        rng = np.random.default_rng(seed)
        slot = list(self.slots)[int(rng.integers(len(self.slots)))]
        n = self.kv.tokens(slot)
        lo = int(rng.integers(n))
        hi = int(rng.integers(lo, n)) + 1
        covered = self.kv.table(slot)[lo // BS:self.kv.blocks_for(hi)]
        n_copies = sum(self.kv.pool.refcount(b) > 1 for b in covered)
        if n_copies > self.kv.available_blocks:
            # a real scheduler preempts before COW can exhaust the pool
            # (admission reserves the copy block up front)
            return
        shared_before = [b for b in self.kv.table(slot)[lo // BS:]
                        if self.kv.pool.refcount(b) > 1]
        pairs = self.kv.cow_for_range(slot, lo, hi)
        self.kv.evicted_pending.clear()
        for bi in range(lo // BS, self.kv.blocks_for(hi)):
            b = self.kv.table(slot)[bi]
            others = sum(b in self.kv.table(s) for s in self.slots
                         if s != slot)
            assert self.kv.pool.refcount(b) == 1 + others + (
                b in self.pc._blocks) and others == 0 and \
                b not in self.pc._blocks, \
                f"post-COW block {b} still shared (refs " \
                f"{self.kv.pool.refcount(b)})"
        if pairs and shared_before:
            # sources survive the copy (cache/donor still reference them)
            assert all(self.kv.pool.refcount(src) >= 1 for src, _ in pairs)
        if lo // BS < len(self.slots[slot][0]) // BS:
            # the write touched a full-prompt block: its content no longer
            # matches the prompt tokens, so this slot must never insert
            self.slots[slot][1] = True

    def insert(self, seed):
        if not self.slots:
            return
        rng = np.random.default_rng(seed)
        slot = list(self.slots)[int(rng.integers(len(self.slots)))]
        prompt, tainted = self.slots[slot]
        if tainted:                              # blocks no longer hold prompt
            return
        # full prompt blocks only — the partial tail block (which also
        # holds the decode row) is never indexed
        n_ins = len(prompt) // BS
        if not n_ins:
            return
        self.pc.insert(prompt[:n_ins * BS], self.kv.table(slot)[:n_ins])
        # round-trip: the inserted prefix is immediately matchable
        got = self.pc.match(prompt[:n_ins * BS])
        assert len(got) == n_ins

    def retire(self, seed):
        if not self.slots:
            return
        rng = np.random.default_rng(seed)
        slot = list(self.slots)[int(rng.integers(len(self.slots)))]
        freed = self.kv.release(slot)
        for b in freed:
            assert self.kv.pool.refcount(b) == 0
        del self.slots[slot]

    def evict(self, seed):
        rng = np.random.default_rng(seed)
        before = self.pc.size
        evicted = self.pc.reclaim(int(rng.integers(1, 4)))
        assert self.pc.size == before - len(evicted)
        for b in evicted:
            assert self.kv.pool.refcount(b) == 0
            assert b not in self.pc._blocks

    def lock_cycle(self, seed):
        """Lock a prefix, apply reclaim pressure, verify the locked blocks
        survive, then release the lock (eviction-races-admission)."""
        prompt = self._prompt(seed)
        locked = self.pc.lock(prompt)
        evicted = self.pc.reclaim(2)
        assert set(evicted).isdisjoint(locked)
        for b in locked:                         # still valid to attach
            assert self.kv.pool.refcount(b) >= 1
        self.pc.unlock(locked)

    OPS = (admit, write, insert, retire, evict, lock_cycle)

    # -- invariants --------------------------------------------------------

    def check(self):
        pool = self.kv.pool
        pool.check_invariants()                  # partition + free-list shape
        expected = [0] * self.kv.num_blocks
        for slot in self.slots:
            for b in self.kv.table(slot):
                expected[b] += 1
        for b in self.pc._blocks:
            expected[b] += 1
        for b in range(self.kv.num_blocks):
            assert pool.refcount(b) == expected[b], \
                f"block {b}: refcount {pool.refcount(b)} != " \
                f"shadow {expected[b]} (leak or double-count)"
        free = {b for b in range(self.kv.num_blocks) if expected[b] == 0}
        assert set(pool._free) == free, "free list != zero-ref blocks"


@settings(max_examples=500)
@given(st.lists(st.tuples(st.integers(0, len(_Machine.OPS) - 1),
                          st.integers(0, 10**6)),
                min_size=1, max_size=40))
def test_property_interleavings(ops):
    """500 random admit/share/COW-write/insert/retire/evict interleavings
    keep every refcount invariant."""
    m = _Machine()
    for code, seed in ops:
        _Machine.OPS[code](m, seed)
        m.check()


@settings(max_examples=24)
@given(st.lists(st.tuples(st.integers(0, len(_Machine.OPS) - 1),
                          st.integers(0, 10**6)),
                min_size=30, max_size=120),
       st.booleans())
def test_property_interleavings_under_pressure(ops, tiny):
    """Same campaign on an undersized pool: allocation-triggered eviction
    races admission and the invariants must still hold."""
    m = _Machine(num_blocks=6 if tiny else 9, capacity=3, max_blocks=5)
    for code, seed in ops:
        _Machine.OPS[code](m, seed)
        m.check()
    assert m.kv.evicted_total >= 0       # counter only moves forward


# ---------------------------------------------------------------------------
# scheduler-level eviction under pressure (sim backend)


def _model(batches=(1, 2, 4, 8, 16, 32)):
    return LatencyModel(alpha={b: 1e-4 * b ** 0.8 for b in batches},
                        beta={b: 5e-3 for b in batches},
                        t_s={b: 2e-4 for b in batches}, c=0.9, gamma=0.548)


def _sim_reqs(n, sys_len=16, tail=5, max_new=8):
    sys = np.arange(100, 100 + sys_len, dtype=np.int32)
    out = []
    for i in range(n):
        toks = np.concatenate(
            [sys, np.arange(1000 * i, 1000 * i + tail, dtype=np.int32)])
        out.append(Request(rid=i, arrival=0.0, tokens=toks,
                           prompt_len=len(toks), max_new=max_new))
    return out


def test_sim_shared_vs_cold_scheduling_signature():
    """With ImmediateAdmit and a roomy pool the cache changes *when work
    happens inside an iteration*, never *what the scheduler decides*: the
    full scheduling signature is identical to a cold run."""
    def run(cache):
        be = SimStepBackend(_model(), capacity=4, seed=3, block_size=8,
                            num_blocks=40, max_context=64,
                            prefix_cache=cache, prefill_token_cost=1e-3)
        sched = ContinuousScheduler(be, fixed_controller(4),
                                    policy=ImmediateAdmit())
        res = sched.run(_sim_reqs(4))
        return be, sched, res

    be_c, sc_c, res_c = run(True)
    be_0, sc_0, res_0 = run(False)
    sig = lambda tr: [(t.occupancy, t.s, t.rids,
                       tuple(sorted(t.committed.items())), t.admitted,
                       t.preempted, t.done_rids) for t in tr]
    assert sig(sc_c.trace) == sig(sc_0.trace)
    hits = [h for t in sc_c.trace for h in t.cache_hits]
    assert hits and all(p == 16 for _, p in hits)
    assert all(not t.cache_hits for t in sc_0.trace)
    be_c.kv.pool.check_invariants()
    # cached prefills fed fewer rows => strictly earlier first tokens
    assert (sum(r.first_token for r in res_c.requests)
            < sum(r.first_token for r in res_0.requests))


def test_sim_replay_with_cache_hits():
    """replay_sources over a cache-on trace reproduces it exactly —
    including the cache_hits column (chunked admission path)."""
    def build(**src):
        return SimStepBackend(_model(), capacity=4, seed=3, block_size=8,
                              num_blocks=40, max_context=96,
                              prefix_cache=True, **src)

    reqs = lambda: _sim_reqs(3, sys_len=16, tail=20)
    be = build(prefill_token_cost=1e-3)
    sched = ContinuousScheduler(be, fixed_controller(4),
                                policy=PrefillBudgetAdmit(token_budget=16,
                                                          chunk=8))
    sched.run(reqs())
    assert any(t.cache_hits for t in sched.trace)
    assert any(t.chunked for t in sched.trace)
    accept, duration, prefill, done, chunk = replay_sources(sched.trace)
    be2 = build(accept_source=accept, duration_source=duration,
                prefill_source=prefill, done_source=done, chunk_source=chunk)
    sched2 = ContinuousScheduler(be2, fixed_controller(4),
                                 policy=PrefillBudgetAdmit(token_budget=16,
                                                           chunk=8))
    sched2.run(reqs())
    assert sched2.trace == sched.trace


def test_sim_eviction_under_pressure_completes():
    """Undersized pool: cache blocks are evicted to make room, admissions
    never map evicted blocks (the lock protocol), every request completes,
    and the pool accounting survives."""
    be = SimStepBackend(_model(), capacity=3, seed=3, block_size=4,
                        num_blocks=12, max_context=48, prefix_cache=True,
                        prefill_token_cost=1e-3)
    sched = ContinuousScheduler(be, fixed_controller(2))
    reqs = _sim_reqs(10, sys_len=8, tail=8, max_new=6)
    res = sched.run(reqs)
    assert all(r.n_generated == r.max_new for r in res.requests)
    assert be.kv.evicted_total > 0       # pressure actually evicted
    assert be.cache.hits > 0             # and sharing still happened
    assert any(t.preempted for t in sched.trace)  # preemption raced it too
    be.kv.pool.check_invariants()
    # every slot retired: only the cache may still hold blocks
    assert be.kv.active_slots() == []
    assert (be.kv.free_blocks + be.kv.cached_blocks) == be.kv.num_blocks


def test_gauges_reach_telemetry():
    """The scheduler publishes cache gauges every iteration."""
    from repro.serving.telemetry import Telemetry
    tel = Telemetry()
    be = SimStepBackend(_model(), capacity=4, seed=0, block_size=8,
                        num_blocks=40, max_context=64, prefix_cache=True)
    sched = ContinuousScheduler(be, fixed_controller(2), telemetry=tel)
    sched.run(_sim_reqs(3))
    assert tel.iterations > 0, "telemetry hub recorded no iterations"
    for key in ("cache_hit_rate", "shared_blocks", "cached_blocks",
                "evicted_blocks", "cache_hit_tokens"):
        assert key in tel.gauges
    assert tel.gauges["cache_hit_rate"] > 0
    assert tel.gauges["cache_hit_tokens"] >= 16


# ---------------------------------------------------------------------------
# live-engine parity (token- and StepTrace-identity vs cold)

CACHE_LEN = 96


@pytest.fixture(scope="module")
def engine():
    import jax
    from repro.configs import registry as R
    from repro.core.spec_decode import SpecDecodeEngine
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


def _ctrl():
    return AdaptiveController(lut=SpeculationLUT({1: 4, 2: 3, 4: 2}))


def _live_reqs(tcfg, n=4, sys_len=16, tail=5, max_new=8, seed=5):
    rng = np.random.default_rng(seed)
    sys = rng.integers(0, tcfg.vocab_size, (sys_len,)).astype(np.int32)
    out = []
    for i in range(n):
        toks = np.concatenate(
            [sys, rng.integers(0, tcfg.vocab_size, (tail,)).astype(np.int32)])
        out.append(Request(rid=i, arrival=0.0, tokens=toks,
                           prompt_len=len(toks), max_new=max_new))
    return out


def _run_live(engine, reqs, *, prefix_cache, policy=None, num_blocks=48,
              capacity=4, paged_fused=None, s_cap=4):
    eng, tp, dp, _ = engine
    be = ContinuousEngineBackend(eng, tp, dp, capacity=capacity,
                                 cache_len=CACHE_LEN, warm_s=[2, 3, 4],
                                 block_size=8, num_blocks=num_blocks,
                                 collect_outputs=True, s_cap=s_cap,
                                 paged_fused=paged_fused,
                                 prefix_cache=prefix_cache)
    sched = ContinuousScheduler(be, _ctrl(), policy=policy)
    sched.run(reqs)
    return be, sched


def _sig(trace):
    return [(t.occupancy, t.s, t.rids, tuple(sorted(t.committed.items())),
             t.admitted, t.preempted, t.done_rids) for t in trace]


@pytest.mark.parametrize("fused", [False, True])
def test_live_shared_vs_cold_identity(engine, fused):
    """Shared-prefix serving is token- AND StepTrace-identical to cold,
    on both paged kernel paths."""
    reqs = lambda: _live_reqs(engine[3])
    be_c, sc_c = _run_live(engine, reqs(), prefix_cache=True,
                           paged_fused=fused)
    be_0, sc_0 = _run_live(engine, reqs(), prefix_cache=False,
                           paged_fused=fused)
    hits = [h for t in sc_c.trace for h in t.cache_hits]
    assert len(hits) == 3 and all(p == 16 for _, p in hits)
    for rid in range(4):
        np.testing.assert_array_equal(be_c.outputs[rid], be_0.outputs[rid],
                                      err_msg=f"rid {rid}")
        assert len(be_c.outputs[rid]) == 8
    assert _sig(sc_c.trace) == _sig(sc_0.trace)
    be_c.kv.pool.check_invariants()


def test_live_chunked_partial_hit(engine):
    """Chunked admission of a partially-cached prompt: the cached prefix is
    attached, only the uncached suffix is fed through the chunk machinery,
    and token outputs equal the cold run's."""
    reqs = lambda: _live_reqs(engine[3], n=3, sys_len=16, tail=20,
                              max_new=6, seed=9)
    pol = lambda: PrefillBudgetAdmit(token_budget=16, chunk=8)
    be_c, sc_c = _run_live(engine, reqs(), prefix_cache=True, policy=pol())
    be_0, sc_0 = _run_live(engine, reqs(), prefix_cache=False, policy=pol())
    hits = [h for t in sc_c.trace for h in t.cache_hits]
    assert hits, "no cache hit on the shared prefix"
    assert any(t.chunked for t in sc_c.trace)
    for rid in range(3):
        np.testing.assert_array_equal(be_c.outputs[rid], be_0.outputs[rid],
                                      err_msg=f"rid {rid}")
    be_c.kv.pool.check_invariants()


def test_live_preempt_then_restore_shared_prefix(engine):
    """An undersized pool forces preemption; the victim's re-admission
    re-attaches the surviving shared prefix (a cache hit for a rid that
    was preempted) and final tokens equal the roomy cold run."""
    # 12 blocks: all four admit cheaply through the shared prefix (need is
    # ~1 block each past the 2 shared), but full growth to 19+16 tokens
    # wants 2 shared + 4×3 exclusive = 14 blocks, so decode must preempt
    mk = lambda: _live_reqs(engine[3], n=4, sys_len=16, tail=3, max_new=16,
                            seed=11)
    be_c, sc_c = _run_live(engine, mk(), prefix_cache=True, num_blocks=12,
                           capacity=4, s_cap=4)
    preempted = [r for t in sc_c.trace for r in t.preempted]
    assert preempted, "pool was not small enough to force preemption"
    hits = [h for t in sc_c.trace for h in t.cache_hits]
    hit_rids = {rid for rid, _ in hits}
    assert hit_rids & set(preempted), \
        "no preempted request re-admitted via the shared prefix"
    be_0, _ = _run_live(engine, mk(), prefix_cache=False, num_blocks=48)
    for rid in range(4):
        np.testing.assert_array_equal(be_c.outputs[rid], be_0.outputs[rid],
                                      err_msg=f"rid {rid}")
    be_c.kv.pool.check_invariants()


def test_sim_vs_live_replay_with_cache(engine):
    """A cache-on live trace replays exactly on a cache-on sim backend with
    the live pool geometry — including the cache_hits column."""
    reqs = lambda: _live_reqs(engine[3], n=4, sys_len=16, tail=5, max_new=8)
    be, sc = _run_live(engine, reqs(), prefix_cache=True)
    accept, duration, prefill, done, chunk = replay_sources(sc.trace)
    sim = SimStepBackend(_model(), capacity=4, seed=0, block_size=8,
                         num_blocks=48, max_context=CACHE_LEN,
                         prefix_cache=True, accept_source=accept,
                         duration_source=duration, prefill_source=prefill,
                         done_source=done, chunk_source=chunk)
    sched = ContinuousScheduler(sim, _ctrl())
    sched.run(reqs())
    assert sched.trace == sc.trace
