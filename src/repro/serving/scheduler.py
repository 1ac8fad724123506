"""Iteration-level (Orca-style) continuous-batching scheduler.

Requests JOIN and LEAVE the running batch at *speculative-step* granularity:
every iteration the scheduler (1) admits arrived requests into free KV slots
via a pluggable :class:`AdmissionPolicy`, (2) asks the
:class:`~repro.core.adaptive.AdaptiveController` for the speculation length
at the **live occupancy** — the finest-grained use of the paper's b -> s_opt
LUT — and (3) runs one speculative step, retiring finished slots.

Two step backends answer the same protocol, so the identical scheduling code
runs against hardware truth and against the fitted simulation:

  * :class:`ContinuousEngineBackend` — a live
    :class:`~repro.core.spec_decode.SpecDecodeEngine` slot pool
    (``prefill_into`` / masked step / ``retire_slot``), wall-clock timed
    with compiles warmed outside the timed region;
  * :class:`SimStepBackend` — one discrete-event step from a fitted
    :class:`~repro.core.analytical.LatencyModel` with the shared
    truncated-geometric acceptance process (serving/acceptance.py).

``serve_continuous_live()`` is the live entrypoint mirroring
:func:`repro.serving.server.serve_continuous` (which now runs this same
scheduler over :class:`SimStepBackend`), so Fig. 5-7 traffic studies can be
replayed on a real engine and validated against the simulation
(sim-vs-live parity on identical traces).

Paged KV + preemption: when the engine slot pool is paged (fixed-size
blocks + a free list, core/spec_decode.py design note), the scheduler also
(a) admits by block feasibility — a prompt only enters when the free list
covers it, (b) hard-rejects requests whose worst-case footprint
(prompt + max_new + the controller's speculation ceiling) exceeds the
per-request capacity (previously they silently wrapped their KV ring), and
(c) preempts under memory pressure: if covering this step's worst-case
commit (s+1 tokens per live slot) could exhaust the free list, the victim
with the longest remaining budget (ties: most recently admitted, i.e.
LIFO) is evicted back to the backlog and later re-prefilled from prompt +
its generated-token stash.  Preemptions are recorded in
:class:`StepTrace`; because they are pure functions of the block
accounting, a :class:`SimStepBackend` built with the same pool geometry
re-derives them exactly during replay.

In-step chunked prefill (Sarathi-style; SNIPPETS §2): with a
:class:`PrefillBudgetAdmit` policy, admission work is bounded by a strict
per-iteration token budget.  A prompt that fits the budget prefills whole;
a longer one is admitted *chunked* — its slot carries PREFILLING state
across iterations (``Request.prefill_pos``), each iteration feeds at most
one ``chunk`` of tokens (interleaved with the running batch's decode
steps), and the slot joins the decode batch only when its last chunk
commits.  The controller therefore keeps seeing the *decode* batch size,
admission can no longer stall every running request for a whole-prompt
burst, and chunk events are recorded in :class:`StepTrace` so the sim
backend replays them for exact sim-vs-live parity.

Sharded serving (the production mesh): ``serve_continuous_live(mesh=...)``
runs the engine slot pool sharded over the mesh's data axes (engine design
note in core/spec_decode.py).  The scheduler side is deliberately small:
the capacity axis splits into ``backend.n_shards`` contiguous slot ranges —
one per data shard, i.e. per serving host in a multi-host deployment — and
a :class:`HostShardQueue` round-robins slot claims across those ranges so
every shard carries an even share of the live batch.  Because the queue
only changes *which slot* a request lands in (never *when* it is admitted,
FCFS order is untouched) and StepTrace records request ids, the sharded
run's trace is identical to the single-device run's — the sharded parity
contract tests/test_sharded_serving.py enforces.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.adaptive import AdaptiveController
from repro.core.analytical import LatencyModel
from repro.core.spec_decode import S_MAX
from repro.kernels.tuning import grid_steps_dense, grid_steps_ragged
from repro.serving.acceptance import GeometricAcceptance
from repro.serving.request import BatchRecord, Request
from repro.serving.slots import PagedKVTables, SlotPool
from repro.serving.telemetry import NULLSPAN, span


# ---------------------------------------------------------------------------
# admission policies


class AdmissionPolicy:
    """Chooses which backlog requests to admit into free slots this step.

    Protocol contract (every policy must honour it):

    * ``backlog`` is the FCFS-ordered list of arrived, not-yet-admitted
      requests (a re-admitted preemption victim sits at the head).  The
      policy must treat it as read-only — the scheduler removes admitted
      requests itself.
    * ``free_slots`` is the number of currently claimable slots;
      ``clock`` is the scheduler's virtual time in seconds (policies may
      use it for deadline/aging decisions).
    * Returns the requests to admit this iteration, in admission order, a
      subset of ``backlog`` with ``len(result) <= free_slots``.  Returning
      a request not in ``backlog`` is a protocol violation.
    * The policy only *selects*; feasibility is the scheduler's job.  The
      scheduler may admit fewer than selected (KV-block feasibility,
      oversize rejection), and on a chunk-capable backend a
      :class:`PrefillBudgetAdmit` policy's budget/chunk attributes are read
      directly by the scheduler instead of :meth:`select` (see that class).
    * Policies may keep internal state across calls (e.g. deferral
      counters); the scheduler instantiates one policy per run.
    """

    def select(self, backlog: Sequence[Request], free_slots: int,
               clock: float) -> List[Request]:
        raise NotImplementedError


class ImmediateAdmit(AdmissionPolicy):
    """Admit FCFS into every free slot (Orca-style, the default)."""

    def select(self, backlog, free_slots, clock):
        return list(backlog[:free_slots])


class PrefillBudgetAdmit(AdmissionPolicy):
    """Chunked-prefill-style admission: cap the prefill tokens injected per
    iteration so admission work cannot starve the running batch (bounds the
    inter-token latency hit of each admission burst; SNIPPETS §2).

    ``chunk`` (default: the budget) is the fixed chunk size used when the
    scheduler runs a chunk-capable backend: a prompt longer than the
    remaining budget is then admitted chunked — never as a whole-prompt
    burst — and continues across iterations.  On a backend without chunk
    support, :meth:`select` falls back to whole-prompt budgeting: an
    over-budget head prompt waits (without blocking smaller backlog
    requests that still fit this step's budget) but only for at most
    ``max_defer`` iterations — after that it is admitted whole so a steady
    stream of small prompts cannot starve it forever — and when nothing
    fits at all the head is admitted whole immediately (no deadlock).
    """

    def __init__(self, token_budget: int = 64, chunk: Optional[int] = None,
                 max_defer: int = 16):
        if token_budget < 1:
            raise ValueError("token_budget must be >= 1")
        self.token_budget = token_budget
        self.chunk_tokens = token_budget if chunk is None else chunk
        if self.chunk_tokens < 1:
            raise ValueError("chunk must be >= 1")
        self.max_defer = max_defer
        self._deferred: Dict[int, int] = {}    # rid -> times passed over

    def select(self, backlog, free_slots, clock):
        out: List[Request] = []
        used = 0
        for req in backlog:
            if len(out) >= free_slots or used >= self.token_budget:
                break                  # nothing else can fit this step
            if used + req.prompt_len > self.token_budget:
                skips = self._deferred.get(req.rid, 0) + 1
                if skips > self.max_defer and not out:
                    # aging escape: a chronically deferred prompt bursts
                    # whole rather than being starved by a steady stream
                    # of smaller fits (chunk-capable backends never get
                    # here — the scheduler admits it chunked instead)
                    self._deferred.pop(req.rid, None)
                    out.append(req)
                    used += req.prompt_len
                    continue
                # over budget this step: wait — but do not block smaller
                # backlog requests that still fit (the head-of-line fix)
                self._deferred[req.rid] = skips
                continue
            out.append(req)
            used += req.prompt_len
            self._deferred.pop(req.rid, None)
        if not out and backlog and free_slots > 0:
            # nothing fits the budget at all: whole-prompt fallback so the
            # policy never deadlocks
            req = backlog[0]
            self._deferred.pop(req.rid, None)
            out.append(req)
        return out


class FCFSBacklog(AdmissionPolicy):
    """At most ``max_per_step`` admissions per iteration (rate-limited FCFS,
    the gentlest admission schedule)."""

    def __init__(self, max_per_step: int = 1):
        self.max_per_step = max_per_step

    def select(self, backlog, free_slots, clock):
        return list(backlog[:min(free_slots, self.max_per_step)])


class HostShardQueue:
    """Per-host admission queue for a mesh-sharded slot pool.

    A slot pool sharded over ``n_shards`` data shards places slot rows in
    contiguous ranges — shard ``i`` (one serving host's devices in a
    multi-host deployment) owns slots ``[i * capacity/n, (i+1) *
    capacity/n)``, exactly the layout a NamedSharding gives the capacity
    axis.  This queue claims slots ROUND-ROBIN across those ranges (lowest
    free slot within the chosen shard), so admissions spread evenly over
    the shards instead of filling shard 0 first — every host carries an
    even share of the live batch and of the per-step KV writes.

    It deliberately does NOT reorder admissions: the scheduler admits in
    the same FCFS order with or without a mesh, which is what keeps the
    sharded StepTrace identical to the single-device one (rids, commits,
    preemptions are all slot-number-free).
    """

    def __init__(self, capacity: int, n_shards: int):
        if n_shards < 1 or capacity % n_shards != 0:
            raise ValueError(
                f"capacity {capacity} does not split into {n_shards} "
                f"equal shard ranges")
        self.n_shards = n_shards
        self.per_shard = capacity // n_shards
        self._next = 0                 # round-robin cursor

    def claim(self, pool: SlotPool, req: Request) -> int:
        """Claim a slot for ``req``, round-robining across shard ranges.

        Starts at the cursor and takes the first shard with a free slot
        (lowest slot id within it), then advances the cursor past that
        shard.  Deterministic: a pure function of the pool's free set and
        the claim history.
        """
        for k in range(self.n_shards):
            sh = (self._next + k) % self.n_shards
            lo = sh * self.per_shard
            for slot in range(lo, lo + self.per_shard):
                if pool.is_free(slot):
                    self._next = (sh + 1) % self.n_shards
                    return pool.claim(req, slot=slot)
        raise RuntimeError("slot pool full")


# ---------------------------------------------------------------------------
# step backends


def controller_s_cap(controller) -> int:
    """Largest speculation length ``controller`` can ever choose.

    This — not the global S_MAX — is the right worst-case reservation unit
    for admission and KV-overflow checks: one speculative step commits at
    most ``s + 1`` tokens, so every "can this request still fit its KV
    budget" bound is of the form ``prompt + max_new + s_cap``, and a
    controller capped below S_MAX can serve requests the S_MAX bound would
    wrongly reject.

    Derivation: the max over the controller's LUT entries, raised to
    ``controller.s_max`` when an online acceptance model may rebuild LUT
    entries upward, clamped to the engine's hard S_MAX (the ``out``-buffer
    headroom).  Controllers without a LUT (e.g. ad-hoc stubs) conservatively
    get S_MAX.
    """
    try:
        cap = max(controller.lut.table.values())
    except (AttributeError, ValueError):
        return S_MAX
    if getattr(controller, "model", None) is not None:
        # online LUT refresh may rebuild entries up to controller.s_max
        cap = max(cap, getattr(controller, "s_max", S_MAX))
    return min(int(cap), S_MAX)


def _reject_oversize(req: Request, max_context: int,
                     s_cap: int = S_MAX) -> None:
    """Hard admission bound: a request whose worst-case KV footprint exceeds
    the per-request capacity can never be served — deferring it would spin
    forever, and admitting it would silently wrap the ring / overrun the
    block table and corrupt the KV (the PR-1 bug this check closes).
    ``s_cap`` is the scheduler's speculation ceiling (one step can overshoot
    ``max_new`` by at most that many tokens)."""
    if req.prompt_len + req.max_new + s_cap > max_context:
        raise ValueError(
            f"request {req.rid}: prompt_len={req.prompt_len} + "
            f"max_new={req.max_new} + s_cap={s_cap} exceeds the per-request "
            f"KV capacity {max_context}; the KV ring would wrap and corrupt "
            f"itself")


class ContinuousEngineBackend:
    """Live-engine step backend: a SpecDecodeEngine slot pool on hardware.

    Prefill compiles (per prompt bucket) and step compiles (per s) are warmed
    outside the timed regions — serving latency is steady-state, matching
    EngineBackend's treatment of compile time.

    With ``block_size`` set, the engine slot pool is the paged KV block pool
    (``self.kv`` holds its host free list / block tables) and the scheduler
    gains admission feasibility checks and preemption under memory pressure.
    A preempted request's generated tokens are stashed host-side; on
    re-admission it re-prefills from prompt + stash (recompute-style
    restore) and greedy decoding continues exactly where it left off.

    :meth:`prefill_chunk` feeds one chunk of a request's prompt through the
    engine's ``prefill_chunk_into`` (in-step chunked prefill); the slot
    stays masked out of the decode steps until its final chunk commits.

    With ``mesh`` set, the slot pool is sharded over the mesh's data axes
    (one SPMD program per step; core/spec_decode.py sharded-serving note),
    params are placed replicated on the mesh, and ``n_shards`` reports how
    many data shards the capacity axis splits into — the scheduler's
    :class:`HostShardQueue` round-robins slot claims across them.
    """

    def __init__(self, engine, tparams, dparams, capacity: int,
                 cache_len: int = 256, warm_s: Sequence[int] = (),
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 collect_outputs: bool = False,
                 s_cap: int = S_MAX,
                 mesh=None,
                 paged_fused=None,
                 prefix_cache: bool = False,
                 mixed_launch: bool = False):
        if engine.tcfg.family in ("encdec", "audio", "vlm"):
            # these families need per-request modality extras (src_embeds /
            # prefix_embeds) that the admission path does not plumb yet; see
            # ROADMAP open items
            raise NotImplementedError(
                f"continuous batching does not support family "
                f"'{engine.tcfg.family}' yet (per-request modality extras)")
        if mesh is not None:
            # replicate params across the serving mesh (data-parallel
            # serving; the engine's sharded jits consume them as such)
            import jax
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(mesh, PartitionSpec())
            tparams = jax.device_put(tparams, rep)
            if dparams is not None:
                dparams = jax.device_put(dparams, rep)
        if paged_fused is not None:
            # force the paged-attention kernel path (fused streaming kernel
            # vs gather path, kernels/paged.py) BEFORE the pool and its
            # jits exist, so every compiled step uses one path.  None
            # deliberately leaves the engine's current routing untouched
            # (an engine constructed with paged_fused=... keeps its choice;
            # call engine.set_paged_fused(None) to restore auto routing)
            engine.set_paged_fused(paged_fused)
        self.engine = engine
        self.tparams = tparams
        self.dparams = dparams
        self.capacity = capacity
        self.s_cap = s_cap
        self.mesh = mesh
        self.state = engine.init_slots(capacity, cache_len,
                                       block_size=block_size,
                                       num_blocks=num_blocks,
                                       mesh=mesh)
        self.n_shards = getattr(engine, "n_data_shards", 1)
        self.kv = self.state.paged               # None => contiguous rings
        self.cache_len = (self.kv.logical_len if self.kv is not None
                          else cache_len)
        self.collect_outputs = collect_outputs
        self.outputs: Dict[int, np.ndarray] = {}   # rid -> generated tokens
        self._stash: Dict[int, np.ndarray] = {}    # rid -> pre-preempt tokens
        self._warm_prefill: set = set()
        self._warm_chunk: set = set()
        self._warm_step: set = set()
        self._warm_attach: set = set()
        self._warm_commit_attached = False
        # cross-request prefix sharing (serving/prefix_cache.py): opt-in,
        # paged + unsharded + chunk-capable only.  `cache is None` keeps
        # every legacy code path bit-identical.
        self.cache = None
        self._locked: Dict[int, List[int]] = {}  # rid -> lock()ed blocks
        if prefix_cache:
            if self.kv is None:
                raise ValueError(
                    "prefix_cache=True needs a paged KV pool (block_size)")
            if mesh is not None:
                raise ValueError(
                    "prefix_cache is not supported on a mesh-sharded pool: "
                    "shared blocks may live on any shard (allocation is not "
                    "shard-local)")
            if not self.can_chunk:
                raise ValueError(
                    "prefix_cache needs chunked prefill support: the "
                    "uncached suffix of a hit is fed through the chunk path")
            from repro.serving.prefix_cache import PrefixCache
            self.cache = PrefixCache(self.kv.pool)
            self.kv.attach_cache(self.cache)
        # mixed verify+chunk launch: NON-final paged prefill chunks defer
        # their forward (host bookkeeping still runs at feed time, so block
        # accounting and StepTrace stay bit-identical) and ride the next
        # speculative step's ragged attention call (engine.step_with_chunk).
        # Every other pool consumer flushes the pending chunk standalone
        # first, so at most one chunk is ever in flight.
        self.mixed_launch = mixed_launch
        self._deferred = None            # Optional[DeferredChunk]
        if mixed_launch:
            if self.kv is None:
                raise ValueError(
                    "mixed_launch=True needs a paged KV pool (block_size): "
                    "the fused launch rides the ragged paged kernel")
            if mesh is not None:
                raise ValueError(
                    "mixed_launch is not supported on a mesh-sharded pool "
                    "yet (the mixed step is registered unsharded only)")
        for s in warm_s:
            self.warm_step(s)

    @property
    def max_context(self) -> int:
        """Per-request KV capacity in tokens (admission hard limit)."""
        return self.cache_len

    @property
    def annotate(self) -> bool:
        """The span switch (the engine's ``annotate``); the scheduler reads
        it once per iteration."""
        return self.engine.annotate

    @property
    def can_chunk(self) -> bool:
        """Whether the engine's model pair supports chunked prefill."""
        eng = self.engine
        return (hasattr(eng.target, "prefill_chunk")
                and (eng.draft is None
                     or hasattr(eng.draft, "prefill_chunk")))

    def warm_step(self, s: int) -> None:
        if s not in self._warm_step:
            self.engine.step(self.tparams, self.dparams, self.state, s,
                             warm=True)
            self._warm_step.add(s)

    def _flush_deferred(self) -> None:
        """Dispatch the pending deferred chunk standalone, if any.

        Called at the top of every other pool consumer (prefill / chunk /
        attach / commit / step-without-mixing / preempt / retire / output
        reads): the deferred forward must land before anything else touches
        the pool or the state buffers it will consume.  Chunk rows are
        slot-private, so dispatch order relative to the *step* is free —
        this guard is about buffer lineage, not numerics.
        """
        if self._deferred is not None:
            chunk, self._deferred = self._deferred, None
            self.state = self.engine.flush_chunk(
                self.tparams, self.dparams, self.state, chunk)

    def _bucket(self, n: int) -> int:
        p = 4
        while p < n:
            p *= 2
        return min(p, self.cache_len)   # never wider than the KV capacity

    def _full_prompt(self, req: Request) -> np.ndarray:
        """Prompt plus any tokens generated before a preemption."""
        stash = self._stash.get(req.rid)
        if stash is None:
            return np.asarray(req.tokens[:req.prompt_len], np.int32)
        return np.concatenate(
            [np.asarray(req.tokens[:req.prompt_len], np.int32), stash])

    def prefill(self, req: Request, slot: int) -> float:
        """Inject ``req`` into ``slot``; returns seconds of prefill work."""
        scope = self.engine.scope
        with scope("prefill.prep"):
            _reject_oversize(req, self.max_context, self.s_cap)  # defense in depth
            self._flush_deferred()
            prompt = self._full_prompt(req)
            plen = len(prompt)
            P = self._bucket(plen)
            toks = np.ones((P,), np.int32)
            toks[:plen] = prompt
            if P not in self._warm_prefill:
                # compile the B=1 prefill + inject for this bucket off the
                # clock
                self.engine.prefill_into(self.tparams, self.dparams,
                                         self.state, slot, toks, plen,
                                         self.cache_len, warm=True)
                self._warm_prefill.add(P)
        t0 = time.perf_counter()
        self.state = self.engine.prefill_into(
            self.tparams, self.dparams, self.state, slot, toks,
            plen, self.cache_len)
        with scope("prefill.fence"):
            np.asarray(self.state.seq_lens)  # lint: allow-host-sync(deliberate fence: prefill wall-clock timing)
        return time.perf_counter() - t0

    def prefill_chunk(self, req: Request, slot: int, start: int,
                      n: int) -> float:
        """Feed feed-positions ``[start, start + n)`` of ``req``'s prompt
        (+ pre-preemption stash) into ``slot``; returns seconds.

        The feed spans ``len(prompt) - 1`` positions (the last token is
        written by the slot's first decode step, exactly like whole-prompt
        prefill); the chunk carrying the final position also commits the
        slot into the decode batch.
        """
        scope = self.engine.scope
        with scope("chunk.prep"):
            if start == 0:
                _reject_oversize(req, self.max_context, self.s_cap)
            self._flush_deferred()
            prompt = self._full_prompt(req)
            total_len = len(prompt)
            feed_total = total_len - 1
            CB = self._bucket(n)
            toks = np.ones((CB,), np.int32)
            toks[:n] = prompt[start:start + n]
            final = start + n == feed_total
            if CB not in self._warm_chunk:
                # compile begin/chunk/commit for this bucket off the clock
                self.engine.prefill_chunk_into(
                    self.tparams, self.dparams, self.state, slot,
                    np.ones((CB,), np.int32), 0, CB, CB + 2, warm=True)
                self._warm_chunk.add(CB)
        if self.mixed_launch and not final:
            # defer the forward: host bookkeeping runs now (block accounting
            # and admission decisions are unchanged), the dispatch rides the
            # next speculative step — or a standalone flush, whichever pool
            # consumer comes first
            t0 = time.perf_counter()
            self.state, self._deferred = self.engine.prefill_chunk_into(
                self.tparams, self.dparams, self.state, slot, toks, start,
                n, total_len, defer=True)
            return time.perf_counter() - t0
        t0 = time.perf_counter()
        self.state = self.engine.prefill_chunk_into(
            self.tparams, self.dparams, self.state, slot, toks, start, n,
            total_len, last2=prompt[-2:] if final else None)
        with scope("chunk.fence"):
            np.asarray(self.state.seq_lens)  # lint: allow-host-sync(deliberate fence: chunk wall-clock timing)
        return time.perf_counter() - t0

    # ------------------------------------------------------------------
    # prefix-cache protocol (no-ops unless built with prefix_cache=True;
    # SimStepBackend implements the same five methods over the same host
    # accounting, which is what makes cache admissions replay sim-vs-live)

    def match_and_lock(self, req: Request) -> int:
        """Longest cached prefix of ``req``'s *prompt* (never the stash:
        generated tokens are model outputs the sim backend cannot know, so
        matching them would break sim-vs-live re-derivation).  The matched
        blocks are pinned against eviction until :meth:`attach` or
        :meth:`cancel_match`.  Returns the prefix length in tokens."""
        if self.cache is None:
            return 0
        blocks = self.cache.lock(np.asarray(req.tokens[:req.prompt_len]))
        if not blocks:
            return 0
        self._locked[req.rid] = blocks
        return len(blocks) * self.kv.block_size

    def cancel_match(self, req: Request) -> None:
        """Drop a lock taken by :meth:`match_and_lock` (admission abort)."""
        blocks = self._locked.pop(req.rid, None)
        if blocks:
            self.cache.unlock(blocks)

    def attach(self, req: Request, slot: int, n_prefix: int) -> float:
        """Map the locked prefix blocks into ``slot`` (refcount+1), park
        the slot, and run the draft-only prefix prefill; returns seconds.
        The uncached suffix is then fed via :meth:`prefill_chunk` with
        ``start = n_prefix`` (or, zero-suffix, :meth:`commit_attached`)."""
        scope = self.engine.scope
        with scope("attach.prep"):
            self._flush_deferred()
            blocks = self._locked.pop(req.rid)
            prompt = self._full_prompt(req)
            total_len = len(prompt)
            self.kv.attach(slot, blocks, n_prefix)
            self.cache.unlock(blocks)      # the slot now holds its own refs
            P = self._bucket(total_len)
            toks = np.ones((P,), np.int32)
            toks[:total_len] = prompt
            if P not in self._warm_attach:
                self.engine.attach_prefix(self.dparams, self.state, slot,
                                          toks, n_prefix, total_len,
                                          warm=True)
                self._warm_attach.add(P)
        t0 = time.perf_counter()
        self.state = self.engine.attach_prefix(
            self.dparams, self.state, slot, toks, n_prefix, total_len)
        with scope("attach.fence"):
            np.asarray(self.state.seq_lens)  # lint: allow-host-sync(deliberate fence: attach wall-clock timing)
        return time.perf_counter() - t0

    def commit_attached(self, req: Request, slot: int) -> float:
        """Commit a fully-cached attach into the decode batch (no prefill
        forward at all — COW of the last block if needed, then the ordinary
        chunk-commit).  Returns seconds."""
        scope = self.engine.scope
        with scope("attach.prep"):
            self._flush_deferred()
            prompt = self._full_prompt(req)
            total_len = len(prompt)
            if not self._warm_commit_attached:
                self.engine.commit_attached(self.state, slot, total_len,
                                            prompt[-2:], warm=True)
                self._warm_commit_attached = True
        t0 = time.perf_counter()
        self.state = self.engine.commit_attached(self.state, slot, total_len,
                                                 prompt[-2:])
        with scope("attach.fence"):
            np.asarray(self.state.seq_lens)  # lint: allow-host-sync(deliberate fence: attach-commit wall-clock timing)
        return time.perf_counter() - t0

    def cache_insert(self, req: Request, slot: int) -> None:
        """Publish ``slot``'s prompt blocks into the prefix index (called
        by the scheduler when the slot joins the decode batch).

        Only *prompt* rows strictly below the feed's final row are indexed:
        the block containing row ``total_len - 1`` is excluded, so this
        slot's own decode writes never land in an indexed block and need no
        COW.  First writer wins — prefixes already indexed keep their node.
        """
        if self.cache is None:
            return
        total_len = req.prompt_len + req.n_generated
        rows = min(req.prompt_len, total_len - 1)
        n_ins = rows // self.kv.block_size
        if n_ins:
            self.cache.insert(
                np.asarray(req.tokens[:n_ins * self.kv.block_size]),
                self.kv.table(slot)[:n_ins])

    def step(self, s: int) -> Tuple[float, np.ndarray, np.ndarray]:
        """One speculative step at live occupancy.  Returns
        (wall seconds, committed[capacity], done[capacity]).

        With a deferred chunk pending (``mixed_launch``), the step runs as
        ONE mixed verify+chunk launch — the chunk's prefix-extension rows
        ride the same ragged attention grid as the verify queries instead
        of paying a second kernel launch and weight re-stream.
        """
        scope = self.engine.scope
        with scope("step.prep"):
            self.warm_step(s)
            chunk, self._deferred = self._deferred, None
        t0 = time.perf_counter()
        if chunk is not None:
            self.state, st = self.engine.step_with_chunk(
                self.tparams, self.dparams, self.state, s, chunk)
        else:
            self.state, st = self.engine.step(self.tparams, self.dparams,
                                              self.state, s)
        dt = time.perf_counter() - t0
        with scope("step.done"):
            # lint: allow-host-sync(step boundary: done flags steer retirement)
            return dt, st.committed, np.asarray(self.state.done)

    def preempt(self, slot: int, req: Request) -> None:
        """Evict ``req`` under memory pressure: stash its generated tokens,
        free the slot's KV blocks, and mark the row done."""
        self._flush_deferred()
        with self.engine.scope("preempt.out"):
            dev_n = int(np.asarray(self.state.n_generated)[slot])  # lint: allow-host-sync(preempt is off the steady path; must read victim count)
            fresh = np.asarray(self.state.out)[slot, :dev_n].astype(np.int32)  # lint: allow-host-sync(victim tokens are stashed host-side)
        old = self._stash.get(req.rid)
        self._stash[req.rid] = (fresh if old is None
                                else np.concatenate([old, fresh]))
        self.state = self.engine.retire_slot(self.state, slot)

    def retire(self, slot: int, req: Optional[Request] = None) -> None:
        self._flush_deferred()
        if req is not None:
            if self.collect_outputs:
                # stitch ever-preempted requests now, before the slot (and
                # its out row) is recycled
                self.outputs[req.rid] = self.output_for(slot, req)
            # always drop the stash: keeping it for callers who opted out of
            # output collection would leak memory on long-lived backends
            self._stash.pop(req.rid, None)
        self.state = self.engine.retire_slot(self.state, slot)

    def output_for(self, slot: int, req: Optional[Request] = None) -> np.ndarray:
        """Generated tokens of the request in ``slot``.

        With ``req`` given, the result is truncated to ``req.n_generated``
        (a request with a smaller ``max_new`` than the engine's must not
        surface tokens past its budget) and stitched with any pre-preemption
        stash; without it, the legacy engine-sized row is returned.
        """
        self._flush_deferred()
        with self.engine.scope("retire.out"):
            out = np.asarray(self.state.out)[slot]
        if req is None:
            return out[:self.engine.max_new]
        stash = self._stash.get(req.rid)
        if stash is None:
            return out[:req.n_generated].astype(np.int32)
        cont = out[:req.n_generated - len(stash)].astype(np.int32)
        return np.concatenate([stash, cont])


class SimStepBackend:
    """Discrete-event step backend over a fitted LatencyModel.

    Step duration at live occupancy b is t_L(bk, s) + s * t_S(bk, 1) with bk
    the nearest profiled batch size >= b; acceptance is the shared
    truncated-geometric process — or, for sim-vs-live parity tests, a
    replayed ``accept_source(step_idx, rids, s) -> accepted`` trace.
    """

    can_chunk = True

    def __init__(self, model: LatencyModel, capacity: int, seed: int = 0,
                 accept_source: Optional[Callable] = None,
                 duration_source: Optional[Callable] = None,
                 prefill_source: Optional[Callable] = None,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 max_context: int = 256,
                 done_source: Optional[Callable] = None,
                 chunk_source: Optional[Callable] = None,
                 prefix_cache: bool = False,
                 prefill_token_cost: float = 0.0):
        self.model = model
        self.capacity = capacity
        self.acceptance = GeometricAcceptance(model, seed)
        self.accept_source = accept_source
        self.duration_source = duration_source
        self.prefill_source = prefill_source
        # default prefill cost per fed token (seconds): 0.0 keeps the legacy
        # "prefill is outside the fitted model" behavior; a positive value
        # makes TTFT sensitive to how many rows actually get prefilled —
        # which is what lets the templated-traffic bench show the prefix
        # cache's TTFT win on the sim backend
        self.prefill_token_cost = prefill_token_cost
        # replayed per-step done sets: the live engine marks a slot done on
        # its EOS step (commit > 0) one iteration before it commits 0, and
        # victim selection must see the same flag to replay identically
        self.done_source = done_source
        # replayed per-rid chunk durations (FIFO, like prefill_source)
        self.chunk_source = chunk_source
        self.done = np.ones(capacity, dtype=bool)
        self.rids = np.full(capacity, -1, dtype=np.int64)
        self._step_idx = 0
        # paged-KV mirror: same geometry as the live pool => the scheduler's
        # preemption decisions (functions of free/allocated/token counts
        # only) replay count-for-count against the live run
        if block_size is not None:
            max_blocks = -(-max_context // block_size)
            if num_blocks is None:
                num_blocks = capacity * max_blocks
            self.kv: Optional[PagedKVTables] = PagedKVTables(
                num_blocks, block_size, capacity, max_blocks)
        else:
            self.kv = None
        # the plain sim has no KV to overflow, so no admission hard limit
        self.max_context = (self.kv.logical_len if self.kv is not None
                            else None)
        # prefix cache mirror: the same PrefixCache/refcount machinery as
        # the live backend over the same pool geometry, so cache hits,
        # attach block accounting and evictions re-derive identically
        self.cache = None
        self._locked: Dict[int, List[int]] = {}
        if prefix_cache:
            if self.kv is None:
                raise ValueError(
                    "prefix_cache=True needs a paged KV mirror (block_size)")
            from repro.serving.prefix_cache import PrefixCache
            self.cache = PrefixCache(self.kv.pool)
            self.kv.attach_cache(self.cache)

    def _batch_key(self, b: int) -> int:
        for x in self.model.batch_sizes:
            if x >= b:
                return x
        return self.model.batch_sizes[-1]

    def prefill(self, req: Request, slot: int) -> float:
        self.done[slot] = False
        self.rids[slot] = req.rid
        if self.kv is not None:
            # a re-admitted (preempted) request re-prefills prompt + stash
            self.kv.prefill(slot, req.prompt_len + req.n_generated)
            self.kv.evicted_pending.clear()  # no device rows to wipe in sim
        if self.prefill_source is not None:
            return float(self.prefill_source(req.rid))
        # default: prefill outside the fitted model (0.0 per-token cost)
        return (req.prompt_len + req.n_generated) * self.prefill_token_cost

    def prefill_chunk(self, req: Request, slot: int, start: int,
                      n: int) -> float:
        """Mirror of the live chunked-prefill block accounting: tokens grow
        chunk-by-chunk, the slot stays done (out of the decode batch) until
        the final chunk, then joins with the whole-prompt end state."""
        total_len = req.prompt_len + req.n_generated
        feed_total = total_len - 1
        if start == 0:
            self.done[slot] = True
            self.rids[slot] = req.rid
            if self.kv is not None:
                self.kv.prefill(slot, n)
                self.kv.mark_pending(slot)
        elif self.kv is not None:
            self.kv.ensure(slot, start + n)
            self.kv.commit(slot, n)
        if start + n == feed_total:
            if self.kv is not None:
                # cover the row the first decode step writes (row total-1)
                self.kv.ensure(slot, total_len)
                self.kv.commit(slot, 1)
                self.kv.clear_pending(slot)
            self.done[slot] = False
        if self.kv is not None:
            self.kv.evicted_pending.clear()  # no device rows to wipe in sim
        if self.chunk_source is not None:
            return float(self.chunk_source(req.rid))
        return n * self.prefill_token_cost

    def step(self, s: int) -> Tuple[float, np.ndarray, np.ndarray]:
        active = np.where(~self.done)[0]
        b = len(active)
        bk = self._batch_key(b)
        if self.kv is not None:
            # same slot set as the live engine's pre-step growth: every slot
            # still holding blocks (incl. EOS'd rows awaiting retirement),
            # minus mid-prefill slots (they grow chunk-by-chunk instead)
            for slot in self.kv.active_slots():
                if self.kv.is_pending(slot):
                    continue
                self.kv.ensure(slot, self.kv.tokens(slot) + s)
            self.kv.evicted_pending.clear()  # no device rows to wipe in sim
        if self.duration_source is not None:
            dt = float(self.duration_source(self._step_idx, b, s))
        else:
            dt = self.model.t_verify(bk, s) + s * self.model.t_s[bk]
        if self.accept_source is not None:
            accepted = np.asarray(
                self.accept_source(self._step_idx, self.rids[active], s))
        else:
            accepted = self.acceptance.draw(b, s)
        committed = np.zeros(self.capacity, dtype=np.int64)
        # accepted = -1 encodes a replayed zero-commit step (the live engine
        # had already stopped this request: EOS / engine-level max_new);
        # mirror the live backend by marking the slot done so the scheduler
        # retires it the same iteration
        committed[active] = np.maximum(accepted + 1, 0)
        self.done[active[committed[active] == 0]] = True
        if self.done_source is not None:
            rec = {int(r) for r in self.done_source(self._step_idx)}
            for slot in active:
                if int(self.rids[slot]) in rec:
                    self.done[slot] = True
        if self.kv is not None:
            for slot in self.kv.active_slots():
                if not self.kv.is_pending(slot):
                    self.kv.commit(slot, int(committed[slot]))
        self._step_idx += 1
        return dt, committed, self.done.copy()

    def preempt(self, slot: int, req: Request) -> None:
        self.done[slot] = True
        self.rids[slot] = -1
        if self.kv is not None:
            self.kv.release(slot)

    def retire(self, slot: int, req: Optional[Request] = None) -> None:
        self.done[slot] = True
        self.rids[slot] = -1
        if self.kv is not None:
            self.kv.release(slot)

    # ------------------------------------------------------------------
    # prefix-cache protocol — same five methods as the live backend, over
    # the same PrefixCache machinery, so lock/attach/insert block
    # accounting (and therefore every admission/preemption decision)
    # re-derives identically; only device work (and its clock cost) is
    # absent.

    def match_and_lock(self, req: Request) -> int:
        """Longest cached prefix of the *prompt*, locked; returns tokens."""
        if self.cache is None:
            return 0
        blocks = self.cache.lock(req.tokens[:req.prompt_len])
        if not blocks:
            return 0
        self._locked[req.rid] = blocks
        return len(blocks) * self.kv.block_size

    def cancel_match(self, req: Request) -> None:
        """Drop the lock taken by :meth:`match_and_lock` (admission abort)."""
        blocks = self._locked.pop(req.rid, None)
        if blocks:
            self.cache.unlock(blocks)

    def attach(self, req: Request, slot: int, n_prefix: int) -> float:
        """Map the locked prefix blocks into ``slot``'s table at ref+1."""
        blocks = self._locked.pop(req.rid)
        self.done[slot] = True            # mid-admission: out of decode batch
        self.rids[slot] = req.rid
        self.kv.attach(slot, blocks, n_prefix)
        self.kv.mark_pending(slot)
        self.cache.unlock(blocks)
        return 0.0

    def commit_attached(self, req: Request, slot: int) -> float:
        """Zero-suffix admission: the whole feedable prompt was cached.

        Mirrors the live engine's commit: COW the block holding row
        total-1 if it is shared, grow to cover the first decode row, and
        join the decode batch.
        """
        total_len = req.prompt_len + req.n_generated
        self.kv.cow_for_range(slot, total_len - 1, total_len)
        self.kv.ensure(slot, total_len)
        self.kv.commit(slot, total_len - self.kv.tokens(slot))
        self.kv.clear_pending(slot)
        self.kv.evicted_pending.clear()  # no device rows to wipe in sim
        self.done[slot] = False
        if self.prefill_source is not None:
            return float(self.prefill_source(req.rid))
        return 0.0

    def cache_insert(self, req: Request, slot: int) -> None:
        """Publish ``slot``'s full prompt blocks into the prefix index."""
        if self.cache is None:
            return
        total_len = req.prompt_len + req.n_generated
        # never index the block holding row total-1: the slot's own decode
        # writes land there, and indexed blocks must stay immutable
        rows = min(req.prompt_len, total_len - 1)
        n_ins = rows // self.kv.block_size
        if n_ins:
            self.cache.insert(req.tokens[:n_ins * self.kv.block_size],
                              self.kv.table(slot)[:n_ins])


# ---------------------------------------------------------------------------
# the scheduler


@dataclass
class StepTrace:
    """Per-iteration scheduling record (drives sim-vs-live parity tests)."""
    clock: float
    occupancy: int
    s: int
    rids: Tuple[int, ...]
    committed: Dict[int, int]          # rid -> raw committed this step
    admitted: Tuple[int, ...] = ()
    duration: float = 0.0              # step duration charged to the clock
    prefill_s: Tuple[float, ...] = ()  # per-admission prefill seconds
                                       # (-1.0 => admitted via chunks)
    preempted: Tuple[int, ...] = ()    # rids evicted before this step
    done_rids: Tuple[int, ...] = ()    # rids the backend flagged done after
    chunked: Tuple[Tuple[int, int], ...] = ()  # (rid, tokens) chunk events
    chunk_s: Tuple[float, ...] = ()    # per-chunk-event seconds
    cache_hits: Tuple[Tuple[int, int], ...] = ()  # (rid, prefix tokens)
                                       # per prefix-cache-hit admission


def replay_sources(trace: Sequence[StepTrace]):
    """(accept, duration, prefill, done, chunk) replay callbacks from a
    trace.

    Feeding these into :class:`SimStepBackend` pins every *outcome* (commit
    counts, step durations, prefill and chunk costs, per-step done flags)
    to the recorded run, so a second scheduler run over the sim backend
    must reproduce the recorded admission order, chunk schedule, and
    batch-size sequence exactly — the sim-vs-live parity check.  Preemption
    decisions are NOT replayed: they are pure functions of the block-pool
    accounting plus the done flags, so a sim backend built with the live
    pool's geometry re-derives them (and the parity test checks they
    match).  Chunk *sizes* are likewise re-derived (they are pure functions
    of the admission budget) — only their durations are replayed.

    ``step_idx`` counts executed steps: iterations that only fed prefill
    chunks (no live decode row) record a trace entry but no backend step,
    so the replay indexes into the occupancy > 0 subset of the trace.

    A preempted request is admitted (and so prefilled) more than once, so
    per-rid prefill/chunk costs replay as FIFO queues of the recorded
    durations.

    Prefix-cache admissions need no extra channel: cache decisions are
    re-derived by the sim backend's own cache mirror, a zero-suffix hit
    records its attach+commit seconds as an ordinary ``prefill_s`` entry
    (consumed by the sim's ``commit_attached``), and a hit with an
    uncached suffix folds its attach seconds into the first suffix
    chunk's recorded duration.
    """
    steps = [t for t in trace if t.occupancy > 0]
    prefill: Dict[int, List[float]] = {}
    chunks: Dict[int, List[float]] = {}
    for t in trace:
        for rid, dt in zip(t.admitted, t.prefill_s):
            if dt >= 0:                # -1.0 marks a chunked admission
                prefill.setdefault(rid, []).append(dt)
        for (rid, _m), dt in zip(t.chunked, t.chunk_s):
            chunks.setdefault(rid, []).append(dt)

    def accept(step_idx, rids, s):
        # committed - 1; a recorded 0 maps to -1 (zero-commit step: the
        # recorded run had retired this request via EOS / engine max_new)
        rec = steps[step_idx].committed
        return np.array([rec.get(int(r), 1) - 1 for r in rids])

    def duration(step_idx, b, s):
        return steps[step_idx].duration

    def prefill_src(rid):
        q = prefill.get(rid)
        return q.pop(0) if q else 0.0

    def done_src(step_idx):
        return steps[step_idx].done_rids

    def chunk_src(rid):
        q = chunks.get(rid)
        return q.pop(0) if q else 0.0

    return accept, duration, prefill_src, done_src, chunk_src


class ContinuousScheduler:
    """Iteration-level serving loop over any step backend.

    After :meth:`run`, ``self.trace`` holds one :class:`StepTrace` per
    iteration (admission order, live batch size, per-request commits,
    chunked-prefill events) — the observable scheduling behaviour compared
    in parity tests.
    """

    def __init__(self, backend, controller: AdaptiveController,
                 policy: Optional[AdmissionPolicy] = None,
                 observe: bool = False,
                 telemetry=None):
        self.backend = backend
        self.controller = controller
        self.policy = policy or ImmediateAdmit()
        self.observe = observe
        self.telemetry = telemetry
        # zero-overhead-when-off: _tel is None unless an *enabled* hub was
        # supplied, and with it None and the backend's span switch off,
        # every span of run() is the shared no-op NULLSPAN — no
        # perf_counter calls and no event construction
        self._tel = (telemetry if telemetry is not None
                     and getattr(telemetry, "enabled", True) else None)
        if (self._tel is not None
                and getattr(self._tel, "expected_acceptance", None) is None
                and getattr(controller, "model", None) is not None):
            # the controller carries the analytical model: wire the
            # acceptance observatory's drift baseline automatically
            model = controller.model
            self._tel.attach_expected_acceptance(
                lambda s: model.l_of_s(s) / s)
        self.trace: List[StepTrace] = []
        # the controller's speculation ceiling, not the global S_MAX, is the
        # worst-case reservation unit for admission/overflow checks
        self.s_cap = controller_s_cap(controller)
        if hasattr(backend, "s_cap"):
            backend.s_cap = self.s_cap

    @staticmethod
    def _select_victim(slots: Sequence[int], pool: SlotPool,
                       admit_seq: Dict[int, int]) -> int:
        """Preemption victim: longest remaining token budget, ties broken
        LIFO by admission order (the most recently admitted goes first)."""
        return max(slots, key=lambda sl: (pool.remaining(sl),
                                          admit_seq[pool.request_at(sl).rid]))

    def run(self, requests: Sequence[Request]):
        from repro.serving.server import ServeResult   # avoid import cycle
        pending = sorted(requests, key=lambda r: r.arrival)
        pool = SlotPool(self.backend.capacity)
        # sharded pool: round-robin slot placement across the data shards
        # (placement only — admission order and the trace are unaffected)
        n_shards = getattr(self.backend, "n_shards", 1)
        shardq = (HostShardQueue(self.backend.capacity, n_shards)
                  if n_shards > 1 else None)
        backlog: List[Request] = []
        batches: List[BatchRecord] = []
        self.trace = []
        kv = getattr(self.backend, "kv", None)
        # prefix cache: both stock backends expose .cache (None unless built
        # with prefix_cache=True); foreign backends without the attribute
        # simply never enter the cache paths
        cache_on = getattr(self.backend, "cache", None) is not None
        max_ctx = getattr(self.backend, "max_context", None)
        s_cap = self.s_cap
        chunk_cfg = getattr(self.policy, "chunk_tokens", None)
        budget_cfg = getattr(self.policy, "token_budget", None)
        chunking = (chunk_cfg is not None
                    and getattr(self.backend, "can_chunk", False))
        prefilling: Dict[int, Request] = {}   # slot -> mid-chunked-prefill
        admit_seq: Dict[int, int] = {}
        n_admits = 0
        prev_done: set = set()         # rids the backend flagged done last step

        def decode_slots() -> List[int]:
            return [sl for sl in pool.active_slots() if sl not in prefilling]

        def growth_reserve(s: int) -> int:
            """Blocks the running decode batch may claim this step."""
            return sum(
                max(0, kv.blocks_for(kv.tokens(sl) + s) - kv.allocated(sl))
                for sl in decode_slots())

        def pending_reserve(exclude: Optional[int] = None) -> int:
            """Blocks the mid-prefill slots still need to complete.  Keeping
            ``free >= this`` at all times is what guarantees every admitted
            chunked prefill can finish (no admit-then-starve)."""
            tot = 0
            for sl, rq in prefilling.items():
                if sl == exclude:
                    continue
                tot += max(0, kv.blocks_for(rq.prompt_len + rq.n_generated)
                           - kv.allocated(sl))
            return tot

        tel = self._tel
        on = False            # the span switch, read once per iteration

        def scope(phase: str, **attrs):
            """A span of this iteration (telemetry.span)."""
            if tel is None and not on:
                return NULLSPAN
            return span(phase, tel, on, iter=len(self.trace), **attrs)

        clock, i, n_done, n = 0.0, 0, 0, len(pending)
        while n_done < n:
            on = getattr(self.backend, "annotate", False)
            while i < n and pending[i].arrival <= clock:
                backlog.append(pending[i])
                i += 1
            admitted: List[int] = []
            prefill_s: List[float] = []
            chunked: List[Tuple[int, int]] = []
            chunk_s: List[float] = []
            cache_hits: List[Tuple[int, int]] = []
            budget_left = (budget_cfg if (chunking and budget_cfg is not None)
                           else float("inf"))

            def feed_chunk(req: Request, slot: int, m: int,
                           extra: float = 0.0) -> None:
                # ``extra`` folds a cache-attach's seconds into the first
                # suffix chunk's recorded duration, so replay_sources needs
                # no extra replay channel for attach costs
                nonlocal clock
                start = req.prefill_pos
                with scope("sched.chunk_continue", rid=req.rid, slot=slot,
                           start=start, n=m) as sp:
                    dt = (self.backend.prefill_chunk(req, slot, start, m)
                          + extra)
                    sp.set(dt=dt)
                clock += dt
                chunked.append((req.rid, m))
                chunk_s.append(dt)
                req.prefill_pos += m
                if (cache_on and req.prefill_pos
                        == req.prompt_len + req.n_generated - 1):
                    # final chunk: the slot joins the decode batch — publish
                    # its prompt blocks into the prefix index
                    self.backend.cache_insert(req, slot)

            def claim_for(req: Request) -> int:
                """Shared admission bookkeeping (both admission modes)."""
                nonlocal n_admits
                backlog.remove(req)
                slot = (shardq.claim(pool, req) if shardq is not None
                        else pool.claim(req))
                if req.start is None:  # keep the first admission's start
                    req.start = clock
                n_admits += 1
                admit_seq[req.rid] = n_admits
                admitted.append(req.rid)
                return slot

            def attach_admit(req: Request, slot: int, P: int,
                             suffix_chunk: int) -> None:
                """Admission via a cached prefix: map the matched blocks
                into the slot, then either commit straight into the decode
                batch (zero uncached suffix) or feed the first
                ``suffix_chunk`` uncached feed-positions."""
                nonlocal clock
                total_len = req.prompt_len + req.n_generated
                feed_total = total_len - 1
                cache_hits.append((req.rid, P))
                a_dt = self.backend.attach(req, slot, P)
                req.prefill_pos = P
                if P >= feed_total:
                    with scope("sched.prefill", rid=req.rid, slot=slot,
                               tokens=total_len - P, cached=P) as sp:
                        c_dt = self.backend.commit_attached(req, slot)
                        sp.set(dt=a_dt + c_dt)
                    clock += a_dt + c_dt
                    prefill_s.append(a_dt + c_dt)
                    self.backend.cache_insert(req, slot)
                else:
                    prefill_s.append(-1.0)
                    feed_chunk(req, slot, suffix_chunk, extra=a_dt)

            with scope("sched.admit") as adm:
                # ---- continue in-flight chunked prefills (Sarathi: ongoing
                # prefills spend the budget before new admissions) ----
                if chunking and prefilling:
                    for slot in sorted(prefilling,
                                       key=lambda sl: admit_seq[
                                           prefilling[sl].rid]):
                        if budget_left <= 0:
                            break
                        req = prefilling[slot]
                        feed_total = req.prompt_len + req.n_generated - 1
                        start = req.prefill_pos
                        m = int(min(chunk_cfg, feed_total - start,
                                    budget_left))
                        if kv is not None:
                            # blocks actually available to this chunk right now
                            # (free + reclaimable cache-only blocks: the pool
                            # evicts on demand when the free list runs short)
                            avail = (kv.available_blocks
                                     - growth_reserve(s_cap)
                                     - pending_reserve(exclude=slot))
                            cap_rows = ((kv.allocated(slot) + avail)
                                        * kv.block_size - start)
                            if cap_rows < feed_total - start + 1:
                                # full completion (incl. the +1 commit row)
                                # does not fit yet: feed what fits, short of
                                # the final position
                                m = min(m, max(cap_rows, 0),
                                        feed_total - start - 1)
                        if m <= 0:
                            continue       # blocked on blocks; retry next step
                        feed_chunk(req, slot, m)
                        budget_left -= m
                        if req.prefill_pos == feed_total:
                            del prefilling[slot]
                # ---- admissions ----
                if chunking:
                    # budgeted admission supersedes policy.select(): its
                    # whole-prompt budget semantics (skip over-budget heads)
                    # exist precisely because chunk-incapable backends cannot
                    # split a prompt — here an over-budget prompt is admitted
                    # chunked instead, in the same FCFS order select() uses
                    for req in list(backlog):
                        if pool.free_count == 0 or budget_left <= 0:
                            break
                        if max_ctx is not None:
                            _reject_oversize(req, max_ctx, s_cap)
                        total_len = req.prompt_len + req.n_generated
                        # longest cached prefix of the prompt, pinned against
                        # eviction until attach (or the break below)
                        P = (self.backend.match_and_lock(req) if cache_on
                             else 0)
                        if kv is not None:
                            # reserve the full prompt + first-step worst case
                            # up front (plus the running batch's growth and
                            # the other pending prefills' completion) — a
                            # chunked admission that could not finish would
                            # hold blocks forever.  A cache hit attaches
                            # P // block_size blocks for free (+1 only for the
                            # COW copy when the whole prompt is cached);
                            # reclaimable cache-only blocks count as
                            # available (eviction on demand).
                            need = (kv.blocks_for(total_len + s_cap)
                                    - P // kv.block_size
                                    + (1 if P == total_len else 0))
                            if (need + growth_reserve(s_cap)
                                    + pending_reserve()
                                    > kv.available_blocks):
                                if P:
                                    self.backend.cancel_match(req)
                                break      # head-of-line: wait for free blocks
                        slot = claim_for(req)
                        req.prefill_pos = 0
                        if P:
                            feed_total = total_len - 1
                            if P >= feed_total:
                                attach_admit(req, slot, P, 0)
                            else:
                                m = int(min(chunk_cfg, budget_left,
                                            feed_total - P))
                                attach_admit(req, slot, P, m)
                                budget_left -= m
                                if req.prefill_pos < feed_total:
                                    prefilling[slot] = req
                        elif total_len <= budget_left:
                            with scope("sched.prefill", rid=req.rid, slot=slot,
                                       tokens=total_len) as sp:
                                p_dt = self.backend.prefill(req, slot)
                                sp.set(dt=p_dt)
                            clock += p_dt
                            prefill_s.append(p_dt)
                            budget_left -= total_len
                            if cache_on:
                                self.backend.cache_insert(req, slot)
                        else:
                            # over the remaining budget: admit CHUNKED —
                            # never a whole-prompt burst (bounds this
                            # iteration's stall)
                            prefill_s.append(-1.0)
                            feed_total = total_len - 1
                            m = int(min(chunk_cfg, budget_left, feed_total))
                            feed_chunk(req, slot, m)
                            budget_left -= m
                            if req.prefill_pos < feed_total:
                                prefilling[slot] = req
                else:
                    for req in self.policy.select(backlog, pool.free_count,
                                                  clock):
                        if max_ctx is not None:
                            # oversized requests can NEVER be served (deferring
                            # would spin forever); fail loudly before claiming
                            _reject_oversize(req, max_ctx, s_cap)
                        total_len = req.prompt_len + req.n_generated
                        P = (self.backend.match_and_lock(req) if cache_on
                             else 0)
                        if kv is not None:
                            # admit only if the free list covers the prompt
                            # (plus stash), this request's worst-case first
                            # step, AND the running batch's own worst-case
                            # growth — otherwise a fresh admit pays a full
                            # B=1 prefill just to be evicted by the pressure
                            # check below (prefill thrash).  Cache hits and
                            # reclaimable blocks discount as in the chunked
                            # branch above.
                            need = (kv.blocks_for(total_len + s_cap)
                                    - P // kv.block_size
                                    + (1 if P == total_len else 0))
                            if (need + growth_reserve(s_cap)
                                    > kv.available_blocks):
                                if P:
                                    self.backend.cancel_match(req)
                                break      # head-of-line: wait for free blocks
                        slot = claim_for(req)
                        if P:
                            # no per-iteration budget here: any uncached suffix
                            # is fed as one chunk (cache_on implies can_chunk)
                            req.prefill_pos = 0
                            attach_admit(req, slot, P, total_len - 1 - P)
                            continue
                        with scope("sched.prefill", rid=req.rid, slot=slot,
                                   tokens=total_len) as sp:
                            p_dt = self.backend.prefill(req, slot)
                            sp.set(dt=p_dt)
                        clock += p_dt
                        prefill_s.append(p_dt)
                        if cache_on:
                            self.backend.cache_insert(req, slot)
                if admitted:
                    adm.set(dt=sum(dt for dt in prefill_s if dt > 0),
                            rids=tuple(admitted),
                            n_chunked=sum(1 for dt in prefill_s if dt < 0))
            if pool.occupancy == 0:
                if not backlog and i < n:
                    clock = max(clock, pending[i].arrival)
                continue
            # ---- preemption under memory pressure (paged pool only) ----
            # worst case this step commits s+1 tokens per decode slot, i.e.
            # KV writes up to seq_len + s rows; if covering that (plus the
            # pending prefills' completion) could exhaust the free list,
            # evict victims back to the backlog (they re-prefill from
            # prompt + generated stash later).  A lone slot always fits:
            # admission bounds every request to the pool.
            with scope("sched.choose"):
                preempted: List[int] = []
                if kv is not None:
                    while pool.occupancy > 1:
                        ds = decode_slots()
                        s = self.controller.choose(len(ds))
                        need = (growth_reserve(s) + pending_reserve())
                        if need <= kv.available_blocks:
                            break
                        # never evict a slot the backend already flagged
                        # done (EOS'd, awaiting its zero-commit retirement
                        # step): re-prefilling it would resurrect a finished
                        # request and generate past its EOS.  Mid-prefill
                        # slots are not eligible either: their completion is
                        # what the reservation protects.
                        eligible = [sl for sl in ds
                                    if pool.request_at(sl).rid
                                    not in prev_done]
                        if not eligible:
                            break      # done slots free their blocks shortly
                        victim = self._select_victim(eligible, pool,
                                                     admit_seq)
                        req = pool.retire(victim)
                        with scope("sched.preempt", rid=req.rid,
                                   slot=victim, n_generated=req.n_generated):
                            self.backend.preempt(victim, req)
                        req.prefill_pos = 0
                        backlog.insert(0, req)
                        preempted.append(req.rid)
                ds = decode_slots()
                b = len(ds)
                if b > 0:
                    s = self.controller.choose(b)
            if b > 0:
                with scope("sched.decode_verify", s=s, batch=b) as sp:
                    dt, committed, backend_done = self.backend.step(s)
                    sp.set(dt=dt)
                with scope("sched.commit", batch=b) as commit_sp:
                    done_rids = tuple(sorted(
                        pool.request_at(sl).rid for sl in ds
                        if backend_done[sl]))
                    clock += dt
                    n_done0 = n_done
                    toks = 0
                    raw: Dict[int, int] = {}
                    accepted_live: List[int] = []
                    for slot in ds:
                        req = pool.request_at(slot)
                        c_raw = int(committed[slot])
                        raw[req.rid] = c_raw
                        accepted_live.append(max(c_raw - 1, 0))
                        c = min(c_raw, pool.remaining(slot))
                        if c > 0 and req.first_token is None:
                            req.first_token = clock
                        pool.consume(slot, c)
                        req.n_generated += c
                        toks += c
                        # finished: served its token budget, or the backend
                        # stopped committing for it (EOS / engine-level
                        # max_new)
                        if pool.remaining(slot) <= 0 or (
                                c_raw == 0 and backend_done[slot]):
                            req.finish = clock
                            with scope("sched.retire", rid=req.rid,
                                       slot=slot,
                                       n_generated=req.n_generated):
                                pool.retire(slot)
                                self.backend.retire(slot, req)
                            n_done += 1
                    commit_sp.set(tokens=toks, retired=n_done - n_done0)
                    if tel is not None:
                        tel.observe_step(s=s, batch=b,
                                         accepted=accepted_live, duration=dt)
                    if self.observe and s > 0:
                        # lint: allow-host-sync(accepted_live is already a host list; no device transfer)
                        self.controller.observe(np.asarray(accepted_live), s)
                    batches.append(BatchRecord(
                        start=clock - dt, duration=dt, batch_size=b,
                        s_used=s, tokens_generated=toks, n_steps=1,
                        rids=tuple(sorted(raw))))
            else:
                # no live decode row this iteration (all occupied slots are
                # mid-chunked-prefill): the clock advanced by chunk work only
                if not chunked and not admitted and not preempted:
                    raise RuntimeError(
                        "scheduler stalled: occupied slots but no decode "
                        "step, chunk, admission, or preemption this "
                        "iteration (block accounting out of sync?)")
                s, dt, raw, done_rids = 0, 0.0, {}, ()
            self.trace.append(StepTrace(
                clock=clock - dt, occupancy=b, s=s,
                rids=tuple(sorted(raw)), committed=raw,
                admitted=tuple(admitted), duration=dt,
                prefill_s=tuple(prefill_s), preempted=tuple(preempted),
                done_rids=done_rids, chunked=tuple(chunked),
                chunk_s=tuple(chunk_s), cache_hits=tuple(cache_hits)))
            prev_done = set(done_rids)
            if tel is not None:
                g = dict(occupancy=pool.occupancy, decode_batch=b, s=s,
                         prefilling=len(prefilling), backlog=len(backlog),
                         free_slots=pool.free_count,
                         capacity=self.backend.capacity)
                if kv is not None:
                    # ragged-grid occupancy: the share of the dense
                    # B*MAXB attention grid the ragged kernel actually
                    # launches this iteration (read-only over the host
                    # block tables; kernels/tuning.py owns the arithmetic
                    # so the gauge can never drift from the real grid)
                    tabs = kv.device_tables(exclude_pending=True)
                    g.update(free_blocks=kv.free_blocks,
                             used_blocks=kv.num_blocks - kv.free_blocks,
                             fragmentation=kv.fragmentation,
                             grid_occupancy=(grid_steps_ragged(tabs)
                                             / float(grid_steps_dense(tabs))))
                if cache_on:
                    cache = self.backend.cache
                    g.update(shared_blocks=kv.shared_blocks,
                             cached_blocks=kv.cached_blocks,
                             evicted_blocks=kv.evicted_total,
                             cache_hit_rate=(cache.hits
                                             / max(cache.lookups, 1)),
                             cache_hit_tokens=cache.hit_tokens)
                tel.iteration(len(self.trace) - 1, clock, **g)
        return ServeResult(requests=list(pending), batches=batches)


def serve_continuous_live(requests: Sequence[Request], engine, tparams,
                          dparams, controller: AdaptiveController, *,
                          capacity: int = 8, cache_len: int = 256,
                          policy: Optional[AdmissionPolicy] = None,
                          observe: bool = False,
                          backend: Optional[ContinuousEngineBackend] = None,
                          block_size: Optional[int] = None,
                          num_blocks: Optional[int] = None,
                          mesh=None,
                          paged_fused=None,
                          prefix_cache: bool = False,
                          mixed_launch: bool = False,
                          telemetry=None):
    """Serve a request trace on a LIVE SpecDecodeEngine with iteration-level
    continuous batching: requests join/leave at speculative-step granularity
    and the controller re-chooses s from live occupancy every step.

    The virtual clock advances by measured wall time (compiles warmed
    outside the timed regions), so results are directly comparable with the
    run-to-completion :func:`repro.serving.server.serve` loop and with the
    :class:`SimStepBackend` simulation on the same trace.

    ``block_size`` switches the KV slot pool to the paged block allocator
    (``num_blocks`` sizes it; default worst-case) with preemption under
    memory pressure.  Admission hard-rejects any request whose worst-case
    KV footprint (``prompt_len + max_new`` + the controller's speculation
    ceiling) exceeds the per-request capacity — previously such a request
    silently wrapped its KV ring and corrupted itself.

    A :class:`PrefillBudgetAdmit` policy additionally enables in-step
    chunked prefill: prompts longer than the per-iteration token budget are
    admitted chunk-by-chunk, interleaved with the running batch's decode
    steps.

    ``paged_fused`` forces the paged-attention kernel path for a
    ``block_size`` run: ``True`` streams KV through the block tables with
    the fused Pallas kernel (interpret mode off-TPU), ``False`` keeps the
    materialized gather path, ``None`` (default) leaves the engine's
    current routing untouched — auto (fused on TPU) unless the engine was
    constructed with, or previously forced to, an explicit path.  Token
    outputs and the StepTrace are identical either way
    (tests/test_paged_fused_kernel.py asserts it).

    ``mixed_launch`` (requires ``block_size``) fuses each NON-final prefill
    chunk into the next speculative step as ONE mixed verify+chunk launch
    over the ragged paged kernel: the chunk's prefix-extension queries ride
    the same real-length grid as the batch's verify queries, retiring the
    separate chunk dispatch (and its weight re-stream).  Host block
    accounting still runs at feed time, so admissions, preemptions, token
    outputs and the StepTrace scheduling signature are identical with the
    flag on or off (tests/test_ragged_paged_attn.py asserts it).

    ``prefix_cache`` (requires ``block_size``) turns on cross-request
    prefix sharing: admission matches the longest cached prefix of each
    prompt in a radix index over the block pool, maps those blocks into
    the new slot at refcount+1 and prefills only the uncached suffix;
    shared blocks are copy-on-write and eviction is LRU over cache-only
    blocks.  Token outputs and the StepTrace scheduling signature are
    identical to a cold run (tests/test_prefix_cache.py asserts it).

    ``mesh`` runs the slot pool sharded over the mesh's data axes (SPMD
    serving step, replicated params, round-robin slot placement across the
    data shards via :class:`HostShardQueue`) — token outputs and the
    StepTrace are identical to the single-device run on the same trace.
    On CPU, force multiple host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before importing
    jax to try this without accelerators.

    ``telemetry`` attaches a :class:`repro.serving.telemetry.Telemetry` hub
    to the scheduler and the engine for the run: stamped spans of the
    scheduler, the backend and the engine, the (s, batch) acceptance
    observatory, and pool/scheduler gauges, plus — when the hub was built
    with ``annotate_device`` or ``profile_dir`` — the engine's span switch
    (``engine.annotate``), which puts every span on the profiler timeline as
    a ``repro/<phase>`` ``TraceAnnotation`` (and a profiler trace around the
    run).  Telemetry only *reads* the pipeline: token outputs and the
    StepTrace are identical with it on or off, and a disabled hub costs
    nothing.
    """
    for r in requests:
        if r.max_new > engine.max_new:
            raise ValueError(
                f"request {r.rid} wants {r.max_new} tokens but the engine "
                f"slot pool is sized for max_new={engine.max_new}")
    s_cap = controller_s_cap(controller)
    if (backend is not None and mesh is not None
            and getattr(backend, "mesh", None) is not mesh):
        # an explicit backend owns its pool placement; silently dropping
        # mesh here would let a caller believe a sharded run happened
        raise ValueError(
            "serve_continuous_live: `mesh` conflicts with the explicit "
            "`backend` (which was built with a different mesh, or none); "
            "construct the backend with mesh=... or omit one of the two")
    if backend is not None and paged_fused is not None:
        # the backend compiled its pool with a kernel path already; silently
        # dropping the flag would let a caller believe it took effect
        raise ValueError(
            "serve_continuous_live: pass paged_fused to the "
            "ContinuousEngineBackend constructor when supplying an explicit "
            "backend (the kernel path is baked in at pool init)")
    if backend is not None and mixed_launch:
        # the defer/flush bookkeeping lives on the backend; silently
        # dropping the flag would let a caller believe fusion was on
        raise ValueError(
            "serve_continuous_live: pass mixed_launch=True to the "
            "ContinuousEngineBackend constructor when supplying an explicit "
            "backend (the deferred-chunk bookkeeping lives on it)")
    if backend is not None and prefix_cache:
        # the cache wraps the backend's pool at construction time; silently
        # dropping the flag would let a caller believe sharing was on
        raise ValueError(
            "serve_continuous_live: pass prefix_cache=True to the "
            "ContinuousEngineBackend constructor when supplying an explicit "
            "backend (the cache wraps the pool at init)")
    if backend is None:
        warm = sorted(set(controller.lut.table.values()))
        backend = ContinuousEngineBackend(engine, tparams, dparams,
                                          capacity=capacity,
                                          cache_len=cache_len, warm_s=warm,
                                          block_size=block_size,
                                          num_blocks=num_blocks,
                                          s_cap=s_cap, mesh=mesh,
                                          paged_fused=paged_fused,
                                          prefix_cache=prefix_cache,
                                          mixed_launch=mixed_launch)
    for r in requests:
        if r.prompt_len + r.max_new + s_cap > backend.max_context:
            raise ValueError(
                f"request {r.rid}: prompt_len={r.prompt_len} + "
                f"max_new={r.max_new} + s_cap={s_cap} exceeds the "
                f"per-request KV capacity {backend.max_context}; the KV "
                f"ring would wrap and corrupt itself")
    sched = ContinuousScheduler(backend, controller, policy, observe=observe,
                                telemetry=telemetry)
    tel = sched._tel
    prev = (getattr(engine, "annotate", False),
            getattr(engine, "telemetry", None))
    if tel is not None:
        engine.telemetry = tel
        if tel.annotate_device:
            engine.annotate = True
        tel.start()
    try:
        result = sched.run(requests)
    finally:
        if tel is not None:
            tel.stop()
        engine.annotate, engine.telemetry = prev
    result.trace = sched.trace
    return result
