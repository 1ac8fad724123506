"""Batched speculative decoding (paper §3, Algorithm 1).

One speculative step at speculation length ``s`` for a batch of ``b`` ragged
requests, entirely inside a single jitted computation:

  1. draft phase — the small model (SSM) proposes s tokens autoregressively;
     its first feed is always the *two* most recently committed tokens, which
     restores the draft cache invariant regardless of how much of the
     previous speculation was accepted (DESIGN §3);
  2. verify — the target model scores all b x (s+1) positions in one forward
     (this is the paper's masking trick realized as ragged ring-buffer
     writes + position-based masks);
  3. accept — per request, the longest draft prefix matching the target's
     argmax, plus the target's bonus/correction token (always >=1 token of
     progress per step);
  4. commit — pure length updates for attention caches; checkpoint selection
     for recurrent (SSM / RG-LRU) targets.

``s = 0`` degenerates to plain batched autoregressive decoding (the paper's
no-speculation baseline) with the identical code path.

The engine jit-caches one step function per (batch, s) pair — exactly the
grid the adaptive profiler (core/adaptive.py) measures.

Slot-level runtime support (continuous batching, serving/scheduler.py): a
fixed-capacity :class:`DecodeState` acts as a KV *slot pool*.  Empty slots
are simply rows with ``done = True`` (the step function already masks them
out), so the same compiled step serves every occupancy level.
:meth:`SpecDecodeEngine.init_slots` allocates the pool,
:meth:`SpecDecodeEngine.prefill_into` injects one new request into a live
batch — a jit-cached B=1 prefill followed by a jit-cached per-capacity
scatter into the slot — and :meth:`SpecDecodeEngine.retire_slot` frees a
row, all without recompiling the (capacity, s) step function.

Paged KV design note (vLLM-style, enabling the paper's synergy at high
occupancy): passing ``block_size`` (and optionally ``num_blocks``) to
:meth:`SpecDecodeEngine.init_slots` replaces the per-slot contiguous ring
caches with one shared pool of fixed-size KV blocks.  The device half is
``k/v [nL, num_blocks, block_size, KVH, hd]`` plus a pool-wide ``pos`` map
and a per-slot block table ``bt [capacity, max_blocks]`` threaded through
``DecodeState.tcache``; the host half is a
:class:`~repro.serving.slots.PagedKVTables` free list carried on
``DecodeState.paged``.  Allocation is block-granular and follows the
commit frontier: ``prefill_into`` claims ``ceil(prompt/block)`` blocks and
scatters the B=1 prefill rows block-wise into the pool; every ``step``
first grows each live slot's table to cover its worst-case writes
(``seq_len + s`` rows — the s+1-token commit plus the verify feed) and
afterwards advances the host token mirror by the raw commit counts;
``retire_slot`` frees the blocks and clears their ``pos`` rows with one
jit-cached scatter so a recycled block can never leak stale attendable
keys.  Attention gathers each slot's logical view through the block table
(kernels/paged.py) and reuses the verify kernel unchanged, so short and
long requests stop sharing one worst-case ``cache_len`` and total KV
memory is ``num_blocks * block_size`` instead of ``capacity * cache_len``.
The draft model's (tiny) cache stays a contiguous ring at the logical
per-slot length.

Sharded serving (the production mesh): passing ``mesh`` to
:meth:`SpecDecodeEngine.init_slots` runs the whole slot pool as one SPMD
program.  The pool's capacity axis (and, for paged pools, the shared block
axis) is sharded over the mesh's data axes with the same
:func:`~repro.launch.specs._batch_spec` machinery the decode plans use;
params are placed replicated (data-parallel serving); and every jit-cached
engine function — the (capacity, s) step, the B=1 prefill and chunk
forwards (explicitly replicated), the inject / retire / chunk-commit
scatters — is compiled with explicit ``in_shardings`` / ``out_shardings``
so state never silently migrates or replicates between steps.  Host-side
bookkeeping (block free lists, slot claims, StepTrace) is unchanged, which
is what makes the sharded run token- and trace-identical to the
single-device run (tests/test_sharded_serving.py verifies this under
``XLA_FLAGS=--xla_force_host_platform_device_count=2``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial, wraps
from typing import Any, Dict, Optional, TYPE_CHECKING, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.configs.registry import build_model
from repro.kernels.ops import partition_mesh
from repro.kernels.tuning import host_cu_blocks

if TYPE_CHECKING:  # real import is lazy: serving/__init__ imports back here
    from repro.serving.slots import PagedKVTables

Params = Any

# headroom rows in the per-request output buffer: one speculative step can
# commit up to s + 1 tokens past max_new, and prefill_into scatters B=1
# buffers into pool buffers, so both must size `out` identically.  It is
# also the hard ceiling on s: the step's `out` scatter silently drops
# writes past the buffer, so SpecDecodeEngine.step validates s <= S_MAX.
S_MAX = 8

# shared no-op context of SpecDecodeEngine.scope: with annotation off and no
# hub, every span site enters this (reentrant, stateless) instead of
# constructing a span — the off path does no string formatting and
# allocates nothing
_NULLCTX = contextlib.nullcontext()


def _next_pow2(n: int) -> int:
    """Smallest power of two >= n (host ints; chunk rows-limit buckets)."""
    return 1 << max(int(n) - 1, 0).bit_length()


def _slot_axis(full_shape, single_shape) -> int:
    """The one axis where a B=1 leaf differs from the pool leaf."""
    diff = [i for i, (f, g) in enumerate(zip(full_shape, single_shape))
            if f != g]
    assert len(diff) == 1, (full_shape, single_shape)
    return diff[0]


def _take_slot(full, single, slot):
    """Slice one slot's B=1 view out of every leaf of a pool tree."""
    def one(f, s1):
        ax = _slot_axis(f.shape, s1.shape)
        starts = tuple(slot if i == ax else 0 for i in range(f.ndim))
        return jax.lax.dynamic_slice(f, starts, s1.shape)
    return jax.tree.map(one, full, single)


def _put_slot(full, upd, single, slot):
    """Scatter a B=1 update back into its slot row of a pool tree."""
    def one(f, u, s1):
        ax = _slot_axis(f.shape, s1.shape)
        starts = tuple(slot if i == ax else 0 for i in range(f.ndim))
        return jax.lax.dynamic_update_slice(f, u.astype(f.dtype), starts)
    return jax.tree.map(one, full, upd, single)


@dataclasses.dataclass
class DecodeState:
    """Device-side state of a running batch."""
    tcache: Any
    dcache: Any
    seq_lens: jax.Array      # [B] committed tokens (incl. any modality prefix)
    last2: jax.Array         # [B, 2] tokens at positions n-2, n-1
    out: jax.Array           # [B, max_new + s_max] generated tokens
    n_generated: jax.Array   # [B]
    done: jax.Array          # [B] bool
    # host half of the paged KV pool (block free list + per-slot tables);
    # None for contiguous per-slot ring caches
    paged: Optional["PagedKVTables"] = None


@dataclasses.dataclass
class StepStats:
    accepted: np.ndarray     # [B] accepted draft tokens this step (a)
    committed: np.ndarray    # [B] tokens committed this step (a+1, 0 if done)


@dataclasses.dataclass
class DeferredChunk:
    """A paged, NON-final prefill chunk whose host bookkeeping (block
    allocation, pending marking, first-chunk begin) has already run but
    whose forward dispatch was deferred (``prefill_chunk_into(...,
    defer=True)``).  Consumed either by :meth:`SpecDecodeEngine.
    step_with_chunk` — the mixed verify+chunk launch, one ragged kernel
    call serving both query kinds — or by :meth:`SpecDecodeEngine.
    flush_chunk`, the ordinary standalone dispatch.  Either way the pool
    ends bit-identical (per-query-row independence; the parked slot's
    verify writes are dropped in both orders)."""
    slot: int
    tokens: np.ndarray       # the CB-bucketed chunk tokens
    start: int               # first feed position this chunk writes
    total_len: int           # the request's full prompt(+stash) length
    bt_row: np.ndarray       # [max_blocks] the slot's host table row
    key: Tuple               # the standalone chunk-fn cache key


@dataclasses.dataclass
class PoolShardings:
    """NamedSharding trees of a mesh-sharded slot pool (one per init_slots).

    Every engine jit below threads these through ``in_shardings`` /
    ``out_shardings``: pool-shaped leaves carry their capacity-axis (or
    block-axis) sharding, while params, B=1 prefill outputs, scalars, and
    host-built index vectors use ``rep`` (explicitly replicated).
    """
    tcache: Any
    dcache: Any              # None when the engine has no draft model
    seq_lens: Any
    last2: Any
    out: Any
    n_generated: Any
    done: Any
    rep: Any                 # NamedSharding(mesh, P()) — fully replicated
    cu: Any = None           # cu_blocks / cu_row ragged-grid scalar operands

    @property
    def cu_sh(self):
        """Sharding of the host-built cu operands (rep if spec absent)."""
        return self.cu if self.cu is not None else self.rep

    @property
    def dc(self):
        """Draft-cache sharding usable as a jit prefix (rep if no draft)."""
        return self.dcache if self.dcache is not None else self.rep


@dataclasses.dataclass
class JitEntry:
    """One live engine jit, registered for graph-lint (tools/graphlint).

    Every jit the engine builds goes through
    :meth:`SpecDecodeEngine._register_jit`, which records the compiled
    function together with its standing contracts — which argnums carry KV
    pool / cache leaves and must be donated (``kv_args``), the declared
    output shardings of a sharded pool, whether the paged fused path may
    legally materialize a gathered-KV view — plus a trace counter and the
    arg/out ShapeDtypeStructs captured at trace time, so graph-lint can
    re-lower exactly the jits the dispatch loop runs instead of a drifting
    hand-maintained list.
    """
    name: str                      # jit family: step / prefill / inject / ...
    key: Tuple                     # engine cache key, e.g. (B, s) for step
    hot: bool                      # dispatched inside the serving iteration
    kv_args: Tuple[int, ...]       # argnums that carry pool/cache leaves
    donate: Tuple[int, ...]        # argnums actually passed to donate_argnums
    sharded: bool                  # built with explicit in/out shardings
    out_shardings: Any             # declared out_shardings tree (or None)
    paged_rows: Optional[int]      # paged pool logical_len (gather-view rows)
    paged_fused: Any               # tcfg.paged_fused at build time
    src_file: str                  # def site of the traced fn
    src_line: int
    cu_arg: Optional[int] = None   # argnum of the cu_blocks ragged-grid operand
    fn: Any = None                 # the jax.jit-wrapped callable
    n_traces: int = 0              # incremented on every (re)trace
    arg_specs: Any = None          # ShapeDtypeStruct tree of the last trace
    out_specs: Any = None


def _trace_spec(x):
    """ShapeDtypeStruct of a leaf seen during tracing (tracers carry avals)."""
    aval = getattr(x, "aval", None)
    if aval is not None:
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype)
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))


def _copy_arrays(tree):
    """Deep-copy every jax.Array leaf of ``tree`` (sharding-preserving).

    Warm (compile-only) dispatches discard their results; with buffer
    donation the call would otherwise invalidate the *live* pool buffers it
    was handed, so warm paths feed the jits disposable copies instead.
    """
    return jax.tree.map(
        lambda x: jnp.copy(x) if isinstance(x, jax.Array) else x, tree)


class SpecDecodeEngine:
    """Target + draft pair with adaptive-ready batched speculative stepping."""

    def __init__(self, target_cfg: ModelConfig, draft_cfg: Optional[ModelConfig],
                 max_new: int = 128, eos_id: int = -1, dtype=jnp.float32,
                 sample: bool = False, temperature: float = 1.0,
                 paged_fused: Optional[bool] = None,
                 donate: bool = True):
        if paged_fused is not None:
            # route the paged-pool attention (kernels/paged.py): None = auto
            # (fused on TPU, gather reference on CPU), True = force the
            # fused streaming kernel, False = force gather+verify.  The
            # flag is trace-time static, so it lives on the model config
            # and every engine jit compiled from it picks it up.
            target_cfg = target_cfg.with_(paged_fused=paged_fused)
        self.tcfg = target_cfg
        self.dcfg = draft_cfg
        self.target = build_model(target_cfg)
        self.draft = build_model(draft_cfg) if draft_cfg is not None else None
        self.max_new = max_new
        self.eos_id = eos_id
        self.dtype = dtype
        self.sample = sample
        self.temperature = temperature
        # buffer donation for the KV pool / cache leaves of every state-
        # threading jit (step / inject / retire / chunk): each dispatch
        # reuses its input pool buffers for the outputs instead of double-
        # buffering the multi-GB pool.  donate=False keeps the old copying
        # semantics — callers that re-step a *stale* DecodeState (the input
        # buffers of a previous step) need it, since donation deletes those
        # buffers.  graph-lint's donation pass is the standing proof that
        # the default stays on and actually aliases in the lowered HLO.
        self.donate = donate
        # the span switch (serving/telemetry.py): when True, every span site
        # of the engine, the serving backend and the scheduler — host prep,
        # syncs, and each jit dispatch — runs under a ``repro/<phase>``
        # jax.profiler.TraceAnnotation, so a profiler trace attributes host
        # and device time per phase.  ``telemetry`` (a hub) additionally
        # records every span with perf_counter stamps.  With the flag False
        # and no hub, span sites enter a shared nullcontext and never even
        # format the span name.
        self.annotate: bool = False
        self.telemetry = None
        # draft models are text-only: for VLM targets their positions run
        # without the modality prefix offset
        self.prefix_offset = target_cfg.prefix_len if target_cfg.family == "vlm" else 0
        self._step_fns: Dict[Tuple[int, int], Any] = {}
        self._mixed_step_fns: Dict[Tuple, Any] = {}
        self._prefill_fns: Dict[Tuple[int, int, int], Any] = {}
        self._inject_fn: Any = None
        self._inject_paged_fn: Any = None
        self._retire_fn: Any = None
        self._retire_paged_fn: Any = None
        self._chunk_fns: Dict[Tuple, Any] = {}
        self._chunk_begin_fns: Dict[bool, Any] = {}
        self._chunk_commit_fns: Dict[bool, Any] = {}
        # prefix-cache (shared-block) paths: draft-only prefix prefill keyed
        # (P_pad, L), the attach park scatter keyed (has_draft,), the COW
        # block-copy scatter and the evicted-block pos wipe
        self._attach_fns: Dict[Tuple[int, int], Any] = {}
        self._attach_park_fns: Dict[bool, Any] = {}
        self._block_copy_fn: Any = None
        self._evict_fn: Any = None
        # graph-lint jit registry: one JitEntry per live compiled function,
        # keyed (name, key).  Populated by _register_jit as the caches above
        # fill; cleared with them so the registry never outlives a sharding
        # or kernel-routing change.
        self.jit_registry: Dict[Tuple[str, Tuple], JitEntry] = {}
        # sharded-serving state, set by init_slots(mesh=...): the mesh, the
        # pool's NamedSharding trees, the capacity they were built for, and
        # how many data shards the capacity axis splits into
        self.mesh: Optional[Mesh] = None
        self._shardings: Optional[PoolShardings] = None
        self._shard_capacity: Optional[int] = None
        self.n_data_shards: int = 1
        # True when init_slots auto-pinned paged_fused=False for a sharded
        # paged pool (restored to auto on the next unsharded init_slots)
        self._paged_fused_auto: bool = False

    def set_paged_fused(self, paged_fused: Optional[bool]) -> None:
        """Re-route the paged-pool attention kernel (fused vs gather).

        The flag is baked into every traced step/prefill/chunk function, so
        flipping it rebuilds the target model from its config and drops all
        cached compilations.  Call before :meth:`init_slots` — a pool
        mid-flight would otherwise mix kernels across steps (numerically
        identical, but the point of forcing a path is to not mix them).
        """
        # any explicit call supersedes a sharded-pool auto-pin: the next
        # unsharded init_slots must not silently revert the caller's choice
        self._paged_fused_auto = False
        if paged_fused == self.tcfg.paged_fused:
            return
        self.tcfg = self.tcfg.with_(paged_fused=paged_fused)
        self.target = build_model(self.tcfg)
        self._reset_jit_caches()

    def _reset_jit_caches(self) -> None:
        """Drop every cached compilation.  init_slots calls this so a pool
        re-initialised with a different mesh (or none) can never reuse a
        step/prefill/inject function compiled for the old sharding."""
        self._step_fns.clear()
        self._mixed_step_fns.clear()
        self._prefill_fns.clear()
        self._inject_fn = None
        self._inject_paged_fn = None
        self._retire_fn = None
        self._retire_paged_fn = None
        self._chunk_fns.clear()
        self._chunk_begin_fns.clear()
        self._chunk_commit_fns.clear()
        self._attach_fns.clear()
        self._attach_park_fns.clear()
        self._block_copy_fn = None
        self._evict_fn = None
        self.jit_registry.clear()

    def scope(self, phase: str, *fmt):
        """The span of one phase (``telemetry.span``), named
        ``phase.format(*fmt)``: formatted and built only when the span
        switch is on or a hub is attached."""
        if not self.annotate and self.telemetry is None:
            return _NULLCTX
        from repro.serving.telemetry import span
        return span(phase.format(*fmt) if fmt else phase, self.telemetry,
                    self.annotate)

    @property
    def compile_log(self):
        """The process-wide compile log (``telemetry.compile_log``); each
        entry's ``fun_name`` names the engine jit by registry entry and
        key."""
        from repro.serving.telemetry import compile_log
        return compile_log()

    def _register_jit(self, name: str, key: Tuple, fn, *, hot: bool,
                      kv_args: Tuple[int, ...] = (),
                      in_shardings=None, out_shardings=None,
                      paged_rows: Optional[int] = None,
                      cu_arg: Optional[int] = None):
        """jax.jit ``fn`` with the engine's standing contracts attached.

        ``kv_args`` are the argnums carrying KV pool / cache leaves: they
        become ``donate_argnums`` (unless the engine was built with
        ``donate=False``) and are recorded on the :class:`JitEntry` either
        way, so graph-lint can flag an engine whose pool leaves stopped
        being donated.  The wrapper body only runs while jax traces, so the
        per-entry trace counter and arg/out spec capture cost nothing on
        the cached dispatch path.
        """
        from repro.serving.telemetry import compile_log
        compile_log()                  # listen before the first compile
        donate = tuple(kv_args) if self.donate else ()
        code = fn.__code__
        entry = JitEntry(
            name=name, key=tuple(key), hot=hot, kv_args=tuple(kv_args),
            donate=donate, sharded=in_shardings is not None,
            out_shardings=out_shardings, paged_rows=paged_rows,
            paged_fused=self.tcfg.paged_fused, cu_arg=cu_arg,
            src_file=code.co_filename, src_line=code.co_firstlineno)

        # a jit compiled for the serving mesh traces its kernels per device
        # (XLA cannot partition a Pallas TPU kernel; kernels/ops.py)
        mesh = self.mesh if in_shardings is not None else None

        @wraps(fn)
        def counted(*args, **kwargs):
            entry.n_traces += 1
            entry.arg_specs = jax.tree.map(_trace_spec, args)
            with partition_mesh(mesh):
                out = fn(*args, **kwargs)
            entry.out_specs = jax.tree.map(_trace_spec, out)
            return out
        # the compile log's fun_name: registry entry and key
        counted.__name__ = (f"{name}__{'_'.join(map(str, key))}" if key
                            else name)

        kw: Dict[str, Any] = {}
        if in_shardings is not None:
            kw["in_shardings"] = in_shardings
            kw["out_shardings"] = out_shardings
        if donate:
            kw["donate_argnums"] = donate
        # lint: allow-jit-sharding(shardings thread through **kw; every builder call site picks them under its own `sh is None` branch)
        entry.fn = jax.jit(counted, **kw)
        self.jit_registry[(name, tuple(key))] = entry
        return entry.fn

    # ------------------------------------------------------------------
    # prefill

    def _build_prefill(self, B: int, P: int, cache_len: int):
        tgt, drf = self.target, self.draft

        def fn(tparams, dparams, tokens, prompt_lens, tkw):
            src_len = (tkw["src_embeds"].shape[1]
                       if self.tcfg.family in ("encdec", "audio") else None)
            tcache, dcache = self._init_caches(B, cache_len, src_len)
            _, tcache, total = tgt.prefill(tparams, tokens, tcache,
                                           prompt_lens=prompt_lens - 1, **tkw)
            seq_lens = total + 1
            if drf is not None:
                _, dcache, _ = drf.prefill(dparams, tokens, dcache,
                                           prompt_lens=prompt_lens - 2)
            bidx = jnp.arange(B)
            last2 = jnp.stack([tokens[bidx, prompt_lens - 2],
                               tokens[bidx, prompt_lens - 1]], axis=1)
            return tcache, dcache, seq_lens, last2

        sh = self._shardings
        if sh is None:
            return self._register_jit("prefill", (B, P, cache_len), fn,
                                      hot=False)
        # sharded pool: the B=1 admission prefill runs explicitly REPLICATED
        # across the mesh (B=1 cannot shard the batch axis) so its outputs
        # can be scattered into any slot of any data shard without an
        # implicit-replication round-trip
        return self._register_jit("prefill", (B, P, cache_len), fn, hot=False,
                                  in_shardings=(sh.rep,) * 5,
                                  out_shardings=sh.rep)

    def prefill(self, tparams, dparams, tokens: jax.Array, prompt_lens: jax.Array,
                cache_len: int, target_extras: Optional[Dict] = None) -> DecodeState:
        B, P = tokens.shape
        assert int(np.min(np.asarray(prompt_lens))) >= 3, "prompts need >= 3 tokens"
        key = (B, P, cache_len)
        if key not in self._prefill_fns:
            self._prefill_fns[key] = self._build_prefill(B, P, cache_len)
        with self.scope("prefill[B={},P={}]", B, P):
            tcache, dcache, seq_lens, last2 = self._prefill_fns[key](
                tparams, dparams, jnp.asarray(tokens),
                jnp.asarray(prompt_lens), target_extras or {})
        return DecodeState(
            tcache=tcache, dcache=dcache, seq_lens=seq_lens, last2=last2,
            out=jnp.zeros((B, self.max_new + S_MAX + 1), jnp.int32),
            n_generated=jnp.zeros((B,), jnp.int32),
            done=jnp.zeros((B,), bool),
        )

    # ------------------------------------------------------------------
    # slot pool (continuous batching; serving/scheduler.py drives this)

    def _init_caches(self, B: int, cache_len: int, src_len: Optional[int] = None):
        tgt, drf = self.target, self.draft
        if self.tcfg.family in ("encdec", "audio"):
            tcache = tgt.init_cache(B, cache_len=cache_len, dtype=self.dtype,
                                    src_len=src_len or cache_len)
        elif self.tcfg.family == "ssm":
            tcache = tgt.init_cache(B, dtype=self.dtype)
        else:
            tcache = tgt.init_cache(B, cache_len=cache_len, dtype=self.dtype)
        dcache = (drf.init_cache(B, cache_len=cache_len, dtype=self.dtype)
                  if drf is not None else None)
        return tcache, dcache

    def init_slots(self, capacity: int, cache_len: int,
                   src_len: Optional[int] = None, *,
                   block_size: Optional[int] = None,
                   num_blocks: Optional[int] = None,
                   mesh: Optional[Mesh] = None) -> DecodeState:
        """Blank fixed-capacity slot pool: every row is an empty slot
        (``done = True``), ready to be claimed via :meth:`prefill_into`.

        With ``block_size`` set, the target KV lives in a paged block pool
        instead of per-slot rings: ``cache_len`` becomes the per-slot
        *logical* cap (rounded up to whole blocks) and ``num_blocks``
        (default: worst case, ``capacity * blocks_per_slot``) sizes the
        shared pool — undersize it to trade memory for scheduler
        preemptions.  See the module docstring's paged KV design note.

        With ``mesh`` set, the pool lives sharded on that mesh (module
        docstring, sharded-serving note): the capacity axis — and the block
        axis of a paged pool — splits over the mesh's data axes, and every
        engine function compiled for this pool carries explicit in/out
        shardings.  Params must be placed replicated on the same mesh by
        the caller (``jax.device_put(params, NamedSharding(mesh, P()))``;
        :class:`~repro.serving.scheduler.ContinuousEngineBackend` does
        this).  Each init_slots call resets the jit caches and the engine's
        sharding state, so the same engine can serve sharded and unsharded
        pools in sequence (never concurrently).

        Sharded **paged** pools pin the paged-attention routing to the
        gather path when it is on auto (``paged_fused=None``): the fused
        kernel's scalar-prefetched block table may reference any shard's
        blocks (allocation is not shard-local), which GSPMD cannot
        partition through a ``pallas_call``.  Forcing ``paged_fused=True``
        overrides; the next unsharded init_slots restores auto routing.
        """
        if mesh is not None or self._shardings is not None:
            # entering or leaving sharded mode: compilations for the other
            # placement must never be reused.  Unsharded -> unsharded keeps
            # the caches (repeat backends stay warm).
            self._reset_jit_caches()
        if block_size is not None and mesh is not None \
                and self.tcfg.paged_fused is None:
            # sharded paged pool + auto kernel routing: the fused kernel's
            # pallas_call cannot be partitioned over the block-sharded pool
            # by GSPMD (its prefetched block table may reference any
            # shard's blocks — the allocator is not shard-local), so auto
            # routes through the gather path's collectives.  Forcing
            # paged_fused=True overrides (ROADMAP: block-locality-aware
            # allocation is the open item that would lift this).
            self.set_paged_fused(False)
            self._paged_fused_auto = True
        elif getattr(self, "_paged_fused_auto", False) and mesh is None:
            # leaving sharded mode: restore auto routing (fused on TPU)
            self.set_paged_fused(None)
            self._paged_fused_auto = False
        self.mesh = mesh
        self._shardings = None
        self._shard_capacity = None
        self.n_data_shards = 1
        if block_size is None:
            tcache, dcache = self._init_caches(capacity, cache_len, src_len)
            paged = None
        else:
            from repro.serving.slots import PagedKVTables
            if not hasattr(self.target, "init_paged_cache"):
                raise NotImplementedError(
                    f"paged KV is not supported for family "
                    f"'{self.tcfg.family}'")
            max_blocks = -(-cache_len // block_size)
            if num_blocks is None:
                num_blocks = capacity * max_blocks
            paged = PagedKVTables(num_blocks, block_size, capacity, max_blocks)
            tcache = self.target.init_paged_cache(num_blocks, block_size,
                                                  dtype=self.dtype)
            tcache["bt"] = jnp.full((capacity, max_blocks), -1, jnp.int32)
            # the (tiny) draft keeps a contiguous ring at the logical cap
            dcache = (self.draft.init_cache(capacity,
                                            cache_len=paged.logical_len,
                                            dtype=self.dtype)
                      if self.draft is not None else None)
        state = DecodeState(
            tcache=tcache, dcache=dcache,
            # seq_lens = 2 keeps the masked step's positions non-negative
            seq_lens=jnp.full((capacity,), 2, jnp.int32),
            last2=jnp.zeros((capacity, 2), jnp.int32),
            out=jnp.zeros((capacity, self.max_new + S_MAX + 1), jnp.int32),
            n_generated=jnp.zeros((capacity,), jnp.int32),
            done=jnp.ones((capacity,), bool),
            paged=paged)
        if mesh is not None:
            state = self._shard_slot_pool(state, mesh, capacity)
        return state

    def _shard_slot_pool(self, state: DecodeState, mesh: Mesh,
                         capacity: int) -> DecodeState:
        """Place a fresh slot pool on ``mesh`` and record its shardings.

        Reuses the decode-plan sharding machinery (launch/specs.py): the
        capacity axis shards like a decode plan's batch dim, paged block
        arrays shard over the block axis, everything else replicates.
        """
        # lazy import: launch/specs.py imports make_spec_step back from here
        from repro.launch.specs import _ns, slot_pool_specs
        sp = slot_pool_specs(
            mesh, self.target, self.draft, capacity,
            paged_num_blocks=(state.paged.num_blocks
                              if state.paged is not None else None))
        sh = PoolShardings(
            tcache=_ns(mesh, sp.tcache),
            dcache=(_ns(mesh, sp.dcache) if sp.dcache is not None else None),
            seq_lens=_ns(mesh, sp.seq_lens), last2=_ns(mesh, sp.last2),
            out=_ns(mesh, sp.out), n_generated=_ns(mesh, sp.n_generated),
            done=_ns(mesh, sp.done),
            rep=NamedSharding(mesh, PartitionSpec()),
            cu=_ns(mesh, sp.cu_blocks))
        state = dataclasses.replace(
            state,
            tcache=jax.device_put(state.tcache, sh.tcache),
            dcache=(jax.device_put(state.dcache, sh.dcache)
                    if state.dcache is not None else None),
            seq_lens=jax.device_put(state.seq_lens, sh.seq_lens),
            last2=jax.device_put(state.last2, sh.last2),
            out=jax.device_put(state.out, sh.out),
            n_generated=jax.device_put(state.n_generated, sh.n_generated),
            done=jax.device_put(state.done, sh.done))
        self._shardings = sh
        self._shard_capacity = capacity
        self.n_data_shards = sp.n_shards
        return state

    @staticmethod
    def _slot_axis(full_shape, single_shape) -> int:
        """The one axis where a B=1 leaf differs from the pool leaf."""
        return _slot_axis(full_shape, single_shape)

    def _build_inject(self, paged_pool: bool = False):
        """Scatter every B=1 prefill leaf into its slot row of the pool.

        On a sharded pool the jit carries explicit shardings: the pool tuple
        keeps its capacity-axis shardings on both sides of the scatter and
        the replicated B=1 leaves are consumed as such — the update-slice at
        a dynamic slot lowers to SPMD without replicating the pool.
        ``paged_pool`` only selects the sharding tuple for the ``full``
        argument (the paged path injects the target cache separately via
        :meth:`_build_inject_paged`).
        """
        def fn(full, single, slot):
            def upd(f, x):
                ax = self._slot_axis(f.shape, x.shape)
                starts = tuple(slot if i == ax else 0 for i in range(f.ndim))
                return jax.lax.dynamic_update_slice(f, x.astype(f.dtype), starts)
            return jax.tree.map(upd, full, single)

        sh = self._shardings
        if sh is None:
            return self._register_jit("inject", (paged_pool,), fn, hot=True,
                                      kv_args=(0,))
        if paged_pool:
            full_sh = (sh.dc, sh.seq_lens, sh.last2, sh.out,
                       sh.n_generated, sh.done)
        else:
            full_sh = (sh.tcache, sh.dc, sh.seq_lens, sh.last2, sh.out,
                       sh.n_generated, sh.done)
        return self._register_jit("inject", (paged_pool,), fn, hot=True,
                                  kv_args=(0,),
                                  in_shardings=(full_sh, sh.rep, sh.rep),
                                  out_shardings=full_sh)

    def _build_inject_paged(self):
        """Scatter a B=1 contiguous prefill into the paged pool block-wise.

        ``scat_tbl`` is the slot's block table padded with ``num_blocks``
        (an out-of-range row that ``mode="drop"`` discards) so unallocated
        logical blocks never touch the pool; ``bt_row`` is the same table
        padded with -1 for the device block table.
        """
        def fn(tcache, single_tc, slot, scat_tbl, bt_row):
            NB, bs = tcache["pos"].shape
            MAXB = scat_tbl.shape[0]
            new = {}
            # per-row leaves (k/v, plus k_scale/v_scale on an int8 pool):
            # the B=1 contiguous row [nL, L, ...] folds to [nL, MAXB, bs,
            # ...] and scatters block-wise through the slot's table
            for name in tcache:
                if name in ("pos", "bt"):
                    continue
                s1 = single_tc[name][:, 0]               # [nL, L, ...]
                nL = s1.shape[0]
                s1 = s1.reshape(nL, MAXB, bs, *s1.shape[2:])
                # lint: allow-cow-write(whole-prompt inject: scat_tbl holds only blocks prefill just allocated at refcount 1 — a shared block can never appear here)
                new[name] = tcache[name].at[:, scat_tbl].set(
                    s1.astype(tcache[name].dtype), mode="drop")
            spos = single_tc["pos"][0].reshape(MAXB, bs)
            # lint: allow-cow-write(same freshly-allocated scat_tbl as the k/v scatter above)
            new["pos"] = tcache["pos"].at[scat_tbl].set(spos, mode="drop")
            new["bt"] = tcache["bt"].at[slot].set(bt_row)
            return new

        sh = self._shardings
        if sh is None:
            return self._register_jit("inject_paged", (), fn, hot=True,
                                      kv_args=(0,))
        return self._register_jit("inject_paged", (), fn, hot=True,
                                  kv_args=(0,),
                                  in_shardings=(sh.tcache, sh.rep, sh.rep,
                                                sh.rep, sh.rep),
                                  out_shardings=sh.tcache)

    def prefill_into(self, tparams, dparams, state: DecodeState, slot: int,
                     tokens, prompt_len: int, cache_len: int,
                     target_extras: Optional[Dict] = None,
                     warm: bool = False) -> DecodeState:
        """Inject one new request into row ``slot`` of a live slot pool.

        Runs the (jit-cached, B=1) prefill for the prompt, then scatters every
        per-slot leaf — KV/state caches, seq_lens, last2, out, n_generated,
        done — into the pool with one jit-cached dynamic-update-slice tree.
        The (capacity, s) step function is untouched, so admitting a request
        never recompiles the serving step.

        Paged pool: allocates ``ceil(prompt_len / block_size)`` blocks from
        the free list and scatters the prefill rows block-wise through the
        table.  ``warm=True`` compiles the path without allocating blocks or
        mutating host bookkeeping (the result must be discarded).
        """
        tokens = np.asarray(tokens, np.int32).reshape(1, -1)
        if state.paged is not None:
            cache_len = state.paged.logical_len
        single = self.prefill(tparams, dparams, tokens,
                              np.array([prompt_len], np.int32), cache_len,
                              target_extras)
        capacity = int(state.seq_lens.shape[0])
        if self._inject_fn is None:
            self._inject_fn = self._build_inject(
                paged_pool=state.paged is not None)
        if warm:
            # donation shield: the discarded warm dispatch must not consume
            # the live pool's buffers
            state = self._warm_shield(state)
        if state.paged is None:
            if capacity == 1:
                return single
            full = (state.tcache, state.dcache, state.seq_lens, state.last2,
                    state.out, state.n_generated, state.done)
            one = (single.tcache, single.dcache, single.seq_lens, single.last2,
                   single.out, single.n_generated, single.done)
            with self.scope("inject"):
                return DecodeState(*self._inject_fn(full, one,
                                                    jnp.int32(slot)))
        pk = state.paged
        with self.scope("prefill.prep"):
            scat_tbl = np.full((pk.max_blocks,), pk.num_blocks, np.int32)
            bt_row = np.full((pk.max_blocks,), -1, np.int32)
            if not warm:
                pk.prefill(slot, prompt_len)
                ids = pk.table(slot)
                scat_tbl[:len(ids)] = ids
                bt_row[:len(ids)] = ids
                # the allocation may have reclaimed cache blocks: wipe their
                # stale pos rows before the inject can hand them a new owner
                state = self._drain_evicted(state)
        if self._inject_paged_fn is None:
            self._inject_paged_fn = self._build_inject_paged()
        with self.scope("inject"):
            tcache = self._inject_paged_fn(state.tcache, single.tcache,
                                           jnp.int32(slot),
                                           jnp.asarray(scat_tbl),
                                           jnp.asarray(bt_row))
            full = (state.dcache, state.seq_lens, state.last2, state.out,
                    state.n_generated, state.done)
            one = (single.dcache, single.seq_lens, single.last2, single.out,
                   single.n_generated, single.done)
            dcache, seq_lens, last2, out, n_gen, done = \
                self._inject_fn(full, one, jnp.int32(slot))
        return DecodeState(tcache=tcache, dcache=dcache, seq_lens=seq_lens,
                           last2=last2, out=out, n_generated=n_gen, done=done,
                           paged=pk)

    def retire_slot(self, state: DecodeState, slot: int) -> DecodeState:
        """Free a slot (mark done): the masked step stops committing for it,
        and the row can be re-claimed by the next :meth:`prefill_into`.

        Both paths are jit-cached device scatters — no host round-trip, so
        retirement stays off the step loop's critical path.  The paged path
        additionally frees the slot's blocks and clears their ``pos`` rows,
        so a recycled block can never leak stale attendable keys into its
        next owner.
        """
        sh = self._shardings
        if state.paged is not None:
            pk = state.paged
            with self.scope("retire.prep"):
                freed = pk.release(slot)
                pad = np.full((pk.max_blocks,), pk.num_blocks, np.int32)
                pad[:len(freed)] = freed
            if self._retire_paged_fn is None:
                def fn(done, pos, bt, slot, freed):
                    return (done.at[slot].set(True),
                            pos.at[freed].set(-1, mode="drop"),  # lint: allow-cow-write(retire wipe: freed is the actually-freed list from release — refcount-0 by construction; surviving shared blocks are excluded)
                            bt.at[slot].set(-1))
                if sh is None:
                    self._retire_paged_fn = self._register_jit(
                        "retire_paged", (), fn, hot=True, kv_args=(0, 1, 2))
                else:
                    self._retire_paged_fn = self._register_jit(
                        "retire_paged", (), fn, hot=True, kv_args=(0, 1, 2),
                        in_shardings=(sh.done, sh.tcache["pos"],
                                      sh.tcache["bt"], sh.rep, sh.rep),
                        out_shardings=(sh.done, sh.tcache["pos"],
                                       sh.tcache["bt"]))
            with self.scope("retire"):
                done, pos, bt = self._retire_paged_fn(
                    state.done, state.tcache["pos"], state.tcache["bt"],
                    jnp.int32(slot), jnp.asarray(pad))
            return dataclasses.replace(
                state, done=done, tcache=dict(state.tcache, pos=pos, bt=bt))
        if self._retire_fn is None:
            fn = lambda done, slot: done.at[slot].set(True)
            self._retire_fn = (
                self._register_jit("retire", (), fn, hot=True, kv_args=(0,))
                if sh is None else
                self._register_jit("retire", (), fn, hot=True, kv_args=(0,),
                                   in_shardings=(sh.done, sh.rep),
                                   out_shardings=sh.done))
        with self.scope("retire"):
            done = self._retire_fn(state.done, jnp.int32(slot))
        return dataclasses.replace(state, done=done)

    # ------------------------------------------------------------------
    # prefix-cache admission (shared blocks; serving/prefix_cache.py is the
    # host index, serving/scheduler.py drives this.  Unsharded paged pools
    # only — the backend refuses prefix_cache + mesh, see scheduler.py)

    def _require_unsharded(self, what: str) -> None:
        if self._shardings is not None:
            raise RuntimeError(
                f"{what} is not supported on a mesh-sharded pool: shared "
                f"blocks may live on any shard (allocation is not "
                f"shard-local) — serve with prefix_cache=False, or "
                f"unsharded")

    def _build_draft_prefill(self, P: int, L: int):
        """Draft-only B=1 prefill of a cached prefix: shared target blocks
        carry target KV only, so the (tiny, contiguous-ring) draft cache
        recomputes its rows ``[0, limit)`` for the attached prompt."""
        drf = self.draft

        def fn(dparams, tokens, limit):
            dcache = drf.init_cache(1, cache_len=L, dtype=self.dtype)
            _, dcache, _ = drf.prefill(dparams, tokens, dcache,
                                       prompt_lens=limit)
            return dcache

        return self._register_jit("draft_prefill", (P, L), fn, hot=False)

    def _build_attach_park(self, has_draft: bool):
        """Park an attach-admitted slot: scatter the draft's B=1 prefix
        cache into its pool row (replacing the previous occupant's rows
        wholesale, same stale-key guarantee as inject) and park
        ``seq_lens[slot]`` at the feed's final length — the identical
        parked-row contract chunked prefill relies on (_build_chunk_begin):
        interleaved decode steps' masked garbage writes for the still-done
        slot land past every suffix-chunk query and are rewritten by the
        slot's own first real step."""
        if has_draft:
            def fn(dcache, d_single, seq_lens, slot, total_len):
                def upd(f, x):
                    ax = self._slot_axis(f.shape, x.shape)
                    starts = tuple(slot if i == ax else 0
                                   for i in range(f.ndim))
                    return jax.lax.dynamic_update_slice(
                        f, x.astype(f.dtype), starts)
                return (jax.tree.map(upd, dcache, d_single),
                        seq_lens.at[slot].set(total_len))
            kv = (0, 2)
        else:
            def fn(seq_lens, slot, total_len):
                return seq_lens.at[slot].set(total_len)
            kv = (0,)
        return self._register_jit("attach_park", (has_draft,), fn, hot=True,
                                  kv_args=kv)

    def _build_block_copy(self):
        """COW resolve: copy every leaf's rows of blocks ``src[i]`` into
        ``dst[i]``.  Pairs are padded with ``num_blocks`` — the gather
        clamps (reads a garbage block) and the scatter drops (never writes
        it), so one compilation serves any pair count."""
        def fn(tcache, src, dst):
            new = {}
            for name in tcache:
                if name == "bt":
                    new[name] = tcache[name]
                elif name == "pos":
                    new[name] = tcache[name].at[dst].set(
                        tcache[name][src], mode="drop")
                else:
                    new[name] = tcache[name].at[:, dst].set(
                        tcache[name][:, src], mode="drop")
            return new
        return self._register_jit("block_copy", (), fn, hot=True,
                                  kv_args=(0,))

    def _build_evict_clear(self):
        def fn(pos, blocks):
            # lint: allow-cow-write(eviction wipe: the blocks are refcount-0 by construction — reclaim just freed them — and -1 rows are never attendable)
            return pos.at[blocks].set(-1, mode="drop")
        return self._register_jit("evict_clear", (), fn, hot=True,
                                  kv_args=(0,))

    def _drain_evicted(self, state: DecodeState) -> DecodeState:
        """Wipe device ``pos`` rows of cache blocks evicted by
        reclaim-under-pressure (slots.PagedKVTables.evicted_pending).

        Must run after any host allocation and before the next dispatch
        that could write (or attend) the re-allocated ids — that restores
        the standing "free blocks carry pos = -1" invariant before the
        block can be handed to a new owner.  Every allocating engine entry
        point calls this on its non-warm path.
        """
        pk = state.paged
        if pk is None or not pk.evicted_pending:
            return state
        ids = pk.evicted_pending
        pk.evicted_pending = []
        pad = np.full((pk.num_blocks,), pk.num_blocks, np.int32)
        pad[:len(ids)] = ids
        if self._evict_fn is None:
            self._evict_fn = self._build_evict_clear()
        with self.scope("evict_clear"):
            pos = self._evict_fn(state.tcache["pos"], jnp.asarray(pad))
        return dataclasses.replace(state, tcache=dict(state.tcache, pos=pos))

    def attach_prefix(self, dparams, state: DecodeState, slot: int,
                      tokens, n_prefix: int, total_len: int, *,
                      warm: bool = False) -> DecodeState:
        """Admit a request whose first ``n_prefix`` feed rows are cached.

        Host side: the (already locked) cache blocks were mapped into the
        slot's table by the backend (`PagedKVTables.attach`); this call
        marks the slot pending and handles the device half — a draft-only
        prefix prefill (shared blocks hold target KV only) scattered into
        the slot's draft ring, and the parked ``seq_lens``.  The uncached
        suffix rows ``[n_prefix, total_len - 1)`` then flow through the
        ordinary :meth:`prefill_chunk_into` path (``start = n_prefix``),
        which a zero-suffix admission skips (see the backend's
        ``commit_attached``).

        ``tokens`` is the bucket-padded feed (prompt + stash); the draft
        consumes rows ``[0, min(n_prefix, total_len - 2))`` of it.

        ``warm=True`` compiles the draft-prefill and park paths for this
        token bucket without touching host bookkeeping (result discarded).
        """
        self._require_unsharded("prefix-cache attach")
        pk = state.paged
        assert pk is not None, "attach_prefix needs a paged pool"
        if warm:
            state = self._warm_shield(state)
        else:
            pk.mark_pending(slot)
        has_draft = self.draft is not None
        if has_draft:
            tokens = np.asarray(tokens, np.int32).reshape(1, -1)
            P = int(tokens.shape[1])
            L = pk.logical_len
            dlim = min(n_prefix, total_len - 2)
            if (P, L) not in self._attach_fns:
                self._attach_fns[(P, L)] = self._build_draft_prefill(P, L)
            with self.scope("draft_prefill[P={}]", P):
                d_single = self._attach_fns[(P, L)](
                    dparams, jnp.asarray(tokens),
                    jnp.full((1,), dlim, jnp.int32))
        if has_draft not in self._attach_park_fns:
            self._attach_park_fns[has_draft] = \
                self._build_attach_park(has_draft)
        with self.scope("attach_park"):
            if has_draft:
                dcache, seq_lens = self._attach_park_fns[True](
                    state.dcache, d_single, state.seq_lens, jnp.int32(slot),
                    jnp.int32(total_len))
                return dataclasses.replace(state, dcache=dcache,
                                           seq_lens=seq_lens)
            seq_lens = self._attach_park_fns[False](
                state.seq_lens, jnp.int32(slot), jnp.int32(total_len))
            return dataclasses.replace(state, seq_lens=seq_lens)

    def commit_attached(self, state: DecodeState, slot: int,
                        total_len: int, last2, *,
                        warm: bool = False) -> DecodeState:
        """Turn a fully-cached (zero-suffix) attached slot into a live
        decode row — no prefill forward at all.

        The first decode step writes feed row ``total_len - 1``; when the
        cached prefix covers it (``n_prefix == total_len``) that row lives
        in a shared block, which is first COW-resolved through the
        block-copy scatter.  Then the table grows to cover ``total_len``
        and the ordinary chunk-commit scatter publishes the block table
        and row state, leaving the slot bit-identical to a chunked (and
        hence whole-prompt) admission.
        """
        self._require_unsharded("prefix-cache attach")
        pk = state.paged
        assert pk is not None
        if warm:
            # compile block_copy + chunk_commit with no-op pad-only args,
            # off the host bookkeeping and off the live pool's buffers
            state = self._warm_shield(state)
            pad = np.full((pk.max_blocks,), pk.num_blocks, np.int32)
            if self._block_copy_fn is None:
                self._block_copy_fn = self._build_block_copy()
            tcache = self._block_copy_fn(state.tcache, jnp.asarray(pad),
                                         jnp.asarray(pad))
            state = dataclasses.replace(state, tcache=tcache)
            if True not in self._chunk_commit_fns:
                self._chunk_commit_fns[True] = self._build_chunk_commit(True)
            self._chunk_commit_fns[True](
                state.seq_lens, state.last2, state.out, state.n_generated,
                state.done, jnp.int32(slot), jnp.int32(total_len),
                jnp.zeros((2,), jnp.int32), state.tcache["bt"],
                jnp.full((pk.max_blocks,), -1, jnp.int32))
            return state
        pairs = pk.cow_for_range(slot, total_len - 1, total_len)
        pk.ensure(slot, total_len)
        pk.commit(slot, total_len - pk.tokens(slot))
        pk.clear_pending(slot)
        state = self._drain_evicted(state)
        if pairs:
            src = np.full((pk.max_blocks,), pk.num_blocks, np.int32)
            dst = np.full((pk.max_blocks,), pk.num_blocks, np.int32)
            for i, (s_, d_) in enumerate(pairs):
                src[i], dst[i] = s_, d_
            if self._block_copy_fn is None:
                self._block_copy_fn = self._build_block_copy()
            with self.scope("block_copy"):
                tcache = self._block_copy_fn(state.tcache, jnp.asarray(src),
                                             jnp.asarray(dst))
            state = dataclasses.replace(state, tcache=tcache)
        ids = pk.table(slot)
        bt_row = np.full((pk.max_blocks,), -1, np.int32)
        bt_row[:len(ids)] = ids
        if True not in self._chunk_commit_fns:
            self._chunk_commit_fns[True] = self._build_chunk_commit(True)
        cargs = (state.seq_lens, state.last2, state.out, state.n_generated,
                 state.done, jnp.int32(slot), jnp.int32(total_len),
                 jnp.asarray(np.asarray(last2, np.int32)),
                 state.tcache["bt"], jnp.asarray(bt_row))
        with self.scope("chunk_commit"):
            seq_lens, l2, out, n_gen, done, bt = \
                self._chunk_commit_fns[True](*cargs)
        return dataclasses.replace(
            state, seq_lens=seq_lens, last2=l2, out=out, n_generated=n_gen,
            done=done, tcache=dict(state.tcache, bt=bt))

    # ------------------------------------------------------------------
    # chunked prefill into a slot (in-step chunked prefill; the scheduler
    # interleaves these chunks with decode steps of the running batch)

    def _build_chunk_begin(self, paged: bool):
        """First-chunk setup: clear the slot's stale pos rows (contiguous
        target + draft ring — a whole-prompt inject replaces the full row,
        chunked writes do not, so the previous occupant's attendable keys
        must be wiped first) and PARK the slot's seq_lens at the prompt's
        final length.  Parking matters: the interleaved decode steps still
        compute (masked, garbage) writes for this done row, and at
        seq_lens = total_len those land at positions >= total_len - 1 —
        beyond every chunk query, and rewritten by the slot's own first
        real step before they can ever be attended (the ring invariant)."""
        def fn(tpos, dpos, seq_lens, slot, plen):
            new_tpos = tpos if paged else tpos.at[slot].set(-1)
            new_dpos = None if dpos is None else dpos.at[slot].set(-1)
            return new_tpos, new_dpos, seq_lens.at[slot].set(plen)

        # paged pools return tpos untouched and the caller keeps using the
        # *input* pos buffer — donating arg 0 there would delete a buffer
        # that stays live, so only the contiguous path donates it
        kv = (1, 2) if paged else (0, 1, 2)
        sh = self._shardings
        if sh is None:
            return self._register_jit("chunk_begin", (paged,), fn, hot=True,
                                      kv_args=kv)
        tpos_sh = sh.tcache["pos"]
        dpos_sh = (sh.dcache["pos"]
                   if isinstance(sh.dcache, dict) and "pos" in sh.dcache
                   else sh.rep)
        return self._register_jit("chunk_begin", (paged,), fn, hot=True,
                                  kv_args=kv,
                                  in_shardings=(tpos_sh, dpos_sh, sh.seq_lens,
                                                sh.rep, sh.rep),
                                  out_shardings=(tpos_sh, dpos_sh,
                                                 sh.seq_lens))

    def _build_chunk_commit(self, paged: bool):
        """Last-chunk commit: the slot becomes a live decode row — exactly
        the non-cache half of what prefill_into's inject scatters."""
        def fn(seq_lens, last2, out, n_gen, done, slot, plen, l2,
               bt=None, bt_row=None):
            out_row = jnp.zeros_like(out[0])
            res = (seq_lens.at[slot].set(plen),
                   last2.at[slot].set(l2),
                   out.at[slot].set(out_row),
                   n_gen.at[slot].set(0),
                   done.at[slot].set(False))
            if paged:
                res = res + (bt.at[slot].set(bt_row),)
            return res

        kv = (0, 1, 2, 3, 4) + ((8,) if paged else ())
        sh = self._shardings
        if sh is None:
            return self._register_jit("chunk_commit", (paged,), fn, hot=True,
                                      kv_args=kv)
        in_sh = [sh.seq_lens, sh.last2, sh.out, sh.n_generated, sh.done,
                 sh.rep, sh.rep, sh.rep]
        out_sh = [sh.seq_lens, sh.last2, sh.out, sh.n_generated, sh.done]
        if paged:
            in_sh += [sh.tcache["bt"], sh.rep]
            out_sh += [sh.tcache["bt"]]
        return self._register_jit("chunk_commit", (paged,), fn, hot=True,
                                  kv_args=kv,
                                  in_shardings=tuple(in_sh),
                                  out_shardings=tuple(out_sh))

    def _build_chunk(self, key: Tuple, t_single, d_single):
        """One bucketed chunk forward for one slot.

        ``key`` carries a rows-limit bucket ``R`` (power-of-two cover of
        ``start + CB``, capped at the logical length): during chunked
        prefill every attendable key lives below row ``start + CB``, so
        the contiguous forwards (target ring and the draft ring trailing a
        paged target) bound their attention to ``R`` rows instead of
        streaming the dead tail of the full logical cache.  Paged targets
        instead take a per-chunk ``cu_row`` operand — the slot's ragged
        grid-step count — so the chunk's pool attention runs the ragged
        kernel over exactly the slot's allocated blocks.

        Contiguous pool: the slot's B=1 caches are sliced out, extended by
        the chunk (model.prefill_chunk — the verify-attention masking makes
        the prefix extension exact), and scattered back.  Paged pool: the
        chunk writes the shared block pool in place through the slot's host
        block table (bt_row), so there is nothing to slice; the device
        ``bt`` row stays -1 until the final chunk commits (step growth
        uploads exclude pending slots), which keeps the interleaved decode
        steps' garbage writes for this row dropped.  Even without that, the
        parked-seq_lens invariant (see _build_chunk_begin) guarantees any
        such write lands past every chunk query and is rewritten before it
        is ever attendable — the same argument the contiguous path relies
        on.
        """
        CB, paged, capacity, L, R = key
        tgt, drf = self.target, self.draft

        def take(full, single, slot):
            def one(f, s1):
                ax = self._slot_axis(f.shape, s1.shape)
                starts = tuple(slot if i == ax else 0
                               for i in range(f.ndim))
                return jax.lax.dynamic_slice(f, starts, s1.shape)
            return jax.tree.map(one, full, single)

        def put(full, upd, single, slot):
            def one(f, u, s1):
                ax = self._slot_axis(f.shape, s1.shape)
                starts = tuple(slot if i == ax else 0
                               for i in range(f.ndim))
                return jax.lax.dynamic_update_slice(f, u.astype(f.dtype),
                                                    starts)
            return jax.tree.map(one, full, upd, single)

        def fn(tparams, dparams, tcache, dcache, slot, toks, start,
               t_limit, d_limit, bt_row=None, cu_row=None):
            off = jnp.full((1,), start, jnp.int32)
            tl = jnp.full((1,), t_limit, jnp.int32)
            dl = jnp.full((1,), d_limit, jnp.int32)
            toks1 = toks[None, :]
            if paged:
                # the pool IS the B=1 cache (writes land in place through
                # the slot's host table); only bt is a per-slot view
                t1 = dict({n: tcache[n] for n in tcache if n != "bt"},
                          bt=bt_row[None, :])
                _, t1n = tgt.prefill_chunk(tparams, toks1, t1, off, tl,
                                           cu_blocks=cu_row)
                new_t = dict(tcache,
                             **{n: t1n[n] for n in t1n if n != "bt"})
            elif t_single is None:       # capacity == 1: the pool IS the slot
                _, new_t = tgt.prefill_chunk(tparams, toks1, tcache, off, tl,
                                             rows_limit=R)
            else:
                _, t1n = tgt.prefill_chunk(
                    tparams, toks1, take(tcache, t_single, slot), off, tl,
                    rows_limit=R)
                new_t = put(tcache, t1n, t_single, slot)
            if drf is None:
                return new_t, dcache
            if d_single is None:
                _, new_d = drf.prefill_chunk(dparams, toks1, dcache, off, dl,
                                             rows_limit=R)
            else:
                _, d1n = drf.prefill_chunk(
                    dparams, toks1, take(dcache, d_single, slot), off, dl,
                    rows_limit=R)
                new_d = put(dcache, d1n, d_single, slot)
            return new_t, new_d

        rows = L if paged else None
        cu_arg = 10 if paged else None
        sh = self._shardings
        if sh is None:
            return self._register_jit("chunk", key, fn, hot=True,
                                      kv_args=(2, 3), paged_rows=rows,
                                      cu_arg=cu_arg)
        in_sh = [sh.rep, sh.rep, sh.tcache, sh.dc, sh.rep, sh.rep, sh.rep,
                 sh.rep, sh.rep]
        if paged:
            in_sh += [sh.rep, sh.cu_sh]   # bt_row + cu_row (host-built)
        return self._register_jit("chunk", key, fn, hot=True,
                                  kv_args=(2, 3), paged_rows=rows,
                                  cu_arg=cu_arg,
                                  in_shardings=tuple(in_sh),
                                  out_shardings=(sh.tcache, sh.dc))

    def _get_chunk_fn(self, key: Tuple):
        """The jit-cached standalone chunk forward for ``key`` (compiling
        it on first use) — shared by prefill_chunk_into and flush_chunk."""
        if key not in self._chunk_fns:
            CB, paged, capacity, L, R = key
            if capacity == 1:
                t_single = d_single = None
            else:
                t_tmpl, d_tmpl = jax.eval_shape(
                    lambda: self._init_caches(1, L))
                t_single = None if paged else t_tmpl
                d_single = d_tmpl
            self._chunk_fns[key] = self._build_chunk(key, t_single, d_single)
        return self._chunk_fns[key]

    def prefill_chunk_into(self, tparams, dparams, state: DecodeState,
                           slot: int, tokens, start: int, n: int,
                           total_len: int, last2=None, *,
                           warm: bool = False, defer: bool = False):
        """Feed one prefill chunk of a request into row ``slot``.

        The request's feed (prompt, or prompt + pre-preemption stash) has
        ``total_len`` tokens; this call writes feed positions
        ``[start, start + n)`` of the target cache (the draft trails by one:
        its limit is ``total_len - 2``, exactly mirroring the whole-prompt
        prefill which leaves the last prompt token to the first decode
        step).  ``tokens`` is the bucket-padded chunk (first ``n`` entries
        real).

        Row-state contract (what the interleaved decode steps may observe):

        * **first chunk** (``start == 0``): the slot's stale ``pos`` rows
          are wiped (contiguous target ring + draft ring — a previous
          occupant's keys must never be attendable) and ``seq_lens[slot]``
          is PARKED at ``total_len``.  Parking is load-bearing: the slot is
          still ``done``, so interleaved decode steps compute masked
          garbage writes for it, and at ``seq_lens = total_len`` those land
          at positions ``>= total_len - 1`` — beyond every chunk query, and
          rewritten by the slot's own first real decode step before they
          can ever be attended.  On a paged pool the slot is additionally
          marked *pending*: its device block-table row stays ``-1`` (decode
          writes drop) until the final chunk publishes it.
        * **middle chunks**: only cache rows ``[start, start + n)`` change;
          ``done/out/n_generated/last2`` stay untouched, so the scheduler
          sees an occupied-but-not-decoding slot.
        * **final chunk** (``start + n == total_len - 1``): ``last2`` (the
          feed's final two tokens) must be supplied; the commit reproduces
          exactly the non-cache row state a whole-prompt ``prefill_into``
          would have left — ``seq_lens = total_len``, ``last2`` set, ``out``
          zeroed, ``n_generated = 0``, ``done = False``, and (paged) the
          block table published including the block covering row
          ``total_len - 1``, which the first decode step writes.  From the
          next iteration on, the slot is indistinguishable from a
          whole-prompt admission — that equivalence is what makes
          chunk-vs-whole token equality (tests/test_chunked_prefill.py)
          hold bit-for-bit.

        ``warm=True`` compiles the begin/chunk/commit paths for this chunk
        bucket without touching host block bookkeeping (result discarded).

        ``defer=True`` (paged, NON-final, non-warm chunks only) runs the
        host bookkeeping and begin path as usual but SKIPS the forward
        dispatch, returning ``(state, DeferredChunk)`` instead of a state:
        the caller later folds the forward into the next speculative step
        (:meth:`step_with_chunk`, the mixed verify+chunk launch) or
        dispatches it standalone (:meth:`flush_chunk`).
        """
        if not hasattr(self.target, "prefill_chunk") or (
                self.draft is not None
                and not hasattr(self.draft, "prefill_chunk")):
            raise NotImplementedError(
                f"chunked prefill is not supported for family "
                f"'{self.tcfg.family}' (model lacks a prefill_chunk path)")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        CB = int(tokens.shape[0])
        feed_total = total_len - 1
        final = (not warm) and (start + n == feed_total)
        if not warm and not 0 < n <= CB:
            raise ValueError(f"chunk carries n={n} tokens in a {CB} bucket")
        if not warm and start + n > feed_total:
            raise ValueError(
                f"chunk [{start}, {start + n}) overruns the {feed_total}"
                f"-token feed (prompt of {total_len})")
        if final and (last2 is None or len(np.asarray(last2)) != 2):
            raise ValueError(
                "the final chunk must pass last2 = the feed's last 2 tokens")
        pk = state.paged
        paged = pk is not None
        capacity = int(state.seq_lens.shape[0])
        if warm:
            # donation shield: warm begin/chunk/commit dispatches discard
            # their results and must not consume the live pool's buffers
            state = self._warm_shield(state)

        # ---- first chunk: wipe stale rows, park seq_lens ----
        if start == 0 or warm:
            if paged not in self._chunk_begin_fns:
                self._chunk_begin_fns[paged] = self._build_chunk_begin(paged)
            dpos = (state.dcache["pos"]
                    if (self.draft is not None and isinstance(state.dcache, dict)
                        and "pos" in state.dcache) else None)
            with self.scope("chunk_begin"):
                tpos, dpos_new, seq_lens = self._chunk_begin_fns[paged](
                    state.tcache["pos"], dpos, state.seq_lens,
                    jnp.int32(slot), jnp.int32(total_len))
            # rebind even when warm: begin just consumed (donated) the
            # shielded copy's pos/seq_lens buffers, so the warm chunk and
            # commit dispatches below must see the outputs, not the inputs
            tcache = (state.tcache if paged
                      else dict(state.tcache, pos=tpos))
            dcache = (dict(state.dcache, pos=dpos_new)
                      if dpos is not None else state.dcache)
            state = dataclasses.replace(state, tcache=tcache,
                                        dcache=dcache, seq_lens=seq_lens)

        # ---- host block accounting + this chunk's block table ----
        with self.scope("chunk.prep"):
            bt_row = None
            if paged:
                bt_row = np.full((pk.max_blocks,), -1, np.int32)
                if not warm:
                    if start == 0:
                        pk.prefill(slot, n)
                        pk.mark_pending(slot)
                    else:
                        pk.ensure(slot, start + n)
                        pk.commit(slot, n)
                    ids = pk.table(slot)
                    bt_row[:len(ids)] = ids
                    state = self._drain_evicted(state)
            L = (pk.logical_len if paged
                 else int(state.tcache["pos"].shape[1]))
            # rows-limit bucket: every attendable key lives below row
            # start + CB (positions equal rows before the first wrap, and
            # chunks never wrap), so the contiguous/draft forwards attend a
            # power-of-two cover of it instead of the whole logical cache
            R = min(max(_next_pow2(start + CB), 16), L)
            key = (CB, paged, capacity, L, R)
            fn = self._get_chunk_fn(key)
            if defer:
                if not paged or final or warm:
                    raise ValueError(
                        "defer=True needs a paged, non-final, non-warm chunk")
                return state, DeferredChunk(
                    slot=int(slot), tokens=tokens, start=int(start),
                    total_len=int(total_len), bt_row=bt_row, key=key)
            args = (tparams, dparams, state.tcache, state.dcache,
                    jnp.int32(slot), jnp.asarray(tokens), jnp.int32(start),
                    jnp.int32(feed_total), jnp.int32(feed_total - 1))
            if paged:
                # [0, max(live, 1)]: the slot's one-row ragged grid plan
                cu_row = host_cu_blocks(bt_row[None, :])
                args = args + (jnp.asarray(bt_row), jnp.asarray(cu_row))

        # ---- the chunk forward ----
        with self.scope("chunk[CB={}]", CB):
            new_t, new_d = fn(*args)
        if warm:
            # compile the commit path too, then discard everything
            if paged not in self._chunk_commit_fns:
                self._chunk_commit_fns[paged] = self._build_chunk_commit(paged)
            cargs = (state.seq_lens, state.last2, state.out,
                     state.n_generated, state.done, jnp.int32(slot),
                     jnp.int32(total_len), jnp.zeros((2,), jnp.int32))
            if paged:
                # the warm chunk dispatch above consumed state.tcache, so
                # the block table must come from its output
                cargs = cargs + (new_t["bt"], jnp.asarray(bt_row))
            self._chunk_commit_fns[paged](*cargs)
            # hand back only live buffers (the chunk consumed state.tcache/
            # dcache); warm callers discard this anyway
            return dataclasses.replace(state, tcache=new_t, dcache=new_d)
        state = dataclasses.replace(state, tcache=new_t, dcache=new_d)

        # ---- final chunk: the slot becomes a live decode row ----
        if final:
            with self.scope("chunk.prep"):
                if paged:
                    # cover row total_len - 1 (written by the first decode
                    # step)
                    pk.ensure(slot, total_len)
                    pk.commit(slot, 1)
                    pk.clear_pending(slot)
                    ids = pk.table(slot)
                    bt_row = np.full((pk.max_blocks,), -1, np.int32)
                    bt_row[:len(ids)] = ids
                    state = self._drain_evicted(state)
                if paged not in self._chunk_commit_fns:
                    self._chunk_commit_fns[paged] = \
                        self._build_chunk_commit(paged)
                cargs = (state.seq_lens, state.last2, state.out,
                         state.n_generated, state.done, jnp.int32(slot),
                         jnp.int32(total_len),
                         jnp.asarray(np.asarray(last2, np.int32)))
            if paged:
                cargs = cargs + (state.tcache["bt"], jnp.asarray(bt_row))
                with self.scope("chunk_commit"):
                    seq_lens, l2, out, n_gen, done, bt = \
                        self._chunk_commit_fns[paged](*cargs)
                state = dataclasses.replace(
                    state, seq_lens=seq_lens, last2=l2, out=out,
                    n_generated=n_gen, done=done,
                    tcache=dict(state.tcache, bt=bt))
            else:
                with self.scope("chunk_commit"):
                    seq_lens, l2, out, n_gen, done = \
                        self._chunk_commit_fns[paged](*cargs)
                state = dataclasses.replace(
                    state, seq_lens=seq_lens, last2=l2, out=out,
                    n_generated=n_gen, done=done)
        return state

    def flush_chunk(self, tparams, dparams, state: DecodeState,
                    chunk: DeferredChunk) -> DecodeState:
        """Dispatch a deferred chunk's forward standalone.

        The host bookkeeping already ran at defer time, so this is exactly
        the chunk-fn dispatch :meth:`prefill_chunk_into` skipped — callers
        use it when no speculative step follows before the next pool
        consumer (another chunk, an admission prefill, a preemption).
        """
        fn = self._get_chunk_fn(chunk.key)
        CB = chunk.key[0]
        feed_total = chunk.total_len - 1
        cu_row = host_cu_blocks(chunk.bt_row[None, :])
        args = (tparams, dparams, state.tcache, state.dcache,
                jnp.int32(chunk.slot), jnp.asarray(chunk.tokens),
                jnp.int32(chunk.start), jnp.int32(feed_total),
                jnp.int32(feed_total - 1), jnp.asarray(chunk.bt_row),
                jnp.asarray(cu_row))
        with self.scope("chunk[CB={}]", CB):
            new_t, new_d = fn(*args)
        return dataclasses.replace(state, tcache=new_t, dcache=new_d)

    def step_with_chunk(self, tparams, dparams, state: DecodeState, s: int,
                        chunk: DeferredChunk,
                        rng: Optional[jax.Array] = None,
                        ) -> Tuple[DecodeState, StepStats]:
        """One speculative step FUSED with a deferred chunk's forward —
        the mixed verify+chunk launch.

        The chunk slot's queries (its prefix-extension rows, read/written
        through its host table row) ride the same ragged attention call as
        every decode slot's verify queries, so the separate chunk dispatch
        — and its grid, weight re-streaming and launch overhead —
        disappears.  Numerically this is bit-identical to
        ``flush_chunk(...)`` followed by ``step(...)``: attention rows are
        independent per query, the parked chunk slot's verify writes are
        dropped in both orders (its device table row is still ``-1``), and
        its accept count is forced to zero by its ``done`` flag.
        """
        if not 0 <= s <= S_MAX:
            raise ValueError(
                f"s={s} outside [0, {S_MAX}]: the step's output buffer is "
                f"sized for at most S_MAX={S_MAX} speculative tokens and "
                f"would silently drop commits beyond it")
        pk = state.paged
        if pk is None:
            raise ValueError("step_with_chunk needs a paged slot pool")
        with self.scope("step.prep"):
            grew = False
            for slot in pk.active_slots():
                if pk.is_pending(slot):
                    continue
                grew |= bool(pk.ensure(slot, pk.tokens(slot) + s))
            if grew:
                state = dataclasses.replace(
                    state, tcache=dict(state.tcache, bt=jnp.asarray(
                        pk.device_tables(exclude_pending=True))))
            state = self._drain_evicted(state)
            B = int(state.seq_lens.shape[0])
            CB, _, _, L, R = chunk.key
            key = (B, s, CB, L, R)
            if key not in self._mixed_step_fns:
                self._mixed_step_fns[key] = self._build_step_mixed(B, s, CB,
                                                                   L, R)
            # the grid plan covers the chunk row's blocks through the
            # patched table (the kernel reads them via bt_eff, not the
            # device bt)
            tables = pk.device_tables(exclude_pending=True)
            tables[chunk.slot] = chunk.bt_row
            cu = host_cu_blocks(tables)
            feed_total = chunk.total_len - 1
            args = (tparams, dparams, state.tcache, state.dcache,
                    state.seq_lens, state.last2, state.out,
                    state.n_generated, state.done, jnp.asarray(cu),
                    jnp.int32(chunk.slot), jnp.asarray(chunk.tokens),
                    jnp.int32(chunk.start), jnp.int32(feed_total),
                    jnp.int32(feed_total - 1), jnp.asarray(chunk.bt_row))
        if self.sample:
            if rng is None:
                with self.scope("step.key"):
                    rng = jax.random.PRNGKey(
                        int(np.asarray(state.n_generated).sum()))
            args = (*args, rng)
        with self.scope("step_mixed[B={},s={},CB={}]", B, s, CB):
            (tc, dcache, seq_lens, last2, out, n_gen, done, a, n_commit) = \
                self._mixed_step_fns[key](*args)
        new_state = DecodeState(tc, dcache, seq_lens, last2, out, n_gen,
                                done, paged=pk)
        with self.scope("step.wait"):
            stats = StepStats(accepted=np.asarray(a),
                              committed=np.asarray(n_commit))
        with self.scope("step.commit"):
            for slot in pk.active_slots():
                if not pk.is_pending(slot):
                    pk.commit(slot, int(stats.committed[slot]))
        return new_state, stats

    # ------------------------------------------------------------------
    # one speculative step

    def _warm_shield(self, state: DecodeState) -> DecodeState:
        """Disposable copy of a DecodeState's device leaves.

        Warm (compile-only) dispatches discard their results; with pool
        donation on, handing them the live state would delete the very
        buffers the next real step needs.  ``donate=False`` engines keep the
        zero-copy warm path.
        """
        if not self.donate:
            return state
        return dataclasses.replace(
            state,
            tcache=_copy_arrays(state.tcache),
            dcache=_copy_arrays(state.dcache),
            seq_lens=_copy_arrays(state.seq_lens),
            last2=_copy_arrays(state.last2),
            out=_copy_arrays(state.out),
            n_generated=_copy_arrays(state.n_generated),
            done=_copy_arrays(state.done))

    def _build_step(self, B: int, s: int, paged_rows: Optional[int] = None):
        paged = paged_rows is not None
        fn = make_spec_step(
            self.target, self.draft, B, s, eos_id=self.eos_id,
            max_new=self.max_new, prefix_offset=self.prefix_offset,
            sample=self.sample, temperature=self.temperature, paged=paged)
        cu_arg = 9 if paged else None
        # donate every DecodeState leaf the step threads through — except
        # the target cache of recurrent families, whose checkpoint-selecting
        # commit makes buffer reuse shape-incompatible (launch/specs.py
        # makes the same call for the decode plans)
        kv = (tuple(range(3, 9)) if self.tcfg.family in ("ssm", "hybrid")
              else tuple(range(2, 9)))
        sh = self._shardings
        if sh is None or B != self._shard_capacity:
            # no mesh, or a non-pool batch size (generate()/warmup paths):
            # plain single-placement jit
            return self._register_jit("step", (B, s, paged), fn, hot=True,
                                      kv_args=kv, paged_rows=paged_rows,
                                      cu_arg=cu_arg)
        # sharded pool: the serving step is one explicit SPMD program —
        # params replicated, every pool-shaped leaf sharded on its capacity
        # (or block) axis on both sides, per-slot stats sharded like seq_lens
        in_sh = [sh.rep, sh.rep, sh.tcache, sh.dc, sh.seq_lens, sh.last2,
                 sh.out, sh.n_generated, sh.done]
        if paged:
            in_sh.append(sh.cu_sh)            # cu_blocks (host-built, tiny)
        if self.sample:
            in_sh.append(sh.rep)
        out_sh = (sh.tcache, sh.dc, sh.seq_lens, sh.last2, sh.out,
                  sh.n_generated, sh.done, sh.seq_lens, sh.seq_lens)
        return self._register_jit("step", (B, s, paged), fn, hot=True,
                                  kv_args=kv, paged_rows=paged_rows,
                                  cu_arg=cu_arg,
                                  in_shardings=tuple(in_sh),
                                  out_shardings=out_sh)

    def _build_step_mixed(self, B: int, s: int, CB: int, L: int, R: int):
        """The mixed verify+chunk step jit (see :meth:`step_with_chunk`).

        Same contract as the plain paged step — ``cu_blocks`` at argnum 9
        so the graph-lint ragged pass checks both the same way — plus the
        six chunk operands (slot, tokens, start, target/draft limits, host
        table row) after it."""
        d_single = None
        if self.draft is not None and B != 1:
            _, d_single = jax.eval_shape(lambda: self._init_caches(1, L))
        fn = make_spec_step(
            self.target, self.draft, B, s, eos_id=self.eos_id,
            max_new=self.max_new, prefix_offset=self.prefix_offset,
            sample=self.sample, temperature=self.temperature, paged=True,
            chunk=(CB, R, d_single))
        kv = tuple(range(2, 9))
        key = (B, s, CB, L, R)
        sh = self._shardings
        if sh is None or B != self._shard_capacity:
            return self._register_jit("step_mixed", key, fn, hot=True,
                                      kv_args=kv, paged_rows=L, cu_arg=9)
        in_sh = [sh.rep, sh.rep, sh.tcache, sh.dc, sh.seq_lens, sh.last2,
                 sh.out, sh.n_generated, sh.done, sh.cu_sh,
                 sh.rep, sh.rep, sh.rep, sh.rep, sh.rep, sh.rep]
        if self.sample:
            in_sh.append(sh.rep)
        out_sh = (sh.tcache, sh.dc, sh.seq_lens, sh.last2, sh.out,
                  sh.n_generated, sh.done, sh.seq_lens, sh.seq_lens)
        return self._register_jit("step_mixed", key, fn, hot=True,
                                  kv_args=kv, paged_rows=L, cu_arg=9,
                                  in_shardings=tuple(in_sh),
                                  out_shardings=out_sh)



    def step(self, tparams, dparams, state: DecodeState, s: int,
             rng: Optional[jax.Array] = None, *,
             warm: bool = False) -> Tuple[DecodeState, StepStats]:
        """One speculative step at length ``s`` for the whole batch.

        ``s`` must stay within ``S_MAX``: the ``out`` ring scatter sizes its
        headroom from S_MAX and silently drops writes past it, so a larger s
        would lose committed tokens instead of failing loudly.

        Paged pool: before the device step, each live slot's block table is
        grown to cover its worst-case writes this step (``seq_len + s``
        rows); afterwards the host token mirror advances by the raw commit
        counts.  ``warm=True`` compiles the step without touching the host
        block bookkeeping (the result must be discarded).
        """
        if not 0 <= s <= S_MAX:
            raise ValueError(
                f"s={s} outside [0, {S_MAX}]: the step's output buffer is "
                f"sized for at most S_MAX={S_MAX} speculative tokens and "
                f"would silently drop commits beyond it")
        with self.scope("step.prep"):
            if state.paged is not None and not warm:
                pk = state.paged
                grew = False
                for slot in pk.active_slots():
                    if pk.is_pending(slot):
                        # mid-chunked-prefill: the slot is parked done,
                        # writes nothing this step, and grows only when its
                        # next chunk is fed (prefill_chunk_into allocates
                        # those blocks)
                        continue
                    grew |= bool(pk.ensure(slot, pk.tokens(slot) + s))
                if grew:
                    # prefill_into/retire_slot keep the device table in
                    # sync, so the host->device upload only happens on
                    # actual growth.  Pending (mid-chunked-prefill) slots'
                    # rows stay -1 so their parked rows' masked decode
                    # writes remain dropped; their blocks are published by
                    # the final chunk's commit.
                    state = dataclasses.replace(
                        state, tcache=dict(state.tcache, bt=jnp.asarray(
                            pk.device_tables(exclude_pending=True))))
                state = self._drain_evicted(state)
            B = state.seq_lens.shape[0]
            # pagedness is part of the key: the paged wrapper takes the
            # extra cu_blocks operand, so a contiguous pool on the same
            # engine must never reuse a paged-built step fn (or vice versa)
            key = (B, s, state.paged is not None)
            if key not in self._step_fns:
                self._step_fns[key] = self._build_step(
                    B, s, paged_rows=(state.paged.logical_len
                                      if state.paged is not None else None))
            if warm:
                state = self._warm_shield(state)
            args = (tparams, dparams, state.tcache, state.dcache,
                    state.seq_lens, state.last2, state.out,
                    state.n_generated, state.done)
            if state.paged is not None:
                # ragged-grid operand: cumulative live-block counts from the
                # same host tables the device `bt` upload above mirrors, so
                # the kernel's grid always matches the table it prefetches
                cu = host_cu_blocks(
                    state.paged.device_tables(exclude_pending=True))
                args = (*args, jnp.asarray(cu))
        if self.sample:
            if rng is None:
                with self.scope("step.key"):
                    # lint: allow-host-sync(the sampled step's key: the pool's n_generated sum, when the caller passes no rng)
                    rng = jax.random.PRNGKey(int(np.asarray(state.n_generated).sum()))
            args = (*args, rng)
        with self.scope("step[B={},s={}]", B, s):
            (tc, dc, seq_lens, last2, out, n_gen, done, a, n_commit) = \
                self._step_fns[key](*args)
        new_state = DecodeState(tc, dc, seq_lens, last2, out, n_gen, done,
                                paged=state.paged)
        with self.scope("step.wait"):
            # lint: allow-host-sync(step-boundary sync: commit counts drive host-side block accounting)
            stats = StepStats(accepted=np.asarray(a), committed=np.asarray(n_commit))
        if state.paged is not None and not warm:
            with self.scope("step.commit"):
                for slot in state.paged.active_slots():
                    if not state.paged.is_pending(slot):
                        state.paged.commit(slot, int(stats.committed[slot]))
        return new_state, stats

    # ------------------------------------------------------------------
    # full generation driver

    def generate(self, tparams, dparams, tokens, prompt_lens, *, s: int,
                 cache_len: int, max_new: Optional[int] = None,
                 target_extras: Optional[Dict] = None,
                 collect_stats: bool = False,
                 key: Optional[jax.Array] = None):
        """Generate ``max_new`` tokens for every request with fixed s.
        Returns (tokens [B, max_new], list[StepStats], n_steps)."""
        state = self.prefill(tparams, dparams, tokens, prompt_lens, cache_len,
                             target_extras)
        stats = []
        n_steps = 0
        limit = max_new or self.max_new
        if self.sample and key is None:
            key = jax.random.PRNGKey(0)
        while True:
            rng = jax.random.fold_in(key, n_steps) if self.sample else None
            state, st = self.step(tparams, dparams, state, s, rng=rng)
            n_steps += 1
            if collect_stats:
                stats.append(st)
            if bool(np.asarray(state.done).all()) or n_steps > limit * 2 + 8:
                break
        return np.asarray(state.out)[:, :self.max_new], stats, n_steps

    def warmup(self, tparams, dparams, batch_sizes, s_values, cache_len: int,
               prompt_len: int = 8):
        """Pre-compile step functions for the profiling grid."""
        for b in batch_sizes:
            tokens = np.full((b, prompt_len), 3, np.int32)
            lens = np.full((b,), prompt_len, np.int32)
            state = self.prefill(tparams, dparams, tokens, lens, cache_len)
            for s in s_values:
                # warm=True: compile-only, and the donation shield keeps the
                # discarded dispatch from consuming `state` for the next s
                self.step(tparams, dparams, state, s, warm=True)


def make_spec_step(tgt, drf, B: int, s: int, *, eos_id: int = -1,
                   max_new: int = 128, prefix_offset: int = 0,
                   sample: bool = False, temperature: float = 1.0,
                   paged: bool = False,
                   chunk: Optional[Tuple[int, int, Any]] = None):
    """Pure one-speculative-step function (paper Algorithm 1, batched).

    Signature: fn(tparams, dparams, tcache, dcache, seq_lens, last2, out,
    n_generated, done[, cu_blocks][, rng]) -> (tcache', dcache', seq_lens',
    last2', out', n_generated', done', accepted, n_commit).

    ``paged=True`` adds the ``cu_blocks [B + 1]`` operand (host cumulative
    ragged grid-step counts, kernels/tuning.py) right after ``done``; the
    target verify forward threads it into the paged attention so the fused
    path runs the ragged kernel (kernels/paged.py) — the gather reference
    ignores it, so the flag is numerically free.

    ``chunk = (CB, R, d_single)`` (requires ``paged``) builds the MIXED
    verify+chunk step: six extra operands after ``cu_blocks`` — chunk
    slot, CB-bucketed tokens, start, target/draft feed limits, and the
    slot's host block-table row — and the target verify runs
    ``decode_step_mixed``, one ragged attention launch per layer serving
    both the decode slots' verify queries and the chunk slot's
    prefix-extension queries.  The draft's trailing chunk forward runs
    first (B=1 slice bounded to ``R`` rows, exactly the standalone chunk
    fn's draft half), then the usual draft loop; the chunk slot is parked
    ``done`` so its accept count is forced to zero and its row state never
    moves.  Bit-identical to standalone-chunk-then-step by per-query-row
    independence (see :meth:`SpecDecodeEngine.step_with_chunk`).

    ``sample=False`` (default) is the paper's argmax verification.
    ``sample=True`` is Leviathan/Chen-style stochastic speculative sampling
    (beyond-paper, DESIGN §10): the draft SAMPLES proposals from
    q(x) = softmax(logits/T); the target accepts token t_i with probability
    min(1, p_i(t_i)/q_i(t_i)) and on first rejection resamples from the
    residual norm(max(p − q, 0)) — provably distributed exactly as sampling
    from the target alone.  Takes one extra ``rng`` argument.

    Exposed at module level so the multi-pod dry-run can lower exactly the
    serving step the engine runs (launch/dryrun.py jits it with explicit
    in/out shardings); the engine jit-caches one instance per (B, s).
    """
    eos = eos_id
    assert chunk is None or paged, "the mixed step is paged-pool only"

    def body(tparams, dparams, tcache, dcache, seq_lens, last2, out,
             n_generated, done, cu_blocks, rng, chunk_ops=None):
        if sample:
            assert rng is not None, "sample=True needs an rng argument"
            k_draft, k_acc, k_res = jax.random.split(rng, 3)
        # ---- 0. mixed launch: the draft's trailing chunk forward first
        # (same dispatch order as standalone-chunk-then-step) ----
        if chunk_ops is not None:
            cslot, ctoks, cstart, ctl, cdl, cbt_row = chunk_ops
            CB, R, d_single = chunk
            if drf is not None:
                off1 = jnp.full((1,), cstart, jnp.int32)
                dl1 = jnp.full((1,), cdl, jnp.int32)
                ctoks1 = ctoks[None, :]
                if d_single is None:   # capacity 1: the pool IS the slot
                    _, dcache = drf.prefill_chunk(dparams, ctoks1, dcache,
                                                  off1, dl1, rows_limit=R)
                else:
                    _, d1n = drf.prefill_chunk(
                        dparams, ctoks1, _take_slot(dcache, d_single, cslot),
                        off1, dl1, rows_limit=R)
                    dcache = _put_slot(dcache, d1n, d_single, cslot)
        # ---- 1. draft phase ----
        dlens = seq_lens - prefix_offset
        drafts = []
        q_probs = []                                  # draft probs of drafts
        if s > 0:
            logits, dcache = drf.decode_step(dparams, last2, dcache, dlens - 1)
            lg = logits[:, -1]
            for i in range(0, s):
                if i > 0:
                    logits, dcache = drf.decode_step(dparams, d[:, None],
                                                     dcache, dlens + i)
                    lg = logits[:, 0]
                if sample:
                    qd = jax.nn.softmax(lg / temperature, axis=-1)   # [B, V]
                    d = jax.random.categorical(
                        jax.random.fold_in(k_draft, i), lg / temperature,
                        axis=-1).astype(jnp.int32)
                    q_probs.append(qd)
                else:
                    d = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                drafts.append(d)
            drafts = jnp.stack(drafts, axis=1)                    # [B, s]
        else:
            drafts = jnp.zeros((B, 0), jnp.int32)

        # ---- 2. verify: [t_{n-1}, d_1..d_s] ----
        feed = jnp.concatenate([last2[:, 1:], drafts], axis=1)    # [B, s+1]
        if chunk_ops is not None:
            # one launch, two query kinds: pad both streams to a shared
            # width (padding columns carry position -1 — write nowhere,
            # match nothing) and let the per-row masking sort them out
            Tm = max(s + 1, chunk[0])
            feed_m = (jnp.pad(feed, ((0, 0), (0, Tm - (s + 1))))
                      if Tm > s + 1 else feed)
            ct = (jnp.pad(ctoks, (0, Tm - chunk[0]))
                  if Tm > chunk[0] else ctoks)
            vlogits, tcache_out = tgt.decode_step_mixed(
                tparams, feed_m, tcache, seq_lens, cslot, ct, cstart, ctl,
                cbt_row, s + 1, cu_blocks)
            vlogits = vlogits[:, :s + 1]
        elif paged:
            vlogits, tcache_out = tgt.decode_step(tparams, feed, tcache,
                                                  seq_lens, cu_blocks)
        else:
            vlogits, tcache_out = tgt.decode_step(tparams, feed, tcache,
                                                  seq_lens)
        bidx = jnp.arange(B)

        if not sample:
            # ---- 3a. acceptance (argmax verification, Algorithm 1) ----
            pred = jnp.argmax(vlogits, axis=-1).astype(jnp.int32)  # [B, s+1]
            if s > 0:
                match = drafts == pred[:, :s]                      # [B, s]
                a = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
            else:
                a = jnp.zeros((B,), jnp.int32)
            a = jnp.where(done, 0, a)
            bonus = pred[bidx, a]                                  # [B]
        else:
            # ---- 3b. stochastic acceptance (Leviathan-style) ----
            p_all = jax.nn.softmax(vlogits / temperature, axis=-1)  # [B,s+1,V]
            if s > 0:
                q_all = jnp.stack(q_probs, axis=1)                  # [B,s,V]
                p_at = jnp.take_along_axis(p_all[:, :s],
                                           drafts[..., None], -1)[..., 0]
                q_at = jnp.take_along_axis(q_all, drafts[..., None], -1)[..., 0]
                ratio = p_at / jnp.maximum(q_at, 1e-20)             # [B, s]
                u = jax.random.uniform(k_acc, (B, s))
                acc = u < jnp.minimum(ratio, 1.0)
                a = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)
                # residual distribution at the cut point (or p_s if a == s)
                p_cut = p_all[bidx, a]                              # [B, V]
                q_pad = jnp.concatenate(
                    [q_all, jnp.zeros_like(q_all[:, :1])], axis=1)  # q_s = 0
                q_cut = q_pad[bidx, a]
                resid = jnp.maximum(p_cut - q_cut, 0.0)
                norm = resid.sum(-1, keepdims=True)
                resid = jnp.where(norm > 1e-20, resid / jnp.maximum(norm, 1e-20),
                                  p_cut)
            else:
                a = jnp.zeros((B,), jnp.int32)
                resid = p_all[:, 0]
            a = jnp.where(done, 0, a)
            bonus = jax.random.categorical(
                k_res, jnp.log(jnp.maximum(resid, 1e-30)), axis=-1
            ).astype(jnp.int32)

        # ---- 4. commit ----
        tcache_new = tgt.commit(tcache_out, a)

        # committed tokens this step: drafts[:a] then bonus at index a
        cand = jnp.concatenate([drafts, bonus[:, None]], axis=1)  # [B, s+1]
        cand = cand.at[bidx, a].set(bonus)
        icols = jnp.arange(s + 1)[None, :]                        # [B, s+1]
        write = (icols <= a[:, None]) & (~done[:, None])
        # stop at eos within the committed run
        is_eos = (cand == eos) & write
        eos_cum = jnp.cumsum(is_eos.astype(jnp.int32), axis=1)
        write &= (eos_cum - is_eos.astype(jnp.int32)) == 0        # keep first eos
        n_commit = write.sum(axis=1)

        cols = jnp.where(write, n_generated[:, None] + icols, out.shape[1])
        out = out.at[bidx[:, None], cols].set(cand, mode="drop")
        n_generated = n_generated + n_commit
        seq_lens = seq_lens + n_commit
        hit_eos = (is_eos & write).any(axis=1)
        done = done | hit_eos | (n_generated >= max_new)

        # last two committed tokens for the next draft phase
        last1 = jnp.where(a > 0,
                          cand[bidx, jnp.maximum(a - 1, 0)], last2[:, 1])
        new_last2 = jnp.where(
            done[:, None], last2,
            jnp.stack([last1, bonus], axis=1))
        last2 = jnp.where((n_commit > 0)[:, None], new_last2, last2)
        return (tcache_new, dcache, seq_lens, last2, out, n_generated, done,
                a, n_commit)

    # explicit signatures per variant so legacy callers (launch/dryrun.py,
    # the contiguous pool) keep the 9-arg form while the paged step gains
    # the cu_blocks operand at a fixed argnum (9) graph-lint can check
    if chunk is not None:
        def fn(tparams, dparams, tcache, dcache, seq_lens, last2, out,
               n_generated, done, cu_blocks, chunk_slot, chunk_tokens,
               chunk_start, chunk_t_limit, chunk_d_limit, chunk_bt_row,
               rng=None):
            return body(tparams, dparams, tcache, dcache, seq_lens, last2,
                        out, n_generated, done, cu_blocks, rng,
                        (chunk_slot, chunk_tokens, chunk_start,
                         chunk_t_limit, chunk_d_limit, chunk_bt_row))
    elif paged:
        def fn(tparams, dparams, tcache, dcache, seq_lens, last2, out,
               n_generated, done, cu_blocks, rng=None):
            return body(tparams, dparams, tcache, dcache, seq_lens, last2,
                        out, n_generated, done, cu_blocks, rng)
    else:
        def fn(tparams, dparams, tcache, dcache, seq_lens, last2, out,
               n_generated, done, rng=None):
            return body(tparams, dparams, tcache, dcache, seq_lens, last2,
                        out, n_generated, done, None, rng)
    return fn
