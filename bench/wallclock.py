"""Wall-clock adapter around a step backend of the continuous scheduler.

The scheduler (``serving/scheduler.py``) advances a virtual clock by the
seconds each backend call returns, and jumps to the next arrival when its
pool is empty.  This adapter makes that clock the wall clock: it stamps
``time.perf_counter()`` at every ``prefill`` / ``prefill_chunk`` /
``attach`` / ``commit_attached`` / ``step`` boundary and returns, for each
call, the wall seconds since the scheduler's clock was last set.  Before a
request's first admission call it sleeps until the request's scheduled
arrival, so no request is admitted early.  The backend's own fenced
durations are kept apart for the per-layer metrics.

Everything a metric needs is recorded here, on the wall clock relative to
``t0`` (the start of the traffic): admission, first and last served token,
and per step the rows that were live, what each committed, and the sum of
the pool's ``n_generated`` that the sampled step seeds its random key from.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np


@dataclass
class StepRec:
    t_call: float            # wall seconds since t0 at the call
    t_ret: float             # ... at its return
    s: int                   # speculation length of the step
    dev_s: float             # the backend's own fenced seconds
    key_sum: int             # sum of n_generated over the pool before it
    # (slot, rid, committed, context before the step, tokens served)
    rows: Tuple[Tuple[int, int, int, int, int], ...]
    blocks: int = 0          # KV blocks held in the paged pool after it


@dataclass
class CallRec:
    kind: str                # prefill | chunk | attach | commit_attached
    t_call: float
    t_ret: float
    dev_s: float
    rid: int
    start: int               # first feed position (0 for a whole prefill)
    n: int                   # feed positions written


class WallClockBackend:
    """Wraps ``inner`` (a ``ContinuousEngineBackend``) for the scheduler.

    ``on_boundary(now)`` runs at the start of every timed call with the wall
    seconds since ``t0``; it may raise to end the run (window or drain
    limit).  ``spans`` opens a ``jax.profiler.TraceAnnotation`` around each
    call and around the scheduler's host work between calls.
    """

    def __init__(self, inner, t0: float, n_generated: np.ndarray,
                 on_boundary: Optional[Callable[[float], None]] = None):
        self.inner = inner
        self.t0 = t0
        self.clock = 0.0
        self.on_boundary = on_boundary
        self.spans = False
        self._gap = None
        self.ngen = np.array(n_generated, np.int64)
        self.admit: Dict[int, float] = {}
        self.late: List[float] = []
        self.live: Dict[int, int] = {}            # slot -> rid (decoding)
        self.req: Dict[int, object] = {}          # rid -> Request
        self.raw: Dict[int, int] = {}             # rid -> raw committed
        self.served: Dict[int, int] = {}          # rid -> served tokens
        self.first: Dict[int, float] = {}
        self.last: Dict[int, float] = {}
        self.finish: Dict[int, float] = {}
        self.chunked: set = set()
        self.steps: List[StepRec] = []
        self.calls: List[CallRec] = []
        self.preempted = 0

    # -- attributes the scheduler reads pass through to the inner backend
    def __getattr__(self, name):
        return getattr(self.inner, name)

    @property
    def s_cap(self):
        return self.inner.s_cap

    @s_cap.setter
    def s_cap(self, v):
        self.inner.s_cap = v

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _enter(self, req=None) -> float:
        if self._gap is not None:
            self._gap.__exit__(None, None, None)
            self._gap = None
        sched = self.clock
        if req is not None and req.rid not in self.admit:
            # the scheduler's clock jumps to the next arrival when its pool
            # is empty; otherwise every admitted request has arrived already
            sched = max(self.clock, req.arrival)
            t = self.now()
            if t < req.arrival:
                time.sleep(req.arrival - t)
                t = self.now()
                self.late.append(t - req.arrival)
            self.admit[req.rid] = t
            self.req[req.rid] = req
        if self.on_boundary is not None:
            self.on_boundary(self.now())
        return sched

    def _leave(self, sched: float, now: float) -> float:
        self.clock = now
        if self.spans:
            self._gap = self._span("bench/scheduler")
            self._gap.__enter__()
        return now - sched

    def _timed(self, kind, fn, req, start, n, go_live_slot=None):
        sched = self._enter(req)
        t_call = self.now()
        if self.spans:
            with self._span(f"bench/{kind}"):
                dev = fn()
        else:
            dev = fn()
        t_ret = self.now()
        self.calls.append(CallRec(kind, t_call, t_ret, float(dev), req.rid,
                                  start, n))
        if go_live_slot is not None:
            self.live[go_live_slot] = req.rid
            self.ngen[go_live_slot] = 0
            self.raw.setdefault(req.rid, 0)
            self.served.setdefault(req.rid, 0)
        return self._leave(sched, t_ret)

    # -- timed calls
    def prefill(self, req, slot: int) -> float:
        total = req.prompt_len + req.n_generated
        return self._timed("prefill", lambda: self.inner.prefill(req, slot),
                           req, 0, total - 1, go_live_slot=slot)

    def prefill_chunk(self, req, slot: int, start: int, n: int) -> float:
        self.chunked.add(req.rid)
        final = start + n == req.prompt_len + req.n_generated - 1
        return self._timed(
            "chunk", lambda: self.inner.prefill_chunk(req, slot, start, n),
            req, start, n, go_live_slot=slot if final else None)

    def attach(self, req, slot: int, n_prefix: int) -> float:
        return self._timed("attach",
                           lambda: self.inner.attach(req, slot, n_prefix),
                           req, 0, n_prefix)

    def commit_attached(self, req, slot: int) -> float:
        return self._timed("commit_attached",
                           lambda: self.inner.commit_attached(req, slot),
                           req, 0, 0, go_live_slot=slot)

    def step(self, s: int):
        sched = self._enter()
        key_sum = int(self.ngen.sum())
        t_call = self.now()
        if self.spans:
            with self._span("bench/step"):
                dev, committed, done = self.inner.step(s)
        else:
            dev, committed, done = self.inner.step(s)
        t_ret = self.now()
        self.ngen += np.asarray(committed, np.int64)
        rows = []
        for slot, rid in self.live.items():
            c = int(committed[slot])
            ctx = self.req[rid].prompt_len + self.raw[rid]
            self.raw[rid] += c
            take = min(c, self.req[rid].max_new - self.served[rid])
            rows.append((slot, rid, c, ctx, take))
            if take > 0:
                self.first.setdefault(rid, t_ret)
                self.served[rid] += take
                if self.served[rid] == self.req[rid].max_new:
                    self.last[rid] = t_ret
        self.steps.append(StepRec(t_call, t_ret, s, float(dev), key_sum,
                                  tuple(rows), self.blocks_in_use()))
        return self._leave(sched, t_ret), committed, done

    def blocks_in_use(self) -> int:
        kv = getattr(self.inner, "kv", None)
        return kv.pool.used_count if kv is not None else 0

    # -- untimed calls that change which rows are live
    def retire(self, slot: int, req=None) -> None:
        self.inner.retire(slot, req)
        self.live.pop(slot, None)
        if req is not None:
            self.finish[req.rid] = self.now()

    def preempt(self, slot: int, req) -> None:
        self.inner.preempt(slot, req)
        self.live.pop(slot, None)
        self.preempted += 1

    def close_spans(self) -> None:
        if self._gap is not None:
            self._gap.__exit__(None, None, None)
            self._gap = None
