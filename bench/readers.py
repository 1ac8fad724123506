"""The quantities the metric files in ``bench/metrics/`` report, each a
function of one finished ``harness.Run``.  End-to-end quantities use the
measured window ``[w0, w1)``; per-layer ones the traced window
``[t0, t1)``.  A reader that finds nothing to read returns None.
"""
from __future__ import annotations

import statistics
from typing import List, Optional

from bench import archs
from bench import flops as F
from bench.stats import in_window, percentile, time_weighted_mean, tpot

# The paged attention kernels are the only custom calls whose first operand
# is a scalar-prefetched s32 array (the block plan); with ``cu_blocks``
# passed, as in every verify step and paged chunk, the kernel is the ragged
# one (kernels/paged.py).
RAGGED_KERNEL = r"custom-call\(s32\["


def _window_requests(run, lo, hi):
    return [r for r in run.requests if in_window(r.arrival, lo, hi)]


def ttft_values(run) -> List[float]:
    """Per window request, the wall time of the step that served its first
    token minus its scheduled arrival; a request still waiting at the
    drain limit counts with its wait so far."""
    limit = run.w1 + run.mix.drain_s
    return [run.adapter.first.get(r.rid, limit) - r.arrival
            for r in _window_requests(run, run.w0, run.w1)]


def ttft_p(run, q: float) -> Optional[float]:
    """``q``-th percentile (ms) of ``ttft_values``."""
    vals = ttft_values(run)
    return percentile(vals, q) * 1e3 if vals else None


def tpot_p(run, q: float) -> Optional[float]:
    """``q``-th percentile (ms) over finished window requests of (last
    token - first token) / (tokens - 1)."""
    ad = run.adapter
    vals = [tpot(ad.first[r.rid], ad.last[r.rid], r.max_new)
            for r in _window_requests(run, run.w0, run.w1)
            if r.rid in ad.last and r.max_new >= 2]
    return percentile(vals, q) * 1e3 if vals else None


def output_tokens_per_s(run) -> Optional[float]:
    """Tokens served by steps that returned inside the window, per second
    of window."""
    n = sum(take for st in run.adapter.steps
            if in_window(st.t_ret, run.w0, run.w1) for *_, take in st.rows)
    return n / (run.w1 - run.w0)


def _steps(run):
    return [st for st in run.adapter.steps
            if in_window(st.t_call, run.t0, run.t1)]


def _calls(run):
    return [c for c in run.adapter.calls
            if in_window(c.t_call, run.t0, run.t1)]


def prefill_share(run) -> Optional[float]:
    """Fenced prefill and chunk seconds over the window, in %."""
    calls = _calls(run)
    return 100.0 * sum(c.dev_s for c in calls) / (run.t1 - run.t0)


def step_ms(run) -> Optional[float]:
    steps = _steps(run)
    return statistics.median(st.dev_s for st in steps) * 1e3 if steps else None


def commit_per_row(run) -> Optional[float]:
    rows = [c for st in _steps(run) for _, _, c, _, _ in st.rows]
    return sum(rows) / len(rows) if rows else None


def decode_batch_mean(run) -> Optional[float]:
    steps = _steps(run)
    if not steps:
        return None
    return time_weighted_mean([len(st.rows) for st in steps],
                              [st.t_ret - st.t_call for st in steps])


def window_flops(run) -> float:
    """Target flops of the window's prefilled and committed tokens."""
    m = run.conf["target"]
    A = archs.of(m)
    total = 0.0
    for c in _calls(run):
        if c.kind in ("prefill", "chunk"):
            total += A.tokens_flops(m, c.start, c.n, logits=False)
    for st in _steps(run):
        for _, _, c, ctx, _ in st.rows:
            total += A.tokens_flops(m, ctx - 1, c, logits=True)
    return total


def mfu(run) -> Optional[float]:
    """Window flops over (fenced step and prefill seconds x bf16 peak), %."""
    busy = sum(st.dev_s for st in _steps(run)) + sum(c.dev_s
                                                     for c in _calls(run))
    if busy <= 0:
        return None
    return 100.0 * window_flops(run) / (busy * run.peaks["bf16_flops_per_s"])


def ragged_least_time(run) -> float:
    """Roofline least time of every ragged-kernel call of the window: one
    call per layer per verify step (each live row: s+1 queries against
    its context plus the step's writes) and per paged chunk."""
    m, bs = run.conf["target"], run.conf["serving"]["block_size"]
    A = archs.of(m)
    t = 0.0
    for st in _steps(run):
        rows = [(st.s + 1, ctx + st.s) for _, _, _, ctx, _ in st.rows]
        t += F.least_time(*A.attn_call(m, rows, bs), run.peaks)[0]
    for c in _calls(run):
        if c.kind == "chunk":
            t += F.least_time(*A.attn_call(m, [(c.n, c.start + c.n)], bs),
                              run.peaks)[0]
    return t * m["n_layers"]


def ragged_calls(run) -> int:
    return run.conf["target"]["n_layers"] * (
        len(_steps(run)) + sum(1 for c in _calls(run) if c.kind == "chunk"))


def ragged_attn_roofline(run) -> Optional[float]:
    from bench.trace_reduce import kernel_time
    if run.trace is None:
        return None
    n, secs = kernel_time(run.trace, RAGGED_KERNEL)
    if n == 0 or secs <= 0:
        return None
    return 100.0 * ragged_least_time(run) / secs


def device_idle_share(run) -> Optional[float]:
    if run.reduced is None:
        return None
    return 100.0 * run.reduced["idle_share"]
