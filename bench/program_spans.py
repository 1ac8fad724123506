"""Device-idle time split by the program's own spans.

The program opens a ``repro/<phase>`` ``TraceAnnotation`` around each phase
of the scheduler (``repro/sched.*``), the backend and the engine's step
call (``repro/step.*`` and the dispatch ``repro/step[...]``), admission,
retirement and every jit dispatch, whenever its span switch
(``engine.annotate``) is on, as it is in a traced window.  ``split`` walks
each device-idle interval of the traced window and charges every instant
of it to the innermost host span that covers it (the one that began last),
by overlap: an interval that spans three nested spans is shared among the
three.  Host spans are those ``trace_reduce.compact`` keeps: the program's
``repro/*`` and the benchmark's ``bench/*`` (less the window marker).

The compile log is the program's (``serving/telemetry.py``), reached
through the adapter, which passes attributes through to the backend and
its engine.  A program without these spans or this log (before they were
added) reads as None.
"""
from __future__ import annotations

import heapq
from collections import defaultdict
from typing import Dict, Optional

from bench import trace_reduce as TR

PROGRAM = "repro/"
STEP = "repro/step"          # the step call: step.* and its dispatch scope
STEP_HOST = "repro/step."    # the step call's host spans
SCHED = "repro/sched."       # the scheduler's host code
UNTRACED = "host:untraced"


def split(c: Dict) -> Dict:
    """Idle seconds of the traced window by innermost host span
    (``UNTRACED`` where none is open), the seconds under at least one
    ``repro/*`` span, and the window's idle and total seconds."""
    lo, hi = TR.window(c)
    ops = [r for r in c["device"] if r[1] < hi and r[1] + r[2] > lo]
    gaps, prev = [], lo
    for a, b in TR._busy_intervals(ops, lo, hi) + [[hi, hi]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    spans = [(t0, t0 + d, name) for name, t0, d in c["host"]
             if name != TR.WINDOW_MARK and d > 0 and t0 < hi and t0 + d > lo]
    # span boundaries in time order; at one instant, ends before starts
    marks = sorted([(t0, 1, i) for i, (t0, _, _) in enumerate(spans)]
                   + [(t1, 0, i) for i, (_, t1, _) in enumerate(spans)])
    by_span: Dict[str, float] = defaultdict(float)
    program_ns = 0.0
    heap, ended = [], set()      # open spans, the latest start on top
    n_program = 0                # open repro/* spans

    def apply(mark) -> None:
        nonlocal n_program
        _, start, i = mark
        if start:
            heapq.heappush(heap, (-spans[i][0], i))
        else:
            ended.add(i)
        n_program += (1 if start else -1) * spans[i][2].startswith(PROGRAM)

    def charge(a: float, b: float) -> None:
        nonlocal program_ns
        if b <= a:
            return
        while heap and heap[0][1] in ended:
            heapq.heappop(heap)
        by_span[spans[heap[0][1]][2] if heap else UNTRACED] += (b - a) * 1e-9
        if n_program:
            program_ns += b - a

    k = 0
    for g0, g1 in gaps:
        while k < len(marks) and marks[k][0] <= g0:
            apply(marks[k])
            k += 1
        t = g0
        while k < len(marks) and marks[k][0] < g1:
            charge(t, marks[k][0])
            t = marks[k][0]
            apply(marks[k])
            k += 1
        charge(t, g1)
    idle = sum(g1 - g0 for g0, g1 in gaps) * 1e-9
    return {"by_span": dict(by_span), "program_s": program_ns * 1e-9,
            "idle_s": idle, "window_s": (hi - lo) * 1e-9}


def _has(c: Dict, prefix: str) -> bool:
    return any(name.startswith(prefix) for name, _, _ in c["host"])


def idle_share(run, prefix: str, marker: str) -> Optional[float]:
    """Idle seconds whose innermost span starts with ``prefix``, as a % of
    the traced window; None without a trace or without a ``marker`` span
    (a program that has none)."""
    if run.trace is None or not _has(run.trace, marker):
        return None
    sp = split(run.trace)
    secs = sum(v for k, v in sp["by_span"].items() if k.startswith(prefix))
    return 100.0 * secs / sp["window_s"]


def describe(run, top: int = 8) -> Optional[str]:
    """One log line: idle seconds, those under a program span and those
    under none, and the innermost spans that hold the most."""
    if run.trace is None:
        return None
    sp = split(run.trace)
    rows = sorted(sp["by_span"].items(), key=lambda kv: -kv[1])[:top]
    return (f"program spans: idle {sp['idle_s']:.6f} s of a "
            f"{sp['window_s']:.6f} s window; under a repro/* span "
            f"{sp['program_s']:.6f} s, under none "
            f"{sp['idle_s'] - sp['program_s']:.6f} s; innermost "
            + ", ".join(f"{k} {v:.6f}" for k, v in rows))


def window_compiles(run) -> Optional[list]:
    """``fun_name`` of every backend compile (persistent-cache reads
    included) the program's compile log holds inside the traced window;
    None without the log."""
    engine = getattr(run.adapter, "engine", None)
    log = getattr(engine, "compile_log", None)
    if log is None:
        return None
    lo, hi = run.adapter.t0 + run.t0, run.adapter.t0 + run.t1
    return [name for t_end, _, event, name in list(log)
            if event == "compile" and lo <= t_end < hi]
