"""Profiler trace to per-layer numbers.

``compact`` reads an ``.xplane.pb`` (``jax.profiler`` output) into two
lists: device operations of the first TPU device (name, start, duration in
ns), and host spans named ``repro/*`` (the engine's jit dispatches) or
``bench/*`` (the benchmark's own spans around each backend call, around
the scheduler's host work between calls, and the window markers).
``reduce`` turns that into device busy time over a window, the idle gaps
labelled by the innermost host span open at their midpoint, the device
operations that took most time, and the time of named kernels.  Keeping
the compact form small lets a test check the reduction on a recorded
trace.
"""
from __future__ import annotations

import gzip
import json
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

HOST_PREFIXES = ("repro/", "bench/")
WINDOW_MARK = "bench/window"
NAME_CHARS = 200     # of an operation's HLO text: name, shape, first operand


def compact(xplane_path: str) -> Dict:
    from jax._src.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    device, host, lines = [], [], {}
    dev_planes = sorted((p for p in pd.planes
                         if p.name.startswith("/device:TPU:")),
                        key=lambda p: p.name)
    if dev_planes:
        plane = dev_planes[0]
        names = [l.name for l in plane.lines]
        lines = {"device_plane": plane.name, "device_lines": names}
        for l in plane.lines:
            if l.name != "XLA Ops":
                continue
            for e in l.events:
                device.append([e.name[:NAME_CHARS], float(e.start_ns),
                               float(e.duration_ns)])
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for l in p.lines:
            for e in l.events:
                if e.name.startswith(HOST_PREFIXES):
                    host.append([e.name, float(e.start_ns),
                                 float(e.duration_ns)])
    device.sort(key=lambda r: r[1])
    host.sort(key=lambda r: r[1])
    return {"device": device, "host": host, "lines": lines}


def save(c: Dict, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(c, f)


def load(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def window(c: Dict) -> Tuple[float, float]:
    """The traced window: the ``bench/window`` marker span if present,
    else first to last device operation."""
    for name, t0, d in c["host"]:
        if name == WINDOW_MARK:
            return t0, t0 + d
    if not c["device"]:
        raise ValueError("trace holds no device operation")
    return c["device"][0][1], max(t + d for _, t, d in c["device"])


def _busy_intervals(ops: Sequence, lo: float, hi: float) -> List[List[float]]:
    out: List[List[float]] = []
    for _, t, d in ops:
        a, b = max(t, lo), min(t + d, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def op_kind(text: str) -> str:
    """A short, stable label of an operation's HLO text: its opcode name
    without the instance number, and for a custom call (a Pallas kernel)
    its output shape."""
    name = text.split(" = ")[0].lstrip("%")
    base = re.sub(r"[.\d]+$", "", name)
    if "custom-call(" in text and " = " in text:
        base += " " + text.split(" = ")[1].split("{")[0]
    return base


def self_times(ops: Sequence, lo: float, hi: float) -> Dict[str, float]:
    """Seconds per ``op_kind`` inside the window, each operation counted
    without the operations nested in it (a loop contains its body's
    operations on the same trace line)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[list] = []           # [end, kind, clipped duration]

    def close(entry):
        out[entry[1]] += entry[2] * 1e-9
    for name, t, d in ops:
        while stack and stack[-1][0] <= t:
            close(stack.pop())
        clip = max(0.0, min(t + d, hi) - max(t, lo))
        if stack:
            stack[-1][2] -= clip
        stack.append([t + d, op_kind(name), clip])
    while stack:
        close(stack.pop())
    return out


def _innermost(host: Sequence, t: float) -> str:
    best, best_start = "host:untraced", None
    for name, t0, d in host:
        if t0 > t:
            break
        if t0 <= t < t0 + d and name != WINDOW_MARK and (
                best_start is None or t0 >= best_start):
            best, best_start = name, t0
    return best


def reduce(c: Dict, top: int = 10) -> Dict:
    """Busy and window seconds, idle share, the device operations with the
    most self time (grouped by ``op_kind``), and idle gaps grouped by the
    host span open at their midpoint."""
    lo, hi = window(c)
    ops = [r for r in c["device"] if r[1] < hi and r[1] + r[2] > lo]
    busy = _busy_intervals(ops, lo, hi)
    busy_ns = sum(b - a for a, b in busy)
    by_op = self_times(ops, lo, hi)
    gaps: Dict[str, float] = defaultdict(float)
    prev = lo
    for a, b in busy + [[hi, hi]]:
        if a > prev:
            gaps[_innermost(c["host"], (prev + a) / 2)] += (a - prev) * 1e-9
        prev = max(prev, b)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    window_s = (hi - lo) * 1e-9
    return {"window_s": window_s, "busy_s": busy_ns * 1e-9,
            "idle_share": 1.0 - busy_ns * 1e-9 / window_s,
            "device_ops": [[k, v] for k, v in rank(by_op)],
            "idle_gaps": [[k, v] for k, v in rank(gaps)]}


def kernel_time(c: Dict, pattern: str) -> Tuple[int, float]:
    """Count and device seconds of operations matching ``pattern`` inside
    the window."""
    lo, hi = window(c)
    rx = re.compile(pattern)
    n, t = 0, 0.0
    for name, t0, d in c["device"]:
        if lo <= t0 < hi and rx.search(name):
            n += 1
            t += d * 1e-9
    return n, t
