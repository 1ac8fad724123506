"""Serving telemetry hub: one span system for the scheduler, the backend
and the engine, the program's compile log, the live (s, batch) acceptance
observatory, and pool/scheduler gauges for the continuous-batching runtime.

The hub is strictly **read-only observability**: it never touches the step
pipeline's decisions, so token outputs and the :class:`StepTrace` are
bit-identical with telemetry on or off (tests/test_telemetry.py enforces the
contract on the live engine for the contiguous, paged-under-preemption, and
chunked-admission paths).  It is also **zero-overhead when off**: with
``engine.annotate`` False and no enabled hub, every span site enters the
shared no-op context — no ``perf_counter`` call, no string formatting, no
event construction.

Four instruments:

* **Spans** — :func:`span` is the one scope helper.  It opens a
  ``jax.profiler.TraceAnnotation`` named ``repro/<phase>`` when annotation
  is on (``engine.annotate``), so the span sits on the profiler's host
  timeline beside the device planes, and, with a hub attached, records the
  span stamped with ``time.perf_counter()`` at entry and exit (``t0``,
  ``t1``) plus its attributes.  Groups, one prefix each: ``sched.*`` (the
  scheduler's host code and its calls into the backend), ``step.*`` (the
  step call: ``step.prep`` / ``step.key`` / ``step.wait`` /
  ``step.commit`` / ``step.done``), ``prefill.*`` / ``chunk.*`` /
  ``attach.*`` (admission host prep and fences), ``retire.out`` /
  ``preempt.out`` (output reads), and the engine's jit dispatches
  (``step[B=..,s=..]``, ``prefill[B=..,P=..]``, ``chunk[CB=..]``, ...).
  Records are buffered in memory and optionally streamed as a JSONL event
  log (``jsonl_path=``).

* **The compile log** — :func:`compile_log` registers one
  ``jax.monitoring`` duration listener on first use and keeps a bounded,
  process-wide log of ``(perf_counter at the end, seconds, event,
  fun_name)`` for every trace, lowering, backend compile and persistent-
  cache read.  The engine names each jit after its registry entry and key,
  so ``fun_name`` says which program compiled.  Nothing runs while nothing
  compiles.

* **The (s, batch) acceptance observatory** — per executed decode step the
  accepted-draft counts accumulate into histograms keyed by (chosen s, live
  decode batch size).  With an expected-acceptance callable attached
  (``attach_expected_acceptance``; the scheduler wires the controller's
  analytical model automatically when it has one), the observatory surfaces
  observed-vs-predicted acceptance drift per cell and in aggregate — the
  paper's l(s) model validated online rather than only at profile time.

* **Pool and scheduler gauges** — per-iteration snapshots of slot occupancy
  vs parked-PREFILLING count, backlog depth, block-pool free/used depth and
  free-list fragmentation, plus monotone counters for every span phase and
  for compiles.  :meth:`prometheus_text` renders a Prometheus-style text
  exposition; :meth:`dashboard` renders a console summary (printed every
  ``dashboard_every`` iterations when set).

The standing regression surface over these metrics is
``benchmarks/serving_bench.py`` -> ``results/BENCH_serving.json``.
"""
from __future__ import annotations

import collections
import json
import sys
import time
from typing import Callable, Deque, Dict, List, Optional, Tuple

import jax
import numpy as np

# the scheduler's span taxonomy (ContinuousScheduler.run); the backend and
# the engine add the step / admission / retirement groups and their jit
# dispatch scopes (module docstring)
PHASES = ("sched.admit", "sched.prefill", "sched.chunk_continue",
          "sched.choose", "sched.preempt", "sched.decode_verify",
          "sched.commit", "sched.retire")
PREFIX = "repro/"              # every span's name on the profiler timeline


class _NullSpan:
    """The shared no-op span: what :func:`span` returns with annotation off
    and no hub, so a span site constructs nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


NULLSPAN = _NullSpan()


class Span:
    """One named phase (see :func:`span`)."""
    __slots__ = ("phase", "hub", "attrs", "t0", "_ann")

    def __init__(self, phase: str, hub, annotate: bool, attrs: dict):
        self.phase = phase
        self.hub = hub
        self.attrs = attrs
        self._ann = (jax.profiler.TraceAnnotation(PREFIX + phase)
                     if annotate else None)

    def set(self, **attrs) -> None:
        """Attributes known only once the phase's work is done."""
        self.attrs.update(attrs)

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        if self.hub is not None:
            self.hub.record(self.phase, self.t0, t1, self.attrs)
        return False


def span(phase: str, hub=None, annotate: bool = False, **attrs):
    """The scope of one phase: a ``TraceAnnotation`` ``repro/<phase>`` when
    ``annotate``, and with an enabled ``hub`` a record of the phase with
    ``t0``/``t1`` from ``time.perf_counter()`` (the clock of the backend's
    fences) and ``attrs``.  Neither: the shared :data:`NULLSPAN`."""
    if hub is not None and not hub.enabled:
        hub = None
    if hub is None and not annotate:
        return NULLSPAN
    return Span(phase, hub, annotate, attrs)


# ---------------------------------------------------------------------------
# the compile log

COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
                  "/jax/core/compile/backend_compile_duration": "compile",
                  "/jax/compilation_cache/cache_retrieval_time_sec":
                      "cache_read"}
COMPILE_LOG_LEN = 8192
_compile_log: Deque[Tuple[float, float, str, str]] = collections.deque(
    maxlen=COMPILE_LOG_LEN)
_compile_total = 0             # backend compiles (cache reads included)
_listening = False


def _on_duration(event: str, duration: float, **kwargs) -> None:
    global _compile_total
    kind = COMPILE_EVENTS.get(event)
    if kind is None:
        return
    _compile_log.append((time.perf_counter(), float(duration), kind,
                         str(kwargs.get("fun_name", ""))))
    if kind == "compile":
        _compile_total += 1


def compile_log() -> Deque[Tuple[float, float, str, str]]:
    """The process-wide compile log: ``(perf_counter at the end, seconds,
    event, fun_name)`` per trace / lower / compile (a backend compile, which
    also covers a persistent-cache read) / cache_read, the newest
    ``COMPILE_LOG_LEN`` kept.  The first call registers the listener."""
    global _listening
    if not _listening:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return _compile_log


def compiles_total() -> int:
    """Backend compiles since the listener was registered."""
    compile_log()
    return _compile_total


class Telemetry:
    """Serving telemetry hub (see module docstring).

    Every recording method is a no-op when ``enabled=False`` — but the
    scheduler goes further and never calls them at all in that case, so a
    disabled hub costs exactly nothing on the hot path.

    ``annotate_device`` makes ``serve_continuous_live`` turn the engine's
    ``annotate`` switch on for the run, so every span also sits on the
    profiler timeline.  ``profile_dir`` arms :meth:`start`/:meth:`stop` to
    wrap the serving run in a ``jax.profiler`` trace (and implies
    ``annotate_device=True``).  ``jsonl_path`` streams every event as one
    JSON line at emit time; the in-memory ``events`` buffer always holds the
    same records.
    """

    def __init__(self, enabled: bool = True,
                 jsonl_path: Optional[str] = None,
                 dashboard_every: int = 0,
                 annotate_device: bool = False,
                 profile_dir: Optional[str] = None,
                 stream=None):
        self.enabled = bool(enabled)
        self.profile_dir = profile_dir
        self.annotate_device = bool(annotate_device or profile_dir)
        self.dashboard_every = int(dashboard_every)
        self.stream = stream
        self.events: List[dict] = []
        self.counters: Dict[str, int] = {}
        self.tokens_committed = 0
        self.iterations = 0
        self.gauges: Dict[str, float] = {}
        self.peaks: Dict[str, float] = {}
        # observatory cells: (s, batch) -> accumulators
        self._acc: Dict[Tuple[int, int], dict] = {}
        # s -> expected normalized acceptance (l(s) / s), if a model exists
        self.expected_acceptance: Optional[Callable[[int], float]] = None
        self._jsonl = open(jsonl_path, "w") if jsonl_path else None
        self._profiling = False
        self._compiles0 = compiles_total() if self.enabled else 0

    # ------------------------------------------------------------------
    # recording hooks (called by the scheduler only when enabled)

    def _emit(self, ev: dict) -> None:
        self.events.append(ev)
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(ev, default=float) + "\n")

    def record(self, phase: str, t0: float, t1: float, attrs: dict) -> None:
        """Record one completed span (what :class:`Span` calls on exit):
        ``phase`` ran from ``t0`` to ``t1`` (``time.perf_counter()``)."""
        if not self.enabled:
            return
        self.counters[phase] = self.counters.get(phase, 0) + 1
        if phase == "sched.commit":
            self.tokens_committed += int(attrs.get("tokens", 0))
        self._emit({"ev": "span", "phase": phase, "t0": t0, "t1": t1,
                    **attrs})

    @property
    def compiles(self) -> int:
        """Backend compiles (persistent-cache reads included) in this
        process since the hub was built."""
        return compiles_total() - self._compiles0 if self.enabled else 0

    def observe_step(self, *, s: int, batch: int, accepted,
                     duration: float) -> None:
        """Feed one executed decode step into the acceptance observatory:
        per-row accepted-draft counts at (chosen s, decode batch size)."""
        if not self.enabled or s <= 0:
            return
        key = (int(s), int(batch))
        rec = self._acc.get(key)
        if rec is None:
            rec = self._acc[key] = {"hist": np.zeros(s + 1, np.int64),
                                    "draws": 0, "accepted": 0,
                                    "steps": 0, "time": 0.0}
        a = np.asarray(accepted, dtype=np.int64)
        np.add.at(rec["hist"], np.clip(a, 0, s), 1)
        rec["draws"] += int(a.size)
        rec["accepted"] += int(a.sum())
        rec["steps"] += 1
        rec["time"] += float(duration)

    def iteration(self, iteration: int, clock: float, **vals) -> None:
        """End-of-iteration gauge snapshot (occupancy, backlog, block pool,
        ...); also drives the periodic console dashboard."""
        if not self.enabled:
            return
        self.iterations += 1
        self.gauges.update(vals)
        self.gauges["clock"] = float(clock)
        for k in ("occupancy", "backlog", "used_blocks", "prefilling",
                  "grid_occupancy"):
            if k in vals:
                self.peaks[k] = max(self.peaks.get(k, 0), vals[k])
        self._emit({"ev": "gauges", "iter": int(iteration),
                    "clock": float(clock), **vals})
        if self.dashboard_every and self.iterations % self.dashboard_every == 0:
            print(self.dashboard(), file=self.stream or sys.stdout, flush=True)

    def attach_expected_acceptance(self, fn: Callable[[int], float]) -> None:
        """Attach ``s -> expected normalized acceptance`` (typically
        ``model.l_of_s(s) / s``); enables the drift gauge."""
        self.expected_acceptance = fn

    # ------------------------------------------------------------------
    # observatory views

    def acceptance_table(self) -> List[dict]:
        """One row per observed (s, batch) cell: accepted-token histogram,
        observed normalized acceptance, and — with an expected-acceptance
        model attached — the observed-minus-predicted drift."""
        rows = []
        for (s, b) in sorted(self._acc):
            rec = self._acc[(s, b)]
            observed = (rec["accepted"] / (rec["draws"] * s)
                        if rec["draws"] else None)
            expected = (min(float(self.expected_acceptance(s)), 1.0)
                        if self.expected_acceptance is not None else None)
            drift = (observed - expected
                     if observed is not None and expected is not None
                     else None)
            rows.append({
                "s": s, "batch": b, "steps": rec["steps"],
                "draws": rec["draws"],
                "mean_accepted": rec["accepted"] / max(rec["draws"], 1),
                "acceptance": observed, "expected": expected, "drift": drift,
                "hist": rec["hist"].tolist(),
                "mean_step_s": rec["time"] / max(rec["steps"], 1),
            })
        return rows

    def acceptance_drift(self) -> Optional[float]:
        """Draw-weighted mean observed-minus-predicted acceptance across all
        (s, batch) cells; None without a model or without observations."""
        num = den = 0.0
        for row in self.acceptance_table():
            if row["drift"] is not None:
                num += row["drift"] * row["draws"]
                den += row["draws"]
        return num / den if den else None

    # ------------------------------------------------------------------
    # expositions

    def prometheus_text(self) -> str:
        """Prometheus text exposition of counters, gauges, peaks, and the
        per-(s, batch) acceptance observatory."""
        out = ["# TYPE repro_serving_spans_total counter"]
        for phase in sorted(self.counters):
            out.append(f'repro_serving_spans_total{{phase="{phase}"}} '
                       f"{self.counters[phase]}")
        out.append("# TYPE repro_serving_tokens_committed_total counter")
        out.append(f"repro_serving_tokens_committed_total "
                   f"{self.tokens_committed}")
        out.append("# TYPE repro_serving_iterations_total counter")
        out.append(f"repro_serving_iterations_total {self.iterations}")
        out.append("# TYPE repro_serving_compiles_total counter")
        out.append(f"repro_serving_compiles_total {self.compiles}")
        for name in sorted(self.gauges):
            out.append(f"# TYPE repro_serving_{name} gauge")
            out.append(f"repro_serving_{name} {self.gauges[name]}")
        for name in sorted(self.peaks):
            out.append(f"# TYPE repro_serving_peak_{name} gauge")
            out.append(f"repro_serving_peak_{name} {self.peaks[name]}")
        acc = self.acceptance_table()
        if acc:
            out.append("# TYPE repro_serving_acceptance_observed gauge")
            for r in acc:
                if r["acceptance"] is not None:
                    out.append(
                        f'repro_serving_acceptance_observed{{s="{r["s"]}",'
                        f'batch="{r["batch"]}"}} {r["acceptance"]:.6f}')
            if any(r["drift"] is not None for r in acc):
                out.append("# TYPE repro_serving_acceptance_drift gauge")
                for r in acc:
                    if r["drift"] is not None:
                        out.append(
                            f'repro_serving_acceptance_drift{{s="{r["s"]}",'
                            f'batch="{r["batch"]}"}} {r["drift"]:+.6f}')
            out.append("# TYPE repro_serving_step_seconds gauge")
            for r in acc:
                out.append(f'repro_serving_step_seconds{{s="{r["s"]}",'
                           f'batch="{r["batch"]}"}} {r["mean_step_s"]:.6g}')
        return "\n".join(out) + "\n"

    def dashboard(self) -> str:
        """Multi-line console summary of the latest gauges, counters, and
        the busiest acceptance cells."""
        g = self.gauges
        lines = [f"── serving telemetry · iter {self.iterations} · "
                 f"clock {g.get('clock', 0.0):.3f}s ──"]
        occ = g.get("occupancy", 0)
        cap = g.get("capacity", "?")
        lines.append(
            f" slots {occ}/{cap} occupied · {g.get('prefilling', 0)} "
            f"prefilling · backlog {g.get('backlog', 0)} · decode batch "
            f"{g.get('decode_batch', 0)} (s={g.get('s', 0)})")
        if "free_blocks" in g:
            lines.append(
                f" blocks {g['free_blocks']} free / {g.get('used_blocks', 0)}"
                f" used · fragmentation {g.get('fragmentation', 0.0):.2f}"
                f" · grid occupancy {g.get('grid_occupancy', 0.0):.2f}")
        cnt = " ".join(f"{k}={v}" for k, v in sorted(self.counters.items()))
        lines.append(f" counters: {cnt or '(none)'} · tokens "
                     f"{self.tokens_committed} · compiles {self.compiles}")
        acc = sorted(self.acceptance_table(), key=lambda r: -r["draws"])[:3]
        for r in acc:
            drift = ("" if r["drift"] is None
                     else f", drift {r['drift']:+.3f}")
            lines.append(
                f" acceptance s={r['s']} b={r['batch']}: "
                f"{r['acceptance']:.3f} over {r['draws']} draws{drift}")
        return "\n".join(lines)

    def summary(self) -> dict:
        """JSON-friendly roll-up (the serving benchmark embeds this)."""
        return {
            "iterations": self.iterations,
            "tokens_committed": self.tokens_committed,
            "compiles": self.compiles,
            "counters": dict(self.counters),
            "gauges": dict(self.gauges),
            "peaks": dict(self.peaks),
            "acceptance": self.acceptance_table(),
            "acceptance_drift": self.acceptance_drift(),
        }

    # ------------------------------------------------------------------
    # persistence / profiler lifecycle

    def start(self) -> None:
        """Begin the jax profiler trace when ``profile_dir`` is set (no-op
        otherwise); the serving entry points call this around the run.  The
        Python tracer stays off (it slows every Python call several times
        over); the host tracer level keeps the ``TraceAnnotation`` spans."""
        if self.enabled and self.profile_dir and not self._profiling:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.profile_dir,
                                     profiler_options=opts)
            self._profiling = True

    def stop(self) -> None:
        if self._profiling:
            jax.profiler.stop_trace()
            self._profiling = False
        if self._jsonl is not None:
            self._jsonl.flush()

    def close(self) -> None:
        self.stop()
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None
