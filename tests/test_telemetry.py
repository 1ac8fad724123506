"""Telemetry hub (serving/telemetry.py): zero overhead when off, the phase
span taxonomy + JSONL event log, the (s, batch) acceptance observatory,
pool/scheduler gauges — and the standing contract that telemetry only
READS the step pipeline: token outputs and the StepTrace are identical
with the hub on or off, on the sim backend and on the live engine across
the contiguous, paged-under-preemption, and chunked-admission paths."""
import dataclasses
import io
import json
import time

import jax
import numpy as np
import pytest

from repro.configs import registry as R
from repro.core.adaptive import (AdaptiveController, SpeculationLUT,
                                 lut_from_model)
from repro.core.analytical import LatencyModel
from repro.core.spec_decode import SpecDecodeEngine
from repro.serving.scheduler import (ContinuousEngineBackend,
                                     ContinuousScheduler, PrefillBudgetAdmit,
                                     SimStepBackend, serve_continuous_live)
from repro.serving.server import serve_continuous
from repro.serving.slots import BlockPool
from repro.serving.telemetry import NULLSPAN, PHASES, Telemetry, span
from repro.serving.traffic import TrafficPhase, make_requests, uniform_traffic

CACHE_LEN = 96
BLOCK = 8


def _model(batches=(1, 2, 4, 8, 16)):
    return LatencyModel(alpha={b: 1e-4 * b ** 0.8 for b in batches},
                        beta={b: 5e-3 for b in batches},
                        t_s={b: 2e-4 for b in batches}, c=0.9, gamma=0.548)


# ---------------------------------------------------------------------------
# gauges: BlockPool fragmentation


def test_blockpool_fragmentation_gauge():
    pool = BlockPool(8, 4)
    assert pool.fragmentation == 0.0          # fully free: one run
    blocks = pool.alloc(8)                    # lowest-id first: 0..7
    assert pool.fragmentation == 0.0          # nothing free
    pool.free([blocks[0], blocks[2], blocks[4]])   # {0, 2, 4}: all singles
    assert pool.fragmentation == pytest.approx(1 - 1 / 3)
    pool.free([blocks[1]])                    # {0, 1, 2, 4}: best run is 3
    assert pool.fragmentation == pytest.approx(1 - 3 / 4)
    pool.free([blocks[3], blocks[5], blocks[6], blocks[7]])
    assert pool.fragmentation == 0.0          # whole pool contiguous again


# ---------------------------------------------------------------------------
# sim backend: inertness, parity, spans, observatory, expositions


def test_disabled_telemetry_is_inert():
    m = _model()
    tel = Telemetry(enabled=False)
    sched = ContinuousScheduler(
        SimStepBackend(m, capacity=4, seed=0),
        AdaptiveController(lut=lut_from_model(m, s_max=8)), telemetry=tel)
    # the zero-overhead contract: the scheduler drops a disabled hub
    # entirely, so the hot path never even branches on it
    assert sched._tel is None
    sched.run(uniform_traffic(20, 0.01, 1.0, 100, seed=4, max_new=8))
    assert tel.events == [] and tel.counters == {}
    assert tel.iterations == 0 and tel.acceptance_table() == []
    # direct calls short-circuit too while disabled, and a disabled hub
    # gets no span
    tel.record("sched.prefill", 0.0, 0.1, {"rid": 1})
    assert span("sched.prefill", tel) is NULLSPAN
    tel.observe_step(s=2, batch=2, accepted=[1, 2], duration=0.1)
    tel.iteration(0, 0.0, occupancy=1)
    assert tel.events == [] and tel.counters == {} and tel.gauges == {}


def test_sim_schedule_identical_with_telemetry_on():
    m = _model()

    def go(tel):
        reqs = uniform_traffic(40, 0.01, 2.0, 100, seed=4, max_new=16)
        return serve_continuous(reqs, m,
                                AdaptiveController(lut=lut_from_model(m)),
                                max_batch=8, seed=2, telemetry=tel)

    r0, r1 = go(None), go(Telemetry())
    for f in ("admitted", "occupancy", "committed", "preempted", "chunked"):
        assert ([getattr(t, f) for t in r0.trace]
                == [getattr(t, f) for t in r1.trace]), f
    np.testing.assert_allclose(r0.latencies, r1.latencies)


def _paged_chunked_sim(tel):
    """Paged + chunked sim run sized to actually preempt (13 blocks of 8
    rows across 4 slots, long prompts every third request)."""
    m = _model()
    ctrl = AdaptiveController(lut=SpeculationLUT({1: 4, 2: 3, 4: 2}))
    reqs = make_requests(10, [TrafficPhase(0.01, 1.0, float("inf"))], 100,
                         seed=3, max_new=12)
    rng = np.random.default_rng(0)
    for j, r in enumerate(reqs):
        r.max_new = int(rng.integers(8, 17))
        if j % 3 == 0:
            L = int(rng.integers(40, 57))
            r.tokens = rng.integers(0, 100, (L,)).astype(np.int32)
            r.prompt_len = L
    sched = ContinuousScheduler(
        SimStepBackend(m, capacity=4, seed=1, block_size=BLOCK,
                       num_blocks=13, max_context=96), ctrl,
        policy=PrefillBudgetAdmit(token_budget=16, chunk=8), telemetry=tel)
    res = sched.run(reqs)
    res.trace = sched.trace
    return res


def test_span_taxonomy_counters_and_jsonl(tmp_path):
    path = tmp_path / "events.jsonl"
    tel = Telemetry(jsonl_path=str(path))
    res = _paged_chunked_sim(tel)
    tel.close()
    trace = res.trace
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines, "no events streamed"
    spans = [e for e in lines if e["ev"] == "span"]
    assert {e["phase"] for e in spans} <= set(PHASES)
    # every span is stamped on the perf_counter clock, in order
    assert all(e["t0"] <= e["t1"] for e in spans)
    # counters must reconcile with the StepTrace ground truth
    assert tel.counters["sched.chunk_continue"] == sum(
        len(t.chunked) for t in trace)
    assert tel.counters["sched.preempt"] == sum(
        len(t.preempted) for t in trace)
    assert tel.counters["sched.preempt"] > 0, \
        "geometry lost its preemption bite"
    assert tel.counters["sched.decode_verify"] == sum(
        1 for t in trace if t.occupancy > 0)
    assert tel.counters["sched.commit"] == tel.counters["sched.decode_verify"]
    assert tel.counters["sched.retire"] == len(res.requests)
    # the admission pass runs every iteration; those that admitted say so
    assert tel.counters["sched.admit"] >= len(trace)
    assert sum(1 for e in spans if e["phase"] == "sched.admit"
               and e.get("rids")) == sum(1 for t in trace if t.admitted)
    assert tel.counters.get("sched.prefill", 0) == sum(
        1 for t in trace for dt in t.prefill_s if dt >= 0)
    # the in-memory buffer and the streamed file are the same log
    assert len(tel.events) == len(lines)
    # commit spans accumulate exactly the tokens the requests ended up with
    assert tel.tokens_committed == sum(r.n_generated for r in res.requests)
    # per-span dt totals match the chunk seconds the trace recorded
    chunk_dt = sum(e["dt"] for e in spans
                   if e["phase"] == "sched.chunk_continue")
    assert chunk_dt == pytest.approx(sum(sum(t.chunk_s) for t in trace))


def test_acceptance_observatory_tracks_process():
    m = _model()
    tel = Telemetry()
    tel.attach_expected_acceptance(lambda s: m.l_of_s(s) / s)
    reqs = uniform_traffic(60, 0.005, 1.0, 100, seed=6, max_new=24)
    res = serve_continuous(reqs, m,
                           AdaptiveController(lut=lut_from_model(m)),
                           max_batch=8, seed=1, telemetry=tel)
    table = tel.acceptance_table()
    assert table
    # one draw per live decode row per speculative (s > 0) step
    assert sum(row["draws"] for row in table) == sum(
        t.occupancy for t in res.trace if t.occupancy > 0 and t.s > 0)
    for row in table:
        assert sum(row["hist"]) == row["draws"]
        assert 0.0 <= row["acceptance"] <= 1.0
        assert row["expected"] is not None
    # the sim draws acceptance from the same l(s) the model predicts, so
    # aggregate drift must be small
    drift = tel.acceptance_drift()
    assert drift is not None and abs(drift) < 0.1


def test_gauges_prometheus_and_dashboard():
    stream = io.StringIO()
    tel = Telemetry(dashboard_every=4, stream=stream)
    _paged_chunked_sim(tel)
    g = tel.gauges
    # drained at the end: everything retired, all blocks back on the list
    assert g["occupancy"] == 0 and g["backlog"] == 0
    assert g["free_blocks"] == 13 and g["used_blocks"] == 0
    assert 0.0 <= g["fragmentation"] <= 1.0
    assert tel.peaks["occupancy"] >= 2
    assert tel.peaks["used_blocks"] > 0
    text = tel.prometheus_text()
    assert "repro_serving_occupancy 0" in text
    assert 'repro_serving_spans_total{phase="sched.decode_verify"}' in text
    assert "repro_serving_compiles_total " in text
    assert "repro_serving_acceptance_observed{" in text
    assert "repro_serving_peak_occupancy" in text
    dash = tel.dashboard()
    assert "backlog" in dash and "blocks" in dash
    assert stream.getvalue(), "periodic dashboard never printed"
    summ = tel.summary()
    assert summ["counters"] == tel.counters
    assert summ["tokens_committed"] == tel.tokens_committed


# ---------------------------------------------------------------------------
# live engine: token + StepTrace identity with telemetry on vs off


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


def _ctrl():
    return AdaptiveController(lut=SpeculationLUT({1: 4, 2: 3, 4: 2}))


def _live_trace(tcfg, n=8, seed=7, long_every=0, budget=(4, 17)):
    reqs = make_requests(n, [TrafficPhase(0.002, 1.0, float("inf"))],
                         tcfg.vocab_size, seed=seed, max_new=16)
    rng = np.random.default_rng(3)
    for j, r in enumerate(reqs):
        # arrival = 0: the live clock advances by MEASURED wall times, so
        # nonzero arrivals would make admission composition depend on how
        # fast each run's prefills happened to be — the on-vs-off identity
        # assertion must be purely structural
        r.arrival = 0.0
        r.max_new = int(rng.integers(*budget))
        if long_every and j % long_every == 0:
            L = int(rng.integers(28, 40))
            r.tokens = rng.integers(0, tcfg.vocab_size, (L,)).astype(
                np.int32)
            r.prompt_len = L
    return reqs


# trace/backend/policy per parity case; geometries proven to preempt /
# chunk by tests/test_paged_kv.py and tests/test_chunked_prefill.py
LIVE_CASES = {
    "contiguous": dict(trace={}, backend={}, chunked_policy=False),
    "paged_preempt": dict(trace=dict(budget=(18, 25)),
                          backend=dict(block_size=BLOCK, num_blocks=18),
                          chunked_policy=False),
    "chunked": dict(trace=dict(long_every=3),
                    backend=dict(block_size=BLOCK, num_blocks=40),
                    chunked_policy=True),
}


@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_live_token_and_trace_identity_with_telemetry(engine, case,
                                                      tmp_path):
    cfg = LIVE_CASES[case]
    eng, tp, dp, tcfg = engine

    def go(tel):
        be = ContinuousEngineBackend(eng, tp, dp, capacity=4,
                                     cache_len=CACHE_LEN, warm_s=(2, 3, 4),
                                     collect_outputs=True, **cfg["backend"])
        pol = (PrefillBudgetAdmit(token_budget=16, chunk=8)
               if cfg["chunked_policy"] else None)
        res = serve_continuous_live(_live_trace(tcfg, **cfg["trace"]), eng,
                                    tp, dp, _ctrl(), backend=be, policy=pol,
                                    telemetry=tel)
        return res, be

    tel = Telemetry(jsonl_path=str(tmp_path / f"{case}.jsonl"),
                    annotate_device=True)
    (r0, b0), (r1, b1) = go(None), go(tel)
    assert eng.annotate is False and eng.telemetry is None
    tel.close()
    for f in ("admitted", "occupancy", "committed", "preempted",
              "done_rids", "chunked"):
        assert ([getattr(t, f) for t in r0.trace]
                == [getattr(t, f) for t in r1.trace]), f
    assert set(b0.outputs) == set(b1.outputs)
    for rid in b0.outputs:
        np.testing.assert_array_equal(b0.outputs[rid], b1.outputs[rid],
                                      err_msg=f"{case} rid {rid}")
    # each case exercised the machinery it claims to cover
    if case == "paged_preempt":
        assert tel.counters["sched.preempt"] > 0
        assert tel.counters["preempt.out"] == tel.counters["sched.preempt"]
        assert sum(len(t.preempted) for t in r1.trace) > 0
    if case == "chunked":
        assert tel.counters["sched.chunk_continue"] > 0
        assert tel.counters["chunk.fence"] > 0
        assert sum(len(t.chunked) for t in r1.trace) > 0
    assert tel.counters["sched.retire"] == len(r1.requests)
    # the engine's and the backend's spans reached the hub too
    assert tel.counters["step.wait"] == tel.counters["sched.decode_verify"]
    assert tel.counters["step.done"] == tel.counters["sched.decode_verify"]
    assert tel.counters["retire.out"] == len(r1.requests)
    assert tel.tokens_committed == sum(r.n_generated for r in r1.requests)


def test_device_annotation_scopes_run_and_reset(engine):
    """annotate_device=True routes every jit dispatch through a
    TraceAnnotation scope (a no-op outside an active profiler trace) and
    the engine flag is restored after the run."""
    eng, tp, dp, tcfg = engine
    assert eng.annotate is False
    tel = Telemetry(annotate_device=True)
    res = serve_continuous_live(_live_trace(tcfg, n=4), eng, tp, dp,
                                _ctrl(), capacity=2, cache_len=CACHE_LEN,
                                telemetry=tel)
    assert all(r.finish is not None for r in res.requests)
    assert tel.iterations == len(res.trace)
    assert eng.annotate is False          # restored after the run


def test_spans_off_construct_nothing(engine, monkeypatch):
    """With the span switch off and no enabled hub, every span site takes
    the shared no-op path: a serve completes with TraceAnnotation and Span
    unbuildable, and the hub stays empty."""
    import repro.serving.telemetry as T
    eng, tp, dp, tcfg = engine

    def refuse(*a, **k):
        raise AssertionError("a span was built with spans off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    monkeypatch.setattr(T, "Span", refuse)
    tel = Telemetry(enabled=False)
    be = ContinuousEngineBackend(eng, tp, dp, capacity=4,
                                 cache_len=CACHE_LEN, warm_s=(2, 3, 4),
                                 block_size=BLOCK, num_blocks=40)
    res = serve_continuous_live(_live_trace(tcfg, long_every=3), eng, tp, dp,
                                _ctrl(), backend=be, telemetry=tel,
                                policy=PrefillBudgetAdmit(token_budget=16,
                                                          chunk=8))
    assert all(r.finish is not None for r in res.requests)
    assert sum(len(t.chunked) for t in res.trace) > 0
    assert tel.events == [] and tel.counters == {}
    assert eng.annotate is False and eng.telemetry is None


def test_compile_log_names_the_jit_of_a_shape_change(engine):
    """A prompt in a new bucket, admitted after the serve is under way,
    compiles its prefill mid-serve; the program's compile log names the jit
    by registry entry and key, and the hub counts it."""
    from repro.serving.telemetry import compile_log
    eng, tp, dp, tcfg = engine
    reqs = _live_trace(tcfg, n=5, budget=(6, 9))
    rng = np.random.default_rng(5)
    for r in reqs[:4]:
        r.tokens = rng.integers(0, tcfg.vocab_size, (10,)).astype(np.int32)
        r.prompt_len = 10
    # bucket 96 (the whole cache), served last
    reqs[4].tokens = rng.integers(0, tcfg.vocab_size, (70,)).astype(np.int32)
    reqs[4].prompt_len = 70
    reqs[4].arrival = 1e-9
    tel = Telemetry()
    t_start = time.perf_counter()
    # capacity 3 is a pool shape no other test compiles
    serve_continuous_live(reqs, eng, tp, dp, _ctrl(), capacity=3,
                          cache_len=CACHE_LEN, telemetry=tel)
    log = [e for e in compile_log() if e[0] >= t_start]
    names = {name for _, _, event, name in log if event == "compile"}
    assert any("step__3_" in n for n in names), names
    assert any(n.endswith(f"prefill__1_96_{CACHE_LEN})") for n in names), \
        names
    first_step = min(t for t, _, ev, n in log
                     if ev == "compile" and "step__3_" in n)
    late = [t for t, _, ev, n in log
            if ev == "compile" and n.endswith(f"prefill__1_96_{CACHE_LEN})")]
    assert min(late) > first_step       # compiled after serving began
    assert tel.compiles == len([e for e in log if e[2] == "compile"])
    assert f"repro_serving_compiles_total {tel.compiles}" in \
        tel.prometheus_text()


def test_profile_start_keeps_the_python_tracer_off(monkeypatch, tmp_path):
    seen = {}

    def start(log_dir, profiler_options=None, **_):
        seen["opts"] = profiler_options
    monkeypatch.setattr(jax.profiler, "start_trace", start)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    tel = Telemetry(profile_dir=str(tmp_path))
    assert tel.annotate_device
    tel.start()
    tel.close()
    assert seen["opts"].python_tracer_level == 0
    assert seen["opts"].host_tracer_level == 2


def test_every_allowed_host_sync_sits_in_a_named_scope():
    """Each deliberate sync of the host-sync hot zones (the lines carrying
    ``# lint: allow-host-sync``) runs inside a span scope, so a profiler
    trace names the wait."""
    import ast
    import os
    from tools.lint import astutil, pragmas
    from tools.lint.rules.host_sync import HOT_QUALNAMES
    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src",
                       "repro")
    checked = 0
    for parts, quals in HOT_QUALNAMES.items():
        path = os.path.join(src, *parts)
        source = open(path).read()
        tree = ast.parse(source)
        astutil.attach_parents(tree)
        lines = {p.target_line for p in pragmas.collect(path, source)
                 if p.rule == "host-sync"}
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and node.lineno in lines):
                continue
            fns = [astutil.qualname(f)
                   for f in astutil.enclosing_functions(node)]
            if not any(q == h or q.startswith(h + ".")
                       for q in fns for h in quals):
                continue
            scoped = any(
                isinstance(p, ast.With) and any(
                    isinstance(it.context_expr, ast.Call)
                    and getattr(it.context_expr.func, "attr",
                                getattr(it.context_expr.func, "id", ""))
                    in ("scope", "span") for it in p.items)
                for p in astutil.parents(node))
            assert scoped, f"{'/'.join(parts)}:{node.lineno}"
            checked += 1
    assert checked >= 10
