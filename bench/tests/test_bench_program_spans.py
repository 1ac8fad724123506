"""Device-idle time split by the program's spans (``bench/program_spans.py``)
on a hand-made trace, and the program's spans in a traced serve of the tiny
cell on the CPU: nested inside the benchmark's, and stamped by the hub on the
profiler's clock under one offset."""
import glob
import os
import time
from collections import defaultdict
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench import harness as H
from bench import program_spans as PS
from bench import trace_reduce as TR
from bench.arrivals import Mix
from bench.tests.tiny import JaxConfig, make_root
from bench.wallclock import WallClockBackend

MS = 1e6  # ns


def _trace():
    # device busy 0-10, 40-75, 90-100 of a 0-100 ms window
    device = [["%fusion.1 = f32[8]", 0.0, 10 * MS],
              ["%fusion.2 = f32[8]", 40 * MS, 35 * MS],
              ["%fusion.3 = f32[8]", 90 * MS, 10 * MS]]
    # the gap 10-40 lies under three nested spans; the gap 75-90 under none
    host = [["bench/window", 0.0, 100 * MS],
            ["repro/sched.decode_verify", 5 * MS, 65 * MS],     # 5-70
            ["bench/step", 15 * MS, 45 * MS],                   # 15-60
            ["repro/step.wait", 20 * MS, 10 * MS]]              # 20-30
    return {"device": device, "host": host, "lines": {}}


def test_split_shares_a_gap_among_nested_spans_by_overlap():
    sp = PS.split(_trace())
    assert sp["window_s"] == pytest.approx(0.1)
    assert sp["idle_s"] == pytest.approx(0.045)
    by = sp["by_span"]
    # 10-15 the scheduler's span, 15-20 and 30-40 the benchmark's, 20-30 the
    # step's wait; 75-90 under no span
    assert by["repro/sched.decode_verify"] == pytest.approx(0.005)
    assert by["bench/step"] == pytest.approx(0.015)
    assert by["repro/step.wait"] == pytest.approx(0.010)
    assert by[PS.UNTRACED] == pytest.approx(0.015)
    assert sum(by.values()) == pytest.approx(sp["idle_s"])
    # 10-40 lies under a program span (the scheduler's covers it all)
    assert sp["program_s"] == pytest.approx(0.030)
    # the midpoint rule of trace_reduce gives the whole first gap to one
    assert dict(TR.reduce(_trace())["idle_gaps"])["repro/step.wait"] == \
        pytest.approx(0.030)
    run = SimpleNamespace(trace=_trace())
    assert PS.idle_share(run, PS.STEP, PS.STEP_HOST) == pytest.approx(10.0)
    assert PS.idle_share(run, PS.SCHED, PS.SCHED) == pytest.approx(5.0)
    assert "under none 0.015000 s" in PS.describe(run)


def test_readers_are_silent_on_a_program_without_spans_or_log():
    """A program that has only its dispatch scopes and no compile log (as
    before the step-call spans existed) reads as None, and does not raise."""
    c = _trace()
    c["host"] = [h for h in c["host"] if not h[0].startswith("repro/")]
    c["host"].append(["repro/step[B=32,s=2]", 16 * MS, 1 * MS])
    run = SimpleNamespace(trace=c, adapter=SimpleNamespace(
        engine=SimpleNamespace(), t0=0.0), t0=0.0, t1=1.0)
    assert PS.idle_share(run, PS.STEP, PS.STEP_HOST) is None
    assert PS.idle_share(run, PS.SCHED, PS.SCHED) is None
    assert PS.window_compiles(run) is None   # the log is absent
    assert PS.idle_share(SimpleNamespace(trace=None), PS.SCHED,
                         PS.SCHED) is None


def _inside(inner, outers):
    return any(o0 <= i0 and i0 + d <= o0 + od
               for i0, d in [inner] for o0, od in outers)


def test_traced_tiny_serve_nests_program_spans_on_the_hub_clock(tmp_path):
    from repro.core.adaptive import AdaptiveController, SpeculationLUT
    from repro.serving.scheduler import (PrefillBudgetAdmit,
                                         serve_continuous_live)
    from repro.serving.telemetry import Telemetry
    root = make_root(tmp_path)
    conf = H.load_json(os.path.join(root, "bench", "configs", "tiny.json"))
    mix = Mix.load(os.path.join(root, "bench", "traffic", "tinychat.json"),
                   "tinychat")
    sv = conf["serving"]
    trace_dir = str(tmp_path / "trace")
    with JaxConfig():
        eng, tp, dp, be = H.build(conf, mix, 11)
        H.warm_up(be, conf, mix, conf["target"]["vocab_size"])
        reqs = H._requests(mix, 11, 1.0, conf["target"]["vocab_size"])
        ad = WallClockBackend(be, time.perf_counter(),
                              np.asarray(be.state.n_generated))
        tel = Telemetry()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ad.spans = eng.annotate = True
        try:
            serve_continuous_live(
                reqs, eng, tp, dp,
                AdaptiveController(lut=SpeculationLUT(H.lut_table(conf))),
                backend=ad, telemetry=tel,
                policy=PrefillBudgetAdmit(sv["token_budget"],
                                          chunk=sv["chunk"]))
        finally:
            ad.close_spans()
            eng.annotate = False
            jax.profiler.stop_trace()
    assert eng.telemetry is None
    c = TR.compact(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True)[0])
    by_name = defaultdict(list)
    for name, t0, d in c["host"]:
        by_name[name].append((t0, d))
    names = set(by_name)
    for want in ("repro/step.prep", "repro/step.wait", "repro/step.commit",
                 "repro/step.done", "repro/prefill.prep",
                 "repro/prefill.fence", "repro/retire.out",
                 "repro/sched.admit", "repro/sched.choose",
                 "repro/sched.decode_verify", "repro/sched.commit"):
        assert want in names, want
    # the step call's spans inside the benchmark's step span, admission's
    # inside its prefill or chunk span, the scheduler's host code inside
    # the benchmark's scheduler span, and the scheduler's step call around
    # the benchmark's
    calls = by_name["bench/prefill"] + by_name["bench/chunk"]
    for name, spans in by_name.items():
        for sp in spans:
            if name.startswith(("repro/step.", "repro/step[")):
                assert _inside(sp, by_name["bench/step"]), name
            if name.startswith(("repro/prefill.", "repro/chunk.")):
                assert _inside(sp, calls), name
            if name in ("repro/sched.choose", "repro/sched.commit"):
                assert _inside(sp, by_name["bench/scheduler"]), name
    for sp in by_name["bench/step"]:
        assert _inside(sp, by_name["repro/sched.decode_verify"])
    # every hub record is its profiler event, under one clock offset
    recs = defaultdict(list)
    for ev in tel.events:
        if ev["ev"] == "span":
            recs["repro/" + ev["phase"]].append(ev["t0"])
    offsets = []
    for name, t0s in recs.items():
        evs = sorted(t for t, _ in by_name[name])
        assert len(evs) == len(t0s), name
        offsets += [e * 1e-9 - t for e, t in zip(evs, sorted(t0s))]
    assert len(offsets) > 100
    assert max(offsets) - min(offsets) < 1e-3
