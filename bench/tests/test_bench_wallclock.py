"""The wall-clock adapter: the scheduler's clock tracks the wall clock,
no request is admitted before it arrives, and nothing compiles inside the
measured window (tiny live engine on the CPU)."""
import os
import time

from bench import harness as H
from bench.arrivals import Mix
from bench.compile_clock import CompileClock
from bench.tests.tiny import JaxConfig, make_root

SPARSE = {"kind": "open", "rate_per_s": 4.0, "cv": 1.0, "warm_in_s": 0.3,
          "drain_s": 10.0, "base_seed": 9,
          "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                     "min": 8, "max": 30},
          "output": {"dist": "lognormal", "median": 6, "sigma": 0.3,
                     "min": 4, "max": 8}}


def test_scheduler_clock_is_the_wall_clock(tmp_path):
    root = make_root(tmp_path, mixes={"sparse": SPARSE})
    conf = H.load_json(os.path.join(root, "bench", "configs", "tiny.json"))
    mix = Mix.load(os.path.join(root, "bench", "traffic", "sparse.json"),
                   "sparse")
    with JaxConfig():
        clock = CompileClock()
        eng, tp, dp, be = H.build(conf, mix, 5)
        H.warm_up(be, conf, mix, conf["target"]["vocab_size"])
        run = H.Run(cell="tiny.sparse", conf=conf, mix=mix, seconds=2.0)
        info = H.drive(run, eng, tp, dp, be, 5, False,
                       os.path.join(str(tmp_path), "tr"), clock,
                       time.perf_counter())
    ad = run.adapter
    assert run.ended_by == "drained"
    assert info["window_compile"]["compiles"] == 0
    assert ad.late, "no request found the pool idle: the jump went untested"
    assert len(ad.steps) > 10
    assert all(0 < st.blocks <= be.kv.num_blocks for st in ad.steps
               if st.rows)
    for r in run.requests:
        assert ad.admit[r.rid] >= r.arrival
        assert r.arrival <= r.start <= ad.admit[r.rid] + 1e-9
        assert abs(r.first_token - ad.first[r.rid]) < 1e-6
        assert abs(r.finish - ad.last[r.rid]) < 1e-6
        assert ad.served[r.rid] == r.n_generated == r.max_new
    last = max([st.t_ret for st in ad.steps] + [c.t_ret for c in ad.calls])
    assert ad.clock == last


BACKLOG = {"kind": "backlog", "n_requests": 400, "base_seed": 5,
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                      "min": 8, "max": 60},
           "output": {"dist": "uniform", "min": 8, "max": 24}}


def test_backlog_window_opens_after_turnover_and_closes_the_run(tmp_path):
    root = make_root(tmp_path, mixes={"backlog": BACKLOG})
    conf = H.load_json(os.path.join(root, "bench", "configs", "tiny.json"))
    mix = Mix.load(os.path.join(root, "bench", "traffic", "backlog.json"),
                   "backlog")
    with JaxConfig():
        clock = CompileClock()
        eng, tp, dp, be = H.build(conf, mix, 6)
        H.warm_up(be, conf, mix, conf["target"]["vocab_size"])
        run = H.Run(cell="tiny.backlog", conf=conf, mix=mix, seconds=1.0)
        info = H.drive(run, eng, tp, dp, be, 6, False,
                       os.path.join(str(tmp_path), "tr"), clock,
                       time.perf_counter())
    H.window_counts(run)
    ad = run.adapter
    assert run.ended_by == "window"
    assert info["window_compile"]["compiles"] == 0
    assert abs(run.w1 - run.w0 - 1.0) < 1e-9
    # the window opened once a whole pool's worth of requests had finished
    done_before = [t for t in ad.finish.values() if t <= run.w0]
    assert len(done_before) >= conf["serving"]["slots"]
    assert run.attempted == sum(1 for t in ad.admit.values() if t < run.w1)
    assert run.attempted < len(run.requests)
    assert max(st.t_call for st in ad.steps) < run.w1
