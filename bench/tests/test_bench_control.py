"""The control (the float32 reference rounded to fp8, put in the program's
place) reads above the limit that the program's own run stays under.

At the tiny size the logits are small beside the sampling noise of T = 1,
so fp8 rounding flips a token only now and then; the test serves at T =
0.2 and longer outputs, so that every run gives the control enough
decisions to show."""
import os
import time

from bench import harness as H
from bench.arrivals import Mix
from bench.compile_clock import CompileClock
from bench.tests.tiny import TINY, JaxConfig, make_root

LONGOUT = {"kind": "open", "rate_per_s": 4.0, "cv": 1.0, "warm_in_s": 0.5,
           "drain_s": 10.0, "base_seed": 7,
           "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                      "min": 8, "max": 60},
           "output": {"dist": "lognormal", "median": 40, "sigma": 0.3,
                      "min": 24, "max": 60}}


def test_fp8_control_fails_where_the_program_passes(tmp_path):
    root = make_root(tmp_path, mixes={"longout": LONGOUT},
                     config=dict(TINY, sampling={"temperature": 0.2}))
    conf = H.load_config(root, "tiny")
    mix = Mix.load(os.path.join(root, "bench", "traffic", "longout.json"),
                   "longout")
    seed = 4242
    with JaxConfig():
        clock = CompileClock()
        eng, tp, dp, be = H.build(conf, mix, seed)
        H.warm_up(be, conf, mix, conf["target"]["vocab_size"])
        run = H.Run(cell="tiny.longout", conf=conf, mix=mix, seconds=2.0)
        H.drive(run, eng, tp, dp, be, seed, False,
                os.path.join(str(tmp_path), "tr"), clock, time.perf_counter())
        rids = H.pick_sample(run, seed, conf["correct"]["sample_requests"])
        vals = H.readings(conf, mix, seed, H.collect_sample(run, be, rids),
                          fp8_control=True)
    limits = H.load_limits(root, "tiny.longout")
    limit = limits["max"]["token_gap"]
    assert vals["decisions"] >= limits["min_decisions"]
    assert vals["token_gap"] <= limit
    assert vals["control_token_gap"] > limit
    assert vals["control_accept_gap"] >= 0.0
