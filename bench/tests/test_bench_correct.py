"""``correct`` comes out true for a sound run and false for each fault a
serving cell can have, planted in the timed path under the harness.  One
engine is built and compiled for all cases; each run gets a fresh pool."""
import dataclasses
import json
import os

import numpy as np
import pytest

from bench import harness as H
from bench import run as R
from bench.arrivals import Mix
from bench.tests.tiny import JaxConfig, make_root

SEED = 2147483990


def _token_altered(eng, be):
    """One served token of one row per step is changed after the step."""
    step = be.step

    def altered(s):
        dt, committed, done = step(s)
        live = [i for i, c in enumerate(np.asarray(committed)) if c > 0]
        if live:
            slot = live[0]
            idx = int(np.asarray(be.state.n_generated)[slot]) - 1
            out = be.state.out
            be.state = dataclasses.replace(be.state, out=out.at[slot, idx].set(
                (out[slot, idx] + 1) % eng.tcfg.vocab_size))
        return dt, committed, done
    be.step = altered


def _half_batch(eng, be):
    """The first half of the pool's rows is left out: their step results
    are dropped and zeros served in their place."""
    step = be.step

    def half(s):
        dt, committed, done = step(s)
        c = np.asarray(committed)
        n = np.asarray(be.state.n_generated)
        out = be.state.out
        for slot in range(be.capacity // 2):
            if c[slot] > 0:
                out = out.at[slot, n[slot] - c[slot]:n[slot]].set(0)
        be.state = dataclasses.replace(be.state, out=out)
        return dt, committed, done
    be.step = half


def _state_unchanged(eng, be):
    """The step returns the state it was given: nothing is committed."""
    be.step = lambda s: (1e-3, np.zeros(be.capacity, np.int32),
                         np.asarray(be.state.done))


FAULTS = {"none": None, "token_altered": _token_altered,
          "half_batch": _half_batch, "state_unchanged": _state_unchanged}


@pytest.fixture(scope="module")
def engine(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("correct"))
    conf = H.load_config(root, "tiny")
    mix = Mix.load(os.path.join(root, "bench", "traffic", "tinychat.json"),
                   "tinychat")
    with JaxConfig():
        eng, tp, dp = H.build_engine(conf, mix, SEED)
    return root, eng, tp, dp


@pytest.mark.parametrize("fault", list(FAULTS))
def test_correct_catches_faults(fault, engine, monkeypatch, capsys):
    root, eng, tp, dp = engine

    def faulty(conf, mix, seed):
        assert seed == SEED
        be = H.build_backend(conf, eng, tp, dp)
        if FAULTS[fault] is not None:
            FAULTS[fault](eng, be)
        return eng, tp, dp, be
    monkeypatch.setattr(H, "build", faulty)
    with JaxConfig():
        rc = R.main(["--workload", "tiny.tinychat", "--seed", str(SEED),
                     "--seconds", "2", "--trace", "0"],
                    root=root, require_tpu=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(res)[-1] == "checks"
    assert res["correct"] is (fault == "none"), res["checks"]
    if fault == "none":
        assert res["failed"] == 0 and res["attempted"] > 5
        assert set(res["metrics"]) == {"setup_s", "ttft_p50_ms",
                                       "tpot_p50_ms"}
