"""Trace reduction: busy time as the union of device operations, idle gaps
labelled by the innermost host span, and kernel time, on a hand-made trace
and on a small trace recorded on a TPU v5e."""
import glob
import os

import pytest

from bench import trace_reduce as TR

MS = 1e6  # ns


def _trace():
    device = [["%while.3 = (s32[])", 10 * MS, 20 * MS],   # 10-30, a loop
              ["%fusion.2 = f32[8]", 12 * MS, 10 * MS],   # 12-22, inside it
              ["%closed_call.7 = bf16[32,8]{1,0} custom-call(s32[] %a)",
               50 * MS, 10 * MS],                         # 50-60, a kernel
              ["%fusion.9 = f32[8]", 95 * MS, 20 * MS]]   # 95-115, cut at 100
    host = [["bench/window", 0.0, 100 * MS],
            ["bench/step", 30 * MS, 30 * MS],
            ["repro/step[B=32,s=2]", 36 * MS, 10 * MS],
            ["bench/scheduler", 60 * MS, 40 * MS]]
    return {"device": device, "host": host, "lines": {}}


def test_reduce_hand_made_trace():
    r = TR.reduce(_trace())
    assert r["window_s"] == pytest.approx(0.1)
    # busy: 10-30, 50-60, 95-100
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["idle_share"] == pytest.approx(0.65)
    gaps = dict(r["idle_gaps"])
    # 0-10 untraced, 30-50 inside repro/step (midpoint 40), 60-95 in the
    # scheduler's host work
    assert gaps["host:untraced"] == pytest.approx(0.010)
    assert gaps["repro/step[B=32,s=2]"] == pytest.approx(0.020)
    assert gaps["bench/scheduler"] == pytest.approx(0.035)
    ops = dict(r["device_ops"])
    # self time: the loop without its body, the two fusions, the kernel
    assert ops["while"] == pytest.approx(0.010)
    assert ops["fusion"] == pytest.approx(0.015)
    assert ops["closed_call bf16[32,8]"] == pytest.approx(0.010)
    assert TR.kernel_time(_trace(), r"custom-call\(s32\[") == (
        1, pytest.approx(0.010))


def test_compact_roundtrip(tmp_path):
    p = str(tmp_path / "t.json.gz")
    TR.save(_trace(), p)
    assert TR.load(p) == _trace()


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "trace_*.json.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_reduce_recorded_trace(path):
    c = TR.load(path)
    r = TR.reduce(c)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 <= r["idle_share"] < 1
    assert sum(v for _, v in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9
    n, t = TR.kernel_time(c, c["expect"]["kernel"])
    assert n == c["expect"]["kernel_events"] and t > 0
    assert r["busy_s"] == pytest.approx(c["expect"]["busy_s"], rel=1e-9)
