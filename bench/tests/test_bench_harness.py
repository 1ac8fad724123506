"""The harness is driven by data: a new mix, configuration or metric is a
new file plus a new entry, and no existing file is edited; every metric
has a reader; the warm-up reaches every program shape the traffic can."""
import filecmp
import json
import os

import pytest

from bench import harness as H
from bench.arrivals import Mix
from bench.tests.tiny import REPO, make_root

DUMMY = {"kind": "backlog", "n_requests": 7, "base_seed": 5,
         "prompt": {"dist": "uniform", "min": 9, "max": 20},
         "output": {"dist": "uniform", "min": 4, "max": 6}}


def test_dummy_mix_added_as_file_is_found(tmp_path):
    root = make_root(tmp_path, mixes={"dummy": DUMMY})
    spec = H.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = H.find_cell(spec, "tiny.dummy")
    mix = Mix.load(os.path.join(root, "bench", "traffic",
                                cell["traffic"] + ".json"), cell["traffic"])
    reqs = mix.generate(1, 10.0, 500)
    assert len(reqs) == 7 and all(9 <= r["prompt_len"] <= 20 for r in reqs)
    names = [m["name"] for m in H.cell_metrics(spec, "tiny.dummy", True)]
    assert "step_ms.chat" in names and "step_ms.offline" not in names
    with pytest.raises(SystemExit):
        H.find_cell(spec, "tiny.absent")
    # the cell's limits are its own new file; the configuration names no mix
    conf = H.load_json(os.path.join(root, "bench", "configs", "tiny.json"))
    assert "dummy" not in json.dumps(conf["correct"])
    checks = H.judge(H.load_limits(root, "tiny.dummy"),
                     {"token_gap": 0.0, "accept_gap": 0.0, "decisions": 10},
                     True)
    assert H.passed(checks) and set(checks) == {
        "token_gap", "accept_gap", "key_mirror", "decisions"}
    with pytest.raises(SystemExit):
        H.load_limits(root, "tiny.absent")
    # every file the repo's bench/ holds is there unchanged
    for d, _, files in os.walk(os.path.join(REPO, "bench")):
        if "tests" in d or "__pycache__" in d:
            continue
        for f in files:
            mine = os.path.join(d, f)
            theirs = os.path.join(root, os.path.relpath(mine, REPO))
            assert filecmp.cmp(mine, theirs, shallow=False), theirs


def test_metric_added_under_a_new_name_finds_its_reader(tmp_path):
    root = make_root(tmp_path)
    assert H.metric_path(root, "step_ms.dummy").endswith(
        os.path.join("metrics", "step_ms.py"))
    own = os.path.join(root, "bench", "metrics", "step_ms.dummy.py")
    with open(own, "w") as f:
        f.write("def read(run):\n    return 1.0\n")
    assert H.metric_path(root, "step_ms.dummy") == own
    assert H.load_metric(root, "step_ms.dummy").read(None) == 1.0


def test_every_metric_has_a_reader_that_agrees():
    spec = H.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"] for w in spec["workloads"]}
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            mod = H.load_metric(REPO, m["name"])
            assert callable(mod.read), m["name"]
            if group == "per_layer":
                moved = next(e for e in spec["end_to_end"]
                             if e["name"] == m["moves"])
                for c in m["workloads"]:
                    assert c in moved.get("workloads", cells)
    for w in spec["workloads"]:
        assert H.cell_metrics(spec, w["name"], False)
        assert H.cell_metrics(spec, w["name"], True)
        assert H.load_limits(REPO, w["name"])["max"]


def _reachable(feed_total, chunk, L):
    """Every (chunk bucket, rows bucket) a chunk of this feed can take."""
    out = set()
    for start in range(feed_total):
        for n in range(1, min(chunk, feed_total - start) + 1):
            cb = H.next_pow2_bucket(n, L)
            out.add((cb, H._rows_bucket(start, cb, L)))
    return out


@pytest.mark.parametrize("max_prompt,chunk,L", [(60, 32, 128),
                                                (300, 64, 512),
                                                (1280, 256, 2048),
                                                (1536, 256, 2048),
                                                (512, 256, 2048)])
def test_chunk_walk_reaches_every_program(max_prompt, chunk, L):
    feed = max_prompt - 1
    got = set()
    for cb in sorted({H.next_pow2_bucket(n, L) for n in range(1, chunk + 1)}):
        for walk in H.chunk_walks(feed, cb, chunk, L):
            cur = 0
            for start, n in walk:
                assert start == cur and 0 < n <= chunk
                cur += n
                got.add((H.next_pow2_bucket(n, L), H._rows_bucket(
                    start, H.next_pow2_bucket(n, L), L)))
            assert cur == feed
    assert _reachable(feed, chunk, L) <= got


def test_config_files_name_their_registry_widths():
    spec = H.load_json(os.path.join(REPO, "BENCHMARK.json"))
    for c in spec["configs"]:
        conf = H.load_json(os.path.join(REPO, c["file"]))
        assert conf["reduced"] == c["reduced"]
        t, d = H.model_configs(conf)
        assert t.n_layers == conf["target"]["n_layers"]
        assert d.d_model == conf["draft"]["d_model"]
