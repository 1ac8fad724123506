"""The benchmark's one run: set-up, the measured window, the metrics and
the comparison that decides ``correct``.

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/
<name>.json``), a traffic mix (``bench/traffic/<name>.json``) and the
chips it needs; the limits ``correct`` holds it to are in
``bench/limits/<cell>.json``.  Each model section of a configuration names
its architecture, ``bench/archs/<model>.py``, which builds the program's
config, the seeded weights, the float32 reference layer and the flop
counts (the contract is in ``bench/archs/__init__.py``).  Each metric is
read by ``bench/metrics/<name>.py``, or, for a name
``<quantity>.<suffix>``, by the shared reader
``bench/metrics/<quantity>.py``.  Nothing here names a cell, a mix, a
metric or an architecture: a new one is new files and new entries.

The window drives ``serve_continuous_live`` (``ContinuousScheduler`` over
a ``ContinuousEngineBackend``: paged pool, ragged kernel, chunked
admission, sampled speculative verification) through the wall-clock
adapter of ``bench/wallclock.py``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_SECONDS = 8.0          # the traced part of a --trace 1 window


class WindowClosed(Exception):
    """Raised at the first backend call after a backlog window ends."""


class DrainLimit(Exception):
    """Raised when requests of the window are still open at the limit."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def bench_dir(root: str) -> str:
    return os.path.join(root, "bench")


def use_cache(root: str) -> None:
    """JAX's persistent compile cache in ``.jax_cache/`` of the checkout (a
    fixed path: the path is part of the key), keeping every program, so
    that every run after a checkout's first reads all it compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_cell(spec: Dict, name: str) -> Dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(spec: Dict, cell: str, traced: bool) -> List[Dict]:
    """The end-to-end metrics (untraced) or per-layer metrics (traced)
    that ``cell`` reports: those listing it, or listing no cells at all."""
    group = spec["per_layer"] if traced else spec["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def metric_path(root: str, name: str) -> str:
    """The reader of metric ``name``: its own file where it has one, else
    the reader of the quantity before the first dot."""
    mdir = os.path.join(bench_dir(root), "metrics")
    own = os.path.join(mdir, name + ".py")
    return own if os.path.exists(own) else os.path.join(
        mdir, name.split(".")[0] + ".py")


def load_metric(root: str, name: str):
    path = metric_path(root, name)
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# configuration


def load_config(root: str, name: str) -> Dict:
    """Configuration ``name`` of the checkout at ``root``, each model
    section pointing at its architecture module there."""
    from bench import archs
    conf = load_json(os.path.join(bench_dir(root), "configs", name + ".json"))
    return archs.resolve(conf, root)


def model_configs(conf: Dict):
    """The program's ``ModelConfig`` pair, built by each section's
    architecture module from the file's numbers, and checked against the
    registry entry the file names on every key the file states."""
    from bench import archs
    t = archs.of(conf["target"]).program_config(
        conf["target"], conf["name"], conf["dtype"])
    d = archs.of(conf["draft"]).program_config(
        conf["draft"], conf["name"] + "-draft", conf["dtype"])
    if conf.get("arch"):
        from repro.configs import registry
        for sec, reg in (("target", registry.get_config(conf["arch"])),
                         ("draft", registry.get_draft_config(conf["arch"]))):
            m = conf[sec]
            theirs = archs.of(m).registry_view(reg)
            for k in m:
                if k not in theirs or (k in conf.get("reduced", [])
                                       and sec == "target"):
                    continue
                if theirs[k] != m[k]:
                    raise SystemExit(
                        f"configuration {conf['name']}: {k} = {m[k]} but "
                        f"registry {conf['arch']} has {theirs[k]} and "
                        f"{k} is not in 'reduced'")
    return t, d


def lut_table(conf: Dict) -> Dict[int, int]:
    return {int(b): int(s) for b, s in conf["lut"]["table"].items()}


def next_pow2_bucket(n: int, cap: int) -> int:
    """The scheduler backend's prompt/chunk bucket (powers of two from 4,
    capped at the cache length)."""
    p = 4
    while p < n:
        p *= 2
    return min(p, cap)


def warm_plan(conf: Dict, mix) -> Dict:
    """Every shape the cell's traffic can reach: whole-prompt buckets,
    chunk buckets with each rows-limit bucket, and the LUT's ``s``."""
    sv = conf["serving"]
    L, budget, chunk = sv["cache_len"], sv["token_budget"], sv["chunk"]
    lo, hi = mix.min_prompt, min(mix.max_prompt, budget)
    prefill = sorted({next_pow2_bucket(n, L) for n in range(lo, hi + 1)})
    chunks = sorted({next_pow2_bucket(n, L) for n in range(1, chunk + 1)})
    return {"prefill": prefill, "chunks": chunks,
            "s": sorted(set(lut_table(conf).values()))}


def _rows_bucket(start: int, cb: int, L: int) -> int:
    """The engine's chunk rows-limit bucket for a chunk at ``start``."""
    r = 1
    while r < start + cb:
        r *= 2
    return min(max(r, 16), L)


def chunk_walks(feed_total: int, cb: int, chunk: int, L: int):
    """Prompts fed chunk by chunk, as lists of ``(start, n)``, that between
    them reach every rows-limit bucket a chunk of bucket ``cb`` can have
    within a feed of ``feed_total`` positions; each walk finishes its
    prompt.  A target that an earlier one in the same walk has passed
    starts a new walk."""
    n_min = 1 if cb == 4 else cb // 2 + 1
    targets = []
    r = _rows_bucket(0, cb, L)
    while r <= _rows_bucket(feed_total - 1, cb, L):
        start = min(r - cb, feed_total - n_min)
        if start >= 0 and _rows_bucket(start, cb, L) == r:
            targets.append((start, min(cb, feed_total - start)))
        r *= 2
    walks, walk, cur = [], [], 0

    def advance(to):
        nonlocal cur
        while cur < to:
            m = min(chunk, to - cur)
            walk.append((cur, m))
            cur += m
    for start, n in targets:
        if start < cur:
            advance(feed_total)
            walks.append(walk)
            walk, cur = [], 0
        advance(start)
        walk.append((cur, n))
        cur += n
    advance(feed_total)
    walks.append(walk)
    return walks


def warm_up(be, conf: Dict, mix, vocab: int) -> Dict:
    """Compile (or read from the persistent cache) every program the
    window can run, by real calls through the backend on throwaway
    requests.  The backend's own warm path copies the whole KV pool for
    each program, which a pool sized to the chip cannot afford, so it is
    marked done and these calls compile instead."""
    from repro.serving.request import Request
    plan = warm_plan(conf, mix)
    sv = conf["serving"]
    L, chunk = sv["cache_len"], sv["chunk"]
    be._warm_prefill.update(plan["prefill"])
    be._warm_chunk.update(plan["chunks"])
    be._warm_step.update(plan["s"])
    rng = np.random.default_rng(0)
    rid = [-1]

    def req(n):
        rid[0] -= 1
        return Request(rid=rid[0], arrival=0.0, prompt_len=n, max_new=1,
                       tokens=rng.integers(0, vocab, n).astype(np.int32))
    for P in plan["prefill"]:
        be.prefill(req(max(P, 3)), 0)
        be.retire(0)
    n_chunks = 0
    for cb in plan["chunks"]:
        for walk in chunk_walks(mix.max_prompt - 1, cb, chunk, L):
            r = req(mix.max_prompt)
            for start, n in walk:
                be.prefill_chunk(r, 0, start, n)
                n_chunks += 1
            be.retire(0)
    be.prefill(req(plan["prefill"][0]), 0)
    for s in plan["s"]:
        be.step(s)
    be.retire(0)
    import jax
    jax.block_until_ready(be.state.seq_lens)
    return dict(plan, n_chunk_calls=n_chunks)


# ---------------------------------------------------------------------------
# the run


@dataclass
class Run:
    """What the metric readers see."""
    cell: str
    conf: Dict
    mix: object
    seconds: float
    adapter: object = None
    requests: List = field(default_factory=list)
    w0: float = 0.0              # window, wall seconds since traffic start
    w1: float = 0.0
    t0: float = 0.0              # traced (or, untraced, measured) window
    t1: float = 0.0
    setup_s: float = 0.0
    trace: Optional[Dict] = None     # compact trace (traced runs)
    reduced: Optional[Dict] = None   # trace_reduce.reduce of it
    peaks: Optional[Dict] = None
    failed: int = 0
    attempted: int = 0
    ended_by: str = ""


def _requests(mix, seed: int, seconds: float, vocab: int):
    from repro.serving.request import Request
    return [Request(rid=r["rid"], arrival=r["arrival"], tokens=r["tokens"],
                    prompt_len=r["prompt_len"], max_new=r["max_new"])
            for r in mix.generate(seed, seconds, vocab)]


def build_engine(conf: Dict, mix, seed: int):
    """The engine and its weights, as the window will use them."""
    import jax
    import jax.numpy as jnp
    from repro.core.spec_decode import SpecDecodeEngine
    from bench.weights import make_params
    tcfg, dcfg = model_configs(conf)
    dtype = jnp.dtype(conf["dtype"])
    eng = SpecDecodeEngine(tcfg, dcfg, max_new=mix.max_output, dtype=dtype,
                           sample=True,
                           temperature=conf["sampling"]["temperature"])
    want = jax.eval_shape(lambda k: (eng.target.init(k, dtype),
                                     eng.draft.init(k, dtype)),
                          jax.random.PRNGKey(0))
    tp, dp = make_params(seed, conf["target"], conf["draft"], dtype)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       (tp, dp))
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise SystemExit("the benchmark's weight layout differs from the "
                         "program's parameter tree")
    return eng, tp, dp


def build_backend(conf: Dict, eng, tp, dp):
    """A fresh paged slot pool on ``eng``."""
    from repro.serving.scheduler import ContinuousEngineBackend
    sv = conf["serving"]
    return ContinuousEngineBackend(eng, tp, dp, capacity=sv["slots"],
                                   cache_len=sv["cache_len"], warm_s=(),
                                   block_size=sv["block_size"],
                                   num_blocks=sv.get("num_blocks"),
                                   collect_outputs=True)


def build(conf: Dict, mix, seed: int):
    """Engine, weights and backend."""
    eng, tp, dp = build_engine(conf, mix, seed)
    return eng, tp, dp, build_backend(conf, eng, tp, dp)


def drive(run: Run, eng, tp, dp, be, seed: int, traced: bool,
          trace_dir: str, clock, t_start: float) -> Dict:
    """Serve the mix on the wall clock; returns compile counts of the
    measured window and what the trace recorded."""
    import jax
    from repro.core.adaptive import AdaptiveController, SpeculationLUT
    from repro.serving.scheduler import (PrefillBudgetAdmit,
                                         serve_continuous_live)
    from bench.wallclock import WallClockBackend
    mix, conf = run.mix, run.conf
    sv = conf["serving"]
    vocab = conf["target"]["vocab_size"]
    run.requests = _requests(mix, seed, run.seconds, vocab)
    ctrl = AdaptiveController(lut=SpeculationLUT(lut_table(conf)))
    policy = PrefillBudgetAdmit(sv["token_budget"], chunk=sv["chunk"])
    open_mix = mix.kind == "open"
    if open_mix:
        run.w0 = mix.warm_in_s
        run.w1 = run.w0 + run.seconds
    drain = mix.drain_s
    state = {"window": None, "tracing": False, "mark": None,
             "c0": None, "c1": None}
    trace_len = min(TRACE_SECONDS, run.seconds)

    def begin_window(now):
        state["c0"] = clock.snapshot()
        run.t0 = now
        run.t1 = now + (trace_len if traced else run.seconds)
        if traced:
            jax.profiler.start_trace(trace_dir)
            state["tracing"] = True
            ad.spans = True
            eng.annotate = True
            state["mark"] = jax.profiler.TraceAnnotation("bench/window")
            state["mark"].__enter__()

    def end_trace():
        ad.close_spans()
        state["mark"].__exit__(None, None, None)
        ad.spans = False
        eng.annotate = False
        jax.profiler.stop_trace()
        state["tracing"] = False

    def on_boundary(now):
        if not open_mix and run.w0 == run.w1 == 0.0 \
                and len(ad.finish) >= sv["slots"]:
            run.w0, run.w1 = now, now + run.seconds
        if state["c0"] is None and run.w1 > 0 and now >= run.w0:
            begin_window(now)
        if state["tracing"] and now >= run.t1:
            end_trace()
        if state["c0"] is not None and state["c1"] is None \
                and now >= run.w1:
            state["c1"] = clock.snapshot()
        if not open_mix and run.w1 > 0 and now >= run.w1:
            raise WindowClosed()
        if open_mix and now >= run.w1 + drain:
            raise DrainLimit()

    n_gen = np.asarray(be.state.n_generated)
    ad = WallClockBackend(be, time.perf_counter(), n_gen, on_boundary)
    run.adapter = ad
    run.setup_s = ad.t0 - t_start
    try:
        serve_continuous_live(run.requests, eng, tp, dp, ctrl, backend=ad,
                              policy=policy)
        run.ended_by = "drained"
    except WindowClosed:
        run.ended_by = "window"
    except DrainLimit:
        run.ended_by = "drain limit"
    if state["tracing"]:
        end_trace()
    if state["c1"] is None:
        state["c1"] = clock.snapshot()
    if not open_mix and run.t1 == 0.0:
        raise SystemExit("the backlog never turned over: no window")
    win = {k: state["c1"][k] - state["c0"][k] for k in state["c0"]}
    return {"window_compile": win}


def window_counts(run: Run) -> None:
    """``attempted`` and ``failed`` of the window."""
    ad = run.adapter
    if run.mix.kind == "open":
        inw = [r for r in run.requests if run.w0 <= r.arrival < run.w1]
        run.attempted = len(inw)
        run.failed = sum(1 for r in inw if r.rid not in ad.last)
    else:
        run.attempted = sum(1 for t in ad.admit.values() if t < run.w1)
        run.failed = 0


# ---------------------------------------------------------------------------
# correctness


def pick_sample(run: Run, seed: int, n: int) -> List[int]:
    """Finished requests, drawn from the seed, with the longest in it and,
    where the run had both, one that was chunked and one that was not."""
    ad = run.adapter
    done = [r.rid for r in run.requests if r.rid in ad.last]
    if not done:
        return []
    served = lambda rid: ad.served[rid]
    longest = max(done, key=lambda rid: (served(rid), rid))
    rng = np.random.default_rng(seed)
    rest = [rid for rid in done if rid != longest]
    rng.shuffle(rest)
    pick = [longest]
    for want_chunked in (True, False):
        if not any((rid in ad.chunked) == want_chunked for rid in pick):
            for rid in rest:
                if (rid in ad.chunked) == want_chunked and rid not in pick:
                    pick.append(rid)
                    break
    for rid in rest:
        if len(pick) >= n:
            break
        if rid not in pick:
            pick.append(rid)
    return pick


def collect_sample(run: Run, be, rids: List[int]):
    """Prompt, served tokens and step records of the sampled requests."""
    ad = run.adapter
    by_rid = {r.rid: r for r in run.requests}
    steps = {rid: [] for rid in rids}
    for st in ad.steps:
        for slot, rid, c, _ctx, _take in st.rows:
            if rid in steps and c > 0:
                steps[rid].append((st.key_sum, slot, st.s, c))
    prompts = {rid: np.asarray(by_rid[rid].tokens[:by_rid[rid].prompt_len])
               for rid in rids}
    served = {rid: np.asarray(be.outputs[rid]) for rid in rids}
    return prompts, served, steps


def reference_shapes(conf: Dict, mix) -> Tuple[int, int, int]:
    """Fixed (rows, sequence, served) sizes of the reference's batch, so
    that its programs compile once per cell and then come from the cache:
    the sample size, and the longest prompt plus output the mix allows."""
    R = conf["correct"]["sample_requests"]
    L = int(math.ceil((mix.max_prompt + mix.max_output) / 128) * 128)
    return R, L, mix.max_output


def reference_logits(conf: Dict, mix, seed: int, prompts, served, order,
                     fp8: bool = False, rows: Optional[int] = None):
    """Target and draft logits ``[R, N, V]`` predicting each sampled
    request's served tokens (row ``i`` is ``order[i]``; rows and positions
    past the sample are padding; ``rows`` overrides ``R``)."""
    import jax.numpy as jnp
    from bench import reference as ref
    from bench.weights import model_key
    R, L, N = reference_shapes(conf, mix)
    R = rows or R
    toks = np.zeros((R, L), np.int32)
    first = np.zeros((R,), np.int32)
    for i, r in enumerate(order):
        seq = np.concatenate([prompts[r], served[r][:-1]])
        toks[i, :len(seq)] = seq
        first[i] = len(prompts[r]) - 1
    idx = np.minimum(first[:, None] + np.arange(N)[None, :], L - 1)
    dt = jnp.dtype(conf["dtype"])
    out = []
    for model, m in ((0, conf["target"]), (1, conf["draft"])):
        mkey = model_key(seed, model)
        h = ref.hidden(mkey, m, toks, fp8=fp8, dtype=dt)
        h = jnp.take_along_axis(h, jnp.asarray(idx)[..., None], axis=1)
        out.append(ref.logits(mkey, m, h, fp8=fp8, dtype=dt))
        del h
    return out[0], out[1]


def readings(conf: Dict, mix, seed: int, sample, fp8_control: bool = False):
    """The numbers ``correct`` compares: the widest token gap and the
    widest acceptance gap over the sample (and, with ``fp8_control``, the
    same two for the fp8 control put in the program's place)."""
    from bench import reference as ref
    prompts, served, steps = sample
    order = list(prompts)
    d = ref.decisions_of(steps, served, order)
    T = conf["sampling"]["temperature"]
    B = conf["serving"]["slots"]
    zt, zd = reference_logits(conf, mix, seed, prompts, served, order)
    g = ref.gaps(zt, zd, d, T, B)
    out = {"token_gap": float(g["token"].max()),
           "accept_gap": float(g["accept"].max()),
           "decisions": int(len(d.j)),
           "served_tokens": int(sum(len(served[r]) for r in order))}
    if fp8_control:
        # four logit arrays at once do not fit beside each other for long
        # outputs: the control is read two requests at a time
        del zt, zd
        tok, acc = [], []
        for lo in range(0, len(order), 2):
            part = order[lo:lo + 2]
            dp = ref.decisions_of(steps, served, part)
            zt, zd = reference_logits(conf, mix, seed, prompts, served, part,
                                      rows=2)
            at, ad = reference_logits(conf, mix, seed, prompts, served, part,
                                      fp8=True, rows=2)
            gc_ = ref.gaps(zt, zd, dp, T, B, actor=(at, ad))
            tok.append(gc_["token"].max())
            acc.append(gc_["accept"].max())
            del zt, zd, at, ad
        out["control_token_gap"] = float(max(tok))
        out["control_accept_gap"] = float(max(acc))
    return out


def load_limits(root: str, cell: str) -> Dict:
    """The limits of ``cell`` (``bench/limits/<cell>.json``), set from
    readings at that cell's own sizes."""
    path = os.path.join(bench_dir(root), "limits", cell + ".json")
    if not os.path.exists(path):
        raise SystemExit(f"no limits for cell {cell!r}: {path} is missing")
    return load_json(path)


def judge(limits: Dict, vals: Dict, mirror_ok: bool) -> Dict:
    """Each compared number beside its limit: the widest gaps at most
    theirs, at least ``min_decisions`` decisions compared."""
    checks = {k: {"value": vals.get(k, float("nan")), "limit": lim}
              for k, lim in limits["max"].items()}
    checks["key_mirror"] = {"value": 0 if mirror_ok else 1, "limit": 0}
    checks["decisions"] = {"value": vals.get("decisions", 0),
                           "limit": limits["min_decisions"]}
    return checks


def passed(checks: Dict) -> bool:
    ok = checks["decisions"]["value"] >= checks["decisions"]["limit"]
    return ok and all(v["value"] <= v["limit"] for k, v in checks.items()
                      if k != "decisions")
