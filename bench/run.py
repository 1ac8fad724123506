"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs the cell named in ``BENCHMARK.json`` on the chip(s) of this machine
and prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (plus
``breakdown`` in a traced run, and the compared numbers with their limits
under ``checks``, last).  It exits nonzero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.  JAX's persistent
compile cache lives in ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(*a):
    print(*a, flush=True)


def main(argv=None, root: str = ROOT, require_tpu: bool = True,
         t_start: float = T_START) -> int:
    args = _args(argv)
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness as H
    from bench.arrivals import Mix
    spec = H.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = H.find_cell(spec, args.workload)
    bdir = H.bench_dir(root)
    conf = H.load_config(root, cell["config"])
    mix = Mix.load(os.path.join(bdir, "traffic", cell["traffic"] + ".json"),
                   cell["traffic"])
    limits = H.load_limits(root, cell["name"])

    import jax
    H.use_cache(root)
    from bench.compile_clock import CompileClock
    clock = CompileClock()
    devs = jax.devices()
    dev = devs[0]
    if require_tpu and dev.platform != "tpu":
        print(f"bench: no TPU (JAX found {dev.platform!r} devices); this "
              "benchmark runs only on the chip", file=sys.stderr)
        return 2
    if len(devs) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} chips, JAX "
              f"sees {len(devs)}", file=sys.stderr)
        return 2
    peaks_all = H.load_json(os.path.join(bdir, "peaks.json"))
    if dev.device_kind not in peaks_all and require_tpu:
        print(f"bench: no peaks for device kind {dev.device_kind!r} in "
              "bench/peaks.json", file=sys.stderr)
        return 2
    peaks = peaks_all.get(dev.device_kind) or peaks_all["TPU v5 lite"]
    traced = bool(args.trace)

    eng, tp, dp, be = H.build(conf, mix, args.seed)
    c_built = clock.snapshot()
    warm = H.warm_up(be, conf, mix, conf["target"]["vocab_size"])
    log(f"warm-up: prefill buckets {warm['prefill']}, chunk buckets "
        f"{warm['chunks']} ({warm['n_chunk_calls']} chunk calls), s "
        f"{warm['s']}; compiles {clock.since(c_built)}")
    run = H.Run(cell=cell["name"], conf=conf, mix=mix, seconds=args.seconds,
                peaks=peaks)
    trace_dir = os.path.join(root, ".bench_trace", f"{cell['name']}-{args.seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    info = H.drive(run, eng, tp, dp, be, args.seed, traced, trace_dir, clock,
                   t_start)
    H.window_counts(run)
    ad = run.adapter
    late = sorted(ad.late)
    log(f"set-up {run.setup_s:.3f} s (compile totals {clock.snapshot()}); "
        f"warm-in {run.w0:.3f} s; window [{run.w0:.3f}, {run.w1:.3f}) s; "
        f"ended by {run.ended_by}; steps {len(ad.steps)}, calls "
        f"{len(ad.calls)}, preempted {ad.preempted}")
    if mix.kind == "open":
        from bench import readers
        from bench.stats import beyond
        tt = readers.ttft_values(run)
        log(f"window requests {len(tt)}: {beyond(tt, 95) if tt else 0} "
            f"beyond the 95th percentile of time to first token; its p95 "
            f"{readers.ttft_p(run, 95)} ms; time per output token p95 "
            f"{readers.tpot_p(run, 95)} ms")
    held = [st.blocks for st in ad.steps if run.w0 <= st.t_call < run.w1]
    if held:
        log(f"KV blocks held over the window: peak {max(held)}, mean "
            f"{sum(held) / len(held):.1f} of {be.kv.num_blocks}")
    p99 = late[min(len(late) - 1, int(0.99 * len(late)))] if late else 0.0
    log(f"generator lateness p99 {p99 * 1e3:.3f} ms over {len(late)} idle "
        f"admissions; compiles inside the window: "
        f"{info['window_compile']['compiles']:.0f} "
        f"(trace {info['window_compile']['trace']:.3f} s)")

    result_extra = {}
    if traced:
        from bench import trace_reduce as TR
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            run.trace = TR.compact(files[0])
            run.reduced = TR.reduce(run.trace)
            from bench import readers
            n_k, t_k = TR.kernel_time(run.trace, readers.RAGGED_KERNEL)
            log(f"trace: lines {run.trace['lines']}; window "
                f"{run.reduced['window_s']:.6f} s, busy "
                f"{run.reduced['busy_s']:.6f} s; ragged kernel events "
                f"{n_k} ({t_k:.6f} s) against {readers.ragged_calls(run)} "
                f"calls expected")
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in H.cell_metrics(spec, cell["name"], traced):
        mod = H.load_metric(root, m["name"])
        v = mod.read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    if traced and run.reduced is not None:
        device["busy_s"] = run.reduced["busy_s"]
        device["window_s"] = run.reduced["window_s"]
        result_extra["breakdown"] = {
            "device_ops": run.reduced["device_ops"],
            "idle_gaps": run.reduced["idle_gaps"]}

    # correctness, after the window has closed and the peak was read
    import numpy as np
    mirror_ok = bool(np.array_equal(np.asarray(be.state.n_generated),
                                    ad.ngen))
    rids = H.pick_sample(run, args.seed, conf["correct"]["sample_requests"])
    sample = H.collect_sample(run, be, rids)
    be.state = be.tparams = be.dparams = None
    del tp, dp, eng
    gc.collect()
    t_ref = time.perf_counter()
    vals = (H.readings(conf, mix, args.seed, sample) if rids
            else {"decisions": 0})
    checks = H.judge(limits, vals, mirror_ok)
    ok = H.passed(checks)
    log(f"reference: {len(rids)} requests, {vals.get('served_tokens', 0)} "
        f"served tokens, {time.perf_counter() - t_ref:.3f} s")
    result = {"correct": ok, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": device,
              **result_extra, "checks": checks}
    for k, v in checks.items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
