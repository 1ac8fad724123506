"""One-off knee sweep of an open-loop cell: the same traffic at several
fixed rates, in one process, each with its own fresh pool.

    python3 bench/sweep.py --workload internlm2-1.8b.chat --rates 4,6,8 \
        --seconds 30 --out sweep.jsonl

For each rate: the number of requests waiting (arrived, not yet admitted)
sampled at every backend call over the window, its growth (least-squares
slope in requests per second, and last-quarter mean minus first-quarter
mean), the tails the cell judges, and served tokens per second.  The knee
is the highest rate whose waiting count does not grow over the window.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, root=ROOT, require_tpu=True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=17)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import numpy as np
    from bench import harness as H
    H.use_cache(root)
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    from bench import readers
    from bench.arrivals import Mix
    from bench.compile_clock import CompileClock
    spec = H.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = H.find_cell(spec, args.workload)
    bdir = H.bench_dir(root)
    conf = H.load_json(os.path.join(bdir, "configs", cell["config"] + ".json"))
    base = Mix.load(os.path.join(bdir, "traffic", cell["traffic"] + ".json"),
                    cell["traffic"])
    clock = CompileClock()
    eng, tp, dp = H.build_engine(conf, base, args.seed)
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = Mix(base.name, base.kind, dict(base.params, rate_per_s=rate))
        be = H.build_backend(conf, eng, tp, dp)
        H.warm_up(be, conf, mix, conf["target"]["vocab_size"])
        run = H.Run(cell=cell["name"], conf=conf, mix=mix,
                    seconds=args.seconds)
        H.drive(run, eng, tp, dp, be, args.seed, False,
                os.path.join(root, ".bench_trace", "sweep"), clock,
                time.perf_counter())
        H.window_counts(run)
        ad = run.adapter
        arrivals = np.sort([r.arrival for r in run.requests])
        admits = np.sort(list(ad.admit.values()))
        ts = np.array([c.t_call for c in ad.calls] + [s.t_call
                                                      for s in ad.steps])
        ts = np.sort(ts[(ts >= run.w0) & (ts < run.w1)])
        waiting = (np.searchsorted(arrivals, ts, side="right")
                   - np.searchsorted(admits, ts, side="right"))
        q = max(len(ts) // 4, 1)
        slope = float(np.polyfit(ts, waiting, 1)[0]) if len(ts) > 2 else 0.0
        run.t0, run.t1 = run.w0, run.w1
        rec = dict(rate=rate, attempted=run.attempted, failed=run.failed,
                   ended_by=run.ended_by, waiting_slope_per_s=slope,
                   waiting_growth=float(waiting[-q:].mean()
                                        - waiting[:q].mean()),
                   waiting_max=int(waiting.max()) if len(ts) else 0,
                   ttft_p95_ms=readers.ttft_p(run, 95),
                   tpot_p50_ms=readers.tpot_p(run, 50),
                   tpot_p95_ms=readers.tpot_p(run, 95),
                   tokens_per_s=readers.output_tokens_per_s(run),
                   step_ms=readers.step_ms(run),
                   commit_per_row=readers.commit_per_row(run),
                   decode_batch=readers.decode_batch_mean(run))
        be.state = None
        del be
        print(json.dumps(rec), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
