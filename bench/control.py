"""The readings ``correct``'s limits are set from: the program's numbers on
many seeds and the fp8 control's on some, in one process on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --control-seeds 1,2,3 --seconds 10 --out readings.jsonl

Each seed gets its own weights, a fresh pool and a short window of the
cell's own traffic through the same harness a run uses; the program's
engine and compiled programs are shared between seeds.  For a control
seed, the float32 reference rounded to fp8 is put in the program's place
and its first choice at every decision is read by the float32 reference.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None, root=ROOT, require_tpu=True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from bench import harness as H
    H.use_cache(root)
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    from bench.arrivals import Mix
    from bench.compile_clock import CompileClock
    from bench.weights import make_params
    spec = H.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = H.find_cell(spec, args.workload)
    bdir = H.bench_dir(root)
    conf = H.load_config(root, cell["config"])
    mix = Mix.load(os.path.join(bdir, "traffic", cell["traffic"] + ".json"),
                   cell["traffic"])
    seeds = [int(x) for x in args.seeds.split(",") if x]
    ctl = {int(x) for x in args.control_seeds.split(",") if x}
    clock = CompileClock()
    eng = tp = dp = None
    with open(args.out, "a") as out:
        for seed in seeds:
            t0 = time.perf_counter()
            if eng is None:
                eng, tp, dp = H.build_engine(conf, mix, seed)
            else:
                tp, dp = make_params(seed, conf["target"], conf["draft"],
                                     jax.numpy.dtype(conf["dtype"]))
            be = H.build_backend(conf, eng, tp, dp)
            H.warm_up(be, conf, mix, conf["target"]["vocab_size"])
            run = H.Run(cell=cell["name"], conf=conf, mix=mix,
                        seconds=args.seconds)
            H.drive(run, eng, tp, dp, be, seed, False,
                    os.path.join(root, ".bench_trace", "control"), clock,
                    time.perf_counter())
            import numpy as np
            mirror = bool(np.array_equal(np.asarray(be.state.n_generated),
                                         run.adapter.ngen))
            rids = H.pick_sample(run, seed, conf["correct"]["sample_requests"])
            sample = H.collect_sample(run, be, rids)
            be.state = be.tparams = be.dparams = None
            del be, tp, dp
            gc.collect()
            vals = H.readings(conf, mix, seed, sample, fp8_control=seed in ctl)
            rec = dict(seed=seed, cell=cell["name"], mirror=mirror,
                       requests=len(rids), ended_by=run.ended_by,
                       seconds=time.perf_counter() - t0, **vals)
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
