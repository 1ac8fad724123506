"""One-off chip calibration of a configuration: the acceptance of sampled
verification against temperature, and the paged step's time over batch x
speculation length, from which the paper's rule builds the LUT.

    python3 bench/calibrate.py --config internlm2-1.8b --seed 7 --out f.json

Acceptance: the program's per-token acceptance at the file's temperature,
read from ``s = 1`` steps (accepted drafts per live row), beside the
reference's expected acceptance ``sum_v min(p_v, q_v)`` at several
temperatures.  Timing: for each batch ``b`` and ``s``, ``b`` rows are
prefilled with 512-token prompts and the backend's fenced step seconds are
averaged over 16 steps after 2 untimed ones; the LUT takes, for each
``b``, the ``s`` with the least seconds per committed token.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BATCHES = (1, 2, 4, 8, 16, 32)
S_VALUES = (0, 1, 2, 3, 4)
TEMPS = (0.5, 0.75, 1.0, 1.25, 1.5, 2.0)


def main(argv=None, root=ROOT, require_tpu=True) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", required=True)
    ap.add_argument("--prompt", type=int, default=512)
    ap.add_argument("--steps", type=int, default=16)
    args = ap.parse_args(argv)
    for p in (root, os.path.join(root, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import harness as H
    H.use_cache(root)
    if require_tpu and jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    from bench import reference as ref
    from bench.arrivals import Mix
    from bench.weights import model_key
    from repro.serving.request import Request
    conf = H.load_config(root, args.config)
    mix = Mix("calibration", "backlog", {
        "prompt": {"min": args.prompt, "max": args.prompt},
        "output": {"max": 512}})
    eng, tp, dp, be = H.build(conf, mix, args.seed)
    from bench import archs
    A = archs.of(conf["target"])
    if not hasattr(A, "program_params"):
        gen = jax.jit(lambda k, l: A.layer_weights(k, conf["target"], l))
        for l in (0, conf["target"]["n_layers"] - 1):
            one = gen(model_key(args.seed, 0), l)
            both = jax.tree_util.tree_flatten_with_path(
                jax.tree.map(lambda a, b: int(
                    (np.asarray(a[l]).astype(np.float32)
                     != np.asarray(b).astype(np.float32)).sum()),
                    tp["layers"], one))[0]
            diff = {".".join(str(k.key) for k in p): v for p, v in both}
            print(f"layer {l}: elements differing between the program's "
                  f"tree and the reference's regeneration: {diff}",
                  flush=True)
    be._warm_step.update(S_VALUES)
    be._warm_prefill.add(H.next_pow2_bucket(args.prompt,
                                            conf["serving"]["cache_len"]))
    vocab = conf["target"]["vocab_size"]
    rng = np.random.default_rng(args.seed)
    max_new = min(512, conf["serving"]["cache_len"] - args.prompt - 8)
    live = []

    def fill(b):
        for slot in live:
            be.retire(slot)
        live.clear()
        for slot in range(b):
            r = Request(rid=slot, arrival=0.0, prompt_len=args.prompt,
                        max_new=max_new, tokens=rng.integers(
                            0, vocab, args.prompt).astype(np.int32))
            be.prefill(r, slot)
            live.append(slot)

    grid, cpr = {}, {}
    t_all = time.perf_counter()
    for b in BATCHES:
        grid[b], cpr[b] = {}, {}
        for s in S_VALUES:
            fill(b)
            for _ in range(2):
                be.step(s)
            dts, com = [], []
            for _ in range(args.steps):
                dt, c, _ = be.step(s)
                dts.append(dt)
                com.append(np.asarray(c)[:b].mean())
            grid[b][s] = float(np.mean(dts))
            cpr[b][s] = float(np.mean(com))
            print(f"b={b} s={s}: step {grid[b][s] * 1e3:.3f} ms, committed "
                  f"per row {cpr[b][s]:.4f}", flush=True)
    lut = {b: min(S_VALUES, key=lambda s: grid[b][s] / cpr[b][s])
           for b in BATCHES}
    alpha_prog = float(np.mean([cpr[b][1] - 1.0 for b in BATCHES]))
    print(f"timing {time.perf_counter() - t_all:.1f} s; LUT {lut}; program "
          f"acceptance at T={conf['sampling']['temperature']}: {alpha_prog}",
          flush=True)
    be.state = be.tparams = be.dparams = None
    del tp, dp
    # the reference's expected acceptance over temperature
    toks = rng.integers(0, vocab, (4, args.prompt)).astype(np.int32)
    z = []
    for model, m in ((0, conf["target"]), (1, conf["draft"])):
        mk = model_key(args.seed, model)
        h = ref.hidden(mk, m, toks)
        z.append(ref.logits(mk, m, h[:, args.prompt // 2:]))
    alpha_ref = {}
    for T in TEMPS:
        p = jax.nn.softmax(z[0] / T, axis=-1)
        q = jax.nn.softmax(z[1] / T, axis=-1)
        alpha_ref[T] = float(jnp.minimum(p, q).sum(-1).mean())
    print(f"reference acceptance by temperature: {alpha_ref}", flush=True)
    out = {"config": args.config, "device": jax.devices()[0].device_kind,
           "temperature": conf["sampling"]["temperature"],
           "alpha_program": alpha_prog, "alpha_reference": alpha_ref,
           "step_s": grid, "committed_per_row": cpr, "lut": lut,
           "prompt": args.prompt, "steps": args.steps}
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
