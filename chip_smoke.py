#!/usr/bin/env python3
"""Chip smoke run: the paged speculative server end to end on a TPU.

    python chip_smoke.py             # one chip: kernel check + serving
    python chip_smoke.py --chips 4   # four chips: sharded serving only

One chip.  Serves a seeded trace (16 requests, prompts of 64-512 tokens,
32 new tokens each) through ``serve_continuous_live`` at the full width of
internlm2-1.8b (24 layers, d_model 2048, 16 heads / 8 KV heads, head_dim
128, vocab 92,544) with its dense draft, random bf16 weights from
``--seed``, a paged KV pool (block 16, capacity 8, 1024 tokens per slot)
and chunked admission, so both paged-attention call sites — the verify
step and the chunk prefill — run the ragged Pallas kernel natively.  A
hand-written speculation table makes the run use three ``(B, s)`` steps;
the same trace then runs with ``s = 0`` (no speculation) and once more
with the speculative table on warm compilations.  Before serving, the
ragged, dense and contiguous verify kernels are checked on the chip
against the float32 jnp reference (``kernels/ref.py``) at the same widths.

Four chips (``--chips 4``).  The same trace on a four-way data mesh
(``make_serving_mesh(4)``) beside the same trace on one device of the same
process, with the same kernel routing; the two runs' tokens are compared.

The script exits nonzero, and prints no result, when JAX finds no TPU: it
never falls back to the CPU.  Any failed check raises.  The last line of
standard output is one JSON object, ``{"ok": true, "device": {...}}``.
Every time it prints is bring-up evidence, not a benchmark.

Compile cache: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its
persistent cache there; otherwise this script puts it in ``.jax_cache/``
at the root of the checkout, so a second run in the same checkout reuses
the first run's compilations.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")

ARCH = "internlm2-1.8b"
LUT = {1: 4, 4: 2, 8: 1}          # batch size -> speculation length
N_REQUESTS = 16
PROMPT_RANGE = (64, 512)
MAX_NEW = 32
CAPACITY = 8
CACHE_LEN = 1024
BLOCK_SIZE = 16
TOKEN_BUDGET = 256                # PrefillBudgetAdmit: prefill tokens/iter
CHUNK = 128                       # and its chunk width
KERNEL_TOL = 2e-2                 # bf16 kernel vs float32 reference



class CheckFailed(RuntimeError):
    """A smoke check did not hold."""


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def _import_repro() -> None:
    """Put the checkout's own ``src/`` first on the path; a copy of this
    script outside a checkout stops here."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro", "serving")):
        raise SystemExit(f"chip_smoke: no repro package under {src}; run "
                         "this script from a checkout of the repository")
    if src not in sys.path:
        sys.path.insert(0, src)


def _use_compile_cache(jax) -> str:
    """JAX_COMPILATION_CACHE_DIR wins; else a fixed directory in the
    checkout (the path is part of the cache key, so it must not move)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def compile_since(t0: float) -> dict:
    """What the program's compile log (``serving/telemetry.py``) holds after
    ``time.perf_counter()`` time ``t0``: seconds spent tracing, lowering and
    backend-compiling, programs compiled, and how many of those were read
    from the persistent cache.  Trace spans nest (an inner jit's trace runs
    inside its caller's), so only the backend compile — which also covers a
    persistent-cache read — is a clean total, and it is the part the
    persistent cache saves."""
    from repro.serving.telemetry import compile_log
    out = {"trace": 0.0, "lower": 0.0, "backend": 0.0, "programs": 0,
           "cache_reads": 0}
    for t_end, secs, event, _ in list(compile_log()):
        if t_end < t0:
            continue
        if event == "compile":
            out["backend"] += secs
            out["programs"] += 1
        elif event == "cache_read":
            out["cache_reads"] += 1
        else:
            out[event] += secs
    return out


def describe_compile(c: dict) -> str:
    """One log fragment for a :func:`compile_since` result."""
    return (f"compile {c['backend']:.3f} s (trace {c['trace']:.3f} s, "
            f"lower {c['lower']:.3f} s; {c['programs']} programs, "
            f"{c['cache_reads']} read from the persistent cache)")


# ---------------------------------------------------------------------------
# the trace


def make_trace(vocab: int, seed: int, n: int = N_REQUESTS,
               prompt_range=PROMPT_RANGE, max_new: int = MAX_NEW):
    """``n`` requests, all arriving at t=0 (so admission order is purely
    structural and two runs schedule alike), prompt lengths uniform in
    ``prompt_range``, token ids uniform over the vocabulary."""
    import numpy as np
    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = int(rng.integers(prompt_range[0], prompt_range[1] + 1))
        toks = rng.integers(0, vocab, (plen,)).astype(np.int32)
        reqs.append(Request(rid=rid, arrival=0.0, tokens=toks,
                            prompt_len=plen, max_new=max_new))
    return reqs


# ---------------------------------------------------------------------------
# phase 1: the attention kernels against the float32 reference


def _paged_case(B, T, H, KVH, hd, bs, max_blocks, seed):
    """A random bf16 pool with ragged per-slot lengths, one empty slot and
    interior ``-1`` holes, plus the float32 logical view of every slot
    built independently on the host (numpy), for the reference."""
    import numpy as np
    rng = np.random.default_rng(seed)
    NB = B * max_blocks
    cap = max_blocks * bs
    lens = rng.integers(T, cap - T, B)
    lens[1] = 0                                   # an empty slot
    lens[2] = cap                                 # a full slot
    k = rng.standard_normal((NB, bs, KVH, hd)).astype(np.float32)
    v = rng.standard_normal((NB, bs, KVH, hd)).astype(np.float32)
    pos = np.full((NB, bs), -1, np.int32)
    bt = np.full((B, max_blocks), -1, np.int32)
    free = list(rng.permutation(NB))
    for b in range(B):
        nblk = -(-int(lens[b]) // bs)
        for j in range(nblk):
            if b == 3 and j % 5 == 2:
                continue                           # interior hole
            blk = int(free.pop())
            bt[b, j] = blk
            p = j * bs + np.arange(bs)
            pos[blk] = np.where(p < lens[b], p, -1)
    q = rng.standard_normal((B, T, H, hd)).astype(np.float32)
    q_pos = (np.maximum(lens, T) - T)[:, None] + np.arange(T)[None]
    q_pos = np.where(lens[:, None] > 0, q_pos, -1).astype(np.int32)
    # logical views: row r of slot b is table entry r // bs, offset r % bs
    safe = np.maximum(bt, 0)
    kl = k[safe].reshape(B, cap, KVH, hd)
    vl = v[safe].reshape(B, cap, KVH, hd)
    kp = np.where((bt < 0)[:, :, None], -1, pos[safe]).reshape(B, cap)
    return dict(q=q, k=k, v=v, q_pos=q_pos, pos=pos, bt=bt, kl=kl, vl=vl,
                kp=kp.astype(np.int32))


def kernel_check(*, B=8, T=4, H=16, KVH=8, hd=128, bs=BLOCK_SIZE,
                 max_blocks=CACHE_LEN // BLOCK_SIZE, seed=0,
                 interpret=False) -> dict:
    """Ragged (at the launch config serving uses, and the manual-DMA ring),
    dense fused and contiguous verify kernels vs the float32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    from repro.kernels import ref as R
    from repro.kernels.paged_verify_attn import (
        paged_verify_attn_pallas, ragged_paged_verify_attn_pallas)
    from repro.kernels.tuning import host_cu_blocks, lookup_config

    c = _paged_case(B, T, H, KVH, hd, bs, max_blocks, seed)
    bf = jnp.bfloat16
    q = jnp.asarray(c["q"], bf)
    k, v = (jnp.asarray(c[n], bf)[None] for n in ("k", "v"))   # one layer
    q_pos, pos, bt = (jnp.asarray(c[n]) for n in ("q_pos", "pos", "bt"))
    cu = jnp.asarray(host_cu_blocks(c["bt"]))
    # the reference sees the kernel's bf16 inputs, widened to float32
    f32 = lambda a: jnp.asarray(np.asarray(jnp.asarray(a, bf), np.float32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.gqa_masked_ref(
            f32(c["q"]), f32(c["kl"]), f32(c["vl"]), q_pos,
            jnp.asarray(c["kp"])), np.float32)
    served = lookup_config(B, T, max_blocks)
    runs = {
        f"ragged(num_buffers={served.num_buffers})":
            lambda: ragged_paged_verify_attn_pallas(
                q, k, v, q_pos, pos, bt, 0, cu,
                num_buffers=served.num_buffers,
                vmem_limit_bytes=served.vmem_limit_bytes,
                interpret=interpret),
        "ragged(num_buffers=2)":
            lambda: ragged_paged_verify_attn_pallas(
                q, k, v, q_pos, pos, bt, 0, cu, num_buffers=2,
                interpret=interpret),
        "dense": lambda: paged_verify_attn_pallas(
            q, k, v, q_pos, pos, bt, 0, interpret=interpret),
        "contiguous": lambda: ops.spec_verify_attn(
            q, jnp.asarray(c["kl"], bf), jnp.asarray(c["vl"], bf), q_pos,
            jnp.asarray(c["kp"]), use_pallas=True),
    }
    out = {"served_config": dataclasses.asdict(served), "max_abs_err": {}}
    got = {}
    for name, fn in runs.items():
        g = np.asarray(jax.block_until_ready(jax.jit(fn)()), np.float32)
        got[name] = g
        _check(bool(np.isfinite(g).all()), f"{name}: non-finite output")
        err = float(np.abs(g - want).max())
        out["max_abs_err"][name] = err
        _check(np.allclose(g, want, rtol=KERNEL_TOL, atol=KERNEL_TOL),
               f"{name}: max |err| {err} vs float32 reference exceeds "
               f"bf16 tolerance {KERNEL_TOL}")
    first = next(iter(got))
    out["ragged_vs_dense_max_diff"] = float(
        np.abs(got[first] - got["dense"]).max())
    return out


# ---------------------------------------------------------------------------
# phase 2: serving


def build_engine(tcfg, dcfg, *, seed: int, dtype, paged_fused=None):
    """The target/draft pair with random weights from ``seed``."""
    import jax
    from repro.core.spec_decode import SpecDecodeEngine
    eng = SpecDecodeEngine(tcfg, dcfg, max_new=MAX_NEW, dtype=dtype,
                           paged_fused=paged_fused)
    key = jax.random.PRNGKey(seed)
    tparams = eng.target.init(jax.random.fold_in(key, 0), dtype=dtype)
    dparams = eng.draft.init(jax.random.fold_in(key, 1), dtype=dtype)
    return eng, tparams, dparams


def serve_once(eng, tparams, dparams, requests, lut, *, mesh=None,
               capacity=CAPACITY, cache_len=CACHE_LEN,
               block_size=BLOCK_SIZE, budget=TOKEN_BUDGET, chunk=CHUNK):
    """One ``serve_continuous_live`` run of ``requests`` (fresh copies);
    returns ``{rid: tokens}``, wall seconds and what was compiled."""
    import numpy as np
    from repro.core.adaptive import AdaptiveController, SpeculationLUT
    from repro.serving.scheduler import (ContinuousEngineBackend,
                                         PrefillBudgetAdmit,
                                         serve_continuous_live)
    reqs = [dataclasses.replace(r, tokens=np.array(r.tokens))
            for r in requests]
    ctrl = AdaptiveController(lut=SpeculationLUT(dict(lut)))
    t0 = time.perf_counter()
    be = ContinuousEngineBackend(eng, tparams, dparams, capacity=capacity,
                                 cache_len=cache_len,
                                 warm_s=sorted(set(lut.values())),
                                 block_size=block_size,
                                 collect_outputs=True, mesh=mesh)
    res = serve_continuous_live(reqs, eng, tparams, dparams, ctrl,
                                backend=be,
                                policy=PrefillBudgetAdmit(budget, chunk=chunk))
    wall = time.perf_counter() - t0
    return {"outputs": {r: np.asarray(o) for r, o in be.outputs.items()},
            "backend": be, "wall_s": wall,
            "compile": compile_since(t0),
            "chunked": sum(len(t.chunked) for t in res.trace),
            "steps": len(res.trace)}


def check_outputs(run, requests, vocab: int, what: str) -> int:
    """Every request finished with its max_new in-vocabulary tokens."""
    import numpy as np
    n_tok = 0
    for r in requests:
        o = run["outputs"].get(r.rid)
        _check(o is not None, f"{what}: request {r.rid} did not finish")
        _check(len(o) == r.max_new,
               f"{what}: request {r.rid} got {len(o)} of {r.max_new} tokens")
        _check(bool(((o >= 0) & (o < vocab)).all()),
               f"{what}: request {r.rid} has out-of-vocabulary tokens")
        n_tok += int(np.asarray(o).size)
    return n_tok


def agreement(a, b) -> float:
    """Share of token positions that two runs' outputs agree on."""
    import numpy as np
    same = total = 0
    for rid, x in a["outputs"].items():
        y = b["outputs"][rid]
        same += int((np.asarray(x) == np.asarray(y)).sum())
        total += len(x)
    return same / max(total, 1)


def launch_configs(eng) -> list:
    """The ragged-kernel launch config of every paged jit the run built:
    ``lookup_config`` of its ``(batch, q length, table width)`` cell."""
    from repro.kernels.ops import kernel_mode
    from repro.kernels.tuning import lookup_config
    lines = []
    mode = kernel_mode(eng.tcfg.paged_fused)
    for (name, key), e in sorted(eng.jit_registry.items(),
                                 key=lambda kv: (kv[0][0], str(kv[0][1]))):
        if e.cu_arg is None or e.arg_specs is None:
            continue
        bt = e.arg_specs[2]["bt"]                 # tcache of step / chunk
        B, maxb = bt.shape
        T = key[1] + 1 if name == "step" else key[0]
        B = B if name == "step" else 1
        cfg = lookup_config(B, T, maxb)
        lines.append(f"{name}{tuple(key)}: B={B} T={T} MAXB={maxb} "
                     f"mode={mode} num_buffers={cfg.num_buffers} "
                     f"vmem_limit_bytes={cfg.vmem_limit_bytes}")
    return lines


def verify_step_text(eng, s: int) -> str:
    """Compiled HLO text of the paged verify step at speculation ``s``."""
    for (name, key), e in eng.jit_registry.items():
        if name == "step" and key[1] == s and key[2]:
            return e.fn.lower(*e.arg_specs).compile().as_text()
    raise CheckFailed(f"no paged verify step with s={s} was compiled")


def serving_phase(tcfg, dcfg, *, seed: int, dtype, paged_fused=None,
                  requests=None, budget=TOKEN_BUDGET, chunk=CHUNK,
                  log=print) -> dict:
    """Speculative run (cold), ``s = 0`` run, speculative run (warm)."""
    requests = requests or make_trace(tcfg.vocab_size, seed)
    eng, tp, dp = build_engine(tcfg, dcfg, seed=seed, dtype=dtype,
                               paged_fused=paged_fused)
    kw = dict(budget=budget, chunk=chunk)
    spec = serve_once(eng, tp, dp, requests, LUT, **kw)
    n_tok = check_outputs(spec, requests, tcfg.vocab_size, "speculative")
    log(f"serve speculative (cold): {len(requests)} requests, {n_tok} "
        f"tokens, {spec['steps']} iterations, {spec['chunked']} chunk "
        f"feeds, wall {spec['wall_s']:.3f} s, "
        f"{describe_compile(spec['compile'])}")
    for line in launch_configs(eng):
        log(f"kernel launch {line}")
    base = serve_once(eng, tp, dp, requests, {b: 0 for b in LUT}, **kw)
    check_outputs(base, requests, tcfg.vocab_size, "s=0")
    log(f"serve s=0: wall {base['wall_s']:.3f} s, "
        f"{describe_compile(base['compile'])}")
    warm = serve_once(eng, tp, dp, requests, LUT, **kw)
    check_outputs(warm, requests, tcfg.vocab_size, "speculative (warm)")
    log(f"serve speculative (warm): wall {warm['wall_s']:.3f} s, "
        f"{describe_compile(warm['compile'])}")
    share = agreement(spec, base)
    log(f"token agreement speculative vs s=0: {share}")
    log(f"token agreement speculative cold vs warm: {agreement(spec, warm)}")
    return {"engine": eng, "spec": spec, "base": base, "warm": warm,
            "tokens": n_tok, "agreement": share}


def sharded_phase(tcfg, dcfg, *, seed: int, dtype, n_chips: int,
                  requests=None, budget=TOKEN_BUDGET, chunk=CHUNK,
                  log=print) -> dict:
    """The trace on a data mesh of ``n_chips`` beside one device, both with
    the routing the sharded pool takes (the gather path)."""
    from repro.launch.mesh import make_serving_mesh
    requests = requests or make_trace(tcfg.vocab_size, seed)
    mesh = make_serving_mesh(n_chips)
    eng, tp, dp = build_engine(tcfg, dcfg, seed=seed, dtype=dtype)
    kw = dict(budget=budget, chunk=chunk)
    shard = serve_once(eng, tp, dp, requests, LUT, mesh=mesh, **kw)
    routing = eng.tcfg.paged_fused
    n_tok = check_outputs(shard, requests, tcfg.vocab_size, "sharded")
    pool = shard["backend"].state.tcache["k"]
    devs = sorted(d.id for d in pool.sharding.device_set)
    _check(len(devs) == n_chips,
           f"the sharded KV pool lives on devices {devs}, not {n_chips}")
    log(f"serve sharded x{n_chips}: {n_tok} tokens, paged_fused={routing}, "
        f"KV pool on devices {devs} as {pool.sharding.spec}, wall "
        f"{shard['wall_s']:.3f} s, {describe_compile(shard['compile'])}")
    one_eng, tp1, dp1 = build_engine(tcfg, dcfg, seed=seed, dtype=dtype,
                                     paged_fused=routing)
    one = serve_once(one_eng, tp1, dp1, requests, LUT, **kw)
    check_outputs(one, requests, tcfg.vocab_size, "one device")
    share = agreement(shard, one)
    log(f"serve one device: wall {one['wall_s']:.3f} s, "
        f"{describe_compile(one['compile'])}")
    log(f"token agreement sharded x{n_chips} vs one device: {share}")
    return {"agreement": share, "tokens": n_tok, "devices": devs}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded-serving phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _import_repro()
    import jax
    import jax.numpy as jnp
    cache = _use_compile_cache(jax)
    from repro.serving.telemetry import compile_log
    compile_log()                     # listen from the start
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices); "
              "this run needs the chip", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)} "
              "device(s)", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {cache}", flush=True)

    from repro.configs import registry
    tcfg = registry.get_config(ARCH)
    dcfg = registry.get_draft_config(ARCH)
    if args.chips == 4:
        sharded_phase(tcfg, dcfg, seed=args.seed, dtype=jnp.bfloat16,
                      n_chips=4)
        count = 4
    else:
        t0 = time.perf_counter()
        kc = kernel_check(seed=args.seed)
        print(f"kernel check ({time.perf_counter() - t0:.3f} s): "
              f"max |err| vs float32 reference {kc['max_abs_err']} "
              f"(tolerance {KERNEL_TOL}); served launch config "
              f"{kc['served_config']}; ragged vs dense max diff "
              f"{kc['ragged_vs_dense_max_diff']}", flush=True)
        res = serving_phase(tcfg, dcfg, seed=args.seed, dtype=jnp.bfloat16)
        t0 = time.perf_counter()
        text = verify_step_text(res["engine"], max(LUT.values()))
        has_kernel = "tpu_custom_call" in text
        print(f"verify step s={max(LUT.values())}: tpu_custom_call in "
              f"compiled program: {has_kernel} (inspection "
              f"{describe_compile(compile_since(t0))})", flush=True)
        _check(has_kernel, "the compiled verify step holds no Pallas TPU "
                           "kernel (tpu_custom_call)")
        count = 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
