"""Sharded continuous serving: `serve_continuous_live(mesh=...)` on a forced
2-device host mesh must produce token-identical outputs and an identical
StepTrace to the 1-device run — for the contiguous slot pool, the paged
block pool under preemption pressure, and chunked admission.

The comparison runs in a subprocess because the device count must be forced
(``XLA_FLAGS=--xla_force_host_platform_device_count=2``) before jax
initialises; the main test process keeps its single CPU device.  Fast tier:
the engine is the tiny smoke pair and the traces are short.
"""
import json
import os
import subprocess
import sys

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                           + os.environ.get("XLA_FLAGS", ""))
import dataclasses, json
import jax, numpy as np
from repro.configs import registry as R
from repro.core.adaptive import AdaptiveController, SpeculationLUT
from repro.core.spec_decode import SpecDecodeEngine
from repro.launch.mesh import make_serving_mesh
from repro.serving.scheduler import (ContinuousEngineBackend,
                                     PrefillBudgetAdmit,
                                     serve_continuous_live)
from repro.serving.traffic import TrafficPhase, make_requests

assert jax.device_count() == 2, jax.devices()
tcfg = R.get_smoke_config("yi-9b")
d = R.get_draft_config("yi-9b")
dcfg = dataclasses.replace(
    d, n_layers=1, d_model=64, d_ff=128, vocab_size=tcfg.vocab_size,
    dtype="float32",
    attn=dataclasses.replace(d.attn, n_heads=2, n_kv_heads=2, head_dim=32))
eng0 = SpecDecodeEngine(tcfg, dcfg, max_new=12)
tparams = eng0.target.init(jax.random.PRNGKey(0))
dparams = eng0.draft.init(jax.random.PRNGKey(1))
mesh = make_serving_mesh(2)
ctrl = lambda: AdaptiveController(lut=SpeculationLUT({1: 3, 2: 2, 4: 2}))

def trace(long=False, hungry=False):
    reqs = make_requests(8, [TrafficPhase(0.002, 1.0, float("inf"))],
                         tcfg.vocab_size, seed=7, max_new=12)
    rng = np.random.default_rng(3)
    for j, r in enumerate(reqs):
        # arrival = 0: the scheduler clock advances by MEASURED wall times,
        # so nonzero arrivals would make admission composition depend on
        # how fast each run's prefills happened to be — the live-vs-live
        # exact-trace assertion below must be purely structural
        r.arrival = 0.0
        if long and j % 3 == 0:
            L = int(rng.integers(40, 60))
            r.tokens = rng.integers(0, tcfg.vocab_size, (L,)).astype(np.int32)
            r.prompt_len = L
        r.max_new = int(rng.integers(10, 13) if hungry
                        else rng.integers(4, 11))
    return reqs

def run(mesh, *, long=False, hungry=False, policy=None, **bkw):
    # fresh engine per run: init_slots resets the jit caches, but a fresh
    # instance makes sharded/unsharded compilations fully independent
    eng = SpecDecodeEngine(tcfg, dcfg, max_new=12)
    be = ContinuousEngineBackend(eng, tparams, dparams, capacity=4,
                                 cache_len=96, warm_s=[2, 3],
                                 collect_outputs=True, mesh=mesh, **bkw)
    res = serve_continuous_live(trace(long=long, hungry=hungry), eng,
                                tparams, dparams, ctrl(), backend=be,
                                policy=policy)
    return res, be

def compare(name, one, two):
    (r1, b1), (r2, b2) = one, two
    t1, t2 = r1.trace, r2.trace
    assert [t.admitted for t in t1] == [t.admitted for t in t2], name
    assert [t.occupancy for t in t1] == [t.occupancy for t in t2], name
    assert [t.committed for t in t1] == [t.committed for t in t2], name
    assert [t.preempted for t in t1] == [t.preempted for t in t2], name
    assert [t.done_rids for t in t1] == [t.done_rids for t in t2], name
    assert [t.chunked for t in t1] == [t.chunked for t in t2], name
    assert set(b1.outputs) == set(b2.outputs), name
    for rid in b1.outputs:
        np.testing.assert_array_equal(b1.outputs[rid], b2.outputs[rid],
                                      err_msg=f"{name} rid {rid}")
    assert b2.n_shards == 2, (name, b2.n_shards)
    return {"iters": len(t1),
            "preempts": sum(len(t.preempted) for t in t1),
            "chunks": sum(len(t.chunked) for t in t1)}

out = {}
out["contiguous"] = compare("contiguous", run(None), run(mesh))
# undersized paged pool + near-engine-max budgets => preemption pressure
pg = dict(long=True, hungry=True, block_size=8, num_blocks=14)
out["paged"] = compare("paged", run(None, **pg), run(mesh, **pg))
ck = dict(long=True)
out["chunked"] = compare(
    "chunked",
    run(None, policy=PrefillBudgetAdmit(token_budget=16), **ck),
    run(mesh, policy=PrefillBudgetAdmit(token_budget=16), **ck))
print(json.dumps(out))
"""


def test_sharded_serve_parity_two_devices():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)           # the script forces its own devices
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    # every study compared trace-identical and token-identical inside the
    # subprocess; here we only sanity-check each actually exercised its path
    assert out["contiguous"]["iters"] > 0
    assert out["paged"]["preempts"] > 0, out
    assert out["chunked"]["chunks"] > 0, out


KERNEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                           + os.environ.get("XLA_FLAGS", ""))
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.kernels import ops
from repro.launch.mesh import make_serving_mesh

mesh = make_serving_mesh(2)
rng = np.random.default_rng(0)
for B in (4, 1):                 # a batch that splits, and B=1 (replicated)
    T, H, KVH, hd, L = 3, 4, 2, 32, 40
    q = rng.standard_normal((B, T, H, hd)).astype(np.float32)
    k = rng.standard_normal((B, L, KVH, hd)).astype(np.float32)
    v = rng.standard_normal((B, L, KVH, hd)).astype(np.float32)
    lens = rng.integers(T, L, B)
    qp = (lens - T)[:, None] + np.arange(T)[None]
    kp = np.where(np.arange(L)[None] < lens[:, None], np.arange(L)[None], -1)
    args = [jnp.asarray(a) for a in (q, k, v, qp.astype(np.int32),
                                     kp.astype(np.int32))]
    sh = NamedSharding(mesh, P("data") if B % 2 == 0 else P())

    def f(*a):
        with ops.partition_mesh(mesh):
            return ops.spec_verify_attn(*a, block_k=8, use_pallas=True)

    got = jax.jit(f, in_shardings=(sh,) * 5, out_shardings=sh)(*args)
    want = ops.spec_verify_attn(*args, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    text = jax.jit(f, in_shardings=(sh,) * 5).lower(*args).as_text()
    manual = [l for l in text.splitlines() if "sdy.manual_computation" in l]
    assert len(manual) == 1, text[:2000]
    # B=4 splits over "data"; B=1 runs whole on each device
    assert ('[{"data"}' in manual[0]) == (B % 2 == 0), manual[0][:300]
print("ok")
"""


def test_verify_kernel_runs_per_device_under_a_mesh():
    """Under a 2-device mesh the verify kernel runs inside shard_map (XLA
    cannot partition a Pallas TPU kernel): per-device batch slices, or the
    whole batch on each device for B=1, both equal to the reference."""
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", KERNEL_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert proc.stdout.strip().splitlines()[-1] == "ok"
