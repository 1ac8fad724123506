"""CPU rehearsal of ``chip_smoke.py``, the one-command chip run.

The phases run in process on the CPU at a tiny size, with the Pallas
kernels in interpret mode (``paged_fused=True`` steers the serving path
onto the fused ragged kernel; the script itself has no option for it).
The script run as a program must refuse the CPU: nonzero exit, no result.
"""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from repro.configs import registry  # noqa: E402


def test_kernel_check_phase_runs_on_cpu():
    out = cs.kernel_check(B=4, T=4, H=4, KVH=2, hd=32, bs=8, max_blocks=8,
                          interpret=True)
    assert len(out["max_abs_err"]) == 4
    assert max(out["max_abs_err"].values()) < cs.KERNEL_TOL
    assert out["ragged_vs_dense_max_diff"] == 0.0


def test_serving_phase_runs_on_cpu():
    tcfg = registry.get_smoke_config(cs.ARCH)
    d = registry.get_draft_config(cs.ARCH)
    dcfg = dataclasses.replace(
        d, n_layers=1, d_model=64, d_ff=128, vocab_size=tcfg.vocab_size,
        attn=dataclasses.replace(d.attn, n_heads=2, n_kv_heads=2,
                                 head_dim=32))
    reqs = cs.make_trace(tcfg.vocab_size, 0, n=6, prompt_range=(16, 80),
                         max_new=8)
    lines = []
    res = cs.serving_phase(tcfg, dcfg, seed=0, dtype=jnp.float32,
                           paged_fused=True, requests=reqs, budget=32,
                           chunk=16, log=lines.append)
    assert res["tokens"] == 6 * 8
    assert res["spec"]["chunked"] > 0            # chunked admission ran
    # the warm repeat compiles nothing new in process
    cold, warm = res["spec"]["compile"], res["warm"]["compile"]
    assert warm["backend"] < cold["backend"]
    # greedy speculation is lossless: float32 outputs match s = 0 exactly
    assert res["agreement"] == 1.0
    launches = [l for l in lines if l.startswith("kernel launch")]
    assert any("step" in l for l in launches)
    assert any("chunk" in l for l in launches)
    assert all("mode=interpret" in l for l in launches)


def _run_script(cwd):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(stdout: str) -> bool:
    return '"ok": true' not in stdout


def test_script_refuses_the_cpu():
    proc = _run_script(ROOT)
    assert proc.returncode != 0
    assert _no_result(proc.stdout), proc.stdout


def test_script_refuses_to_run_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    proc = _run_script(str(tmp_path))
    assert proc.returncode != 0
    assert _no_result(proc.stdout), proc.stdout
