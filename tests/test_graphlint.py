"""graph-lint (tools/graphlint): the compiled-artifact contract checker.

The heavy fixture drives the real engine through the driver's paged+fused
double replay once per module and every check reads from it: retrace
stability (each (name, key) jit traces exactly once, the second identical
replay traces nothing), transfer-free jaxprs, no gathered-KV
materialization on the fused path (with the gather-path probe proving the
detector sees the view it is banning), and donation aliasing in the
lowered HLO.  Pass logic is also unit-tested on fabricated entries, and
the subprocess tests prove the CLI/citier gate fails *loudly* on injected
violations (exit 1) and on a vacuous zero-jit run (exit 5).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.spec_decode import JitEntry
from tools.graphlint import cli as gl_cli
from tools.graphlint.passes import (donation, materialize, retrace,
                                    sharding, transfer_free)
from tools.lint import pragmas as P
from tools.lint.report import Finding

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def collections():
    from tools.graphlint import driver
    return driver.collect_fused(), driver.collect_gather_probe()


def _entry(name="step", key=(1, 1), **kw):
    defaults = dict(hot=True, kv_args=(), donate=(), sharded=False,
                    out_shardings=None, paged_rows=None, paged_fused=None,
                    src_file="src/repro/core/spec_decode.py", src_line=10)
    defaults.update(kw)
    return JitEntry(name=name, key=tuple(key), **defaults)


def _jaxprs(entries):
    return {(e.name, e.key): e.fn.trace(*e.arg_specs).jaxpr
            for e in entries if e.arg_specs is not None}


# ---------------------------------------------------------------------------
# the driven collection


def test_registry_covers_every_dispatch_family(collections):
    """The replay exercises every paged-serving jit family the engine can
    build — if a family is missing here, the driver's trace shrank and the
    passes went partially blind."""
    col, _ = collections
    names = {e.name for e in col.entries}
    assert names >= {"step", "prefill", "inject", "inject_paged",
                     "chunk", "chunk_begin", "chunk_commit", "retire_paged"}
    # the adaptive LUT sweeps s with occupancy: at least two step keys
    assert len([e for e in col.entries if e.name == "step"]) >= 2


def test_retrace_stability_exactly_once_then_cached(collections):
    """Satellite contract: one full serving replay compiles each (name,
    key) exactly once, and an identical second replay against the same
    engine compiles nothing at all."""
    col, _ = collections
    assert col.run1 and all(n == 1 for n in col.run1.values()), col.run1
    assert all(n == 0 for n in col.run2.values()), col.run2
    assert retrace.check(col.entries, col.run1, col.run2) == []


def test_transfer_free_on_real_engine(collections):
    col, probe = collections
    assert transfer_free.check(col.entries, _jaxprs(col.entries)) == []
    assert transfer_free.check(probe.entries, _jaxprs(probe.entries)) == []


def test_fused_never_materializes_and_probe_does(collections):
    col, probe = collections
    findings = materialize.check(
        col.entries, _jaxprs(col.entries), col.kv_trailing,
        guard_entries=probe.entries, guard_jaxprs=_jaxprs(probe.entries))
    assert findings == []
    # the probe's gather-path step really builds the [B, L, KVH, hd] view
    e = next(e for e in probe.entries if e.name == "step")
    hits = materialize.find_gathered_views(
        e.fn.trace(*e.arg_specs).jaxpr.jaxpr, e.paged_rows, col.kv_trailing)
    assert hits, "gather probe lost the materialized view"


def test_donation_aliased_in_lowering(collections):
    col, _ = collections
    lowered = {(e.name, e.key): e.fn.lower(*e.arg_specs).as_text()
               for e in col.entries
               if e.name in donation.DONATING_NAMES and e.arg_specs}
    assert lowered, "no donating jits collected"
    assert donation.check(col.entries, lowered) == []


def test_sharded_collection_needs_two_devices():
    from tools.graphlint import driver
    if len(jax.devices()) < 2:
        assert driver.collect_sharded() is None


# ---------------------------------------------------------------------------
# pass logic on fabricated entries


def test_transfer_free_catches_callback():
    def fn(x):
        jax.debug.print("x={}", x)
        return x + 1

    e = _entry(name="step", key=(2, 2))
    e.fn = jax.jit(fn)
    e.arg_specs = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    jaxprs = {(e.name, e.key): e.fn.trace(*e.arg_specs).jaxpr}
    findings = transfer_free.check([e], jaxprs)
    assert len(findings) == 1 and findings[0].rule == "transfer-free"
    # jax.debug.print traces to its own `debug_print` primitive
    assert "`debug_print`" in findings[0].message


def test_transfer_free_catches_pure_callback():
    def fn(x):
        return jax.pure_callback(lambda a: a, jax.ShapeDtypeStruct(
            x.shape, x.dtype), x) + 1

    e = _entry(name="step", key=(2, 2))
    e.fn = jax.jit(fn)
    e.arg_specs = (jax.ShapeDtypeStruct((4,), jnp.float32),)
    jaxprs = {(e.name, e.key): e.fn.trace(*e.arg_specs).jaxpr}
    findings = transfer_free.check([e], jaxprs)
    assert len(findings) == 1 and findings[0].rule == "transfer-free"
    assert "callback" in findings[0].message


def test_donation_flags_lost_annotation_and_undonated():
    lost = _entry(name="retire", key=(), kv_args=(), donate=())
    undonated = _entry(name="inject", key=(), kv_args=(0,), donate=())
    f = donation.check([lost, undonated], {})
    assert [x.rule for x in f] == ["donation", "donation"]
    assert "annotation was lost" in f[0].message
    assert "not donated" in f[1].message


def test_donation_flags_declined_aliasing():
    e = _entry(name="inject", key=(), kv_args=(0,), donate=(0,))
    e.arg_specs = ((jax.ShapeDtypeStruct((2, 2), jnp.float32),
                    jax.ShapeDtypeStruct((2, 2), jnp.float32)),)
    f = donation.check([e], {(e.name, e.key): "module @jit_inject {}"})
    assert len(f) == 1 and "aliases only 0" in f[0].message


def test_retrace_flags_midflight_and_repeat_compiles():
    a = _entry(name="step", key=(1, 1))
    b = _entry(name="step", key=(1, 2))
    f = retrace.check([a, b],
                      {("step", (1, 1)): 3, ("step", (1, 2)): 1},
                      {("step", (1, 2)): 2})
    assert len(f) == 2
    assert "traced 3x" in f[0].message
    assert "retraced 2x" in f[1].message


def test_materialize_vacuous_guard_fires():
    e = _entry(name="step", key=(1, 1), paged_rows=16, paged_fused=True)

    def clean(x):
        return x * 2.0

    e.fn = jax.jit(clean)
    e.arg_specs = (jax.ShapeDtypeStruct((2, 4), jnp.float32),)
    jaxprs = {(e.name, e.key): e.fn.trace(*e.arg_specs).jaxpr}
    probe = _entry(name="step", key=(9, 9), paged_rows=16, paged_fused=False)
    probe.fn = e.fn
    probe.arg_specs = e.arg_specs
    guard_jaxprs = {(probe.name, probe.key): jaxprs[(e.name, e.key)]}
    f = materialize.check([e], jaxprs, (2, 4),
                          guard_entries=[probe], guard_jaxprs=guard_jaxprs)
    assert len(f) == 1 and "vacuous" in f[0].message


def test_find_gathered_views_trailing_filter():
    def gatherish(x):
        # [1, 16, 2, 4]: rows=16 leading + KV trailing (2, 4) => the view
        return jnp.broadcast_to(x, (1, 16, 2, 4)) + 1.0

    closed = jax.make_jaxpr(gatherish)(
        jax.ShapeDtypeStruct((2, 4), jnp.float32))
    assert materialize.find_gathered_views(closed.jaxpr, 16, (2, 4))
    # same rows, wrong KV geometry (a draft-cache-shaped array): filtered
    assert not materialize.find_gathered_views(closed.jaxpr, 16, (1, 8))
    # kernel_bench mode (trailing=None): rows alone decides
    assert materialize.find_gathered_views(closed.jaxpr, 16)


def test_broadcast_decl_prefix_semantics():
    spec = {"k": (jax.ShapeDtypeStruct((2,), jnp.float32),
                  jax.ShapeDtypeStruct((3,), jnp.float32)),
            "v": jax.ShapeDtypeStruct((4,), jnp.float32)}
    # a single None broadcasts over every leaf
    pairs = sharding.broadcast_decl(None, spec)
    assert len(pairs) == 3 and all(d is None for d, _ in pairs)
    # dict prefix: one decl per key, tuple decl zips elementwise
    decl = {"k": (None, None), "v": None}
    pairs = sharding.broadcast_decl(decl, spec)
    assert len(pairs) == 3


def test_sharding_flags_entry_without_shardings():
    e = _entry(name="step", key=(4, 2), sharded=False)
    f = sharding.check([e], {})
    assert len(f) == 1 and "without explicit shardings" in f[0].message


# ---------------------------------------------------------------------------
# pragma grammar + CLI contract


def test_graphlint_pragma_marker_roundtrip():
    src = ("x = 1\n"
           "y = 2  # graphlint: allow-donation(tcache checkpoint cannot alias)\n"
           "z = 3  # graphlint: allow-retrace()\n")
    prags = P.collect("src/repro/core/spec_decode.py", src,
                      pattern=gl_cli.PRAGMA_RE)
    assert [(p.rule, p.target_line) for p in prags] == [
        ("donation", 2), ("retrace", 3)]
    hit = Finding(file="src/repro/core/spec_decode.py", line=2, col=0,
                  rule="donation", severity="error", message="m")
    kept, problems = P.apply([hit], prags)
    assert kept == []                      # the valid pragma suppressed it
    assert [p.rule for p in problems] == ["malformed-pragma"]


def test_repro_lint_marker_is_not_a_graphlint_pragma():
    src = "y = 2  # lint: allow-donation(wrong subsystem)\n"
    assert P.collect("f.py", src, pattern=gl_cli.PRAGMA_RE) == []


def test_exit_codes_match_repro_lint():
    assert (gl_cli.EXIT_CLEAN, gl_cli.EXIT_FINDINGS,
            gl_cli.EXIT_USAGE, gl_cli.EXIT_NO_JITS) == (0, 1, 2, 5)


def _run_cli(*args, timeout=900):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)            # the CLI forces its own devices
    return subprocess.run([sys.executable, "-m", "tools.graphlint", *args],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_cli_vacuous_run_exits_5():
    proc = _run_cli("--inject", "no-jits")
    assert proc.returncode == 5, proc.stderr[-2000:]
    assert "no jits collected" in proc.stderr


def test_cli_usage_error_exits_2():
    proc = _run_cli("--inject", "bogus")
    assert proc.returncode == 2


@pytest.mark.slow
def test_cli_injected_no_donation_fails_loudly():
    proc = _run_cli("--no-sharded", "--inject", "no-donation")
    assert proc.returncode == 1, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "donation" in proc.stdout and "not donated" in proc.stdout


@pytest.mark.slow
def test_citier_graph_tier_fails_loudly_on_injected_retrace():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join("tools", "citier.py"), "graph",
         "--no-sharded", "--inject", "retrace"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 1, (proc.stdout[-2000:], proc.stderr[-2000:])
    assert "retraced" in proc.stdout
    assert "graph-lint FAILED" in proc.stderr
