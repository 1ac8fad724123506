"""Architectures as modules (``bench/archs/``).

The dense decoder's weights, reference logits, flop and byte counts and
program configs are those recorded in ``data/golden_dense.json`` before
they moved into ``bench/archs/dense_gqa.py``.  A second architecture
(``arch_gqa_moe.py``: qk-norm and sparse experts) is added to a throwaway
checkout as new files only, and its cell runs with ``correct`` true."""
import dataclasses
import hashlib
import json
import os
import textwrap
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import archs
from bench import harness as H
from bench import readers
from bench import reference as ref
from bench import run as R
from bench import weights as W
from bench.tests.tiny import REPO, TINY, JaxConfig, make_root

with open(os.path.join(os.path.dirname(__file__), "data",
                       "golden_dense.json")) as _f:
    GOLDEN = json.load(_f)
SEED = 2**33 + 7


def _hash(a) -> str:
    a = np.asarray(a)
    return hashlib.sha256(a.tobytes()).hexdigest() + f":{a.dtype}:{a.shape}"


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_golden_dense_weights(dtype):
    tp, dp = W.make_params(SEED, TINY["target"], TINY["draft"],
                           jnp.dtype(dtype))
    for name, tree in (("target", tp), ("draft", dp)):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        got = {jax.tree_util.keystr(p): _hash(v) for p, v in flat}
        assert got == GOLDEN["weights"][f"{name}.{dtype}"], name


@pytest.mark.parametrize("sec,fp8,dtype", [
    (s, f, d) for s in ("target", "draft") for f in (False, True)
    for d in ("bfloat16", "float32")])
def test_golden_dense_reference_logits(sec, fp8, dtype):
    toks = np.random.default_rng(5).integers(0, 500, (2, 24)).astype(np.int32)
    m, dt = TINY[sec], jnp.dtype(dtype)
    mk = W.model_key(SEED, 0 if sec == "target" else 1)
    z = ref.logits(mk, m, ref.hidden(mk, m, toks, fp8=fp8, dtype=dt),
                   fp8=fp8, dtype=dt)
    assert _hash(z) == GOLDEN["logits"][f"{sec}.fp8={fp8}.{dtype}"]


@pytest.mark.parametrize("sec", ["target", "draft"])
def test_golden_dense_flops_and_bytes(sec):
    m = H.load_config(REPO, "internlm2-1.8b")[sec]
    A = archs.of(m)
    g = GOLDEN["flops"][sec]
    for first, n, logits, want in g["tokens_flops"]:
        assert A.tokens_flops(m, first, n, logits) == want
    for rows, block, want in g["ragged_call"]:
        assert list(A.attn_call(m, [tuple(r) for r in rows], block)) == want


@pytest.mark.parametrize("name", ["internlm2-1.8b", "tiny"])
def test_golden_dense_program_configs(name):
    conf = H.load_config(REPO, name) if name != "tiny" else TINY
    t, d = H.model_configs(conf)
    got = [dataclasses.asdict(t), dataclasses.asdict(d)]
    assert json.loads(json.dumps(got)) == GOLDEN["model_config"][name]


def test_missing_module_names_its_file(tmp_path):
    root = make_root(tmp_path, config=dict(
        TINY, target=dict(TINY["target"], model="absent")))
    with pytest.raises(SystemExit, match=os.path.join(
            "bench", "archs", "absent.py")):
        H.load_config(root, "tiny")


def test_non_uniform_stack_generates_each_kind(tmp_path):
    """A module that states each layer's kind gets its leaves made per
    kind in the reference, and builds the program's tree itself."""
    path = tmp_path / "two_kinds.py"
    path.write_text(textwrap.dedent('''
        import jax.numpy as jnp
        from bench.archs import dense_gqa
        from bench.weights import leaf, leaf_key
        LEAVES = dense_gqa.LEAVES
        global_shapes = dense_gqa.global_shapes

        def layer_kind(m, layer):
            return "wide" if layer == 0 else "narrow"

        def layer_weights(mkey, m, layer, dtype, kind):
            width = 3 if kind == "wide" else 2
            return {"w_up": leaf(leaf_key(mkey, LEAVES, "w_up", layer),
                                 "w_up", (m["d_model"], width), dtype)}

        def program_params(mkey, m, dtype):
            return {"layers": [layer_weights(mkey, m, l, dtype,
                                             layer_kind(m, l))
                               for l in range(m["n_layers"])]}

        def layer(w, h, m, fp8):
            return h + w["w_up"].shape[1]
    '''))
    m = dict(TINY["target"], n_layers=3, model_file=str(path))
    mk = W.model_key(1, 0)
    h = ref.hidden(mk, m, np.zeros((1, 4), np.int32), dtype=jnp.float32)
    # embedding row 0 through layers of widths 3, 2 and 2
    e = W.global_weights(mk, m, jnp.float32)["embed"][0] + 7.0
    np.testing.assert_allclose(np.asarray(h)[0, 2],
                               np.asarray(ref.rms(e, 1.0, m["norm_eps"])),
                               rtol=1e-6)
    assert archs.layer_kinds(archs.of(m), m) == ["wide", "narrow", "narrow"]
    tree = W.program_params(W.model_key(1, 0), m, jnp.float32)
    assert [l["w_up"].shape[1] for l in tree["layers"]] == [3, 2, 2]


# ---------------------------------------------------------------------------
# a second architecture, as new files only

MOE = {"model": "gqa_moe", "n_layers": 2, "d_model": 128, "vocab_size": 500,
       "vocab_padded": 512, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
       "rope_theta": 1e6, "norm_eps": 1e-6, "n_experts": 4, "top_k": 2,
       "d_ff_expert": 96, "capacity_factor": 2.0}
TINY_MOE = dict(TINY, target=MOE)
MOE_FILES = {os.path.join("bench", "archs", "gqa_moe.py"):
             os.path.join("bench", "tests", "arch_gqa_moe.py")}


@pytest.fixture(scope="module")
def moe_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("moe"), config=TINY_MOE,
                     files=MOE_FILES)


def test_moe_reference_matches_program_forward(moe_root):
    from repro.models.transformer import DecoderLM
    conf = H.load_config(moe_root, "tiny")
    tcfg, _ = H.model_configs(conf)
    assert tcfg.moe.capacity_factor >= MOE["n_experts"] / MOE["top_k"]
    m = conf["target"]
    tp, _ = W.make_params(3, m, conf["draft"], jnp.float32)
    want_tree = jax.eval_shape(lambda k: DecoderLM(tcfg).init(k, jnp.float32),
                               jax.random.PRNGKey(0))
    assert jax.tree.structure(tp) == jax.tree.structure(want_tree)
    toks = np.random.default_rng(0).integers(0, 500, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = DecoderLM(tcfg).forward(tp, jnp.asarray(toks))
    mk = W.model_key(3, 0)
    got = ref.logits(mk, m, ref.hidden(mk, m, toks, dtype=jnp.float32),
                     dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got)[..., :500],
                               np.asarray(want)[..., :500],
                               rtol=2e-4, atol=2e-4)


def test_moe_cell_runs_correct_from_new_files(moe_root, capsys):
    with JaxConfig():
        rc = R.main(["--workload", "tiny.tinychat", "--seed", "2147483991",
                     "--seconds", "2", "--trace", "0"],
                    root=moe_root, require_tpu=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 5
    # nothing outside the throwaway root names the new architecture
    assert not os.path.exists(os.path.join(REPO, "bench", "archs",
                                           "gqa_moe.py"))


def test_moe_flops_count_top_k_experts(moe_root):
    m = H.load_config(moe_root, "tiny")["target"]
    A = archs.of(m)
    d, ff, L = m["d_model"], m["d_ff_expert"], m["n_layers"]
    one = A.tokens_flops(m, 0, 1, logits=False)
    more = A.tokens_flops(dict(m, top_k=m["top_k"] + 1), 0, 1, logits=False)
    assert more - one == L * 2 * 3 * d * ff
    attention = one - L * 2 * (m["top_k"] * 3 * d * ff + d * m["n_experts"])
    assert attention == archs.of({"model": "dense_gqa"}).tokens_flops(
        dict(m, d_ff=0), 0, 1, logits=False)
    # mfu's reader counts the window's tokens by the cell's module
    call = SimpleNamespace(t_call=0.5, kind="prefill", start=0, n=1)
    run = H.Run(cell="tiny.x", conf={"target": m}, mix=None, seconds=1.0,
                adapter=SimpleNamespace(steps=[], calls=[call]), t1=1.0)
    assert readers.window_flops(run) == one


def test_moe_fp8_control_departs_from_float32(moe_root):
    m = H.load_config(moe_root, "tiny")["target"]
    mk = W.model_key(4, 0)
    toks = np.random.default_rng(1).integers(0, 500, (1, 32)).astype(np.int32)
    z32 = np.asarray(ref.logits(mk, m, ref.hidden(mk, m, toks)))[..., :500]
    z8 = np.asarray(ref.logits(mk, m, ref.hidden(mk, m, toks, fp8=True),
                               fp8=True))[..., :500]
    err = np.abs(z8 - z32).max()
    assert 1e-3 < err < 0.5 * np.abs(z32).max()
