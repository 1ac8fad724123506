"""The benchmark's weights and float32 reference against the program."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import archs
from bench import reference as ref
from bench import weights as W
from bench.harness import model_configs
from bench.tests.tiny import TINY


def test_program_layout_equals_per_layer_regeneration():
    m = TINY["target"]
    mk = W.model_key(2**32 + 9, 0)
    tree = jax.jit(lambda k: W.program_params(k, m, jnp.bfloat16))(mk)
    gen = jax.jit(lambda k, l: archs.of(m).layer_weights(
        k, m, l, jnp.bfloat16))
    for l in range(m["n_layers"]):
        one = gen(mk, l)
        for name, leaf in one.items():
            assert np.array_equal(np.asarray(tree["layers"][name][l]),
                                  np.asarray(leaf)), (name, l)
    glob = jax.jit(lambda k: W.global_weights(k, m, jnp.bfloat16))(mk)
    assert np.array_equal(np.asarray(tree["unembed"]),
                          np.asarray(glob["unembed"]))


def test_seed_keys_keep_high_bits():
    a = W.seed_key(5)
    b = W.seed_key(2**33 + 5)
    assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_reference_matches_program_forward():
    from repro.models.transformer import DecoderLM
    tcfg, _ = model_configs(TINY)
    tcfg = tcfg.with_(dtype="float32")
    m = TINY["target"]
    tp, _ = W.make_params(3, m, TINY["draft"], jnp.float32)
    toks = np.random.default_rng(0).integers(0, 500, (2, 40)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = DecoderLM(tcfg).forward(tp, jnp.asarray(toks))
    mk = W.model_key(3, 0)
    got = ref.logits(mk, m, ref.hidden(mk, m, toks, dtype=jnp.float32),
                     dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got)[..., :500],
                               np.asarray(want)[..., :500],
                               rtol=2e-4, atol=2e-4)


def test_fp8_control_departs_from_float32():
    m = TINY["target"]
    mk = W.model_key(4, 0)
    toks = np.random.default_rng(1).integers(0, 500, (1, 32)).astype(np.int32)
    z32 = np.asarray(ref.logits(mk, m, ref.hidden(mk, m, toks)))[..., :500]
    z8 = np.asarray(ref.logits(mk, m, ref.hidden(mk, m, toks, fp8=True),
                               fp8=True))[..., :500]
    err = np.abs(z8 - z32).max()
    assert 1e-3 < err < 0.5 * np.abs(z32).max()


def test_residual_band_covers_only_near_ties():
    """A token whose p and q nearly agree keeps a residual within the band;
    one that the draft overrates by far has none either way."""
    zt = jnp.log(jnp.array([0.40, 0.30, 0.20, 0.10]))
    zd = jnp.log(jnp.array([0.10, 0.31, 0.20, 0.39]))
    floor = float(jnp.log(1e-30))

    def resid(rel):
        return np.asarray(ref._perturbed(zt, zd, 1, 0, 1, 1.0, rel))
    exact, most, least = resid(0.0), resid(ref.RESID_REL), resid(-ref.RESID_REL)
    assert exact[1] == floor and most[1] > floor + 10       # near tie
    assert exact[3] == most[3] == floor                      # q >> p
    assert least[0] < exact[0] < most[0]                     # p > q
    assert least[2] == floor < most[2]                       # exact tie
