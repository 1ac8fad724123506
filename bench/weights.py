"""Weights made by the benchmark from ``--seed``, in the layout the program
serves them in, and regenerated layer by layer for the reference.

Every leaf is ``normal(key, shape) * scale`` (or ones for norm gains) with
its own key: ``fold_in(fold_in(fold_in(seed_key, model), leaf), layer)``,
``leaf`` being the name's place in the architecture module's ``LEAVES``
(``bench/archs/``).  The scales follow the program's default initialiser
(embedding tables 0.02, every other matrix 1/sqrt of its first unstacked
dimension), so the served model behaves like the one the program's own
tests build.  The program gets the whole tree from one jitted call, in
bf16, on the device; the reference rebuilds any one layer from the same
keys.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from bench import archs


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (``PRNGKey`` keeps only 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def leaf(key, name: str, shape, dtype):
    """A leaf by the default rule: ones for a norm gain, else a normal draw
    at 0.02 (embedding tables) or 1/sqrt of the first dimension."""
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    scale = 0.02 if name in ("embed", "unembed") else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def leaf_key(model_key, leaves: Sequence[str], name: str, layer=None):
    k = jax.random.fold_in(model_key, leaves.index(name))
    return k if layer is None else jax.random.fold_in(k, layer)


def model_key(seed: int, model: int) -> jax.Array:
    """``model`` 0 is the target, 1 the draft."""
    return jax.random.fold_in(seed_key(seed), model)


def global_weights(mkey, m: Dict, dtype=jnp.bfloat16) -> Dict:
    A = archs.of(m)
    return {n: leaf(leaf_key(mkey, A.LEAVES, n), n, shp, dtype)
            for n, shp in A.global_shapes(m).items()}


def program_params(mkey, m: Dict, dtype=jnp.bfloat16) -> Dict:
    """The program's tree: global leaves plus ``layers`` stacked on a
    leading axis, built one layer at a time (``lax.map``) so that no
    float32 copy of a stacked leaf is ever live."""
    A = archs.of(m)
    if hasattr(A, "program_params"):
        return A.program_params(mkey, m, dtype)
    p = global_weights(mkey, m, dtype)
    p["layers"] = jax.lax.map(lambda l: A.layer_weights(mkey, m, l, dtype),
                              jnp.arange(m["n_layers"]))
    return p


def make_params(seed: int, target: Dict, draft: Dict, dtype=jnp.bfloat16):
    """Target and draft trees, on the default device, in one jitted call."""
    fn = jax.jit(lambda kt, kd: (program_params(kt, target, dtype),
                                 program_params(kd, draft, dtype)))
    return fn(model_key(seed, 0), model_key(seed, 1))
