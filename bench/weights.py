"""Weights made by the benchmark from ``--seed``, in the layout the program
serves them in, and regenerated layer by layer for the reference.

Every leaf is ``normal(key, shape) * scale`` (or ones for norm gains) with
its own key: ``fold_in(fold_in(fold_in(seed_key, model), leaf), layer)``.
The scales follow the program's default initialiser (embedding tables
0.02, every other matrix 1/sqrt of its first unstacked dimension), so the
served model behaves like the one the program's own tests build.  The
program gets the whole tree from one jitted call, in bf16, on the device;
the reference rebuilds any one layer from the same keys.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

LEAVES = ("embed", "unembed", "final_norm", "attn_norm", "mlp_norm", "wq",
          "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LAYER_LEAVES = LEAVES[3:]


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of any size (``PRNGKey`` keeps only 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def global_shapes(m: Dict) -> Dict[str, Tuple[int, ...]]:
    d, vp = m["d_model"], m["vocab_padded"]
    return {"embed": (vp, d), "unembed": (vp, d), "final_norm": (d,)}


def layer_shapes(m: Dict) -> Dict[str, Tuple[int, ...]]:
    d, ff = m["d_model"], m["d_ff"]
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return {"attn_norm": (d,), "mlp_norm": (d,), "wq": (d, H, hd),
            "wk": (d, KVH, hd), "wv": (d, KVH, hd), "wo": (H, hd, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def _leaf(key, name: str, shape, dtype):
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    scale = 0.02 if name in ("embed", "unembed") else 1.0 / math.sqrt(shape[0])
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _key(model_key, name: str, layer=None):
    k = jax.random.fold_in(model_key, LEAVES.index(name))
    return k if layer is None else jax.random.fold_in(k, layer)


def model_key(seed: int, model: int) -> jax.Array:
    """``model`` 0 is the target, 1 the draft."""
    return jax.random.fold_in(seed_key(seed), model)


def layer_weights(mkey, m: Dict, layer, dtype=jnp.bfloat16) -> Dict:
    """One layer's leaves (``layer`` may be traced)."""
    return {n: _leaf(_key(mkey, n, layer), n, shp, dtype)
            for n, shp in layer_shapes(m).items()}


def global_weights(mkey, m: Dict, dtype=jnp.bfloat16) -> Dict:
    return {n: _leaf(_key(mkey, n), n, shp, dtype)
            for n, shp in global_shapes(m).items()}


def program_params(mkey, m: Dict, dtype=jnp.bfloat16) -> Dict:
    """The program's tree: global leaves plus ``layers`` stacked on a
    leading axis, built one layer at a time (``lax.map``) so that no
    float32 copy of a stacked leaf is ever live."""
    p = global_weights(mkey, m, dtype)
    p["layers"] = jax.lax.map(lambda l: layer_weights(mkey, m, l, dtype),
                              jnp.arange(m["n_layers"]))
    return p


def make_params(seed: int, target: Dict, draft: Dict, dtype=jnp.bfloat16):
    """Target and draft trees, on the default device, in one jitted call."""
    fn = jax.jit(lambda kt, kd: (program_params(kt, target, dtype),
                                 program_params(kd, draft, dtype)))
    return fn(model_key(seed, 0), model_key(seed, 1))
