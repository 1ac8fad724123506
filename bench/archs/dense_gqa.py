"""Dense decoder with grouped-query attention: RMSNorm, rotary attention
with grouped KV heads, SwiGLU MLP (InternLM2, Yi, the repo's dense draft).

File keys: ``n_layers``, ``d_model``, ``d_ff``, ``vocab_size``,
``vocab_padded``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
``rope_theta``, ``norm_eps``.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from bench.flops import BF16
from bench.reference import mm, rms, rope, round_fp8
from bench.weights import leaf, leaf_key

LEAVES = ("embed", "unembed", "final_norm", "attn_norm", "mlp_norm", "wq",
          "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def program_config(m: Dict, name: str, dtype: str):
    from repro.configs.base import AttnConfig, ModelConfig
    return ModelConfig(
        name=name, family="dense", n_layers=m["n_layers"],
        d_model=m["d_model"], d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        attn=AttnConfig(n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                        head_dim=m["head_dim"], rope_theta=m["rope_theta"]),
        norm_eps=m["norm_eps"], dtype=dtype)


def registry_view(cfg) -> Dict:
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "n_heads": cfg.attn.n_heads, "n_kv_heads": cfg.attn.n_kv_heads,
            "head_dim": cfg.attn.head_dim, "rope_theta": cfg.attn.rope_theta,
            "norm_eps": cfg.norm_eps}


# ---------------------------------------------------------------------------
# weights


def global_shapes(m: Dict) -> Dict[str, Tuple[int, ...]]:
    d, vp = m["d_model"], m["vocab_padded"]
    return {"embed": (vp, d), "unembed": (vp, d), "final_norm": (d,)}


def layer_shapes(m: Dict) -> Dict[str, Tuple[int, ...]]:
    d, ff = m["d_model"], m["d_ff"]
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return {"attn_norm": (d,), "mlp_norm": (d,), "wq": (d, H, hd),
            "wk": (d, KVH, hd), "wv": (d, KVH, hd), "wo": (H, hd, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def layer_weights(mkey, m: Dict, layer, dtype=jnp.bfloat16) -> Dict:
    """One layer's leaves (``layer`` may be traced)."""
    return {n: leaf(leaf_key(mkey, LEAVES, n, layer), n, shp, dtype)
            for n, shp in layer_shapes(m).items()}


# ---------------------------------------------------------------------------
# reference


def layer(w, h, m, fp8):
    """One decoder layer over ``h [R, L, d]`` (positions 0..L-1, causal)."""
    R, L, _ = h.shape
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps = m["norm_eps"]
    pos = jnp.arange(L)[None, :]
    x = rms(h, w["attn_norm"], eps)
    q = rope(mm("rld,dhk->rlhk", x, w["wq"], fp8, 0), pos, m["rope_theta"])
    k = rope(mm("rld,dhk->rlhk", x, w["wk"], fp8, 0), pos, m["rope_theta"])
    v = mm("rld,dhk->rlhk", x, w["wv"], fp8, 0)
    G = H // KVH
    qg = q.reshape(R, L, KVH, G, hd)
    if fp8:
        qg, k, v = round_fp8(qg, -1), round_fp8(k, -1), round_fp8(v, 1)
    sc = jnp.einsum("rqkgh,rskh->rkgqs", qg, k,
                    precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    sc = jnp.where(causal, sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    if fp8:
        p = round_fp8(p, -1)
    o = jnp.einsum("rkgqs,rskh->rqkgh", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(R, L, H, hd)
    h = h + mm("rlhk,hkd->rld", o.reshape(R, L, H, hd), w["wo"], fp8, (0, 1))
    x = rms(h, w["mlp_norm"], eps)
    g = mm("rld,df->rlf", x, w["w_gate"], fp8, 0)
    u = mm("rld,df->rlf", x, w["w_up"], fp8, 0)
    return h + mm("rlf,fd->rld", jax.nn.silu(g) * u, w["w_down"], fp8, 0)


# ---------------------------------------------------------------------------
# operations and bytes


def layer_matmul_flops(m: Dict) -> float:
    """Multiply-add flops of one token through one layer's projections."""
    d, ff = m["d_model"], m["d_ff"]
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return 2.0 * (d * H * hd + 2 * d * KVH * hd + H * hd * d + 3 * d * ff)


def attn_flops(m: Dict, keys: float) -> float:
    """One query of one layer against ``keys`` keys (scores and values)."""
    return 4.0 * m["n_heads"] * m["head_dim"] * keys


def tokens_flops(m: Dict, first_pos: int, n: int, logits: bool) -> float:
    """``n`` consecutive tokens at positions ``first_pos ..``, each
    attending to every key up to itself, through all layers (and the
    output head when ``logits``)."""
    if n <= 0:
        return 0.0
    keys = n * first_pos + n * (n + 1) / 2.0      # sum of (pos + 1)
    per = m["n_layers"] * (n * layer_matmul_flops(m) + attn_flops(m, keys))
    if logits:
        per += n * 2.0 * m["d_model"] * m["vocab_size"]
    return per


def attn_call(m: Dict, rows: Iterable[Tuple[int, int]],
              block: int) -> Tuple[float, float]:
    """(flops, bytes) of one layer's ragged paged-attention call.

    ``rows`` holds ``(queries, keys)`` per live slot: ``queries`` new
    positions attending to ``keys`` cached positions.  Bytes are the slot's
    live K and V blocks, their position rows, and the queries in and
    outputs out, all at the served bf16 width.
    """
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    flops = nbytes = 0.0
    for q, k in rows:
        blocks = math.ceil(k / block)
        flops += 4.0 * H * hd * q * k
        nbytes += blocks * block * (2 * KVH * hd * BF16 + 4)
        nbytes += 2 * q * H * hd * BF16
    return flops, nbytes
