"""Grouped-query decoder with qk-norm and a mixture-of-experts MLP: the
router's softmax picks ``top_k`` of ``n_experts`` SwiGLU experts per
token, weighted by their probabilities renormalised over the chosen; no
shared expert (the registry's qwen3-moe-30b-a3b family).  A test drops
this file into a throwaway checkout as ``bench/archs/gqa_moe.py``: a
second architecture added as new files only.

File keys: those of ``dense_gqa`` less ``d_ff``, plus ``n_experts``,
``top_k``, ``d_ff_expert`` and ``capacity_factor`` (at least
``n_experts / top_k``, so that the program drops no token).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from bench.archs import dense_gqa
from bench.reference import mm, rms, rope, round_fp8
from bench.weights import leaf, leaf_key

# the attention's call and the leaves outside the stack are dense_gqa's
attn_call = dense_gqa.attn_call
global_shapes = dense_gqa.global_shapes

LEAVES = ("embed", "unembed", "final_norm", "attn_norm", "mlp_norm", "wq",
          "wk", "wv", "wo", "q_norm", "k_norm", "moe.router", "moe.w_gate",
          "moe.w_up", "moe.w_down")


def program_config(m: Dict, name: str, dtype: str):
    from repro.configs.base import AttnConfig, ModelConfig, MoEConfig
    return ModelConfig(
        name=name, family="moe", n_layers=m["n_layers"],
        d_model=m["d_model"], d_ff=m["d_ff_expert"],
        vocab_size=m["vocab_size"],
        attn=AttnConfig(n_heads=m["n_heads"], n_kv_heads=m["n_kv_heads"],
                        head_dim=m["head_dim"], qk_norm=True,
                        rope_theta=m["rope_theta"]),
        moe=MoEConfig(n_experts=m["n_experts"], top_k=m["top_k"],
                      d_ff_expert=m["d_ff_expert"],
                      capacity_factor=m["capacity_factor"]),
        norm_eps=m["norm_eps"], dtype=dtype)


def registry_view(cfg) -> Dict:
    a, e = cfg.attn, cfg.moe
    return {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
            "vocab_size": cfg.vocab_size, "n_heads": a.n_heads,
            "n_kv_heads": a.n_kv_heads, "head_dim": a.head_dim,
            "rope_theta": a.rope_theta, "norm_eps": cfg.norm_eps,
            "n_experts": e.n_experts, "top_k": e.top_k,
            "d_ff_expert": e.d_ff_expert,
            "capacity_factor": e.capacity_factor}


def layer_weights(mkey, m: Dict, layer, dtype=jnp.bfloat16) -> Dict:
    """One layer's leaves; an expert matrix is scaled by 1/sqrt of its own
    input width, not of the stacked expert axis."""
    d, E, ff = m["d_model"], m["n_experts"], m["d_ff_expert"]
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    shapes = {"attn_norm": (d,), "mlp_norm": (d,), "wq": (d, H, hd),
              "wk": (d, KVH, hd), "wv": (d, KVH, hd), "wo": (H, hd, d),
              "q_norm": (hd,), "k_norm": (hd,)}
    w = {n: leaf(leaf_key(mkey, LEAVES, n, layer), n, shp, dtype)
         for n, shp in shapes.items()}
    experts = {"router": ((d, E), d), "w_gate": ((E, d, ff), d),
               "w_up": ((E, d, ff), d), "w_down": ((E, ff, d), ff)}
    w["moe"] = {n: (jax.random.normal(leaf_key(mkey, LEAVES, "moe." + n,
                                               layer), shp, jnp.float32)
                    / math.sqrt(fan_in)).astype(dtype)
                for n, (shp, fan_in) in experts.items()}
    return w


def layer(w, h, m, fp8):
    """One layer over ``h [R, L, d]``: every expert runs on every token and
    the unchosen ones are weighted 0."""
    R, L, _ = h.shape
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps, hi = m["norm_eps"], jax.lax.Precision.HIGHEST
    pos = jnp.arange(L)[None, :]
    x = rms(h, w["attn_norm"], eps)
    q = rms(mm("rld,dhk->rlhk", x, w["wq"], fp8, 0), w["q_norm"], eps)
    k = rms(mm("rld,dhk->rlhk", x, w["wk"], fp8, 0), w["k_norm"], eps)
    q, k = rope(q, pos, m["rope_theta"]), rope(k, pos, m["rope_theta"])
    v = mm("rld,dhk->rlhk", x, w["wv"], fp8, 0)
    qg = q.reshape(R, L, KVH, H // KVH, hd)
    if fp8:
        qg, k, v = round_fp8(qg, -1), round_fp8(k, -1), round_fp8(v, 1)
    sc = jnp.einsum("rqkgh,rskh->rkgqs", qg, k, precision=hi) / math.sqrt(hd)
    causal = jnp.arange(L)[:, None] >= jnp.arange(L)[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    if fp8:
        p = round_fp8(p, -1)
    o = jnp.einsum("rkgqs,rskh->rqkgh", p, v, precision=hi)
    h = h + mm("rlhk,hkd->rld", o.reshape(R, L, H, hd), w["wo"], fp8, (0, 1))
    x = rms(h, w["mlp_norm"], eps)
    e = w["moe"]
    probs = jax.nn.softmax(mm("rld,de->rle", x, e["router"], fp8, 0), -1)
    top_p, top_i = jax.lax.top_k(probs, m["top_k"])
    top_p = top_p / top_p.sum(-1, keepdims=True)
    gate = (top_p[..., None] * jax.nn.one_hot(top_i, m["n_experts"])).sum(-2)
    g = mm("rld,edf->rlef", x, e["w_gate"], fp8, 1)
    u = mm("rld,edf->rlef", x, e["w_up"], fp8, 1)
    y = mm("rlef,efd->rled", jax.nn.silu(g) * u, e["w_down"], fp8, 1)
    return h + jnp.einsum("rle,rled->rld", gate, y, precision=hi)


def layer_matmul_flops(m: Dict) -> float:
    """One token through one layer's projections, the router and its
    ``top_k`` experts."""
    d, ff = m["d_model"], m["d_ff_expert"]
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return 2.0 * (d * H * hd + 2 * d * KVH * hd + H * hd * d
                  + d * m["n_experts"] + m["top_k"] * 3 * d * ff)


def tokens_flops(m: Dict, first_pos: int, n: int, logits: bool) -> float:
    if n <= 0:
        return 0.0
    keys = n * first_pos + n * (n + 1) / 2.0
    per = m["n_layers"] * (n * layer_matmul_flops(m)
                          + dense_gqa.attn_flops(m, keys))
    if logits:
        per += n * 2.0 * m["d_model"] * m["vocab_size"]
    return per
