"""Decoder-only transformer covering the dense / MoE / MLA / VLM-backbone
families (yi-9b, yi-34b, qwen3-8b, internlm2-1.8b, qwen3-moe-30b-a3b,
deepseek-v2-236b, paligemma-3b) plus the paper's own OPT pair.

Three entry points, all pure:
  ``forward``      full-sequence causal forward (training / scoring)
  ``prefill``      full-sequence forward that also populates the KV cache
  ``decode_step``  incremental forward of T new tokens against the cache
                   (T = 1 for plain decode, T = s+1 for speculative verify)

The KV cache is a ring buffer indexed by absolute position modulo cache
length, with a per-row absolute-position array driving the attention mask
(DESIGN §3); rollback after a rejected speculation is a pure length update.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, pad_vocab
from repro.kernels.ops import spec_verify_attn
from repro.kernels.paged import paged_verify_attn
from repro.models import common as cm
from repro.models.common import ParamDef
from repro.models.moe import moe_defs, moe_forward
from repro.runtime.meshctx import shard

Params = Any


def _quant_rows(x: jax.Array):
    """Symmetric int8 per-(row, kv-head) quantization. x: [B,T,KVH,hd]."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0   # [B,T,KVH]
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _dequant(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


class DecoderLM:
    """Functional decoder-only LM; construct once per config, methods are pure."""

    def __init__(self, cfg: ModelConfig):
        assert cfg.attn is not None, "DecoderLM needs an attention config"
        self.cfg = cfg
        self.padded_vocab = pad_vocab(cfg.vocab_size)

    # ------------------------------------------------------------------
    # parameters

    def param_defs(self) -> Dict:
        c, a = self.cfg, self.cfg.attn
        d, hd = c.d_model, a.head_dim
        H, KVH = a.n_heads, a.n_kv_heads
        defs: Dict[str, Any] = {
            "embed": ParamDef((self.padded_vocab, d), ("vocab", "d_model"), scale=0.02),
            "final_norm": ParamDef((d,), ("d_model",), init="ones"),
        }
        if not c.tie_embeddings:
            defs["unembed"] = ParamDef((self.padded_vocab, d), ("vocab", "d_model"), scale=0.02)
        layer: Dict[str, Any] = {
            "attn_norm": ParamDef((d,), ("d_model",), init="ones", stacked=True),
            "mlp_norm": ParamDef((d,), ("d_model",), init="ones", stacked=True),
        }
        if a.kind == "mla":
            rd, lr, vd = a.rope_head_dim, a.kv_lora_rank, a.vdim
            if a.q_lora_rank:
                layer["wq_a"] = ParamDef((d, a.q_lora_rank), ("d_model", None), stacked=True)
                layer["q_norm"] = ParamDef((a.q_lora_rank,), (None,), init="ones", stacked=True)
                layer["wq_b"] = ParamDef((a.q_lora_rank, H, hd + rd), (None, "heads", None), stacked=True)
            else:
                layer["wq"] = ParamDef((d, H, hd + rd), ("d_model", "heads", None), stacked=True)
            layer["w_dkv"] = ParamDef((d, lr), ("d_model", "lora"), stacked=True)
            layer["kv_norm"] = ParamDef((lr,), ("lora",), init="ones", stacked=True)
            layer["w_krope"] = ParamDef((d, rd), ("d_model", "rope_dim"), stacked=True)
            layer["w_uk"] = ParamDef((lr, H, hd), ("lora", "heads", None), stacked=True)
            layer["w_uv"] = ParamDef((lr, H, vd), ("lora", "heads", None), stacked=True)
            layer["wo"] = ParamDef((H, vd, d), ("heads", None, "d_model"), stacked=True)
        else:
            layer["wq"] = ParamDef((d, H, hd), ("d_model", "heads", None), stacked=True)
            layer["wk"] = ParamDef((d, KVH, hd), ("d_model", "kv_heads", "head_dim"), stacked=True)
            layer["wv"] = ParamDef((d, KVH, hd), ("d_model", "kv_heads", "head_dim"), stacked=True)
            layer["wo"] = ParamDef((H, hd, d), ("heads", None, "d_model"), stacked=True)
            if a.qk_norm:
                layer["q_norm"] = ParamDef((hd,), (None,), init="ones", stacked=True)
                layer["k_norm"] = ParamDef((hd,), (None,), init="ones", stacked=True)
        if c.moe is not None:
            layer["moe"] = moe_defs(c)
        else:
            layer["w_gate"] = ParamDef((d, c.d_ff), ("d_model", "ffn"), stacked=True)
            layer["w_up"] = ParamDef((d, c.d_ff), ("d_model", "ffn"), stacked=True)
            layer["w_down"] = ParamDef((c.d_ff, d), ("ffn", "d_model"), stacked=True)
        defs["layers"] = layer
        return defs

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return cm.init_params(self.param_defs(), key, self.cfg.n_layers, dtype)

    def shapes(self, dtype=jnp.bfloat16) -> Params:
        return cm.param_shapes(self.param_defs(), self.cfg.n_layers, dtype)

    def specs(self, rules: Dict[str, Optional[str]]) -> Params:
        return cm.param_specs(self.param_defs(), rules)

    # ------------------------------------------------------------------
    # KV cache

    def init_cache(self, batch: int, cache_len: int, dtype=jnp.float32) -> Dict:
        c, a = self.cfg, self.cfg.attn
        nL = c.n_layers
        if a.kind == "mla":
            return {
                "ckv": jnp.zeros((nL, batch, cache_len, a.kv_lora_rank), dtype),
                "krope": jnp.zeros((nL, batch, cache_len, a.rope_head_dim), dtype),
                "pos": jnp.full((batch, cache_len), -1, jnp.int32),
            }
        if c.kv_quant:
            return {
                "k": jnp.zeros((nL, batch, cache_len, a.n_kv_heads, a.head_dim), jnp.int8),
                "v": jnp.zeros((nL, batch, cache_len, a.n_kv_heads, a.head_dim), jnp.int8),
                "k_scale": jnp.zeros((nL, batch, cache_len, a.n_kv_heads), dtype),
                "v_scale": jnp.zeros((nL, batch, cache_len, a.n_kv_heads), dtype),
                "pos": jnp.full((batch, cache_len), -1, jnp.int32),
            }
        return {
            "k": jnp.zeros((nL, batch, cache_len, a.n_kv_heads, a.head_dim), dtype),
            "v": jnp.zeros((nL, batch, cache_len, a.n_kv_heads, a.head_dim), dtype),
            "pos": jnp.full((batch, cache_len), -1, jnp.int32),
        }

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=jnp.float32) -> Dict:
        """Paged KV pool shared by every slot (vLLM-style; DESIGN in
        core/spec_decode.py).  Rows live in fixed-size blocks addressed
        through per-slot block tables (the ``bt`` entry is added by
        :meth:`~repro.core.spec_decode.SpecDecodeEngine.init_slots`):

            k/v : [nL, num_blocks, block_size, KVH, hd]
            pos : [num_blocks, block_size]  absolute position, -1 unwritten
        """
        c, a = self.cfg, self.cfg.attn
        if a.kind == "mla":
            raise NotImplementedError(
                "paged KV does not support MLA's compressed cache yet")
        nL = c.n_layers
        if c.kv_quant:
            # int8 block pool with per-(row, kv-head) dequant scales: both
            # the fused kernel (VMEM dequant after a 1 B/elem stream) and
            # the gather reference consume them (kernels/paged.py)
            return {
                "k": jnp.zeros((nL, num_blocks, block_size, a.n_kv_heads,
                                a.head_dim), jnp.int8),
                "v": jnp.zeros((nL, num_blocks, block_size, a.n_kv_heads,
                                a.head_dim), jnp.int8),
                "k_scale": jnp.zeros((nL, num_blocks, block_size,
                                      a.n_kv_heads), dtype),
                "v_scale": jnp.zeros((nL, num_blocks, block_size,
                                      a.n_kv_heads), dtype),
                "pos": jnp.full((num_blocks, block_size), -1, jnp.int32),
            }
        return {
            "k": jnp.zeros((nL, num_blocks, block_size, a.n_kv_heads,
                            a.head_dim), dtype),
            "v": jnp.zeros((nL, num_blocks, block_size, a.n_kv_heads,
                            a.head_dim), dtype),
            "pos": jnp.full((num_blocks, block_size), -1, jnp.int32),
        }

    def cache_shapes(self, batch: int, cache_len: int, dtype=jnp.bfloat16) -> Dict:
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            jax.eval_shape(lambda: self.init_cache(batch, cache_len, dtype)))

    def cache_specs(self, rules: Dict[str, Optional[str]],
                    batch_axis="data", seq_axis=None) -> Dict:
        a = self.cfg.attn
        if a.kind == "mla":
            return {
                "ckv": P(None, batch_axis, seq_axis, rules.get("lora")),
                "krope": P(None, batch_axis, seq_axis, rules.get("rope_dim")),
                "pos": P(batch_axis, seq_axis),
            }
        specs = {
            "k": P(None, batch_axis, seq_axis, rules.get("kv_heads"), rules.get("head_dim")),
            "v": P(None, batch_axis, seq_axis, rules.get("kv_heads"), rules.get("head_dim")),
            "pos": P(batch_axis, seq_axis),
        }
        if self.cfg.kv_quant:
            specs["k_scale"] = P(None, batch_axis, seq_axis, rules.get("kv_heads"))
            specs["v_scale"] = P(None, batch_axis, seq_axis, rules.get("kv_heads"))
        return specs

    # ------------------------------------------------------------------
    # attention blocks

    def _qkv_gqa(self, lp: Dict, x: jax.Array, positions: jax.Array):
        """x: [B,T,d] -> q,k,v with RoPE applied. positions: [B,T]."""
        a = self.cfg.attn
        q = jnp.einsum("btd,dhk->bthk", x, lp["wq"])
        k = jnp.einsum("btd,dhk->bthk", x, lp["wk"])
        v = jnp.einsum("btd,dhk->bthk", x, lp["wv"])
        if a.qk_norm:
            q = cm.rms_norm(q, lp["q_norm"], self.cfg.norm_eps)
            k = cm.rms_norm(k, lp["k_norm"], self.cfg.norm_eps)
        q = cm.apply_rope(q, positions, a.rope_theta)
        k = cm.apply_rope(k, positions, a.rope_theta)
        return q, k, v

    def _attn_full(self, lp: Dict, x: jax.Array, positions: jax.Array,
                   prefix_len: int, train: bool = False) -> jax.Array:
        """Full-sequence self attention.  ``train=True`` uses the q-block
        rematerializing attention (differentiable at 4k-32k without storing
        per-pair residuals); inference prefill keeps the causal-FLOPs-optimal
        tri variant."""
        c, a = self.cfg, self.cfg.attn
        attn = cm.flash_attention_train if train else cm.flash_attention_tri
        if a.kind == "mla":
            q_nope, q_rope, ckv, krope = self._mla_proj(lp, x, positions)
            k_nope = jnp.einsum("btl,lhk->bthk", ckv, lp["w_uk"])
            vv = jnp.einsum("btl,lhv->bthv", ckv, lp["w_uv"])
            H = a.n_heads
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(krope[:, :, None], (*k_nope.shape[:3], a.rope_head_dim))],
                axis=-1)
            scale = 1.0 / math.sqrt(a.head_dim + a.rope_head_dim)
            out = attn(q, k, vv, positions, positions,
                       window=a.window, prefix_len=prefix_len, scale=scale)
            return jnp.einsum("bthv,hvd->btd", out, lp["wo"])
        q, k, v = self._qkv_gqa(lp, x, positions)
        out = attn(q, k, v, positions, positions,
                   window=a.window, prefix_len=prefix_len)
        return jnp.einsum("bthk,hkd->btd", out, lp["wo"])

    def _mla_proj(self, lp: Dict, x: jax.Array, positions: jax.Array):
        a, eps = self.cfg.attn, self.cfg.norm_eps
        if a.q_lora_rank:
            qa = cm.rms_norm(jnp.einsum("btd,dr->btr", x, lp["wq_a"]), lp["q_norm"], eps)
            q = jnp.einsum("btr,rhk->bthk", qa, lp["wq_b"])
        else:
            q = jnp.einsum("btd,dhk->bthk", x, lp["wq"])
        q_nope, q_rope = q[..., :a.head_dim], q[..., a.head_dim:]
        q_rope = cm.apply_rope(q_rope, positions, a.rope_theta)
        ckv = cm.rms_norm(jnp.einsum("btd,dl->btl", x, lp["w_dkv"]), lp["kv_norm"], eps)
        krope = jnp.einsum("btd,dr->btr", x, lp["w_krope"])
        krope = cm.apply_rope(krope[:, :, None, :], positions, a.rope_theta)[:, :, 0, :]
        return q_nope, q_rope, ckv, krope

    def _attn_decode(self, lp: Dict, x: jax.Array, positions: jax.Array,
                     layer_cache: Dict, pos_arr: jax.Array, rows: jax.Array,
                     prefix_len: int, rows_limit: Optional[int] = None,
                     ) -> Tuple[jax.Array, Dict]:
        """Incremental attention: write new KV at ``rows`` then attend.

        x: [B,T,d]; positions: [B,T] absolute; rows: [B,T] ring-buffer rows;
        pos_arr: [B,L] updated row->abs-position map (already includes the
        new writes).  Returns (attn_out [B,T,d], updated layer cache).

        ``rows_limit`` (static) bounds the *attended* key rows to the first
        ``rows_limit`` of the cache — callers that know every visible key
        lives below a row bound (chunked prefill: rows < prefix + chunk)
        skip streaming the dead tail.  Rows beyond the bound are unwritten
        or stale-wiped (position -1, never attendable), so the bound is
        numerically free; writes still land in the full cache.
        """
        c, a = self.cfg, self.cfg.attn
        B, T, _ = x.shape
        bidx = jnp.arange(B)[:, None]
        R = rows_limit if rows_limit is not None else pos_arr.shape[1]
        if a.kind == "mla":
            q_nope, q_rope, ckv_new, krope_new = self._mla_proj(lp, x, positions)
            ckv = layer_cache["ckv"].at[bidx, rows].set(
                ckv_new.astype(layer_cache["ckv"].dtype), mode="drop")
            krope = layer_cache["krope"].at[bidx, rows].set(
                krope_new.astype(layer_cache["krope"].dtype), mode="drop")
            # absorbed attention: score via compressed cache
            q_abs = jnp.einsum("bthk,lhk->bthl", q_nope, lp["w_uk"])
            s1 = jnp.einsum("bthl,bsl->bhts", q_abs, ckv[:, :R])
            s2 = jnp.einsum("bthr,bsr->bhts", q_rope, krope[:, :R])
            scale = 1.0 / math.sqrt(a.head_dim + a.rope_head_dim)
            scores = (s1 + s2).astype(jnp.float32) * scale
            mask = cm.position_mask(positions, pos_arr[:, :R], a.window,
                                    prefix_len)
            scores = jnp.where(mask[:, None], scores, -1e30)
            p = jax.nn.softmax(scores, axis=-1)
            o_lora = jnp.einsum("bhts,bsl->bthl", p.astype(ckv.dtype),
                                ckv[:, :R])
            out = jnp.einsum("bthl,lhv->bthv", o_lora, lp["w_uv"])
            out = jnp.einsum("bthv,hvd->btd", out, lp["wo"])
            return out, {"ckv": ckv, "krope": krope}
        q, k_new, v_new = self._qkv_gqa(lp, x, positions)
        if c.kv_quant:
            kq, ks = _quant_rows(k_new)
            vq, vs = _quant_rows(v_new)
            new_lcache = {
                "k": layer_cache["k"].at[bidx, rows].set(kq, mode="drop"),
                "v": layer_cache["v"].at[bidx, rows].set(vq, mode="drop"),
                "k_scale": layer_cache["k_scale"].at[bidx, rows].set(
                    ks.astype(layer_cache["k_scale"].dtype), mode="drop"),
                "v_scale": layer_cache["v_scale"].at[bidx, rows].set(
                    vs.astype(layer_cache["v_scale"].dtype), mode="drop"),
            }
            # int8 tiles + scales go straight into the kernel wrapper: the
            # TPU kernel streams 1 B/elem and dequantizes in VMEM, the CPU
            # reference dequantizes up front (same numerics)
            out = spec_verify_attn(q, new_lcache["k"][:, :R],
                                   new_lcache["v"][:, :R],
                                   positions, pos_arr[:, :R], window=a.window,
                                   prefix_len=prefix_len,
                                   k_scale=new_lcache["k_scale"][:, :R],
                                   v_scale=new_lcache["v_scale"][:, :R])
            out = jnp.einsum("bthk,hkd->btd", out, lp["wo"])
            return out, new_lcache
        k = layer_cache["k"].at[bidx, rows].set(
            k_new.astype(layer_cache["k"].dtype), mode="drop")
        v = layer_cache["v"].at[bidx, rows].set(
            v_new.astype(layer_cache["v"].dtype), mode="drop")
        new_lcache = {"k": k, "v": v}
        # verify-step attention: s+1 tiny q rows vs the ragged ring-buffer
        # cache — the paper's hot spot (Pallas spec_verify_attn on TPU,
        # reference path on CPU; identical masking semantics)
        out = spec_verify_attn(q, k[:, :R], v[:, :R], positions,
                               pos_arr[:, :R],
                               window=a.window, prefix_len=prefix_len)
        out = jnp.einsum("bthk,hkd->btd", out, lp["wo"])
        return out, new_lcache

    def _attn_paged(self, lp: Dict, x: jax.Array, positions: jax.Array,
                    pool: Dict, layer: jax.Array, pos_arr: jax.Array,
                    pb: jax.Array, off: jax.Array, bt: jax.Array,
                    prefix_len: int, cu_blocks: Optional[jax.Array] = None,
                    ) -> Tuple[jax.Array, Dict]:
        """Paged incremental attention for layer ``layer`` of the whole
        pool (``[nL, NB, bs, ...]`` leaves, carried through the layer scan
        by :meth:`_paged_forward`): scatter this step's KV rows in place at
        ``(layer, pb, off)`` (``pb``/``off`` physical addresses through the
        block table, out-of-range => dropped write), then attend via
        :func:`~repro.kernels.paged.paged_verify_attn` — the fused streaming
        kernel, reading ``(layer, block)`` tiles, or the gather reference
        per ``cfg.paged_fused``.  No slice of the layer is ever taken, so
        the donated pool is updated where it lies.  ``cu_blocks [B + 1]``
        (host-computed cumulative grid-step counts) upgrades the fused path
        to the ragged grid — steps = sum of live blocks instead of
        ``B * MAXB``.  Returns (attn_out, updated pool).
        """
        c, a = self.cfg, self.cfg.attn
        q, k_new, v_new = self._qkv_gqa(lp, x, positions)
        if c.kv_quant:
            kq, ks = _quant_rows(k_new)
            vq, vs = _quant_rows(v_new)
            rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        else:
            rows = {"k": k_new, "v": v_new}
        pool = {n: pool[n].at[layer, pb, off].set(
                    r.astype(pool[n].dtype), mode="drop")
                for n, r in rows.items()}
        out = paged_verify_attn(
            q, pool["k"], pool["v"], positions, pos_arr, bt, layer,
            window=a.window, prefix_len=prefix_len,
            k_scale=pool.get("k_scale"), v_scale=pool.get("v_scale"),
            use_pallas=c.paged_fused, cu_blocks=cu_blocks)
        return jnp.einsum("bthk,hkd->btd", out, lp["wo"]), pool

    def _paged_forward(self, params: Params, x: jax.Array, cache: Dict,
                       positions: jax.Array, pos_arr: jax.Array,
                       pb: jax.Array, off: jax.Array, bt: jax.Array,
                       cu_blocks: Optional[jax.Array]) -> Tuple[jax.Array, Dict]:
        """The layers and output head of the three paged forwards, from the
        embedded ``x`` and the step's write addresses.  The pool leaves
        (``k``/``v``, plus ``k_scale``/``v_scale`` for int8) ride the layer
        scan's carry, not its ``xs``/``ys``: each layer writes its rows in
        place and the kernel reads the layer through its index, so the scan
        never slices a layer out of the pool nor stacks one back.  ``bt`` is
        the table attention reads through; the returned cache keeps
        ``cache["bt"]``.  Returns (logits, updated cache)."""
        c = self.cfg
        prefix_len = c.prefix_len if c.bidirectional_prefix else 0

        def body(carry, xs):
            h, pool = carry
            lp, li = xs
            hn = cm.rms_norm(h, lp["attn_norm"], c.norm_eps)
            a_out, pool = self._attn_paged(lp, hn, positions, pool, li,
                                           pos_arr, pb, off, bt, prefix_len,
                                           cu_blocks)
            h = h + shard(a_out, "data", None, None)
            m_out, _ = self._mlp(lp, cm.rms_norm(h, lp["mlp_norm"], c.norm_eps))
            h = h + shard(m_out, "data", None, None)
            return (h, pool), None

        pool = {k: v for k, v in cache.items() if k not in ("pos", "bt")}
        layers = jnp.arange(c.n_layers, dtype=jnp.int32)
        (x, pool), _ = jax.lax.scan(body, (x, pool),
                                    (params["layers"], layers))
        x = cm.rms_norm(x, params["final_norm"], c.norm_eps)
        table = params["embed"] if c.tie_embeddings else params["unembed"]
        logits = cm.unembed(x, table, c.vocab_size)
        return logits, dict(pool, pos=pos_arr, bt=cache["bt"])

    # ------------------------------------------------------------------
    # MLP

    def _mlp(self, lp: Dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """Returns (out, aux_loss)."""
        if self.cfg.moe is not None:
            return moe_forward(self.cfg, lp["moe"], x)
        return cm.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), jnp.zeros((), jnp.float32)

    # ------------------------------------------------------------------
    # full-sequence forward (training / scoring)

    def forward(self, params: Params, tokens: jax.Array,
                prefix_embeds: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array]:
        """tokens: [B, T] -> (logits [B, P+T, V], moe_aux_loss scalar)."""
        c = self.cfg
        x = cm.embed(tokens, params["embed"])
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        B, T, _ = x.shape
        # sequence-parallel residual stream: tokens sharded over 'model'
        # between layers, so per-device activations (and the remat residuals
        # the layer scan carries) shrink by the model-axis size
        x = shard(x, "data", "model", None)
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        prefix_len = c.prefix_len if (prefix_embeds is not None and c.bidirectional_prefix) else 0

        @partial(jax.checkpoint, static_argnums=())   # remat per layer
        def layer(carry, lp):
            h, aux = carry
            a_out = self._attn_full(lp, cm.rms_norm(h, lp["attn_norm"], c.norm_eps),
                                    positions, prefix_len, train=True)
            h = h + shard(a_out, "data", "model", None)
            m_out, l_aux = self._mlp(lp, cm.rms_norm(h, lp["mlp_norm"], c.norm_eps))
            h = h + shard(m_out, "data", "model", None)
            return (h, aux + l_aux), None

        (x, aux), _ = jax.lax.scan(layer, (x, jnp.zeros((), jnp.float32)), params["layers"])
        x = cm.rms_norm(x, params["final_norm"], c.norm_eps)
        table = params["embed"] if c.tie_embeddings else params["unembed"]
        return cm.unembed(x, table, c.vocab_size), aux

    # ------------------------------------------------------------------
    # prefill: forward + cache population

    def prefill(self, params: Params, tokens: jax.Array, cache: Dict,
                prompt_lens: Optional[jax.Array] = None,
                prefix_embeds: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Dict, jax.Array]:
        """Right-padded prompts [B, Tp] -> (last-token logits [B, V],
        populated cache, seq_lens [B])."""
        c = self.cfg
        x = cm.embed(tokens, params["embed"])
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        B, T, _ = x.shape
        x = shard(x, "data", None, None)
        L = cache["pos"].shape[1]
        if prompt_lens is None:
            prompt_lens = jnp.full((B,), tokens.shape[1], jnp.int32)
        total_lens = prompt_lens + (c.prefix_len if prefix_embeds is not None else 0)
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        valid = positions < total_lens[:, None]
        rows = positions % L
        pos_arr = cache["pos"].at[jnp.arange(B)[:, None], rows].set(
            jnp.where(valid, positions, -1), mode="drop")
        prefix_len = c.prefix_len if (prefix_embeds is not None and c.bidirectional_prefix) else 0

        def layer(carry, xs):
            h = carry
            lp, lcache = xs
            hn = cm.rms_norm(h, lp["attn_norm"], c.norm_eps)
            # full-sequence attention for compute; also write KV rows to cache
            a_out, new_lcache = self._attn_decode(lp, hn, positions, lcache,
                                                  pos_arr, rows, prefix_len)
            h = h + shard(a_out, "data", None, None)
            m_out, _ = self._mlp(lp, cm.rms_norm(h, lp["mlp_norm"], c.norm_eps))
            h = h + shard(m_out, "data", None, None)
            return h, new_lcache

        layer_caches = {k: v for k, v in cache.items() if k != "pos"}
        x, new_caches = jax.lax.scan(layer, x, (params["layers"], layer_caches))
        x = cm.rms_norm(x, params["final_norm"], c.norm_eps)
        last = jnp.take_along_axis(x, (total_lens - 1)[:, None, None], axis=1)[:, 0]
        table = params["embed"] if c.tie_embeddings else params["unembed"]
        logits = cm.unembed(last, table, c.vocab_size)
        new_cache = dict(new_caches, pos=pos_arr)
        return logits, new_cache, total_lens

    # prefill uses the decode (materialized-score) attention path per layer,
    # which is O(T·L) memory; for the 32k prefill dry-run we use
    # ``prefill_flash`` below which runs flash attention and then writes KV.

    def prefill_flash(self, params: Params, tokens: jax.Array, cache: Dict,
                      prompt_lens: Optional[jax.Array] = None,
                      prefix_embeds: Optional[jax.Array] = None,
                      ) -> Tuple[jax.Array, Dict, jax.Array]:
        """Prefill with flash attention (memory-bounded at long context).

        Semantics match :meth:`prefill`; the KV rows are produced by the same
        projections, attention runs blockwise, and the cache is written once.
        """
        c, a = self.cfg, self.cfg.attn
        x = cm.embed(tokens, params["embed"])
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
        B, T, _ = x.shape
        x = shard(x, "data", None, None)
        L = cache["pos"].shape[1]
        if prompt_lens is None:
            prompt_lens = jnp.full((B,), tokens.shape[1], jnp.int32)
        total_lens = prompt_lens + (c.prefix_len if prefix_embeds is not None else 0)
        positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        valid = positions < total_lens[:, None]
        qk_pos = jnp.where(valid, positions, -1)  # padded rows never attended
        rows = positions % L
        pos_arr = cache["pos"].at[jnp.arange(B)[:, None], rows].set(qk_pos, mode="drop")
        prefix_len = c.prefix_len if (prefix_embeds is not None and c.bidirectional_prefix) else 0

        def layer(carry, xs):
            h = carry
            lp, lcache = xs
            hn = cm.rms_norm(h, lp["attn_norm"], c.norm_eps)
            if a.kind == "mla":
                q_nope, q_rope, ckv_new, krope_new = self._mla_proj(lp, hn, positions)
                k_nope = jnp.einsum("btl,lhk->bthk", ckv_new, lp["w_uk"])
                vv = jnp.einsum("btl,lhv->bthv", ckv_new, lp["w_uv"])
                q = jnp.concatenate([q_nope, q_rope], axis=-1)
                k = jnp.concatenate(
                    [k_nope, jnp.broadcast_to(krope_new[:, :, None],
                                              (*k_nope.shape[:3], a.rope_head_dim))], axis=-1)
                scale = 1.0 / math.sqrt(a.head_dim + a.rope_head_dim)
                o = cm.flash_attention_tri(q, k, vv, qk_pos, qk_pos,
                                           window=a.window, prefix_len=prefix_len, scale=scale)
                a_out = jnp.einsum("bthv,hvd->btd", o, lp["wo"])
                bidx = jnp.arange(B)[:, None]
                new_lcache = {
                    "ckv": lcache["ckv"].at[bidx, rows].set(
                        ckv_new.astype(lcache["ckv"].dtype), mode="drop"),
                    "krope": lcache["krope"].at[bidx, rows].set(
                        krope_new.astype(lcache["krope"].dtype), mode="drop"),
                }
            else:
                q, k_new, v_new = self._qkv_gqa(lp, hn, positions)
                o = cm.flash_attention_tri(q, k_new, v_new, qk_pos, qk_pos,
                                           window=a.window, prefix_len=prefix_len)
                a_out = jnp.einsum("bthk,hkd->btd", o, lp["wo"])
                bidx = jnp.arange(B)[:, None]
                if c.kv_quant:
                    kq, ks = _quant_rows(k_new)
                    vq, vs = _quant_rows(v_new)
                    new_lcache = {
                        "k": lcache["k"].at[bidx, rows].set(kq, mode="drop"),
                        "v": lcache["v"].at[bidx, rows].set(vq, mode="drop"),
                        "k_scale": lcache["k_scale"].at[bidx, rows].set(
                            ks.astype(lcache["k_scale"].dtype), mode="drop"),
                        "v_scale": lcache["v_scale"].at[bidx, rows].set(
                            vs.astype(lcache["v_scale"].dtype), mode="drop"),
                    }
                else:
                    new_lcache = {
                        "k": lcache["k"].at[bidx, rows].set(
                            k_new.astype(lcache["k"].dtype), mode="drop"),
                        "v": lcache["v"].at[bidx, rows].set(
                            v_new.astype(lcache["v"].dtype), mode="drop"),
                    }
            h = h + shard(a_out, "data", None, None)
            m_out, _ = self._mlp(lp, cm.rms_norm(h, lp["mlp_norm"], c.norm_eps))
            h = h + shard(m_out, "data", None, None)
            return h, new_lcache

        layer_caches = {k: v for k, v in cache.items() if k != "pos"}
        x, new_caches = jax.lax.scan(layer, x, (params["layers"], layer_caches))
        x = cm.rms_norm(x, params["final_norm"], c.norm_eps)
        last = jnp.take_along_axis(x, (total_lens - 1)[:, None, None], axis=1)[:, 0]
        table = params["embed"] if c.tie_embeddings else params["unembed"]
        return cm.unembed(last, table, c.vocab_size), dict(new_caches, pos=pos_arr), total_lens

    # ------------------------------------------------------------------
    # incremental decode

    def decode_step(self, params: Params, tokens: jax.Array, cache: Dict,
                    seq_lens: jax.Array,
                    cu_blocks: Optional[jax.Array] = None,
                    ) -> Tuple[jax.Array, Dict]:
        """tokens: [B, T] the last committed token followed by T-1 drafts;
        they occupy absolute positions (seq_lens-1) ... (seq_lens+T-2).
        Returns (logits [B, T, V], updated cache).

        A cache with a ``bt`` (block table) entry is a paged pool (see
        :meth:`init_paged_cache`) and takes the paged path — block-table
        scatter writes plus the fused streaming kernel or gather reference
        per ``cfg.paged_fused`` (kernels/paged.py); otherwise the per-row
        ring-buffer path below runs unchanged.  ``cu_blocks [B + 1]``
        (host cumulative grid-step counts; paged + fused only) selects the
        ragged grid — see :func:`~repro.kernels.paged.paged_verify_attn`.
        """
        if "bt" in cache:
            return self._decode_step_paged(params, tokens, cache, seq_lens,
                                           cu_blocks)
        c = self.cfg
        B, T = tokens.shape
        L = cache["pos"].shape[1]
        x = cm.embed(tokens, params["embed"])
        x = shard(x, "data", None, None)
        positions = (seq_lens - 1)[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        rows = positions % L
        pos_arr = cache["pos"].at[jnp.arange(B)[:, None], rows].set(positions, mode="drop")
        prefix_len = c.prefix_len if c.bidirectional_prefix else 0

        def layer(carry, xs):
            h = carry
            lp, lcache = xs
            hn = cm.rms_norm(h, lp["attn_norm"], c.norm_eps)
            a_out, new_lcache = self._attn_decode(lp, hn, positions, lcache,
                                                  pos_arr, rows, prefix_len)
            h = h + shard(a_out, "data", None, None)
            m_out, _ = self._mlp(lp, cm.rms_norm(h, lp["mlp_norm"], c.norm_eps))
            h = h + shard(m_out, "data", None, None)
            return h, new_lcache

        layer_caches = {k: v for k, v in cache.items() if k != "pos"}
        x, new_caches = jax.lax.scan(layer, x, (params["layers"], layer_caches))
        x = cm.rms_norm(x, params["final_norm"], c.norm_eps)
        table = params["embed"] if c.tie_embeddings else params["unembed"]
        logits = cm.unembed(x, table, c.vocab_size)
        return logits, dict(new_caches, pos=pos_arr)

    def _decode_step_paged(self, params: Params, tokens: jax.Array,
                           cache: Dict, seq_lens: jax.Array,
                           cu_blocks: Optional[jax.Array] = None,
                           ) -> Tuple[jax.Array, Dict]:
        """Incremental decode against the paged KV pool.

        Token at absolute position p of slot b lives at physical row
        (bt[b, p // block_size], p % block_size).  Slots whose table has no
        block for a write position (empty or retired slots, bt = -1) have
        their writes dropped; their reads surface key position -1 and are
        masked out, so the same compiled step serves every occupancy level —
        exactly the contiguous slot-pool contract.
        """
        B, T = tokens.shape
        NB, bs = cache["pos"].shape
        bt = cache["bt"]                                        # [B, MAXB]
        x = cm.embed(tokens, params["embed"])
        x = shard(x, "data", None, None)
        positions = (seq_lens - 1)[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        blk = jnp.clip(positions // bs, 0, bt.shape[1] - 1)
        off = positions % bs
        pb = jnp.take_along_axis(bt, blk, axis=1)               # [B, T]
        pb = jnp.where(pb < 0, NB, pb)                          # NB => dropped
        pos_arr = cache["pos"].at[pb, off].set(positions, mode="drop")
        return self._paged_forward(params, x, cache, positions, pos_arr,
                                   pb, off, bt, cu_blocks)

    def decode_step_mixed(self, params: Params, tokens: jax.Array,
                          cache: Dict, seq_lens: jax.Array,
                          chunk_slot: jax.Array, chunk_tokens: jax.Array,
                          chunk_start: jax.Array, chunk_limit: jax.Array,
                          chunk_bt_row: jax.Array, verify_len: int,
                          cu_blocks: Optional[jax.Array] = None,
                          ) -> Tuple[jax.Array, Dict]:
        """One mixed verify+chunk launch against the paged pool.

        Row ``chunk_slot`` of the batch carries a chunk-prefill prefix
        extension (``chunk_tokens`` at absolute positions ``chunk_start ..
        chunk_limit - 1``, reading/writing through ``chunk_bt_row`` — the
        slot's host table row, which on device is still all ``-1`` while
        the slot is parked PREFILLING); every other row carries its usual
        verify feed (first ``verify_len`` columns; the rest is padding
        with position ``-1``, matching nothing and writing nowhere).  Both
        query kinds ride one ragged kernel call per layer — per-query-row
        masking plus per-row block tables make the kernel agnostic to
        which row is which, so a separate chunk launch (and its grid,
        weight re-streaming, and dispatch) disappears.

        The returned cache keeps the *original* device ``bt`` — the
        pending slot's table row stays ``-1`` until its final chunk
        commits, exactly like the standalone chunk forward.  Logits for
        the chunk row are meaningless (the engine's accept mask already
        zeroes pending slots); callers slice ``[:, :verify_len]``.
        """
        B, T = tokens.shape
        NB, bs = cache["pos"].shape
        bt = cache["bt"]                                        # [B, MAXB]
        bt_eff = bt.at[chunk_slot].set(chunk_bt_row)
        toks = tokens.at[chunk_slot].set(chunk_tokens)
        x = cm.embed(toks, params["embed"])
        x = shard(x, "data", None, None)
        col = jnp.arange(T, dtype=jnp.int32)
        positions = jnp.where(col[None] < verify_len,
                              (seq_lens - 1)[:, None] + col[None], -1)
        cpos = chunk_start + col
        positions = positions.at[chunk_slot].set(
            jnp.where(cpos < chunk_limit, cpos, -1))
        valid = positions >= 0
        blk = jnp.clip(positions // bs, 0, bt.shape[1] - 1)
        off = positions % bs
        pb = jnp.take_along_axis(bt_eff, blk, axis=1)           # [B, T]
        pb = jnp.where((pb < 0) | ~valid, NB, pb)               # NB => dropped
        pos_arr = cache["pos"].at[pb, off].set(
            jnp.where(valid, positions, -1), mode="drop")
        return self._paged_forward(params, x, cache, positions, pos_arr,
                                   pb, off, bt_eff, cu_blocks)

    # ------------------------------------------------------------------
    # chunked prefill (prefix extension)

    def prefill_chunk(self, params: Params, tokens: jax.Array, cache: Dict,
                      offset: jax.Array, limit: jax.Array,
                      rows_limit: Optional[int] = None,
                      cu_blocks: Optional[jax.Array] = None,
                      ) -> Tuple[jax.Array, Dict]:
        """One prefill *chunk*: write ``tokens`` [B, T] at absolute positions
        ``offset .. offset+T-1``, attending over the already-written cache
        prefix plus the chunk itself (Sarathi-style chunked prefill).

        Positions at or beyond ``limit`` are bucket padding: their cache
        writes are routed out of bounds and dropped, so a ragged final chunk
        never clobbers live rows (including the ring-wrap case where the
        padded tail would alias row 0).  Attention reuses the verify-step
        position masking unchanged — a chunk query at position p sees exactly
        the keys with position <= p, which is what makes the chunked cache
        bit-compatible with a whole-prompt prefill.

        Returns (logits [B, T, V], updated cache); callers that only extend
        the cache can discard the logits (XLA dead-code-eliminates the
        unembed under jit).

        ``rows_limit`` (static) bounds the attended cache rows: during
        chunked prefill every visible key lives at a row below
        ``offset + T`` (positions equal rows until the first wrap, and
        chunks never wrap), so the engine passes a power-of-two bucket of
        it and the attention stops streaming the dead tail of the logical
        cache.  ``cu_blocks`` selects the ragged grid on the paged path.
        """
        if "bt" in cache:
            return self._prefill_chunk_paged(params, tokens, cache, offset,
                                             limit, cu_blocks)
        c = self.cfg
        B, T = tokens.shape
        L = cache["pos"].shape[1]
        x = cm.embed(tokens, params["embed"])
        x = shard(x, "data", None, None)
        positions = offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        valid = positions < limit[:, None]
        rows = jnp.where(valid, positions % L, L)       # L => dropped write
        pos_arr = cache["pos"].at[jnp.arange(B)[:, None], rows].set(
            jnp.where(valid, positions, -1), mode="drop")
        prefix_len = c.prefix_len if c.bidirectional_prefix else 0

        def layer(carry, xs):
            h = carry
            lp, lcache = xs
            hn = cm.rms_norm(h, lp["attn_norm"], c.norm_eps)
            a_out, new_lcache = self._attn_decode(lp, hn, positions, lcache,
                                                  pos_arr, rows, prefix_len,
                                                  rows_limit)
            h = h + shard(a_out, "data", None, None)
            m_out, _ = self._mlp(lp, cm.rms_norm(h, lp["mlp_norm"], c.norm_eps))
            h = h + shard(m_out, "data", None, None)
            return h, new_lcache

        layer_caches = {k: v for k, v in cache.items() if k != "pos"}
        x, new_caches = jax.lax.scan(layer, x, (params["layers"], layer_caches))
        x = cm.rms_norm(x, params["final_norm"], c.norm_eps)
        table = params["embed"] if c.tie_embeddings else params["unembed"]
        return cm.unembed(x, table, c.vocab_size), dict(new_caches, pos=pos_arr)

    def _prefill_chunk_paged(self, params: Params, tokens: jax.Array,
                             cache: Dict, offset: jax.Array, limit: jax.Array,
                             cu_blocks: Optional[jax.Array] = None,
                             ) -> Tuple[jax.Array, Dict]:
        """Chunked prefill against the paged KV pool: chunk rows scatter
        block-wise through the slot's block table (padding and unallocated
        logical blocks are dropped), and attention reads the slot's prefix
        through the same table — the fused streaming kernel or the gather
        reference per ``cfg.paged_fused`` (kernels/paged.py), masking
        unchanged.  This is the fused prefix-extension chunk forward: the
        chunk's q rows stream the pool exactly like a verify step's."""
        B, T = tokens.shape
        NB, bs = cache["pos"].shape
        bt = cache["bt"]                                        # [B, MAXB]
        x = cm.embed(tokens, params["embed"])
        x = shard(x, "data", None, None)
        positions = offset[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        valid = positions < limit[:, None]
        blk = jnp.clip(positions // bs, 0, bt.shape[1] - 1)
        off = positions % bs
        pb = jnp.take_along_axis(bt, blk, axis=1)               # [B, T]
        pb = jnp.where((pb < 0) | ~valid, NB, pb)               # NB => dropped
        pos_arr = cache["pos"].at[pb, off].set(
            jnp.where(valid, positions, -1), mode="drop")
        return self._paged_forward(params, x, cache, positions, pos_arr,
                                   pb, off, bt, cu_blocks)

    @staticmethod
    def commit(cache_out: Dict, accept_idx: jax.Array) -> Dict:
        """Attention-cache rollback is a pure length update done by the engine
        (stale ring rows are overwritten before they can be attended)."""
        return cache_out
