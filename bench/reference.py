"""Plain float32 reference of the served decoder and of sampled
speculative verification, independent of the program.

``hidden`` runs the published architecture (each layer is the ``layer``
of the section's module in ``bench/archs/``) in float32 at ``highest``
matmul precision, layer by layer, with each layer's weights regenerated
from the seed by the same module: nothing is taken from the program.  With
``fp8=True`` every matmul operand is rounded to float8 (e4m3, scaled per
row / per output channel) first: the control, one precision below the bf16
the configurations state.

The sampled verify step draws its noise from a key made of the sum of the
pool's ``n_generated`` before the step: ``split(PRNGKey(sum), 3)`` gives the
draft, acceptance and resample keys; the i-th draft token is
``argmax(draft_logits / T + gumbel(fold_in(k_draft, i), (B, V))[row])``,
draft ``i`` is accepted while ``u[row, i] < min(1, p/q)`` with
``u = uniform(k_acc, (B, s))``, and the token after the last accepted draft
is ``argmax(log max(resid, 1e-30) + gumbel(k_res, (B, V))[row])`` with
``resid = norm(max(p - q, 0))`` (or ``p`` when every draft was accepted).
Each served token is thus the greedy choice of logits plus known noise,
and ``decisions`` measures, for every served token, the gap by which its
perturbed reference logit lies below the best one.
"""
from __future__ import annotations

import functools
import json
import math
from dataclasses import dataclass
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import archs
from bench.weights import global_weights

E4M3_MAX = 448.0


def round_fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / E4M3_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(eq, x, w, fp8, w_axis):
    if fp8:
        x, w = round_fp8(x, -1), round_fp8(w, w_axis)
    return jnp.einsum(eq, x, w, precision=jax.lax.Precision.HIGHEST)


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _key(m: Dict) -> str:
    return json.dumps(m, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _jits(m_key: str, fp8: bool, dtype):
    """Jitted weight generators and layer of one model shape (cached so
    that repeated runs in one process compile once): the global leaves,
    each layer's kind, a layer generator per kind, and the layer."""
    m = json.loads(m_key)
    A = archs.of(m)
    kinds = archs.layer_kinds(A, m)

    def gen(kind):
        extra = () if kind is None else (kind,)
        return jax.jit(lambda k, l: A.layer_weights(k, m, l, dtype, *extra))
    return (jax.jit(lambda k: global_weights(k, m, dtype)), kinds,
            {kind: gen(kind) for kind in set(kinds)},
            jax.jit(lambda w, h: A.layer(_f32(w), h, m, fp8)))


def hidden(mkey, m: Dict, tokens: np.ndarray, fp8: bool = False,
           dtype=jnp.bfloat16) -> jax.Array:
    """Final-norm hidden states ``[R, L, d]`` of token rows ``[R, L]``;
    the weights are the served ones (``dtype``), widened to float32."""
    glob_fn, kinds, gens, step = _jits(_key(m), fp8, jnp.dtype(dtype))
    glob = _f32(glob_fn(mkey))
    h = jnp.take(glob["embed"], jnp.asarray(tokens), axis=0)
    for l, kind in enumerate(kinds):
        h = step(gens[kind](mkey, l), h)
    return rms(h, glob["final_norm"], m["norm_eps"])


def _globals(mkey, m: Dict, dtype) -> Dict:
    """The global leaves, generated under jit as the program's tree is."""
    return _jits(_key(m), False, jnp.dtype(dtype))[0](mkey)


def logits(mkey, m: Dict, h: jax.Array, fp8: bool = False,
           dtype=jnp.bfloat16) -> jax.Array:
    """Logits ``[..., V_padded]`` of hidden rows, padded ids at -1e30."""
    table = _globals(mkey, m, dtype)["unembed"].astype(jnp.float32)
    z = mm("...d,vd->...v", h, table, fp8, 1)
    return jnp.where(jnp.arange(table.shape[0]) < m["vocab_size"], z, -1e30)


# ---------------------------------------------------------------------------
# sampled speculative verification, decision by decision


@dataclass
class Decisions:
    """Every served token of the sample as one decision of the sampled step.

    ``kind`` 0 is an accepted draft token (index ``i`` within its step),
    1 the token drawn after the accepted drafts.  ``j`` indexes the
    request's logit rows, ``a`` and ``s`` are the step's accepted count and
    speculation length, ``key_sum`` and ``row`` its key and batch row.
    """
    req: np.ndarray
    j: np.ndarray
    kind: np.ndarray
    i: np.ndarray
    a: np.ndarray
    s: np.ndarray
    key_sum: np.ndarray
    row: np.ndarray
    token: np.ndarray


def decisions_of(steps_by_req: Dict[int, List], served: Dict[int, np.ndarray],
                 order: Sequence[int]) -> Decisions:
    """Flatten ``(key_sum, row, s, committed)`` step records of each
    request into decisions; tokens past a request's served count (the step
    overshot ``max_new``) are not served and not checked."""
    cols = {k: [] for k in Decisions.__dataclass_fields__}
    for r_idx, rid in enumerate(order):
        toks = served[rid]
        j0 = 0
        for key_sum, row, s, c in steps_by_req[rid]:
            a = c - 1
            for i in range(c):
                if j0 + i >= len(toks):
                    break
                for k, v in (("req", r_idx), ("j", j0 + i),
                             ("kind", 0 if i < a else 1), ("i", i),
                             ("a", a), ("s", s), ("key_sum", key_sum),
                             ("row", row), ("token", int(toks[j0 + i]))):
                    cols[k].append(v)
            j0 += c
    return Decisions(**{k: np.asarray(v, np.int64) for k, v in cols.items()})


def _noise(key_sum, row, kind, i, B, V):
    """Gumbel noise row that the step added to this decision's logits."""
    kd, _, kr = jax.random.split(jax.random.PRNGKey(key_sum), 3)
    k = jnp.where(kind == 0, jax.random.fold_in(kd, i), kr)
    return jax.random.gumbel(k, (B, V), jnp.float32)[row]


def _uniform(key_sum, row, i, B, s):
    _, ka, _ = jax.random.split(jax.random.PRNGKey(key_sum), 3)
    return jax.random.uniform(ka, (B, s))[row, i]


# A token's residual p - q is known only to within this share of max(p, q)
# at the served precision: where p and q nearly agree, bf16 logits and
# float32 ones can give it opposite signs, and the float32 residual is
# then 0 (log 1e-30) at a token the program rightly drew.
RESID_REL = 0.25


def _perturbed(zt, zd, kind, a, s, T, rel=0.0):
    """The values the step took the argmax of, for one decision; for a
    residual draw, each token's residual moved by ``rel`` x max(p, q)
    (negative: the least it can be, positive: the most)."""
    lq = jax.nn.log_softmax(zd / T)
    p = jax.nn.softmax(zt / T)
    q = jnp.exp(lq)
    resid = jnp.maximum(p - q, 0.0)
    norm = resid.sum()
    moved = jnp.maximum(p - q + rel * jnp.maximum(p, q), 0.0)
    resid = jnp.where(norm > 1e-20, moved / jnp.maximum(norm, 1e-20), p)
    resid = jnp.where(a < s, resid, p)
    return jnp.where(kind == 0, zd / T, jnp.log(jnp.maximum(resid, 1e-30)))


def _pad(x: np.ndarray, n: int) -> np.ndarray:
    return np.concatenate([x, np.zeros(n - len(x), x.dtype)]) if len(x) < n else x


@jax.jit
def _token_gap(Zt, Zd, Za_t, Za_d, idx, kind, i, a, s, ks, row, tok, T, B_):
    B, V = B_.shape[0], Zt.shape[-1]

    def one(ix, kind, i, a, s, ks, row, tok):
        g = _noise(ks, row, kind, i, B, V)
        best = _perturbed(Zt[ix], Zd[ix], kind, a, s, T, -RESID_REL) + g
        most = _perturbed(Zt[ix], Zd[ix], kind, a, s, T, RESID_REL) + g
        if Za_t is None:
            choice = tok
        else:
            choice = jnp.argmax(_perturbed(Za_t[ix], Za_d[ix], kind, a, s, T)
                                + g)
        return jnp.maximum(jnp.max(best) - most[choice], 0.0)
    return jax.vmap(one)(idx, kind, i, a, s, ks, row, tok)


@jax.jit
def _accept_gap(Zt, Zd, Za_t, Za_d, idx, i, ks, row, tok, T, shape):
    B, s = shape.shape

    def one(ix, i, ks, row, tok):
        lu = jnp.log(_uniform(ks, row, i, B, s))
        lp = jax.nn.log_softmax(Zt[ix] / T)[tok]
        lq = jax.nn.log_softmax(Zd[ix] / T)[tok]
        edge = jnp.minimum(0.0, lp - lq)        # log min(1, p/q)
        if Za_t is None:
            accepted = True
        else:
            la = jnp.minimum(0.0, jax.nn.log_softmax(Za_t[ix] / T)[tok]
                             - jax.nn.log_softmax(Za_d[ix] / T)[tok])
            accepted = lu < la
        return jnp.where(accepted, jnp.maximum(0.0, lu - edge),
                         jnp.maximum(0.0, edge - lu))
    return jax.vmap(one)(idx, i, ks, row, tok)


def gaps(zt, zd, d: Decisions, T: float, B: int, actor=None,
         batch: int = 64) -> Dict[str, np.ndarray]:
    """Per decision, ``token``: the reference's gap (perturbed logit units)
    of the chosen token — the served one, or with ``actor = (zt_a, zd_a)``
    the one the actor's logits put first — with each residual taken at
    the least it can be for the best token and the most for the chosen
    one (``RESID_REL``); ``accept``: for each accepted
    draft, how far ``log u`` lies on the wrong side of the reference's
    ``log min(1, p/q)`` for the decision taken (the program's: accepted;
    an actor's: its own ``u < min(1, p/q)`` at the same token).

    ``zt``/``zd`` are ``[R, N, V]``: row ``r`` holds the target's and the
    draft's logits predicting request ``r``'s served tokens."""
    R, N, V = zt.shape
    Zt, Zd = zt.reshape(R * N, V), zd.reshape(R * N, V)
    Za = ((actor[0].reshape(R * N, V), actor[1].reshape(R * N, V))
          if actor is not None else (None, None))
    flat = d.req * N + d.j
    n = len(flat)
    T = jnp.float32(T)
    Bz = jnp.zeros((B,))
    token = np.zeros(n)
    for lo in range(0, n, batch):
        hi = min(lo + batch, n)
        cols = [_pad(x[lo:hi], batch) for x in
                (flat, d.kind, d.i, d.a, d.s, d.key_sum, d.row, d.token)]
        out = _token_gap(Zt, Zd, *Za, *map(jnp.asarray, cols), T, Bz)
        token[lo:hi] = np.asarray(out)[:hi - lo]
    accept = np.zeros(n)
    for s in sorted(set(d.s[d.kind == 0].tolist())):
        sel = np.where((d.kind == 0) & (d.s == s))[0]
        for lo in range(0, len(sel), batch):
            idx = sel[lo:lo + batch]
            cols = [_pad(x[idx], batch) for x in
                    (flat, d.i, d.key_sum, d.row, d.token)]
            out = _accept_gap(Zt, Zd, *Za, *map(jnp.asarray, cols), T,
                              jnp.zeros((B, s)))
            accept[idx] = np.asarray(out)[:len(idx)]
    return {"token": token, "accept": accept}
