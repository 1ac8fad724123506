"""The main-path Pallas kernels compile for a TPU v5e, at InternLM2-1.8B
widths in bf16, without a chip.

Interpret-mode tests cannot see what the TPU compiler refuses (block shapes
off the (8, 128) tiling, unaligned DMA slices, too much VMEM).  These tests
describe a ``v5e:2x2`` topology and compile each kernel for one of its
chips with ``interpret=False``, so a kernel the chip would refuse fails
here.  They call the ``*_pallas`` functions directly: the dispatch layer
sees the CPU and would take its interpret or reference branch.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler library, and every test
worker imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import AxisType, SingleDeviceSharding

from repro.configs import registry as R
from repro.kernels import ops
from repro.kernels.ops import batch_partitioned, partition_mesh
from repro.kernels.paged_verify_attn import (paged_verify_attn_pallas,
                                             ragged_paged_verify_attn_pallas)
from repro.kernels.spec_verify_attn import spec_verify_attn_pallas

# internlm2-1.8b: 24 layers, 16 heads over 8 KV heads of 128; serving pool
# block 16, 1024 tokens per slot, 8 slots, verify at s = 3 (T = 4).  The
# kernels read one layer of the whole [NL, NB, ...] pool.
NL, B, T, H, KVH, HD, BS, MAXB = 24, 8, 4, 16, 8, 128, 16, 64
NB = B * MAXB
BF, I32 = jnp.bfloat16, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described-device compiles cannot be read back from a persistent
    # cache without the chip; keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _paged_args(sh, dtype=BF):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    return dict(q=s((B, T, H, HD), BF), k=s((NL, NB, BS, KVH, HD), dtype),
                v=s((NL, NB, BS, KVH, HD), dtype), q_pos=s((B, T), I32),
                pos=s((NB, BS), I32), bt=s((B, MAXB), I32), layer=s((), I32),
                cu=s((B + 1,), I32), ks=s((NL, NB, BS, KVH), BF),
                vs=s((NL, NB, BS, KVH), BF))


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("num_buffers", [0, 2])
def test_ragged_paged_kernel_compiles_for_v5e(one_chip, num_buffers):
    a = _paged_args(one_chip)
    text = _compiled_text(
        lambda q, k, v, qp, pos, bt, ly, cu: ragged_paged_verify_attn_pallas(
            q, k, v, qp, pos, bt, ly, cu, num_buffers=num_buffers),
        a["q"], a["k"], a["v"], a["q_pos"], a["pos"], a["bt"], a["layer"],
        a["cu"])
    assert "tpu_custom_call" in text


def test_ragged_paged_kernel_int8_pool_compiles_for_v5e(one_chip):
    a = _paged_args(one_chip, dtype=jnp.int8)
    text = _compiled_text(
        lambda q, k, v, qp, pos, bt, ly, cu, ks, vs:
            ragged_paged_verify_attn_pallas(q, k, v, qp, pos, bt, ly, cu,
                                            k_scale=ks, v_scale=vs,
                                            num_buffers=2),
        a["q"], a["k"], a["v"], a["q_pos"], a["pos"], a["bt"], a["layer"],
        a["cu"], a["ks"], a["vs"])
    assert "tpu_custom_call" in text


def test_ragged_paged_kernel_with_vmem_limit_compiles_for_v5e(one_chip):
    """A launch with ``vmem_limit_bytes`` goes through the TPU compiler
    params (``pltpu.CompilerParams``)."""
    a = _paged_args(one_chip)
    text = _compiled_text(
        lambda q, k, v, qp, pos, bt, ly, cu: ragged_paged_verify_attn_pallas(
            q, k, v, qp, pos, bt, ly, cu, vmem_limit_bytes=32 << 20),
        a["q"], a["k"], a["v"], a["q_pos"], a["pos"], a["bt"], a["layer"],
        a["cu"])
    assert "tpu_custom_call" in text


def test_dense_paged_kernel_compiles_for_v5e(one_chip):
    a = _paged_args(one_chip)
    text = _compiled_text(
        lambda q, k, v, qp, pos, bt, ly: paged_verify_attn_pallas(
            q, k, v, qp, pos, bt, ly),
        a["q"], a["k"], a["v"], a["q_pos"], a["pos"], a["bt"], a["layer"])
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("hd,L,quant", [(HD, 1024, False), (64, 1024, False),
                                        (HD, 520, True)],
                         ids=["target", "draft", "int8-odd-length"])
def test_spec_verify_kernel_compiles_for_v5e(one_chip, hd, L, quant):
    """The contiguous verify kernel in its folded layout ([B*KVH, rows,
    hd]): the target's width, the dense draft's head_dim 64, and an int8
    cache whose length takes a 104-row tile."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                sharding=one_chip)
    N = B * KVH
    kv_dt = jnp.int8 if quant else BF
    args = [s((N, 8, hd), BF), s((N, L, hd), kv_dt), s((N, L, hd), kv_dt),
            s((N, 8), I32), s((N, L), I32)]
    if quant:
        args += [s((N, L), BF), s((N, L), BF)]

    def fn(q, k, v, qp, kp, ks=None, vs=None):
        return spec_verify_attn_pallas(q, k, v, qp, kp, k_scale=ks,
                                       v_scale=vs)

    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_spec_verify_kernel_partitions_over_a_v5e_mesh(topo):
    """Under a 4-chip data mesh the kernel runs per device on its slice of
    the batch (shard_map); XLA alone refuses to partition it."""
    mesh = Mesh(np.array(topo.devices), ("data",),
                axis_types=(AxisType.Auto,))
    sh = NamedSharding(mesh, P("data"))
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    L = 1024
    args = [s((B, T, H, HD), BF), s((B, L, KVH, HD), BF),
            s((B, L, KVH, HD), BF), s((B, T), I32), s((B, L), I32)]

    def kernel(q, k, v, qp, kp):
        Bl = q.shape[0]
        qf = (q.reshape(Bl, T, KVH, H // KVH, HD).transpose(0, 2, 3, 1, 4)
              .reshape(Bl * KVH, 8, HD))
        kf = k.transpose(0, 2, 1, 3).reshape(Bl * KVH, L, HD)
        vf = v.transpose(0, 2, 1, 3).reshape(Bl * KVH, L, HD)
        qpf = jnp.repeat(jnp.tile(qp, (1, 2)), KVH, axis=0)
        kpf = jnp.repeat(kp, KVH, axis=0)
        o = spec_verify_attn_pallas(qf, kf, vf, qpf, kpf)
        return o.reshape(Bl, KVH, 8, HD)

    def fn(*a):
        with partition_mesh(mesh):
            return batch_partitioned(kernel, a)

    text = _compiled_text(fn, *args)
    assert "tpu_custom_call" in text
    assert "all-gather" not in text      # each chip reads its own slots


@pytest.mark.parametrize("kernel", ["ragged_paged_verify_attn",
                                    "paged_verify_attn", "spec_verify_attn"])
def test_serving_kernels_are_named_in_the_compiled_program(one_chip, kernel):
    """Each serving kernel's custom call carries its own name in the
    compiled program, so a device trace names it; the ragged one keeps
    the scalar-prefetched s32 plan as its first operand."""
    a = _paged_args(one_chip)
    if kernel == "ragged_paged_verify_attn":
        text = _compiled_text(
            lambda q, k, v, qp, pos, bt, ly, cu:
                ragged_paged_verify_attn_pallas(q, k, v, qp, pos, bt, ly, cu),
            a["q"], a["k"], a["v"], a["q_pos"], a["pos"], a["bt"], a["layer"],
            a["cu"])
    elif kernel == "paged_verify_attn":
        text = _compiled_text(
            lambda q, k, v, qp, pos, bt, ly: paged_verify_attn_pallas(
                q, k, v, qp, pos, bt, ly),
            a["q"], a["k"], a["v"], a["q_pos"], a["pos"], a["bt"], a["layer"])
    else:
        s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                    sharding=one_chip)
        N, L = B * KVH, 1024
        text = _compiled_text(spec_verify_attn_pallas, s((N, 8, HD), BF),
                              s((N, L, HD), BF), s((N, L, HD), BF),
                              s((N, 8), I32), s((N, L), I32))
    calls = re.findall(rf"%{kernel}(?:\.\d+)? = .*custom-call\(", text)
    assert calls, text[:2000]


# operations that move a buffer rather than compute one
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")
_HLO_OP = re.compile(r"\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][\w-]*)\(")


def test_paged_step_writes_the_pool_in_place(one_chip, monkeypatch):
    """The paged decode step at internlm2 widths (2 layers, a 512-block
    pool, B = 8, T = 3, the ragged kernel, the pool donated) carries the
    pool through its layer scan: no copy, dynamic-slice or
    dynamic-update-slice yields a result of the pool's or one layer's
    shape, its temporaries stay below one layer's K, and the donated pool
    is still aliased to the output."""
    # the dispatch sees the CPU here; steer it to the native kernel the
    # chip runs
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    nl, nb, b, t = 2, 512, 8, 3
    cfg = dataclasses.replace(R.get_config("internlm2-1.8b"), n_layers=nl,
                              paged_fused=True)
    model = R.build_model(cfg)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree.map(lambda x: s(x.shape, x.dtype), model.shapes(BF))
    cache = jax.tree.map(lambda x: s(x.shape, x.dtype), jax.eval_shape(
        lambda: model.init_paged_cache(nb, BS, BF)))
    cache["bt"] = s((b, MAXB), I32)
    step = jax.jit(model._decode_step_paged, donate_argnums=(2,))
    compiled = step.lower(params, s((b, t), I32), cache, s((b,), I32),
                          s((b + 1,), I32)).compile()

    layer = f"{nb},{BS},{KVH},{HD}]"
    shapes = (f"[{nl},{layer}", f"[1,{layer}", f"[{layer}")
    moved = []
    for line in compiled.as_text().splitlines():
        m = _HLO_OP.match(line)
        if m is None:
            continue
        name, result, op = m.groups()
        if (op.startswith(_MOVES) or any(mv in name for mv in _MOVES)) \
                and any(sh in result for sh in shapes):
            moved.append(line.strip()[:160])
    assert not moved, "\n".join(moved)

    layer_bytes = nb * BS * KVH * HD * jnp.dtype(BF).itemsize
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < layer_bytes, mem
    assert mem.alias_size_in_bytes >= 2 * nl * layer_bytes, mem
