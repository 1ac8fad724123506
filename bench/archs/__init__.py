"""One module per architecture: what the benchmark knows of a model's
layers.  Each model section of a configuration file (``target``,
``draft``) names its module under ``"model"``; ``<name>`` is the file
``bench/archs/<name>.py`` of the checkout, found the way a metric's reader
is.  A new architecture is a new file here and a configuration naming it:
nothing else under ``bench/`` changes.

A module provides, for a section ``m`` (the file's numbers, under the
file's key names):

``program_config(m, name, dtype) -> ModelConfig``
    The program's configuration, with every option the architecture needs
    (qk-norm, experts, latent attention) set here.
``registry_view(cfg) -> dict``
    A registry entry's values under the file's key names: a configuration
    that names a registry ``arch`` is checked against it on every key it
    states (keys listed in ``reduced`` excepted, for the target).
``LEAVES``
    Every leaf name (a nested leaf as ``group.leaf``); a leaf's seed key
    is ``fold_in(fold_in(model_key, LEAVES.index(name)), layer)``.
``global_shapes(m) -> {name: shape}``
    The leaves outside the layer stack (embedding, output head, final
    norm), made by ``bench/weights.py``'s rule.
``layer_weights(mkey, m, layer, dtype) -> tree``
    One layer's leaves, seeded, in the program's layout (``layer`` may be
    traced).  ``bench/weights.py`` stacks them for the program with one
    ``lax.map``; a module whose stack is not uniform also provides
    ``program_params(mkey, m, dtype)`` (the program's whole tree) and
    ``layer_kind(m, layer) -> str``, and its ``layer_weights`` then takes
    the kind as a last argument, with ``layer`` traced.
``layer(w, h, m, fp8) -> h``
    One layer of the float32 reference over ``h [R, L, d]`` (positions
    0..L-1, causal) at ``Precision.HIGHEST``, taking ``w`` (a layer's
    leaves widened to float32); with ``fp8`` every matmul operand is
    rounded to float8 first (``bench/reference.py``'s ``mm`` and ``fp8``).
``tokens_flops(m, first_pos, n, logits) -> float``
    Flops of ``n`` consecutive tokens from ``first_pos`` through every
    layer (and the output head when ``logits``): ``mfu``'s yardstick.
``attn_call(m, rows, block) -> (flops, bytes)``
    One layer's paged-attention call over ``(queries, keys)`` per live
    slot: the paged-attention roofline's yardstick.
"""
from __future__ import annotations

import functools
import importlib.util
import os
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


@functools.lru_cache(maxsize=None)
def load(file: str):
    """The module in ``file``, executed once per process."""
    if not os.path.exists(file):
        raise SystemExit(f"no architecture module {file}")
    spec = importlib.util.spec_from_file_location(
        "bench_arch_" + os.path.splitext(os.path.basename(file))[0], file)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def of(m: Dict):
    """The module of model section ``m``: the file ``resolve`` found for
    it, or ``bench/archs/<model>.py`` beside this package."""
    return load(m.get("model_file") or os.path.join(HERE, m["model"] + ".py"))


def resolve(conf: Dict, root: str) -> Dict:
    """``conf`` with each model section pointing at its module in the
    checkout at ``root``; a missing module stops the run."""
    out = dict(conf)
    for sec in ("target", "draft"):
        file = os.path.join(root, "bench", "archs",
                            conf[sec]["model"] + ".py")
        load(file)
        out[sec] = dict(conf[sec], model_file=file)
    return out


def layer_kinds(A, m: Dict) -> List[Optional[str]]:
    """Each layer's kind, or None throughout for a uniform stack."""
    kind = getattr(A, "layer_kind", None)
    return [kind(m, l) if kind else None for l in range(m["n_layers"])]
