"""A tiny cell for CPU tests: a throwaway checkout root holding a copy of
``bench/`` plus a two-layer configuration and a short chat mix, driven
through the real harness with the chip check skipped."""
from __future__ import annotations

import json
import os
import shutil

import jax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {
    "name": "tiny", "arch": None, "source": "test", "deployment": "test",
    "dtype": "float32",
    "target": {"model": "dense_gqa", "n_layers": 2, "d_model": 128,
               "d_ff": 256, "vocab_size": 500, "vocab_padded": 512,
               "n_heads": 4, "n_kv_heads": 2, "head_dim": 32,
               "rope_theta": 1e6, "norm_eps": 1e-6},
    "draft": {"model": "dense_gqa", "n_layers": 1, "d_model": 64,
              "d_ff": 128, "vocab_size": 500, "vocab_padded": 512,
              "n_heads": 2, "n_kv_heads": 2, "head_dim": 32,
              "rope_theta": 1e6, "norm_eps": 1e-6},
    "reduced": [], "assumed": {},
    "serving": {"slots": 4, "cache_len": 128, "block_size": 16,
                "token_budget": 64, "chunk": 32},
    "sampling": {"temperature": 1.0},
    "lut": {"table": {"1": 2, "4": 2}},
    "correct": {"sample_requests": 4},
}

# what a new tiny cell brings as its own limits file
TINY_LIMITS = {"max": {"token_gap": 1e-3, "accept_gap": 1e-3},
               "min_decisions": 10}

TINY_CHAT = {
    "kind": "open", "rate_per_s": 8.0, "cv": 1.0, "warm_in_s": 0.5,
    "drain_s": 8.0, "base_seed": 3,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 8,
               "max": 60},
    "output": {"dist": "lognormal", "median": 8, "sigma": 0.5, "min": 4,
               "max": 16},
}


def make_root(tmp, mixes=None, config=None, files=None) -> str:
    """A checkout root under ``tmp`` with the tiny cell ``tiny.tinychat``
    and a cell ``tiny.<name>`` for each further mix: each cell is new
    files (mix, limits) and new ``BENCHMARK.json`` entries only.
    ``files`` maps a path under the root to the file of this repo copied
    there (an architecture module, say)."""
    root = os.path.join(str(tmp), "root")
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for dest, src in (files or {}).items():
        shutil.copy(os.path.join(REPO, src), os.path.join(root, dest))
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(config or TINY, f)
    mixes = dict(mixes or {}, tinychat=TINY_CHAT)
    for name, mix in mixes.items():
        with open(os.path.join(root, "bench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(mix, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    for name in mixes:
        with open(os.path.join(root, "bench", "limits",
                               f"tiny.{name}.json"), "w") as f:
            json.dump(TINY_LIMITS, f)
        spec["workloads"].append({"name": f"tiny.{name}", "config": "tiny",
                                  "traffic": name, "chips": 1, "why": "test"})
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "internlm2-1.8b.chat" in m.get("workloads", ()):
                m["workloads"] = m["workloads"] + [f"tiny.{n}"
                                                   for n in mixes]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


class JaxConfig:
    """Restores the JAX settings a harness run changes."""
    KEYS = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")

    def __enter__(self):
        self.saved = {k: getattr(jax.config, k) for k in self.KEYS}
        jax.config.update("jax_enable_compilation_cache", False)
        return self

    def __exit__(self, *exc):
        for k, v in self.saved.items():
            jax.config.update(k, v)
