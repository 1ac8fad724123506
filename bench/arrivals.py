"""Traffic generator: one function over the parameters of a mix file.

A mix is a JSON file under ``bench/traffic/``.  ``kind`` is ``open`` (Gamma
inter-arrival gaps at a fixed rate, a warm-in before the window) or
``backlog`` (every request queued at t = 0).  Lengths and gaps come from a
fixed base sample drawn with the mix's own ``base_seed``; ``--seed`` only
permutes that sample and draws the token ids, so every seed carries the
same work in another order.  An open mix splits its sample into a warm-in
block and a window block, permutes each on its own, and fits each block's
gaps to its span: every seed brings the same requests into the window.

``gamma_intervals`` and ``arrival_times`` are copies of the repo's
``serving/traffic.py`` arithmetic (Gamma gaps with mean and CV).
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np


def gamma_intervals(n: int, mean: float, cv: float,
                    rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps with mean ``mean`` and coefficient of
    variation ``cv``: shape 1/CV², scale mean·CV²."""
    if mean <= 0:
        return np.zeros(n)
    return rng.gamma(1.0 / (cv * cv), mean * cv * cv, size=n)


def arrival_times(gaps: np.ndarray) -> np.ndarray:
    """Absolute arrival times from gaps (the first request arrives at 0)."""
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])]) if len(gaps) else gaps


def lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths from a ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``
    spec, clipped to ``[min, max]``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, size=n).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


@dataclass
class Mix:
    """A traffic mix as read from its file."""
    name: str
    kind: str
    params: Dict = field(default_factory=dict)

    @classmethod
    def load(cls, path: str, name: str) -> "Mix":
        with open(path) as f:
            p = json.load(f)
        if p.get("kind") not in ("open", "backlog"):
            raise ValueError(f"{path}: kind must be 'open' or 'backlog'")
        return cls(name=name, kind=p["kind"], params=p)

    @property
    def max_prompt(self) -> int:
        return int(self.params["prompt"]["max"])

    @property
    def min_prompt(self) -> int:
        return int(self.params["prompt"]["min"])

    @property
    def max_output(self) -> int:
        return int(self.params["output"]["max"])

    @property
    def warm_in_s(self) -> float:
        return float(self.params.get("warm_in_s", 0.0))

    @property
    def drain_s(self) -> float:
        """How long after the window an open mix may take to finish."""
        return float(self.params.get("drain_s", 30.0))

    def blocks(self, seconds: float) -> List[Tuple[float, float, int]]:
        """``(start, span, requests)`` of each block of arrivals: an open
        mix has its warm-in and its window of ``seconds``, each with the
        requests the rate brings in its span; a backlog has one block of
        its ``n_requests`` at t = 0."""
        p = self.params
        if self.kind == "open":
            rate = p["rate_per_s"]
            return [(0.0, self.warm_in_s, round(rate * self.warm_in_s)),
                    (self.warm_in_s, seconds, round(rate * seconds))]
        return [(0.0, 0.0, int(p["n_requests"]))]

    def generate(self, seed: int, seconds: float, vocab: int) -> List[Dict]:
        """Requests as dicts ``{rid, arrival, prompt_len, max_new,
        tokens}``, ordered by arrival.  Each block's requests arrive
        inside its span: Gamma gaps scaled so that they fill it."""
        p = self.params
        blocks = self.blocks(seconds)
        n = sum(k for *_, k in blocks)
        base = np.random.default_rng(int(p["base_seed"]))
        plens = lengths(p["prompt"], n, base)
        outs = lengths(p["output"], n, base)
        gaps = (gamma_intervals(n, 1.0 / p["rate_per_s"], p["cv"], base)
                if self.kind == "open" else np.zeros(n))
        rng = np.random.default_rng(seed)
        arrivals = np.zeros(n)
        lo = 0
        for start, span, k in blocks:
            idx = lo + rng.permutation(k)
            plens[lo:lo + k], outs[lo:lo + k] = plens[idx], outs[idx]
            g = gaps[lo + rng.permutation(k)]
            if k and g.sum() > 0:
                g = g * (span / g.sum())
            arrivals[lo:lo + k] = start + arrival_times(g)
            lo += k
        return [dict(rid=i, arrival=float(arrivals[i]),
                     prompt_len=int(plens[i]), max_new=int(outs[i]),
                     tokens=rng.integers(0, vocab,
                                         int(plens[i])).astype(np.int32))
                for i in range(n)]
