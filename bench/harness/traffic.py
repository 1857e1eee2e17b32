"""One generator for every traffic mix: it reads a mix's parameters and
draws its schedule (or, for a training job, its batches) from the seed.

Every seed gets the same amount of work: the same set of sizes, in
another order (the quantiles of the stated distribution at evenly spaced
probabilities, shuffled by the seed), and the same number of arrivals,
round(rate * window). The arrivals are a Poisson stream given that count:
independent exponential gaps, scaled together so that they fill the
window.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


def sizes(spec: Dict[str, Any], n: int, rng: np.random.Generator
          ) -> np.ndarray:
    """``n`` whole sizes from ``spec`` ({"dist": "lognormal", "median",
    "sigma", "min", "max"} or {"dist": "fixed", "value"}): the same set for
    every seed, in a shuffled order."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown size distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    vals = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    vals = np.clip(vals, spec["min"], spec["max"]).astype(np.int64)
    return rng.permutation(vals)


def poisson_offsets(rate: float, seconds: float, rng: np.random.Generator
                    ) -> np.ndarray:
    """Arrival offsets in [0, seconds) of an open-loop Poisson stream that
    brings round(rate * seconds) arrivals: that many independent
    exponential gaps, scaled so that all of them fit the window; the first
    arrival is at 0."""
    n = max(1, int(round(rate * seconds)))
    gaps = rng.exponential(1.0 / rate, n)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def counts_by_share(shares: List[float], n: int) -> List[int]:
    """Split ``n`` items by ``shares`` (largest remainders)."""
    total = float(sum(shares))
    exact = [n * s / total for s in shares]
    out = [int(np.floor(e)) for e in exact]
    order = sorted(range(len(shares)), key=lambda i: out[i] - exact[i])
    for i in order[:n - sum(out)]:
        out[i] += 1
    return out


def labels(names: List[str], shares: List[float], n: int,
           rng: np.random.Generator) -> List[str]:
    out: List[str] = []
    for name, c in zip(names, counts_by_share(shares, n)):
        out += [name] * c
    return [out[i] for i in rng.permutation(n)]


@dataclass
class ServeRequest:
    index: int
    offset: float            # due time, seconds after the window opens
    tenant: str
    prompt: np.ndarray       # int32 token ids
    max_new: int


@dataclass
class UnitCreate:
    index: int
    offset: float
    tenant: str
    name: str


def serve_schedule(mix: Dict[str, Any], seconds: float, vocab: int,
                   rng: np.random.Generator) -> List[ServeRequest]:
    offs = poisson_offsets(mix["rate_rps"], seconds, rng)
    n = len(offs)
    plen = sizes(mix["prompt_len"], n, rng)
    olen = sizes(mix["output_len"], n, rng)
    tenants = labels([t["name"] for t in mix["tenants"]],
                     [t["share"] for t in mix["tenants"]], n, rng)
    return [ServeRequest(i, float(offs[i]), tenants[i],
                         rng.integers(0, vocab, int(plen[i]),
                                      dtype=np.int32),
                         int(olen[i]))
            for i in range(n)]


def train_batch(mix: Dict[str, Any], seed: int, step: int, vocab: int,
                image_tokens: int, image_dim: int) -> Dict[str, np.ndarray]:
    """Batch ``step`` of a training job, the same for the same seed and
    step and different for every step. Token ids are Zipf-like with a
    random offset per row (the program's ``SyntheticTokens``, copied); the
    first ``image_tokens`` positions carry image features instead, and the
    loss counts the positions whose next token is text."""
    from .common import seed32
    rng = np.random.default_rng([seed32(seed, 5), int(step)])
    B, S = int(mix["global_batch"]), int(mix["seq_len"])
    u = rng.random((B, S))
    toks = np.minimum((u ** -float(mix["zipf_a"])).astype(np.int64),
                      vocab - 1)
    toks = (toks + rng.integers(0, vocab, (B, 1))) % vocab
    mask = np.ones((B, S), np.float32)
    mask[:, :max(0, image_tokens - 1)] = 0.0
    return {"tokens": toks.astype(np.int32), "mask": mask,
            "patches": rng.standard_normal((B, image_tokens, image_dim),
                                           dtype=np.float32)}


def unit_schedule(cp: Dict[str, Any], seconds: float,
                  rng: np.random.Generator) -> List[UnitCreate]:
    offs = poisson_offsets(cp["create_rps"], seconds, rng)
    names = cp["tenants"]
    zipf = [1.0 / (k + 1) ** cp["zipf_s"] for k in range(len(names))]
    tenants = labels(names, zipf, len(offs), rng)
    return [UnitCreate(i, float(offs[i]), tenants[i], f"wu-{i:05d}")
            for i in range(len(offs))]
