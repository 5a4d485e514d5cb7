"""The one load generator: turns a traffic file into requests or batches.

A traffic file (``bench/traffic/<name>.json``) holds parameters only; this
module is the only code that reads them, so a new mix is a new data file.

Every seed gets the same set of sizes and arrival gaps in another order:
sizes are drawn by stratified quantiles of the stated distribution (one
quantile per request of a round), and only their order, their pairing and
the token ids come from the seed.  Runs with different seeds then differ in
what they compute, not in how much.

Keys of a ``"kind": "requests"`` file:

* ``arrival``: ``{"process": "closed", "clients": C}`` (each client sends
  its next request when its previous one completes; all start together),
  ``{"process": "poisson", "rate": R}`` or ``{"process": "gamma", "rate":
  R, "cv": c}`` (open loop, requests per second; gamma with cv > 1 is
  bursty).
* ``prompt_len`` / ``output_len``: ``{"dist": "uniform", "lo", "hi"}``
  (integers, inclusive) or ``{"dist": "lognormal", "median", "sigma", "lo",
  "hi"}`` (clipped).
* ``round``: requests per stratified round (default: the clients, or 64).
* ``temperature``: 0 for greedy.

A ``"kind": "images"`` file holds ``batch`` (images per batch) and ``pool``
(distinct batches cycled through).
"""
from __future__ import annotations

import json
import math
import statistics
from dataclasses import dataclass
from typing import Iterator, List, Tuple

import numpy as np


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent numpy stream per purpose, from a seed of any size."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng(np.random.SeedSequence([seed, tag]))


def key_seed(seed: int) -> int:
    """A 31-bit seed for ``jax.random.PRNGKey``, which keeps only the low
    32 bits of what it is given: derived by hashing, so seeds that differ
    only above bit 31 still give different weights."""
    return int(np.random.SeedSequence([seed]).generate_state(1)[0] >> 1)


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n integer sizes at the quantiles (i + 0.5) / n of ``dist``."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = dist["lo"], dist["hi"]
    if dist["dist"] == "uniform":
        return lo + np.floor(q * (hi - lo + 1)).astype(np.int64)
    if dist["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(float(p)) for p in q])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
        return np.clip(np.round(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


@dataclass(frozen=True)
class Req:
    prompt: Tuple[int, ...]
    max_new_tokens: int
    temperature: float


def round_size(traffic: dict) -> int:
    arr = traffic["arrival"]
    return traffic.get("round", arr.get("clients", 64))


def requests(traffic: dict, vocab: int, seed: int) -> Iterator[Req]:
    """Requests in the order they are sent, without end."""
    n = round_size(traffic)
    P = quantiles(traffic["prompt_len"], n)
    O = quantiles(traffic["output_len"], n)
    order = rng_for(seed, "order")
    toks = rng_for(seed, "tokens")
    temp = float(traffic.get("temperature", 0.0))
    while True:
        for p, o in zip(order.permutation(P), order.permutation(O)):
            yield Req(tuple(int(t) for t in toks.integers(0, vocab, int(p))),
                      int(o), temp)


def arrival_offsets(traffic: dict, seed: int, seconds: float) -> List[float]:
    """Due times (s after the window opens) of an open loop, up to
    ``seconds``: the round's gaps are the stated distribution's quantiles,
    permuted by the seed."""
    arr = traffic["arrival"]
    rate = float(arr["rate"])
    n = round_size(traffic)
    q = (np.arange(n) + 0.5) / n
    if arr["process"] == "poisson":
        gaps = -np.log1p(-q) / rate
    elif arr["process"] == "gamma":
        shape = 1.0 / arr["cv"] ** 2
        gaps = _gamma_ppf(q, shape) / (shape * rate)
    else:
        raise ValueError(f"not an open loop: {arr['process']!r}")
    rng = rng_for(seed, "arrivals")
    out, t = [], 0.0
    while True:
        for g in rng.permutation(gaps):
            t += float(g)
            if t >= seconds:
                return out
            out.append(t)


def _gamma_ppf(q: np.ndarray, shape: float) -> np.ndarray:
    """Quantiles of Gamma(shape, 1) by bisection on the regularized
    incomplete gamma function (stdlib only)."""
    def cdf(x):
        # series for P(shape, x)
        if x <= 0:
            return 0.0
        term = total = 1.0 / shape
        k = 0
        while term > total * 1e-12 and k < 10000:
            k += 1
            term *= x / (shape + k)
            total += term
        return min(1.0, total * math.exp(-x + shape * math.log(x)
                                         - math.lgamma(shape)))
    out = []
    for p in q:
        lo, hi = 0.0, max(1.0, shape * 50)
        for _ in range(200):
            mid = (lo + hi) / 2
            lo, hi = (mid, hi) if cdf(mid) < p else (lo, mid)
        out.append((lo + hi) / 2)
    return np.array(out)
