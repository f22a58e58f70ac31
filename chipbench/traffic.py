"""Traffic: one general generator that reads a mix's parameter file.

A mix file (``chipbench/traffic/<mix>.json``) gives the loop (closed, with
a fixed number of clients), and the prompt and output length
distributions with their clips.  Lengths are stratified: each block of
``block`` requests holds the ``block`` mid-quantiles of each
distribution, permuted within the block.  The permutation is the same
for every seed, so every seed serves the same sizes in the same order
and only the token ids (and the weights) follow the seed: in a 50-s
window of about a hundred requests, the order of sizes alone moved
throughput and the tails by 5-10% from seed to seed.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np

DISTS = ("lognormal", "fixed")
ORDER_SEED = 0


@dataclasses.dataclass(frozen=True)
class Req:
    uid: int
    prompt: np.ndarray          # int32 token ids
    max_new: int


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified draws (mid-quantiles) of a length distribution,
    rounded and clipped to [min, max]."""
    kind = spec["dist"]
    if kind == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if kind != "lognormal":
        raise ValueError(f"unknown length distribution {kind!r}; "
                         f"known: {DISTS}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def max_len(spec: dict) -> int:
    return int(spec["value"] if spec["dist"] == "fixed" else spec["max"])


def stream(mix: dict, seed: int, vocab: int):
    """The request stream of a mix for ``seed``, without end: block after
    block of ``Req``."""
    block = int(mix["block"])
    order = np.random.default_rng(ORDER_SEED)    # sizes: one fixed order
    ids_rng = np.random.default_rng([seed, 1])   # token ids
    pq = quantiles(mix["prompt"], block)
    oq = quantiles(mix["output"], block)
    uid = 0
    while True:
        pl = order.permutation(pq)
        ol = order.permutation(oq)
        for i in range(block):
            ids = ids_rng.integers(0, vocab, size=int(pl[i]), dtype=np.int64)
            yield Req(uid, ids.astype(np.int32), int(ol[i]))
            uid += 1


def generate(mix: dict, seed: int, vocab: int, n: int) -> list:
    """The first ``n`` requests of the stream."""
    it = stream(mix, seed, vocab)
    return [next(it) for _ in range(n)]


def lengths(mix: dict, n: int) -> list:
    """[(prompt length, output length)] of the first ``n`` requests (the
    same for every seed)."""
    return [(len(r.prompt), r.max_new) for r in generate(mix, 0, 2, n)]
