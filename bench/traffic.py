"""One general generator for every traffic mix: the mix is data.

A traffic file (``bench/traffic/<mix>.json``) gives

  loop          "open" (Poisson arrivals at ``rate_per_s``) or "closed"
                (``clients`` that each send the next request when the last
                one is done)
  prompt_len,   {"median", "sigma", "min", "max"}: lognormal, clipped
  output_len
  pool          closed loop: requests in the corpus, sent in turn
  preroll_s     seconds of the same traffic before the window opens

Every run of a mix gets the same corpus, drawn from ``CORPUS_SEED``: the
same prompts, prompt lengths, output lengths and inter-arrival gaps
(stratified quantiles of the stated distributions), in the same order.  The
run's seed only draws which finished requests the check compares
(``bench.check.sample``).  Blockwise parallel decoding makes the content
part of the work: with random weights a prompt's tokens, down to which of
two tied bf16 logits comes first, decide how many proposals each verify
step accepts, so any change of content or order from seed to seed is other
work.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, NamedTuple, Optional

import numpy as np


class RequestSpec(NamedTuple):
    prompt: List[int]
    max_new: int
    offset_s: Optional[float]   # open loop: due time after traffic start


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` stratified draws of a clipped lognormal, in ascending order."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified inter-arrival gaps of a Poisson process."""
    return -np.log1p(-_quantiles(n)) / rate


CORPUS_SEED = 3141592653


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def num_requests(mix: Dict, seconds: float) -> int:
    """Requests one run may send: open loop, the arrivals due in the
    pre-roll and the window; closed loop, the pool."""
    if mix["loop"] == "open":
        return int(math.ceil(mix["rate_per_s"] * (mix["preroll_s"] + seconds)))
    return int(mix["pool"])


def generate(mix: Dict, seconds: float, vocab: int) -> List[RequestSpec]:
    """The run's requests, in sending order."""
    n = num_requests(mix, seconds)
    plens = _rng(CORPUS_SEED, 1).permutation(
        lognormal_lengths(mix["prompt_len"], n))
    olens = _rng(CORPUS_SEED, 2).permutation(
        lognormal_lengths(mix["output_len"], n))
    offsets = [None] * n
    if mix["loop"] == "open":
        gaps = _rng(CORPUS_SEED, 3).permutation(
            exponential_gaps(mix["rate_per_s"], n))
        offsets = np.cumsum(gaps).tolist()
    tok = _rng(CORPUS_SEED, 4)
    return [RequestSpec(prompt=tok.integers(0, vocab, int(p)).tolist(),
                        max_new=int(o), offset_s=off)
            for p, o, off in zip(plens, olens, offsets)]


def first_budgets(budgets: List[int]) -> List[int]:
    """Closed loop: each client's first request stops after a uniform
    share of its budget, so the slots start at spread ages (the residual
    life of a request) and the batch is in steady state within one
    pre-roll instead of finishing in lockstep."""
    u = _rng(CORPUS_SEED, 5).random(len(budgets))
    return [max(1, int(math.ceil(b * x))) for b, x in zip(budgets, u)]
