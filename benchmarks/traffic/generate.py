"""The one traffic generator. A traffic mix is a data file of
parameters beside this file (`<traffic>.json`); nothing here knows any
mix by name. Everything is drawn from the seed: the same seed gives the
same schedule, lengths and tokens.

Serving mixes (`runner: serve`) are open-loop schedules: an optional
burst at t=0, then arrivals with exponential gaps at `rate_rps`, each
request with a prompt length and an answer length from the mix's
distributions and a prompt of unique random tokens (so the prefix cache
misses). Draws are STRATIFIED: n draws are the distribution's n evenly
spaced quantiles in an order the seed shuffles. Every seed then offers
the same load and the same multiset of lengths (a Poisson count over a
51 s window alone swings the offered load by 10%, which at four fifths
of the knee swings the queue by a factor); what the seed changes is
which request comes when.
Lengths are stratified in ARRIVAL order besides (`_arrival_ranks`):
any LENGTH_BLOCK consecutive requests hold one length from each of
LENGTH_BLOCK equal strata of the distribution. A saturated server
admits, first come first served, only the part of the schedule it can
serve; with one permutation over all n requests, which lengths fell into
that part was the seed's (PERF.md §6, PR 27 and 29), and two seeds'
windows held different work. Prompts and answers are dealt
independently, the answers so that they do not correlate with the
prompts (`draw_lengths(apart_from=)`).
Training mixes (`runner: train`) are an endless stream of token
batches, Zipf-distributed unigrams so the loss can fall.
"""

import dataclasses
from typing import Any, Dict, Iterator, List

import numpy as np


def _quantiles(rng: np.random.Generator, n: int) -> np.ndarray:
    """n evenly spaced probabilities in (0, 1), in a seeded order."""
    return rng.permutation((np.arange(n) + 0.5) / n)


LENGTH_BLOCK = 32
LENGTH_DEALS = 64


def _arrival_ranks(rng: np.random.Generator, n: int,
                   block: int = LENGTH_BLOCK) -> np.ndarray:
    """A permutation of 0..n-1 (ranks among n sorted values) in which
    ANY `block` consecutive entries hold one rank from each of `block`
    equal strata. The strata are consecutive runs of the ranks, equal
    to within one; position i takes its rank from the stratum the seed
    deals to i % block (one of as many ranks as there are such
    positions), and within a stratum the seed shuffles which position
    gets which. So any prefix carries the whole distribution to within
    one block."""
    edges = np.arange(block + 1) * n // block
    size = np.diff(edges)
    per_residue = np.bincount(np.arange(n) % block, minlength=block)
    stratum = np.empty(block, np.int64)
    for k in np.unique(size):
        stratum[per_residue == k] = rng.permutation(np.flatnonzero(size == k))
    rank = np.empty(n, np.int64)
    for r in range(min(block, n)):
        rank[r::block] = edges[stratum[r]] + rng.permutation(per_residue[r])
    return rank


def _sorted_lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    """The n evenly spaced quantiles of a length spec, ascending."""
    if spec["dist"] == "lognormal":
        from scipy.special import ndtri

        x = np.exp(np.log(spec["median"])
                   + spec["sigma"] * ndtri((np.arange(n) + 0.5) / n))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def draw_lengths(rng: np.random.Generator, spec: Dict[str, Any], n: int,
                 apart_from: np.ndarray = None) -> np.ndarray:
    """n integer lengths from a length spec: {"dist": "lognormal",
    "median", "sigma", "min", "max"}, in arrival order.

    With `apart_from` (the n lengths of another quantity, dealt before)
    the seed deals LENGTH_DEALS times and keeps the deal that correlates
    with them least. A deal in arrival order pairs the two quantities'
    strata the same way in every block, so a chance pairing (|r| ~ 0.18
    for 32 strata, 0.39 seen) would hold through the whole schedule,
    and the KV bytes a request's decoding reads grow with prompt x
    answer: that seed's iterations would be slower by percents. Of 64
    deals the best is under 0.03."""
    table = _sorted_lengths(spec, n)
    if apart_from is None or n < 2:
        return table[_arrival_ranks(rng, n)]
    best, best_r = None, np.inf
    for _ in range(LENGTH_DEALS):
        mine = table[_arrival_ranks(rng, n)]
        with np.errstate(invalid="ignore", divide="ignore"):
            r = abs(np.nan_to_num(np.corrcoef(apart_from, mine)[0, 1]))
        if r < best_r:
            best, best_r = mine, r
    return best


@dataclasses.dataclass
class ServeSchedule:
    due_s: np.ndarray          # [n] seconds after the start of the ramp
    prompt_len: np.ndarray     # [n]
    answer_len: np.ndarray     # [n]
    prompts: List[np.ndarray]  # n int32 token arrays


def serve_schedule(mix: Dict[str, Any], seed: int, horizon_s: float,
                   vocab_size: int, rate_rps: float = None) -> ServeSchedule:
    """Arrivals in [0, horizon_s): `burst_at_start` requests due at 0,
    then rate_rps x horizon_s arrivals with (stratified) exponential
    gaps at `rate_rps` (the mix's own unless a sweep passes another)."""
    rng = np.random.default_rng([int(seed), 0x5E57E])
    rate = float(mix["rate_rps"] if rate_rps is None else rate_rps)
    burst = int(mix.get("burst_at_start", 0))
    n_gaps = int(round(rate * horizon_s))
    t = np.cumsum(-np.log1p(-_quantiles(rng, n_gaps)) / rate)
    t = t[t < horizon_s]
    due = np.concatenate([np.zeros((burst,)), t])
    n = len(due)
    plen = draw_lengths(rng, mix["prompt_len"], n)
    alen = draw_lengths(rng, mix["answer_len"], n, apart_from=plen)
    if mix.get("prompt_tokens", "unique_random") != "unique_random":
        raise ValueError(
            f"unknown prompt_tokens {mix['prompt_tokens']!r}")
    tok_rng = np.random.default_rng([int(seed), 0x70C5])
    prompts = [tok_rng.integers(0, vocab_size, int(k)).astype(np.int32)
               for k in plen]
    return ServeSchedule(due, plen, alen, prompts)


def token_batches(mix: Dict[str, Any], seed: int, vocab_size: int,
                  batch_size: int) -> Iterator[Dict[str, np.ndarray]]:
    """Endless {"tokens": [batch_size, seq_len + 1] int32}, a new batch
    every step: Zipf unigrams over the vocabulary (rank r has weight
    r^-exponent), ranks mapped to token ids by a seeded permutation."""
    spec = mix["tokens"]
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown token distribution {spec['dist']!r}")
    rng = np.random.default_rng([int(seed), 0x7EA1])
    w = np.arange(1, vocab_size + 1, dtype=np.float64) ** -float(spec["exponent"])
    cdf = np.cumsum(w / w.sum())
    ids = rng.permutation(vocab_size).astype(np.int32)
    shape = (batch_size, int(mix["seq_len"]) + 1)
    while True:
        ranks = np.searchsorted(cdf, rng.random(shape), side="left")
        yield {"tokens": ids[np.minimum(ranks, vocab_size - 1)]}
