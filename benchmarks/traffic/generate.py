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
Training mixes (`runner: train`) are an endless stream of token
batches, Zipf-distributed unigrams so the loss can fall.
"""

import dataclasses
from typing import Any, Dict, Iterator, List

import numpy as np


def _quantiles(rng: np.random.Generator, n: int) -> np.ndarray:
    """n evenly spaced probabilities in (0, 1), in a seeded order."""
    return rng.permutation((np.arange(n) + 0.5) / n)


def draw_lengths(rng: np.random.Generator, spec: Dict[str, Any], n: int) -> np.ndarray:
    """n integer lengths from a length spec: {"dist": "lognormal",
    "median", "sigma", "min", "max"}."""
    if spec["dist"] == "lognormal":
        from scipy.special import ndtri

        x = np.exp(np.log(spec["median"])
                   + spec["sigma"] * ndtri(_quantiles(rng, n)))
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


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
    alen = draw_lengths(rng, mix["answer_len"], n)
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
