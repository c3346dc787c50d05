"""On-device token sampling for the serving engine.

TPU-native redesign of the reference's sampling story: FastGen gathers
last-token logits on device (ref: inference/v2/kernels/ragged_ops/
logits_gather/) and MII applies the HF LogitsProcessor chain GPU-side;
the v1 engine inherits HF `generate` sampling (ref:
inference/engine.py:613). Here the whole chain — repetition penalty,
temperature, top-k, top-p, and the categorical draw — runs INSIDE the
compiled decode program, so a decode step returns token ids ([S] int32)
instead of shipping [S, vocab] fp32 logits to the host (8-13 MB/step at
batch 64 — round 3's structural serving-latency tax).

Design notes (XLA-first):
- the categorical draw is GUMBEL-MAX: argmax(logits/T + G),
  G = -log(-log(U)). Exact for categoricals, needs no cumsum/sort, and
  is replayable: the same threefry key on any backend yields the same
  U, so a host oracle given the same logits and key reproduces the
  token bit-exactly (tested in tests/test_sampling.py).
- top-p needs sorted cumulative mass; sorting 32k logits per step is
  VPU-hostile, so the CANDIDATES come from lax.top_k — at width
  top_k when top-k is set (the HF chain order means top-p sees the
  top-k-filtered distribution, so the pool never needs to exceed k),
  else cand_width (default 256) — while their masses come from the
  full softmax (or the k survivors). Exact whenever the nucleus fits
  in the candidate width; the host oracle applies the same
  truncation. The reference's sampler post-processes on full vocab —
  document the difference, don't hide it.
- the DRAW also runs at pool width (round 5): gumbel noise over the
  [S, W] candidates + argmax mapped back through the top_k indices —
  per-step PRNG cost W draws per row, not 32k (the r4 bench's 28%
  sampled-decode tax was threefry over the full vocab every step).
  Pure temperature sampling (no top-k/top-p) keeps the full-vocab
  draw.
- repetition penalty needs the seen-token set; a [S, vocab] presence
  bitmap rides the decode scan and is updated with max(presence,
  one_hot(token)) — no scatter (XLA scatter carried a fixed multi-ms
  cost on TPU — measured on an earlier setup; not re-measured).
- per-sequence PRNG streams: key_i = fold_in(base, slot_i), step t uses
  fold_in(key_i, t) — batch composition never changes a sequence's
  stream (the host sampler had the same property via per-uid
  np.random.Generator).
"""

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SamplingConfig:
    """STATIC sampling knobs (compiled into the decode program; the
    engine caches one program per distinct config). Scalar knobs that
    could be traced (temperature, top_p, penalty) are still static
    here: serving configs change rarely and static values let XLA fold
    the filter chain."""

    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    cand_width: int = 256  # top-p candidate pool (exactness bound)

    @property
    def greedy(self) -> bool:
        return (not self.do_sample) or self.temperature <= 0.0

    @property
    def needs_presence(self) -> bool:
        return self.repetition_penalty != 1.0

    def key(self):
        return dataclasses.astuple(self)


def _penalized(logits, cfg: SamplingConfig, presence: Optional[Any]):
    """Repetition penalty (CTRL rule — divide positive seen logits,
    multiply negative; ref HF RepetitionPenaltyLogitsProcessor, which
    the reference engine inherits) + temperature."""
    logits = logits.astype(jnp.float32)
    if cfg.needs_presence and presence is not None:
        seen = presence.astype(jnp.bool_)
        pen = jnp.float32(cfg.repetition_penalty)
        logits = jnp.where(
            seen, jnp.where(logits > 0, logits / pen, logits * pen), logits)
    if not cfg.greedy:
        logits = logits / jnp.float32(max(cfg.temperature, 1e-6))
    return logits


def _pool_width(cfg: SamplingConfig, V: int) -> int:
    """Candidate-pool width: top-k bounds the nucleus when set (TopP
    sees the TOP-K-FILTERED distribution per the HF chain order), so
    the pool never needs to exceed k — pooling at cand_width when k=40
    would pay a 6x-wider lax.top_k for rows that can never win
    (r4 bench: the sampled-decode tax)."""
    k_eff = cfg.top_k if cfg.top_k and 0 < cfg.top_k < V else 0
    if k_eff:
        return min(V, k_eff)
    if 0.0 < cfg.top_p < 1.0:
        return min(V, cfg.cand_width)
    return 0  # pure temperature sampling: full vocab


def _pool_filter(logits, vals, cfg: SamplingConfig):
    """-inf out pool entries (descending [S, W]) cut by top-k/top-p.

    top-k keeps exactly the first k columns (the pool IS the top-k).
    top-p masses come from the top-k-renormalized distribution when
    top-k is set, else from the FULL softmax (pool renormalization
    would inflate every cumulative mass and push the nucleus cutoff
    too deep — r4 review finding). Keeps the smallest prefix reaching
    top_p (always at least the top-1)."""
    if 0.0 < cfg.top_p < 1.0:
        V = logits.shape[-1]
        k_eff = cfg.top_k if cfg.top_k and 0 < cfg.top_k < V else 0
        if k_eff:
            lse = jax.scipy.special.logsumexp(vals, axis=-1, keepdims=True)
        else:
            lse = jax.scipy.special.logsumexp(logits, axis=-1,
                                              keepdims=True)
        probs = jnp.exp(vals - lse)  # true masses, descending order
        csum = jnp.cumsum(probs, axis=-1)
        keep = (csum - probs) < jnp.float32(cfg.top_p)
        vals = jnp.where(keep, vals, -jnp.inf)
    return vals


def apply_penalty_and_filters(logits, cfg: SamplingConfig,
                              presence: Optional[Any] = None):
    """[S, V] f32 logits -> filtered logits (still [S, V]; filtered-out
    entries at -inf). Full-vocab form of the filter chain — kept for
    distribution-level tests; the sampling hot path draws from the
    candidate pool instead (sample_tokens) so the PRNG + argmax run
    over W candidates, not 32k logits."""
    logits = _penalized(logits, cfg, presence)
    if cfg.greedy:
        return logits
    V = logits.shape[-1]
    W = _pool_width(cfg, V)
    if not W:
        return logits
    vals = jax.lax.top_k(logits, W)[0]
    filt = _pool_filter(logits, vals, cfg)
    thr = jnp.min(jnp.where(jnp.isfinite(filt), filt, jnp.inf),
                  axis=-1)[:, None]
    return jnp.where(logits < thr, -jnp.inf, logits)


def sample_tokens(logits, cfg: SamplingConfig, keys=None, step=None,
                  presence: Optional[Any] = None):
    """[S, V] logits -> [S] int32 tokens.

    keys: [S] per-sequence PRNG keys (jax.random key array); step: [S]
    int32 per-sequence draw counters (folded into the key so fused
    multi-step decode advances each stream exactly like stepwise).

    The draw is gumbel-max over the CANDIDATE POOL (top-k/top-p
    survivors, [S, W]): exact for the filtered categorical, and the
    per-step PRNG cost is W draws per row instead of V (the r4 bench's
    28% sampled-decode tax was threefry over [32, 32000] every step)."""
    logits = _penalized(logits, cfg, presence)
    if cfg.greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    V = logits.shape[-1]
    W = _pool_width(cfg, V)
    if W:
        vals, idx = jax.lax.top_k(logits, W)  # [S, W] descending
        pool = _pool_filter(logits, vals, cfg)
    else:
        pool, idx = logits, None

    def draw(key, t, row):
        u = jax.random.uniform(
            jax.random.fold_in(key, t), row.shape,
            minval=jnp.float32(1e-20), maxval=1.0)
        g = -jnp.log(-jnp.log(u))
        return jnp.argmax(row + g).astype(jnp.int32)

    choice = jax.vmap(draw)(keys, step, pool)
    if idx is None:
        return choice
    return jnp.take_along_axis(idx, choice[:, None], axis=1)[:, 0] \
        .astype(jnp.int32)


def update_presence(presence, tokens):
    """presence [S, V] uint8 | tokens [S] -> updated presence (one_hot
    max, not scatter)."""
    oh = jax.nn.one_hot(tokens, presence.shape[-1], dtype=presence.dtype)
    return jnp.maximum(presence, oh)


def presence_from_prompts(prompts, vocab: int, width: int):
    """Host-side initial presence for `width` slots from python/numpy
    token lists (rows beyond len(prompts) stay empty)."""
    import numpy as np

    out = np.zeros((width, vocab), np.uint8)
    for i, p in enumerate(prompts):
        toks = np.asarray(p, np.int64).ravel()
        toks = toks[(toks >= 0) & (toks < vocab)]
        out[i, toks] = 1
    return out


def host_oracle_token(logits, cfg: SamplingConfig, key, t,
                      presence_row=None) -> int:
    """Replay one draw host-side (numpy logits + the same key/step):
    must reproduce sample_tokens bit-exactly — the parity contract the
    tests pin down. Runs the SAME pooled draw as the device path (the
    PRNG stream depends on the pool width, so the oracle must pool
    identically)."""
    import numpy as np

    row = jnp.asarray(np.asarray(logits, np.float32))[None]
    pres = (jnp.asarray(np.asarray(presence_row, np.uint8))[None]
            if presence_row is not None else None)
    if cfg.greedy:
        return int(jnp.argmax(_penalized(row, cfg, pres)[0]))
    keys = jnp.asarray(key)[None]
    steps = jnp.asarray(t, jnp.int32)[None]
    return int(sample_tokens(row, cfg, keys, steps, pres)[0])


def block_unmask(logits, tokens, active, cfg: SamplingConfig, keys, step, *,
                 block_length: int, mask_id: int, reveal: int):
    """The epilogue of one DENOISING PASS of a model that generates by
    diffusion over blocks: [S, V] logits of the pass's rows, `tokens`
    [S] int32 the rows were fed, `active` [S] bool (the rows that are a
    position to GENERATE of a block being denoised; every other row, a
    block's fixed head of prompt tokens, a prompt chunk's, a commit
    pass's or padding, comes back as it went in, the mask id too if a
    prompt holds it) -> [S] int32, the tokens the SAME rows are fed
    next pass. A block is block_length adjacent rows from a multiple of
    block_length on. Under the device scope `block_unmask`.

    Every active row that held `mask_id` samples a token from the logits AT
    its own position, the mask id excluded (sample_tokens: greedy, or
    the chain drawn from `keys` / `step`), and takes as its confidence
    the probability the softmax of those logits gives that token; of a block's masked rows
    the `reveal` most confident (ties to the lowest position) keep
    their token, the others stay masked (the publisher's
    `low_confidence_static` rule). Nothing crosses to the host: the
    look-ahead gathers the next pass's input from this array."""
    with jax.named_scope("block_unmask"):
        # a generated token is never the mask itself: a position that
        # drew it would stay masked and the passes, counted on the host,
        # would not empty the block
        lg = jnp.where(jnp.arange(logits.shape[-1]) == mask_id, -jnp.inf,
                       logits.astype(jnp.float32))
        sampled = sample_tokens(lg, cfg, keys, step)
        conf = jnp.exp(
            jnp.take_along_axis(lg, sampled[:, None], axis=-1)[:, 0]
            - jax.scipy.special.logsumexp(lg, axis=-1))
        masked = ((tokens == mask_id) & active).reshape(-1, block_length)
        # lax.top_k is stable: of equal confidences the lower position
        rank = jax.lax.top_k(
            jnp.where(masked, conf.reshape(-1, block_length), -1.0),
            min(reveal, block_length))[1]
        chosen = jnp.any(
            rank[:, :, None] == jnp.arange(block_length)[None, None], axis=1)
        return jnp.where((chosen & masked).reshape(-1), sampled, tokens)
