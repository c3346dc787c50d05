"""Plain float32 reference of the SDAR-MoE decoder (`sdar_moe`): Qwen3-MoE's
layer under a BLOCK-CAUSAL mask, and the publisher's sampler in its plain
form (generation by diffusion over blocks).

Straight `jax.numpy`, one layer and one sequence at a time, no kernels,
no cache, no sorting; independent of models/transformer.py and
inference/. The layer, all alike:

    n1 = RMSNorm(x)
    q = Wq n1 (H heads of D), k = Wk n1, v = Wv n1 (KV heads of D), no bias
    q, k: RMSNorm over each head's D values (ONE learned scale of D for
          all heads of q, one for k), BEFORE rotary
    rotary over the whole head (rotate_half pairing), theta rope_theta
    a = softmax(q k^T / sqrt(D)) v, H / KV query heads a KV head, under
        THE MASK: position i sees position j iff j // B <= i // B
        (B = block_length): bidirectional inside a block, causal across
    h = x + Wo a
    n2 = RMSNorm(h);  p = softmax(Wr n2) over the experts, in float32
    S = the num_experts_per_tok largest of p (ties to the lowest index);
        weights p[S] / sum p[S] (norm_topk_prob true)
    y = h + sum over e in S of w[e] * Wdown_e(silu(Wgate_e n2) * Wup_e n2)

then the final RMSNorm and an untied head. The logits at position i are
the distribution of the token AT position i: a position fed as
`mask_token_id` predicts itself, there is no shift.

`generate` is the sampler, greedy, by full forward passes: the prompt's
whole blocks stand; a block starts as the prompt's remainder followed by
the mask id; a denoising pass runs the model over everything so far and
the block, takes at every masked position the argmax (the mask id itself
excluded) and its probability, and reveals ceil(B / T) of them, the most
probable first (ties to the lowest position); when none is masked the
block stands and the next begins. There is no commit pass here: it
exists in a system that caches K/V, to leave the block's final K/V, and
computes nothing this reference does not (the final tokens' forward).

Every expert is applied to every token and combined by a [tokens,
experts] weight matrix, a slice of `EXPERT_SLICE` experts at a time so
that a layer's 128 float32 experts (2.4 GB at the published widths)
never sit on the device together beside a live engine. Weights arrive
one layer at a time in the names and shapes of the training layout (wq
[E,H,D], wk/wv [E,KV,D], wo [H,D,E], q_norm_scale / k_norm_scale [D],
w_router [E,X], w_gate/w_in [X,E,F], w_out [X,F,E], ln1_scale/ln2_scale
[E]) in whatever dtype the system holds them and are widened to float32
here. Every matmul runs under default_matmul_precision("highest").

`forward_logits(..., mutate=)` computes deliberately WRONG models (the
tests and the benchmark's limits are set against them): `causal_in_block`
(the plain causal mask), `shifted_logits` (position i's logits read at
i - 1, the next-token convention), `qk_norm_after_rope`,
`no_topk_renorm` (the raw top-k probabilities), `router_bf16` (the
router's scores and softmax in bfloat16, where float32 is stated). `positions=` places the
tokens elsewhere than 0..S-1 (the control `positions_advance`: a pass's
rows at positions that move with the pass).

Departures from the published description. `assumed`, because the
catalog row gives neither: `block_length` 4 and `denoising_steps` 4 (the
publisher's generation settings for its Chat models, as recalled), the
reveal rule `low_confidence_static` (the card's dynamic rule with a
threshold of 0.9 falls back to it whenever no confidence passes the
threshold, which is always under seeded random weights), `mask_token_id`
151,669, no shift of the logits, and that a generated token is never the
mask id (its logit is left out of the argmax and of the confidence: a
trained model does not predict it; random weights would, once in
151,936 draws). The noise schedule is training's; nothing here reads it.
"""

import functools
import json
import math
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MUTANTS = ("causal_in_block", "shifted_logits", "qk_norm_after_rope",
           "no_topk_renorm", "router_bf16")
BLOCK_LENGTH = 4        # where the configuration states none
MASK_TOKEN_ID = 151669
EXPERT_SLICE = 16       # experts whose float32 weights are live together
VOCAB_SLICE = 32768     # rows of the head widened to float32 together


def block_length(hf) -> int:
    return int(hf.get("block_length", BLOCK_LENGTH))


def mask_token_id(hf) -> int:
    return int(hf.get("mask_token_id", MASK_TOKEN_ID))


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, positions, theta):
    """x [S, H, D] at `positions` [S]; rotate_half pairing."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = positions.astype(F32)[:, None] * inv[None, :]          # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def routed_mlp(n, lw, hf, mutate: Optional[str] = None):
    """The routed block alone on normed activations n [S, E]. Float32."""
    X, k = hf["num_experts"], hf["num_experts_per_tok"]
    if mutate == "router_bf16":  # the precision below the one stated
        low = jnp.bfloat16
        p = jax.nn.softmax(jnp.einsum(
            "se,ex->sx", n.astype(low), lw["w_router"].astype(low)),
            axis=-1).astype(F32)
    else:
        logits = jnp.einsum("se,ex->sx", n, lw["w_router"].astype(F32))
        p = jax.nn.softmax(logits.astype(F32), axis=-1)
    _, chosen = jax.lax.top_k(p, k)
    weights = jnp.sum(jax.nn.one_hot(chosen, X, dtype=F32), -2) * p
    if hf.get("norm_topk_prob") and mutate != "no_topk_renorm":
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    out = jnp.zeros_like(n)
    for e0 in range(0, X, EXPERT_SLICE):
        e = slice(e0, min(X, e0 + EXPERT_SLICE))
        gate = jnp.einsum("se,xef->sxf", n, lw["w_gate"][e].astype(F32))
        up = jnp.einsum("se,xef->sxf", n, lw["w_in"][e].astype(F32))
        each = jnp.einsum("sxf,xfe->sxe", jax.nn.silu(gate) * up,
                          lw["w_out"][e].astype(F32))
        out = out + jnp.einsum("sx,sxe->se", weights[:, e], each)
    return out


def _layer(x, positions, lw, hf, mutate: Optional[str] = None):
    """One decoder layer on ONE sequence x [S, E] float32."""
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    B = block_length(hf)
    n = _rms(x, lw["ln1_scale"], eps)
    q = jnp.einsum("se,ehd->shd", n, lw["wq"].astype(F32))
    k = jnp.einsum("se,ehd->shd", n, lw["wk"].astype(F32))
    v = jnp.einsum("se,ehd->shd", n, lw["wv"].astype(F32))
    S, H, D = q.shape
    KV = k.shape[1]
    if mutate == "qk_norm_after_rope":
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    q = _rms(q, lw["q_norm_scale"], eps)
    k = _rms(k, lw["k_norm_scale"], eps)
    if mutate != "qk_norm_after_rope":
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(D)
    # the mask is over where a token STANDS in the sequence, not over the
    # rotary position it was given
    i = jnp.arange(S)
    if mutate == "causal_in_block":
        see = i[None, :] <= i[:, None]
    else:
        see = (i // B)[None, :] <= (i // B)[:, None]
    s = jnp.where(see[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    h = x + jnp.einsum("shd,hde->se", a, lw["wo"].astype(F32))
    return h + routed_mlp(_rms(h, lw["ln2_scale"], eps), lw, hf, mutate)


_READ = ("rms_norm_eps", "rope_theta", "block_length", "num_experts",
         "num_experts_per_tok", "norm_topk_prob")


@functools.lru_cache(maxsize=None)
def _layer_fn(read: str, mutate: Optional[str]):
    """The jitted layer for the keys the layer reads (ONE program a
    configuration and mutant however often the forward is called:
    `generate` calls it a pass)."""
    hf = json.loads(read)
    return jax.jit(lambda x, pos, lw: _layer(x, pos, lw, hf, mutate))


def forward_logits(top: Dict[str, Any], layer_weights: Callable[[int], Dict],
                   tokens, hf: Dict[str, Any], mutate: Optional[str] = None,
                   positions=None, rows=None):
    """Logits [B, S, V] float32 of tokens [B, S]: row i of the result is
    the distribution of the token AT position i. `top` holds `embed`
    [V, E], `ln_f_scale` [E] and `lm_head` [E, V]; `layer_weights(l)`
    returns layer l's weights. `mutate` is None or one of MUTANTS;
    `positions` [B, S] the rotary positions (None: 0..S-1); `rows`
    [B, R] the positions whose logits are wanted, [B, R, V] then (at the
    published widths every position's are 0.8 GB a sequence). The head
    is widened a slice of the vocabulary at a time."""
    if mutate is not None and mutate not in MUTANTS:
        raise ValueError(f"unknown mutant {mutate!r}; there are {MUTANTS}")
    tokens = np.asarray(tokens)
    if positions is None:
        positions = np.broadcast_to(np.arange(tokens.shape[1]), tokens.shape)
    layer = _layer_fn(json.dumps({k: hf.get(k) for k in _READ}), mutate)
    with jax.default_matmul_precision("highest"):
        xs = [jnp.asarray(top["embed"])[jnp.asarray(row)].astype(F32)
              for row in tokens]
        for l in range(hf["num_hidden_layers"]):
            lw = layer_weights(l)
            xs = [layer(x, jnp.asarray(pos, jnp.int32), lw)
                  for x, pos in zip(xs, positions)]
            del lw  # a layer's weights never beside the next one's
        x = jnp.stack(xs)
        del xs
        if mutate == "shifted_logits":  # position i read at i - 1
            x = jnp.roll(x, 1, axis=1)
        if rows is not None:
            x = jnp.take_along_axis(
                x, jnp.asarray(rows, jnp.int32)[:, :, None], axis=1)
        x = _rms(x, jnp.asarray(top["ln_f_scale"]), hf["rms_norm_eps"])
        head = jnp.asarray(top["lm_head"])
        return jnp.concatenate([
            jnp.einsum("bse,ev->bsv", x, head[:, v:v + VOCAB_SLICE].astype(F32))
            for v in range(0, head.shape[1], VOCAB_SLICE)], axis=-1)


def reveal(logits, block: Sequence[int], hf, n_reveal: int,
           n_fixed: int = 0) -> List[int]:
    """One denoising pass's outcome: `logits` [B, V] at the block's
    positions, `block` its tokens as fed (the first n_fixed the
    prompt's, never revealed whatever they hold) -> the block with the
    n_reveal most confident of its masked positions revealed."""
    mask = mask_token_id(hf)
    lg = np.array(logits, np.float64)
    lg[:, mask] = -np.inf
    tok = lg.argmax(-1)
    p = np.exp(lg - lg.max(-1, keepdims=True))
    conf = p[np.arange(len(tok)), tok] / p.sum(-1)
    masked = [i for i, t in enumerate(block) if t == mask and i >= n_fixed]
    # the most confident first; of equals the lower position
    order = sorted(masked, key=lambda i: (-conf[i], i))
    out = list(block)
    for i in order[:n_reveal]:
        out[i] = int(tok[i])
    return out


def generate(top, layer_weights, prompt: Sequence[int], max_new_tokens: int,
             hf: Dict[str, Any], denoising_steps: Optional[int] = None,
             eos_token_id: Optional[int] = None, width: int = 128
             ) -> List[int]:
    """The sampler, greedy, by full forward passes over sequences padded
    to `width` (what follows a block's end cannot reach it): the tokens
    generated for `prompt`, cut at `max_new_tokens` or after
    `eos_token_id` in a finished block."""
    B, mask = block_length(hf), mask_token_id(hf)
    n_reveal = -(-B // (denoising_steps or B))
    prompt = [int(t) for t in prompt]
    whole = len(prompt) - len(prompt) % B
    seq, out = prompt[:whole], []
    block = prompt[whole:] + [mask] * (B - len(prompt) + whole)
    n_fixed = len(prompt) - whole
    while len(out) < max_new_tokens:
        while mask in block[n_fixed:]:
            toks = np.zeros((1, max(width, len(seq) + B)), np.int32)
            toks[0, :len(seq) + B] = seq + block
            logits = forward_logits(top, layer_weights, toks, hf)
            block = reveal(np.asarray(logits[0, len(seq):len(seq) + B]),
                           block, hf, n_reveal, n_fixed)
        new = block[n_fixed:][:max_new_tokens - len(out)]
        if eos_token_id is not None and eos_token_id in new:
            return out + new[:new.index(eos_token_id) + 1]
        out += new
        seq, block, n_fixed = seq + block, [mask] * B, 0
    return out
