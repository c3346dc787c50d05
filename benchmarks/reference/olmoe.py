"""Plain float32 reference of the OLMoE decoder (OlmoeForCausalLM).

Straight `jax.numpy`, one layer at a time, no kernels, no cache, no
sorting, no capacity; independent of models/transformer.py,
inference/model.py and moe/. It follows the published block:

    n1 = RMSNorm(x)
    q = RMSNorm_q(Wq n1),  k = RMSNorm_k(Wk n1),  v = Wv n1
    h = x + Wo Attn(rope(q), rope(k), v)
    n2 = RMSNorm(h);  p = softmax(Wr n2) over the experts, in float32
    S = the num_experts_per_tok largest of p;  weights p[S] AS THEY ARE
        (norm_topk_prob false; true divides them by their sum)
    y = h + sum over e in S of p[e] * Wdown_e(silu(Wgate_e n2) * Wup_e n2)

The two QK norms run over the WHOLE projected vector (all heads of a
token together, num_heads * head_dim values) with a learned scale of
that length, before the split into heads and before rope; rotary
embeddings in the split-halves (rotate_half) pairing over the whole
head; attention causal with no window, every query head with its own
KV head group (h // (H / KV); OLMoE has H == KV); RMSNorm in float32; an
untied output head; next-token cross-entropy as the token mean.

The routed block is computed the plain way: EVERY expert is applied to
every token, and the outputs are combined by a [tokens, experts] weight
matrix that is zero outside the chosen experts. Ties in the top-k go
to the lowest expert index (`lax.top_k`; torch.topk leaves ties
unspecified).

Weights arrive one layer at a time in the names and shapes of the
training layout (wq [E,H,D], wk/wv [E,KV,D], wo [H,D,E], q_norm_scale
[H,D], k_norm_scale [KV,D], w_router [E,X], w_gate/w_in [X,E,F], w_out
[X,F,E], ln1_scale/ln2_scale [E]) in whatever dtype the system holds
them, and are widened to float32 here, so a system that stores bf16 is
compared against exact arithmetic on its own values. Every matmul runs
under default_matmul_precision("highest"): on a TPU a float32 matmul
is otherwise a single bf16 pass.

`forward_logits(..., mutate=)` computes three deliberately WRONG models
(the tests and the tolerance of the benchmark's logits check are set
against them): "renormalised" (the top-k weights divided by their sum),
"no_qk_norm" (both norms left out) and "k_minus_1" (one expert fewer).

Departures from the published description: none in the mathematics.
`clip_qkv` is null and `attention_bias` false in the published
configuration; neither is read.
"""

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MUTANTS = ("renormalised", "no_qk_norm", "k_minus_1")


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x [B, S, H, D]; positions 0..S-1; rotate_half pairing."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]      # [S, D/2]
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def routed_mlp(n, lw, hf, mutate: Optional[str] = None):
    """The routed block alone on normed activations n [..., E]: the sum
    over the chosen experts of p[e] * expert_e(n). Float32."""
    top_k = hf["num_experts_per_tok"] - (mutate == "k_minus_1")
    logits = jnp.einsum("...e,ex->...x", n, lw["w_router"].astype(F32))
    p = jax.nn.softmax(logits.astype(F32), axis=-1)
    _, chosen = jax.lax.top_k(p, top_k)
    # [..., X]: p where the expert is among the chosen, else 0
    weights = jnp.sum(jax.nn.one_hot(chosen, p.shape[-1], dtype=F32), -2) * p
    if hf.get("norm_topk_prob") or mutate == "renormalised":
        weights = weights / jnp.sum(weights, -1, keepdims=True)
    gate = jnp.einsum("...e,xef->...xf", n, lw["w_gate"].astype(F32))
    up = jnp.einsum("...e,xef->...xf", n, lw["w_in"].astype(F32))
    each = jnp.einsum("...xf,xfe->...xe", jax.nn.silu(gate) * up,
                      lw["w_out"].astype(F32))
    return jnp.einsum("...x,...xe->...e", weights, each)


def router_margin(n, lw, hf):
    """How far the router is from choosing another set of experts on
    normed activations n [..., E]: the gap between the smallest chosen
    probability and the largest one left out, as a share of the former.
    A margin under the rounding of the system compared (bf16: 2^-8) is
    an expert that system may swap, which moves that token's logits by
    one expert's output and is no fault."""
    k = hf["num_experts_per_tok"]
    logits = jnp.einsum("...e,ex->...x", n, lw["w_router"].astype(F32))
    p, _ = jax.lax.top_k(jax.nn.softmax(logits.astype(F32), axis=-1), k + 1)
    return (p[..., k - 1] - p[..., k]) / p[..., k - 1]


def _layer(x, lw, hf, mutate: Optional[str] = None):
    """One decoder layer on x [B, S, E] float32, and its router's
    margin [B, S]."""
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    n = _rms(x, lw["ln1_scale"], eps)
    q = jnp.einsum("bse,ehd->bshd", n, lw["wq"].astype(F32))
    k = jnp.einsum("bse,ehd->bshd", n, lw["wk"].astype(F32))
    v = jnp.einsum("bse,ehd->bshd", n, lw["wv"].astype(F32))
    B, S, H, D = q.shape
    KV = k.shape[2]
    if mutate != "no_qk_norm":
        # over the whole projected vector, then back into heads
        q = _rms(q.reshape(B, S, H * D),
                 lw["q_norm_scale"].reshape(H * D), eps).reshape(B, S, H, D)
        k = _rms(k.reshape(B, S, KV * D),
                 lw["k_norm_scale"].reshape(KV * D), eps).reshape(B, S, KV, D)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(mask[None, None], s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    h = x + jnp.einsum("bshd,hde->bse", a, lw["wo"].astype(F32))
    n2 = _rms(h, lw["ln2_scale"], eps)
    return h + routed_mlp(n2, lw, hf, mutate), router_margin(n2, lw, hf)


def forward_logits(top: Dict[str, Any], layer_weights: Callable[[int], Dict],
                   tokens, hf: Dict[str, Any], mutate: Optional[str] = None):
    """Logits [B, S, V] float32 of tokens [B, S]. `top` holds `embed`
    [V, E], `ln_f_scale` [E] and `lm_head` [E, V]; `layer_weights(l)`
    returns layer l's weights (so the model never sits on the device
    twice). `mutate` is None or one of MUTANTS."""
    if mutate is not None and mutate not in MUTANTS:
        raise ValueError(f"unknown mutant {mutate!r}; there are {MUTANTS}")
    return _forward(top, layer_weights, tokens, hf, mutate)[0]


def _forward(top, layer_weights, tokens, hf, mutate):
    layer = jax.jit(lambda x, lw: _layer(x, lw, hf, mutate))
    margins = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(top["embed"])[jnp.asarray(tokens)].astype(F32)
        for l in range(hf["num_hidden_layers"]):
            x, margin = layer(x, layer_weights(l))
            margins.append(margin)
        x = _rms(x, jnp.asarray(top["ln_f_scale"]), hf["rms_norm_eps"])
        return jnp.einsum("bse,ev->bsv", x,
                          jnp.asarray(top["lm_head"]).astype(F32)), \
            jnp.stack(margins)


def router_margins(top, layer_weights, tokens, hf):
    """[layers, B, S]: `router_margin` of every layer at every token of
    the model as published (what `benchmarks/logits_audit.py` sets
    beside the served logits' errors)."""
    return _forward(top, layer_weights, tokens, hf, None)[1]


def loss(top, layer_weights, tokens, hf, mutate: Optional[str] = None) -> float:
    """Token-mean next-token cross-entropy of tokens [B, S + 1]."""
    tokens = np.asarray(tokens)
    logits = forward_logits(top, layer_weights, tokens[:, :-1], hf, mutate)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.asarray(tokens[:, 1:])
    return float(-jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1)))
