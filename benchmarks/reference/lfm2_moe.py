"""Plain float32 reference of the LFM2-MoE decoder (`model_type:
lfm2_moe`): layers of two kinds, a gated short convolution or grouped-
query attention, each followed by a dense or a routed SwiGLU.

Straight `jax.numpy`, one layer at a time, no kernels, no cache, no
state slot, no sorting; independent of `deepspeed_tpu/`. With `x` the
residual stream and every `N_*` an RMSNorm (float32, eps `norm_eps`)
with its own scale:

    layer:  x <- x + Op(N_op(x));  x <- x + F(N_ffn(x))
    Op, a `conv` layer, on h = N_op(x):
        [B; C; X] = W_in h                      (E -> 3E, no bias)
        u_t = B_t * X_t
        v_t = sum_{j=0..K-1} taps[:, j] * u_{t-(K-1)+j}
              (depthwise, causal, K = conv_L_cache taps a channel, no
              bias, u zero before the sequence starts, NO activation):
              HERE an explicit sum over K shifted copies of u over the
              WHOLE sequence
        y_t = W_out (C_t * v_t)
    Op, a `full_attention` layer, on h:
        q = W_q h (H x D), k = W_k h, v = W_v h (KV x D), no bias
        q <- N_q(q), k <- N_k(k): RMSNorm over each head's D values
              with ONE scale of D shared by all heads
        RoPE (split halves, theta `rope_theta`, no scaling) on q and k
        causal softmax over the whole context, scale 1/sqrt(D); W_o
    F, the first `num_dense_layers` layers: W_2(silu(W_1 m) * W_3 m) of
        width `intermediate_size`
    F, the others: sum over the chosen experts e of w_e * expert_e(m),
        each a SwiGLU of width `moe_intermediate_size`; every expert is
        computed for every token and masked by the router's choice
    router on m: s = sigmoid(W_g m) over all experts, float32; the
        `num_experts_per_tok` experts with the largest s + b (b the
        layer's `expert_bias`, for the CHOICE alone); w = s_chosen /
        (sum s_chosen + 1e-6) (`norm_topk_prob`); times
        `routed_scaling_factor`
    logits = E N_out(x), E the embedding (the head is tied to it)

Departures from the published description, each where it is made; the
configuration file lists them under `assumed`:

- config.json cannot say, and there is no network here to read the
  modelling code: the split order [B; C; X] of W_in's output, no
  activation after the convolution, the QK-norm's place before RoPE,
  and the tied head (the published 8.3 B parameters count ONE
  vocabulary matrix of 134 M) are the family's as its description
  gives them, ASSUMED.
- `expert_bias` is float32 at the publisher; it arrives here in
  whatever the system holds (bf16) and is widened.
- ties in the top-k go to the lowest expert index (`lax.top_k`).

Weights arrive in the names and shapes of `models/transformer.init`:
`top` holds `embed` [V, E], `ln_f_scale` [E]; the operators' stacks by
kind `conv_in` [Nc, E, 3E], `conv_taps` [Nc, E, K] (oldest tap first),
`conv_out` [Nc, E, E] and `attn_wq` [Na, E, H, D], `attn_wk` /
`attn_wv` [Na, E, KV, D], `attn_wo` [Na, H, D, E], `attn_q_norm_scale`
/ `attn_k_norm_scale` [Na, D], layer l taking the entry of its place
among the layers of its kind; and the leading dense layers' leaves
`dense_<name>` [n_dense, ...]. `layer_weights(l)` returns routed layer
l's: ln1_scale, ln2_scale [E]; w_router [E, X]; expert_bias [X];
w_gate / w_in [X, E, F], w_out [X, F, E] (a dense layer: w_gate / w_in
[E, Fd], w_out [Fd, E]). They come in whatever dtype the system holds
and are widened to float32 HERE, one expert at a time. Every matmul
runs under default_matmul_precision("highest").

`forward_logits(..., mutate=)` computes deliberately WRONG models (the
tests and the limits of the benchmark's logits check are set against
them): "no_state_carry" (the convolution sees zeros for every input
before the current one: what a slot that is cleared between steps
gives), "taps_reversed" (newest tap first), "bias_in_weights" (s + b
used as the weight too), "k_minus_1" (one expert fewer), "no_qk_norm",
and "float8_cache" (K, V and the convolution's carried inputs rounded
to float8_e4m3: a cache below the bf16 the file states).
"""

import json
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MUTANTS = ("no_state_carry", "taps_reversed", "bias_in_weights", "k_minus_1",
           "no_qk_norm", "float8_cache")
DENSE_PREFIX = "dense_"
KINDS = {"conv": "conv_", "full_attention": "attn_"}


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x [B, S, H, D] rotated at positions 0..S-1, split-halves pairing."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]      # [S, D/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _f8(x):
    return x.astype(jnp.float8_e4m3fn).astype(F32)


def _swiglu(n, w_gate, w_in, w_out):
    return (jax.nn.silu(n @ w_gate.astype(F32)) * (n @ w_in.astype(F32))
            ) @ w_out.astype(F32)


def short_conv(h, ow, hf, mutate=None):
    """The gated short convolution on normed h [B, S, E]."""
    E, K = h.shape[-1], hf["conv_L_cache"]
    bcx = h @ ow["conv_in"].astype(F32)
    b, c, x = bcx[..., :E], bcx[..., E:2 * E], bcx[..., 2 * E:]
    u = b * x
    taps = ow["conv_taps"].astype(F32)                       # [E, K]
    if mutate == "taps_reversed":
        taps = taps[:, ::-1]
    past = _f8(u) if mutate == "float8_cache" else u
    v = u * taps[:, K - 1]
    for j in range(K - 1):                 # tap j multiplies u_{t-(K-1)+j}
        back = K - 1 - j
        shifted = jnp.pad(past, ((0, 0), (back, 0), (0, 0)))[:, :u.shape[1]]
        if mutate == "no_state_carry":
            shifted = jnp.zeros_like(shifted)
        v = v + shifted * taps[:, j]
    return (c * v) @ ow["conv_out"].astype(F32)


def attention(h, ow, hf, mutate=None):
    """Grouped-query attention with a per-head QK-norm on normed h
    [B, S, E]."""
    eps, theta = hf["norm_eps"], float(rope_theta(hf))
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    S = h.shape[1]
    q = jnp.einsum("bse,ehd->bshd", h, ow["attn_wq"].astype(F32))
    k = jnp.einsum("bse,ehd->bshd", h, ow["attn_wk"].astype(F32))
    v = jnp.einsum("bse,ehd->bshd", h, ow["attn_wv"].astype(F32))
    if mutate != "no_qk_norm":
        q = _rms(q, ow["attn_q_norm_scale"], eps)
        k = _rms(k, ow["attn_k_norm_scale"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    if mutate == "float8_cache":
        k, v = _f8(k), _f8(v)
    G = H // KV
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return jnp.einsum("bshd,hde->bse", o, ow["attn_wo"].astype(F32))


def rope_theta(hf):
    return hf.get("rope_theta") or hf["rope_parameters"]["rope_theta"]


def route(n, lw, hf, mutate=None):
    """Normed activations n [T, E] -> the [T, X] combine weights (zero
    outside the chosen), and the router's margin: how far the smallest
    chosen biased score lies above the largest left out, as a share of
    the former."""
    k = hf["num_experts_per_tok"] - (mutate == "k_minus_1")
    s = jax.nn.sigmoid(n @ lw["w_router"].astype(F32))
    biased = s + lw["expert_bias"].astype(F32) if hf.get(
        "use_expert_bias") else s
    top, chosen = jax.lax.top_k(biased, k + 1)
    pick = jnp.sum(jax.nn.one_hot(chosen[..., :k], s.shape[-1], dtype=F32), -2)
    w = pick * (biased if mutate == "bias_in_weights" else s)
    if hf.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    w = w * float(hf.get("routed_scaling_factor", 1.0))
    return w, (top[..., k - 1] - top[..., k]) / jnp.abs(top[..., k - 1])


def moe(n, lw, hf, mutate=None):
    """The routed block on normed n [T, E]: every expert applied to
    every token, one at a time, weighted by its column."""
    w, margin = route(n, lw, hf, mutate)

    def expert(acc, xs):
        w_gate, w_in, w_out, col = xs
        return acc + col[:, None] * _swiglu(n, w_gate, w_in, w_out), None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(n),
                          (lw["w_gate"], lw["w_in"], lw["w_out"], w.T))
    return out, margin


def _layer(x, lw, ow, kind, hf, mutate=None):
    """One layer on x [B, S, E] float32 -> (x, the router's margin
    [B, S], ones for a dense layer). `lw`: its norms and FFN; `ow`: its
    operator's leaves; a layer is routed if it has a router."""
    eps = hf["norm_eps"]
    op = short_conv if kind == "conv" else attention
    x = x + op(_rms(x, lw["ln1_scale"], eps), ow, hf, mutate)
    m = _rms(x, lw["ln2_scale"], eps)
    flat = m.reshape(-1, m.shape[-1])
    if "w_router" in lw:
        y, margin = moe(flat, lw, hf, mutate)
        margin = margin.reshape(m.shape[:-1])
    else:
        y = _swiglu(flat, lw["w_gate"], lw["w_in"], lw["w_out"])
        margin = jnp.ones(m.shape[:-1], F32)
    return x + y.reshape(m.shape), margin


def forward_logits(top: Dict[str, Any], layer_weights: Callable[[int], Dict],
                   tokens, hf: Dict[str, Any], mutate: Optional[str] = None):
    """Logits [B, S, V] float32 of tokens [B, S] (see the module
    docstring for `top` and `layer_weights`). `mutate` is None or one of
    MUTANTS."""
    if mutate is not None and mutate not in MUTANTS:
        raise ValueError(f"unknown mutant {mutate!r}; there are {MUTANTS}")
    return _forward(top, layer_weights, tokens, hf, mutate)[0]


_JITTED = {}  # (the configuration as text, mutant) -> the jitted layer


def _jitted_layer(hf, mutate):
    """One compiled layer a configuration and mutant, kept: a caller
    that checks many sequences of one shape compiles once."""
    key = (json.dumps(hf, sort_keys=True, default=str), mutate)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(
            lambda x, lw, ow, kind: _layer(x, lw, ow, kind, hf, mutate),
            static_argnums=3)
    return _JITTED[key]


def _forward(top, layer_weights, tokens, hf, mutate):
    layer = _jitted_layer(hf, mutate)
    n_dense = int(hf.get("num_dense_layers", 0))
    seen = {kind: 0 for kind in KINDS}
    margins = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(top["embed"])[jnp.asarray(tokens)].astype(F32)
        for l, kind in enumerate(hf["layer_types"]):
            if l < n_dense:
                lw = {k[len(DENSE_PREFIX):]: jnp.asarray(v)[l]
                      for k, v in top.items() if k.startswith(DENSE_PREFIX)}
            else:
                lw = layer_weights(l - n_dense)
            # the operator's leaves: entry (layers of this kind so far)
            ow = {k: jnp.asarray(v)[seen[kind]] for k, v in top.items()
                  if k.startswith(KINDS[kind])}
            seen[kind] += 1
            x, margin = layer(x, lw, ow, kind)
            margins.append(margin)
        x = _rms(x, jnp.asarray(top["ln_f_scale"]), hf["norm_eps"])
        # the head is the embedding (tied) unless the tree has its own
        if "lm_head" in top:
            logits = jnp.einsum("bse,ev->bsv", x,
                                jnp.asarray(top["lm_head"]).astype(F32))
        else:
            logits = jnp.einsum("bse,ve->bsv", x,
                                jnp.asarray(top["embed"]).astype(F32))
        return logits, jnp.stack(margins)


def router_margins(top, layer_weights, tokens, hf):
    """[layers, B, S]: the router's margin of every layer at every
    token of the model as published (1 for a dense layer): what
    `benchmarks/logits_audit.py` sets beside the served logits' errors."""
    return _forward(top, layer_weights, tokens, hf, None)[1]


def loss(top, layer_weights, tokens, hf, mutate: Optional[str] = None) -> float:
    """Token-mean next-token cross-entropy of tokens [B, S + 1]."""
    tokens = np.asarray(tokens)
    logits = forward_logits(top, layer_weights, tokens[:, :-1], hf, mutate)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.asarray(tokens[:, 1:])
    return float(-jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1)))
