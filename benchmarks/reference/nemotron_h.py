"""Plain float32 reference of the Nemotron-H decoder (`model_type:
nemotron_h`; NVIDIA-Nemotron-3-Nano-30B-A3B): layers that are ONE mixer
each, a Mamba-2 mixer whose B and C come in groups, attention without
positions, or a routed block of ungated squared-relu experts beside a
shared one.

Straight `jax.numpy`, one layer at a time, no kernels, no cache, no
state slot, no chunking, no batching of prompts; independent of
`deepspeed_tpu/` and of every other reference here. With `x` the
residual stream and `N(x; s) = x * rsqrt(mean x^2 + norm_eps) * s` in
float32:

    x_0 = E[token]
    layer i:  x <- x + mixer_i(N(x; s_i))      ONE norm, ONE sublayer
    mixer_i by `hybrid_override_pattern[i]`: `M` the Mamba-2 mixer, `E`
    the routed block, `*` attention. No FFN follows a mixer and no
    mixer precedes a routed block.
    logits = N(x_L; s_out) W_head              (the head is not tied)

    `M`, on h (H = mamba_num_heads heads of P = mamba_head_dim, inner
    width I = H P, a state of N = ssm_state_size, G = n_groups groups,
    K = conv_kernel taps):
        [z; xBC; dt] = W_in h (no bias), widths I / I + 2 G N / H;
        xBC <- silu(causal depthwise convolution of K taps + b_conv,
        zeros before the sequence starts: HERE an explicit sum over
        shifted copies of the WHOLE sequence);
        [x; B; C] = xBC, widths I / G N / G N: ONE B and C of N a group
        a token; head h belongs to group h // (H / G);
        dt <- softplus(dt + dt_bias), A = -exp(A_log): one of each a
        head, no clamp;
        a head carries S in R^{P x N}, zero at the sequence's start;
        for each token, as a `lax.scan` over tokens (the RECURRENCE):
            S_h <- exp(dt_h A_h) S_h + (dt_h x_h) B_g^T
            y_h = S_h C_g + D_h x_h
        y <- y * silu(z); then N(.; w_norm) over EACH GROUP's I / G
        values apart (the gate BEFORE the norm); out = W_out y.
    `E`, on h: s = sigmoid(W_r h) in float32 over ALL n_routed_experts;
        the num_experts_per_tok largest of s + e_score_correction_bias
        are chosen (n_group 1: the group-limited step keeps every
        expert); their weights the UNBIASED s, divided by their sum
        (norm_topk_prob), times routed_scaling_factor;
        expert(h) = W_down relu(W_up h)^2: two matrices, no gate;
        out = sum_x w_x expert_x(h) + shared(h), the shared expert of
        the same form, moe_shared_expert_intermediate_size wide,
        every token, unweighted. HERE a loop over the HELD experts,
        each applied to every token and weighted by its column.
    `*`, on h: H query / KV key-value heads of head_dim; q = W_q h,
        k = W_k h, v = W_v h; no bias, no QK-norm, NO positional
        operation; causal softmax of head_dim^-0.5 q k^T, GQA; W_o.

Departures from the published description, each where it is made; the
configuration file lists them under `assumed`:

- `expand` is NOT read: the publisher's `nemotron_h` code sizes the
  mixer from mamba_num_heads x mamba_head_dim (4,096 at the published
  widths, not expand x hidden_size = 5,376).
- NO positions: the publisher's attention applies neither rotary nor
  learned positions (Nemotron-H report, arXiv:2504.03624, section 2);
  `rope_theta` and `partial_rotary_factor` are in the file and unused
  (the `rope_on` mutant alone rotates, at theta 10,000).
- `time_step_min`, `time_step_max` and `time_step_floor` are the
  INITIALISER's (how dt_bias is drawn): no clamp is applied to dt.
- the publisher keeps the state in float32 and so does this; its
  kernels run the chunked form of the same recurrence (`chunk_size`).
- a file that holds a SHARE of the experts (`n_routed_experts` under
  `reduced`, `experts_held.start`): the router keeps its published
  width and top-k, the held experts add their part, what the absent
  ones would add is left out. The vocabulary is the file's.
- ties in the top-k go to the lowest expert index (`lax.top_k`).

Weights arrive in the names and shapes of `models/transformer.init`:
`top` holds `embed` [V, E], `lm_head` [E, V], `ln_f_scale` [E], and the
mixers' stacks by kind, layer l taking the entry of its place among
the layers of its kind: `ssm_in` [Nm, E, 2 I + 2 G N + H], `ssm_taps`
[Nm, I + 2 G N, K] (oldest tap first), `ssm_conv_bias` [Nm, I + 2 G N],
`ssm_a_log` / `ssm_dt_bias` / `ssm_d` [Nm, H], `ssm_norm_scale`
[Nm, I], `ssm_out` [Nm, I, E]; `attn_wq` [Na, E, H, D], `attn_wk` /
`attn_wv` [Na, E, KV, D], `attn_wo` [Na, H, D, E]; `moe_w_router`
[Ne, E, X], `moe_expert_bias` [Ne, X], `moe_w_in` [Ne, Xh, E, F],
`moe_w_out` [Ne, Xh, F, E] (Xh the held experts), `moe_ws_in` [Ne, E,
Fs], `moe_ws_out` [Ne, Fs, E]. `layer_weights(l)` returns layer l's one
norm, `ln1_scale` [E]. They come in whatever dtype the system holds and
are widened to float32 HERE. Every matmul runs under
default_matmul_precision("highest"). The head runs in slabs and the
logits come back as numpy, on the host.

`forward_logits(..., mutate=)` computes deliberately WRONG models (the
tests and the limits of the benchmark's logits check are set against
them): MUTANTS below.
"""

import json
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MUTANTS = (
    "state_bf16",            # the matrix rounded to bf16 after every token
    "bc_one_group",          # every head reads group 0's B and C
    "norm_ungrouped",        # the gated norm over all I values at once
    "relu_not_squared",      # an expert is W_down relu(W_up h)
    "no_shared_expert",      # the routed experts alone
    "no_routed_scale",       # the weights not times routed_scaling_factor
    "no_expert_bias",        # the choice by the unbiased scores
    "k_minus_1",             # one expert fewer a token
    "rope_on",               # rotary positions on q and k (theta 10,000)
    "matrix_state_zero",     # every token reads a zero matrix; past inputs kept
    "matrix_state_other_head",  # the read takes the matrix of the head before
    "no_conv_bias",          # the convolution without its bias
)
KINDS = {"M": "ssm_", "E": "moe_", "*": "attn_"}
VOCAB_SLAB = 8192


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _eps(hf):
    return float(hf.get("norm_eps", hf.get("layer_norm_epsilon", 1e-5)))


def _rope(x, theta):
    """x [B, S, H, D] rotated at positions 0..S-1, split-halves pairing
    (the `rope_on` mutant alone: the model has no positions)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mamba2(h, ow, hf, mutate=None):
    """The Mamba-2 mixer on normed h [B, S, E], token by token."""
    H, P = hf["mamba_num_heads"], hf["mamba_head_dim"]
    N, G, K = hf["ssm_state_size"], hf["n_groups"], hf["conv_kernel"]
    B_, S, _ = h.shape
    I = H * P              # `expand` is not read (module docstring)
    mixed = h @ ow["ssm_in"].astype(F32)
    z, u, dt = jnp.split(mixed, [I, 2 * I + 2 * G * N], axis=-1)
    taps = ow["ssm_taps"].astype(F32)                          # [C, K]
    c = u * taps[:, K - 1]
    for j in range(K - 1):                 # tap j multiplies u_{t-(K-1)+j}
        back = K - 1 - j
        c = c + jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :S] * taps[:, j]
    if mutate != "no_conv_bias":
        c = c + ow["ssm_conv_bias"].astype(F32)
    c = jax.nn.silu(c)
    x = c[..., :I].reshape(B_, S, H, P)
    Bm = c[..., I:I + G * N].reshape(B_, S, G, N)
    Cm = c[..., I + G * N:].reshape(B_, S, G, N)
    if mutate == "bc_one_group":
        Bm, Cm = (jnp.broadcast_to(a[:, :, :1], a.shape) for a in (Bm, Cm))
    # head h reads the B and C of group h // (H / G)
    Bh, Ch = (jnp.repeat(a, H // G, axis=2) for a in (Bm, Cm))
    # no clamp: time_step_min / max / floor are the initialiser's
    dt = jax.nn.softplus(dt + ow["ssm_dt_bias"].astype(F32))   # [B, S, H]
    dec = jnp.exp(dt * -jnp.exp(ow["ssm_a_log"].astype(F32)))
    write = x * dt[..., None]

    def token(state, xs):
        wt, bt, ct, dect = xs          # [B, H, P], [B, H, N] x 2, [B, H]
        if mutate == "matrix_state_zero":
            state = jnp.zeros_like(state)
        state = state * dect[..., None, None] + wt[..., :, None] * bt[..., None, :]
        if mutate == "state_bf16":
            # an explicit rounding: a cast there and back is one XLA may
            # drop on a TPU (excess precision)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        read = (jnp.roll(state, 1, axis=1)
                if mutate == "matrix_state_other_head" else state)
        return state, jnp.einsum("bhpn,bhn->bhp", read, ct)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (write, Bh, Ch, dec))
    _, y = jax.lax.scan(token, jnp.zeros((B_, H, P, N), F32), xs)
    y = jnp.moveaxis(y, 0, 1) + ow["ssm_d"].astype(F32)[:, None] * x
    y = y.reshape(B_, S, I) * jax.nn.silu(z)
    groups = 1 if mutate == "norm_ungrouped" else G
    y = y.reshape(B_, S, groups, I // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + _eps(hf))
    y = y.reshape(B_, S, I) * ow["ssm_norm_scale"].astype(F32)
    return y @ ow["ssm_out"].astype(F32)


def attention(h, ow, hf, mutate=None):
    """Grouped-query attention without positions on normed h [B, S, E]."""
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // H
    S = h.shape[1]
    q = jnp.einsum("bse,ehd->bshd", h, ow["attn_wq"].astype(F32))
    k = jnp.einsum("bse,ehd->bshd", h, ow["attn_wk"].astype(F32))
    v = jnp.einsum("bse,ehd->bshd", h, ow["attn_wv"].astype(F32))
    if mutate == "rope_on":   # the model itself rotates nothing
        q, k = _rope(q, 10000.0), _rope(k, 10000.0)
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * D ** -0.5
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return jnp.einsum("bshd,hde->bse", o, ow["attn_wo"].astype(F32))


def held_experts(hf):
    """(first held expert, experts held, the router's width)."""
    held = hf["n_routed_experts"]
    routed = (hf.get("reduced") or {}).get("n_routed_experts", {}).get(
        "published", held)
    return int((hf.get("experts_held") or {}).get("start", 0)), held, routed


def route(n, ow, hf, mutate=None):
    """Normed activations n [T, E] -> the [T, X] combine weights over
    ALL the router's experts (zero outside the chosen), and the
    router's margin: how far the smallest chosen biased score lies
    above the largest left out, as a share of the former."""
    k = hf["num_experts_per_tok"] - (mutate == "k_minus_1")
    s = jax.nn.sigmoid(n @ ow["moe_w_router"].astype(F32))
    biased = s if mutate == "no_expert_bias" else (
        s + ow["moe_expert_bias"].astype(F32))
    top, chosen = jax.lax.top_k(biased, k + 1)
    w = s * jnp.sum(jax.nn.one_hot(chosen[..., :k], s.shape[-1], dtype=F32), -2)
    if hf.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    if mutate != "no_routed_scale":
        w = w * hf["routed_scaling_factor"]
    return w, (top[..., k - 1] - top[..., k]) / top[..., k - 1]


def _relu2(n, w_up, w_down, mutate=None):
    up = jax.nn.relu(n @ w_up.astype(F32))
    return (up if mutate == "relu_not_squared" else up * up
            ) @ w_down.astype(F32)


def moe(h, ow, hf, mutate=None):
    """The routed block on normed h [B, S, E]: every HELD expert applied
    to every token, one at a time, weighted by its column; the shared
    expert unweighted."""
    n = h.reshape(-1, h.shape[-1])
    w, margin = route(n, ow, hf, mutate)
    start, held, _ = held_experts(hf)

    def expert(acc, xs):
        w_up, w_down, col = xs
        return acc + col[:, None] * _relu2(n, w_up, w_down, mutate), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(n),
        (ow["moe_w_in"], ow["moe_w_out"], w[:, start:start + held].T))
    if "moe_ws_in" in ow and mutate != "no_shared_expert":
        out = out + _relu2(n, ow["moe_ws_in"], ow["moe_ws_out"], mutate)
    return out.reshape(h.shape), margin.reshape(h.shape[:-1])


def _layer(x, lw, ow, kind, hf, mutate=None):
    """One layer on x [B, S, E] float32 -> (x, the router's margin
    [B, S], 1 where the layer routes nothing). `lw`: its one norm;
    `ow`: its mixer's leaves."""
    h = _rms(x, lw["ln1_scale"], _eps(hf))
    if kind == "E":
        y, margin = moe(h, ow, hf, mutate)
        return x + y, margin
    y = (mamba2 if kind == "M" else attention)(h, ow, hf, mutate)
    return x + y, jnp.ones(x.shape[:-1], F32)


def forward_logits(top: Dict[str, Any], layer_weights: Callable[[int], Dict],
                   tokens, hf: Dict[str, Any], mutate: Optional[str] = None):
    """Logits [B, S, V] float32 (numpy, on the host) of tokens [B, S]
    (see the module docstring for `top` and `layer_weights`). `mutate`
    is None or one of MUTANTS."""
    if mutate is not None and mutate not in MUTANTS:
        raise ValueError(f"unknown mutant {mutate!r}; there are {MUTANTS}")
    return _forward(top, layer_weights, tokens, hf, mutate)[0]


_JITTED = {}  # (the configuration as text, mutant) -> the jitted layer


def _jitted_layer(hf, mutate):
    """One compiled layer a configuration and mutant, kept: a caller
    that checks many sequences of one shape compiles once a kind."""
    key = (json.dumps(hf, sort_keys=True, default=str), mutate)
    if key not in _JITTED:
        _JITTED[key] = jax.jit(
            lambda x, lw, ow, kind: _layer(x, lw, ow, kind, hf, mutate),
            static_argnums=3)
    return _JITTED[key]


def _head(x, top, hf):
    """Logits [B, S, V] on the HOST: VOCAB_SLAB columns of the head at a
    time, so that neither the float32 head nor the logits sit on the
    device beside a serving engine."""
    n = _rms(x, jnp.asarray(top["ln_f_scale"]), _eps(hf))
    lm_head = jnp.asarray(top["lm_head"])
    slab = jax.jit(lambda nb, w: nb @ w.astype(F32))
    return np.concatenate(
        [np.asarray(slab(n, lm_head[:, c:c + VOCAB_SLAB]))
         for c in range(0, lm_head.shape[1], VOCAB_SLAB)], axis=-1)


def _forward(top, layer_weights, tokens, hf, mutate):
    pattern = hf["hybrid_override_pattern"]
    if len(pattern) != hf["num_hidden_layers"] or set(pattern) - set(KINDS):
        raise ValueError(f"hybrid_override_pattern {pattern!r} names "
                         f"{sorted(KINDS)} for each layer")
    layer = _jitted_layer(hf, mutate)
    seen = {kind: 0 for kind in KINDS}
    margins = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(top["embed"])[jnp.asarray(tokens)].astype(F32)
        for l, kind in enumerate(pattern):
            # the mixer's leaves: entry (layers of this kind so far)
            ow = {k: jnp.asarray(v)[seen[kind]] for k, v in top.items()
                  if k.startswith(KINDS[kind])}
            seen[kind] += 1
            x, margin = layer(x, layer_weights(l), ow, kind)
            margins.append(margin)
        return _head(x, top, hf), jnp.stack(margins)


def router_margins(top, layer_weights, tokens, hf):
    """[layers, B, S]: the router's margin of every layer at every
    token of the model as published (1 in a layer that routes nothing):
    what `benchmarks/logits_audit.py` sets beside the served logits'
    errors."""
    return _forward(top, layer_weights, tokens, hf, None)[1]


def loss(top, layer_weights, tokens, hf, mutate: Optional[str] = None) -> float:
    """Token-mean next-token cross-entropy of tokens [B, S + 1]."""
    tokens = np.asarray(tokens)
    logits = forward_logits(top, layer_weights, tokens[:, :-1], hf, mutate)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.asarray(tokens[:, 1:])
    return float(-jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1)))
