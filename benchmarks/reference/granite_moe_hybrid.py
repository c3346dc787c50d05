"""Plain float32 reference of the Granite 4.0-H decoder (`model_type:
granitemoehybrid`): Mamba-2 state-space layers and attention layers
without positions, each followed by routed experts beside an ungated
shared expert, under Granite's four scalars.

Straight `jax.numpy`, one layer at a time, no kernels, no cache, no
state slot, no chunking; independent of `deepspeed_tpu/`. With `x` the
residual stream, `N(x; s) = x * rsqrt(mean x^2 + rms_norm_eps) * s` in
float32 and m = `residual_multiplier`:

    x_0 = embedding_multiplier * E[token]
    layer i:  x <- x + m * Op(N(x; s1));  x <- x + m * F(N(x; s2))
    Op is attention where `layer_types[i]` is "attention", the Mamba-2
    mixer where it is "mamba".
    logits = (N(x_L; s_out) E^T) / logits_scaling   (the head is tied)

    Mamba-2 mixer on h (H heads of P, a state of N, ONE group):
        [z; xBC; dt] = W_in h (no bias), widths H P / H P + 2 N / H;
        xBC <- silu(causal depthwise convolution of mamba_d_conv taps
        + b_conv, zeros before the sequence starts: HERE an explicit
        sum over shifted copies of the WHOLE sequence);
        [x; B; C] = xBC, widths H P / N / N: B and C are ONE vector a
        token for all the heads;
        dt <- softplus(dt + dt_bias), A = -exp(A_log): one of each a
        head, no clamp;
        a head carries S in R^{P x N}, zero at the sequence's start;
        for each token t, as a `lax.scan` over tokens (the RECURRENCE,
        not the chunked form):
            S_h <- exp(dt_h A_h) S_h + (dt_h x_h) B^T
            y_h = S_h C + D_h x_h
        y <- N(y * silu(z); w_norm) over ALL H P values together (one
        group; the gate BEFORE the norm);  out = W_out y (no bias)
    Attention on h (H query / KV key-value heads of D):
        q = W_q h, k = W_k h, v = W_v h; no bias, no QK-norm, NO
        positional operation of any kind; causal softmax of
        attention_multiplier * q k^T (1/128 as published: NOT D^-0.5),
        GQA; W_o.
    F on n: l = W_r n over all experts, float32; the
        `num_experts_per_tok` largest; w = softmax over those logits
        (equal to the full softmax renormalised over the chosen);
        y = sum w_i E_i(n), E_i SwiGLU of `intermediate_size`;
        y += E_shared(n), SwiGLU of `shared_intermediate_size`, no gate,
        no weight. Every HELD expert is computed for every token and
        masked by the router's choice.

Departures from the publisher, each where it is made; the configuration
file lists them under `assumed`:

- `W_in`'s columns are [z; x; B; C; dt] as the publisher's `in_proj`;
  an expert's gate and up arrive apart (`w_gate`, `w_in`): the
  publisher fuses them in one `input_linear` (an importer splits it).
- the skip's D arrives as `ssm_d` (the publisher's `D`).
- no clamp on dt (the publisher's `time_step_limit` default (0, inf)).
- the publisher keeps the state in float32 and so does this; its
  kernels run the chunked form of the same recurrence (chunk 256).
- a file that holds a SHARE of the experts (`num_local_experts` under
  `reduced`, `experts_held.start`): the router keeps its published
  width and top-k, the held experts add their part, what the absent
  ones would add is left out. The vocabulary is the file's.
- ties in the top-k go to the lowest expert index (`lax.top_k`).

Weights arrive in the names and shapes of `models/transformer.init`:
`top` holds `embed` [V, E], `ln_f_scale` [E]; the operators' stacks by
kind `ssm_in` [Ns, E, 2 H P + 2 N + H], `ssm_taps` [Ns, H P + 2 N, K]
(oldest tap first), `ssm_conv_bias` [Ns, H P + 2 N], `ssm_a_log` /
`ssm_dt_bias` / `ssm_d` [Ns, H], `ssm_norm_scale` [Ns, H P],
`ssm_out` [Ns, H P, E] and `attn_wq` [Na, E, H, D], `attn_wk` /
`attn_wv` [Na, E, KV, D], `attn_wo` [Na, H, D, E], layer l taking the
entry of its place among the layers of its kind. `layer_weights(l)`
returns layer l's: ln1_scale, ln2_scale [E]; w_router [E, X]; w_gate /
w_in [Xh, E, F], w_out [Xh, F, E] (Xh the held experts); ws_gate / ws_in
[E, Fs], ws_out [Fs, E]. They come in whatever dtype the system holds
and are widened to float32 HERE. Every matmul runs under
default_matmul_precision("highest").

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
    "no_state_carry",        # every token sees a zero matrix and no past input
    "no_decay",              # exp(dt A) = 1: nothing is ever forgotten
    "dt_not_on_input",       # the write is x B^T, not (dt x) B^T
    "no_d_skip",             # y = S C alone
    "norm_before_gate",      # N(y) * silu(z): the DeltaNet's order
    "no_conv_bias",          # the convolution without its bias
    "no_conv_silu",          # ... without its activation
    "state_bf16",            # the matrix rounded to bf16 after every token
    "matrix_state_zero",     # every token reads a zero matrix; past inputs kept
    "matrix_state_other_head",  # the read takes the matrix of the head before
    "bc_per_head",           # B rolled by the head's index: not shared
    "rope_applied",          # rotary positions on q and k (theta rope_theta)
    "scale_rsqrt_d",         # softmax scale D^-0.5
    "no_embed_mult",         # x_0 = E[token]
    "no_residual_mult",      # both branches unscaled
    "no_logits_div",         # logits not divided
    "softmax_all_no_renorm", # the chosen experts' raw full-softmax mass
)
KINDS = {"mamba": "ssm_", "attention": "attn_"}


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x [B, S, H, D] rotated at positions 0..S-1, split-halves pairing
    (the `rope_applied` mutant alone: the model has no positions)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(n, w_gate, w_in, w_out):
    return (jax.nn.silu(n @ w_gate.astype(F32)) * (n @ w_in.astype(F32))
            ) @ w_out.astype(F32)


def mamba2(h, ow, hf, mutate=None):
    """The Mamba-2 mixer on normed h [B, S, E], token by token."""
    H, P, N = hf["mamba_n_heads"], hf["mamba_d_head"], hf["mamba_d_state"]
    K = hf["mamba_d_conv"]
    B_, S, _ = h.shape
    I = H * P
    mixed = h @ ow["ssm_in"].astype(F32)
    z, u, dt = mixed[..., :I], mixed[..., I:2 * I + 2 * N], mixed[..., 2 * I + 2 * N:]
    taps = ow["ssm_taps"].astype(F32)                          # [C, K]
    c = u * taps[:, K - 1]
    for j in range(K - 1):                 # tap j multiplies u_{t-(K-1)+j}
        back = K - 1 - j
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :S]
        if mutate == "no_state_carry":
            shifted = jnp.zeros_like(shifted)
        c = c + shifted * taps[:, j]
    if mutate != "no_conv_bias":
        c = c + ow["ssm_conv_bias"].astype(F32)
    if mutate != "no_conv_silu":
        c = jax.nn.silu(c)
    x = c[..., :I].reshape(B_, S, H, P)
    Bm, Cm = c[..., I:I + N], c[..., I + N:]
    # one B and C a token for all the heads
    Bh = jnp.broadcast_to(Bm[:, :, None, :], (B_, S, H, N))
    Ch = jnp.broadcast_to(Cm[:, :, None, :], (B_, S, H, N))
    if mutate == "bc_per_head":
        # (rolling C alike would leave every C . B, hence y, as it was)
        Bh = jax.vmap(lambda v, k: jnp.roll(v, k, axis=-1), (2, 0), 2)(
            Bh, jnp.arange(H))
    dt = jax.nn.softplus(dt + ow["ssm_dt_bias"].astype(F32))   # [B, S, H]
    dec = jnp.exp(dt * -jnp.exp(ow["ssm_a_log"].astype(F32)))
    if mutate == "no_decay":
        dec = jnp.ones_like(dec)
    write = x if mutate == "dt_not_on_input" else x * dt[..., None]

    def token(state, xs):
        wt, bt, ct, dect = xs          # [B, H, P], [B, H, N] x 2, [B, H]
        if mutate in ("no_state_carry", "matrix_state_zero"):
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
    y = jnp.moveaxis(y, 0, 1)                                  # [B, S, H, P]
    if mutate != "no_d_skip":
        y = y + ow["ssm_d"].astype(F32)[:, None] * x
    y, gate = y.reshape(B_, S, I), jax.nn.silu(z)
    eps = hf["rms_norm_eps"]
    if mutate == "norm_before_gate":
        y = _rms(y, ow["ssm_norm_scale"], eps) * gate
    else:
        y = _rms(y * gate, ow["ssm_norm_scale"], eps)
    return y @ ow["ssm_out"].astype(F32)


def attention(h, ow, hf, mutate=None):
    """Grouped-query attention without positions on normed h [B, S, E]."""
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf["hidden_size"] // H
    S = h.shape[1]
    q = jnp.einsum("bse,ehd->bshd", h, ow["attn_wq"].astype(F32))
    k = jnp.einsum("bse,ehd->bshd", h, ow["attn_wk"].astype(F32))
    v = jnp.einsum("bse,ehd->bshd", h, ow["attn_wv"].astype(F32))
    if mutate == "rope_applied":
        theta = float(hf.get("rope_theta", 10000.0))
        q, k = _rope(q, theta), _rope(k, theta)
    G = H // KV
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    scale = D ** -0.5 if mutate == "scale_rsqrt_d" else hf[
        "attention_multiplier"]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return jnp.einsum("bshd,hde->bse", o, ow["attn_wo"].astype(F32))


def held_experts(hf):
    """(first held expert, experts held, the router's width)."""
    held = hf["num_local_experts"]
    routed = (hf.get("reduced") or {}).get("num_local_experts", {}).get(
        "published", held)
    return int((hf.get("experts_held") or {}).get("start", 0)), held, routed


def route(n, lw, hf, mutate=None):
    """Normed activations n [T, E] -> the [T, X] combine weights over
    ALL the router's experts (zero outside the chosen), and the
    router's margin: how far the smallest chosen probability lies above
    the largest left out, as a share of the former."""
    k = hf["num_experts_per_tok"]
    p = jax.nn.softmax(n @ lw["w_router"].astype(F32), axis=-1)
    top, chosen = jax.lax.top_k(p, k + 1)
    w = p * jnp.sum(jax.nn.one_hot(chosen[..., :k], p.shape[-1], dtype=F32), -2)
    if mutate != "softmax_all_no_renorm":
        # softmax over the chosen logits = the full softmax renormalised
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, (top[..., k - 1] - top[..., k]) / top[..., k - 1]


def moe(n, lw, hf, mutate=None):
    """The FFN on normed n [T, E]: every held expert applied to every
    token, one at a time, weighted by its column; the shared expert
    unweighted."""
    w, margin = route(n, lw, hf, mutate)
    start, held, _ = held_experts(hf)

    def expert(acc, xs):
        w_gate, w_in, w_out, col = xs
        return acc + col[:, None] * _swiglu(n, w_gate, w_in, w_out), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(n),
        (lw["w_gate"], lw["w_in"], lw["w_out"], w[:, start:start + held].T))
    if "ws_gate" in lw:
        out = out + _swiglu(n, lw["ws_gate"], lw["ws_in"], lw["ws_out"])
    return out, margin


def _layer(x, lw, ow, kind, hf, mutate=None):
    """One layer on x [B, S, E] float32 -> (x, the router's margin
    [B, S]). `lw`: its norms and FFN; `ow`: its operator's leaves."""
    eps = hf["rms_norm_eps"]
    m = 1.0 if mutate == "no_residual_mult" else hf["residual_multiplier"]
    op = mamba2 if kind == "mamba" else attention
    x = x + m * op(_rms(x, lw["ln1_scale"], eps), ow, hf, mutate)
    n = _rms(x, lw["ln2_scale"], eps)
    y, margin = moe(n.reshape(-1, n.shape[-1]), lw, hf, mutate)
    return x + m * y.reshape(n.shape), margin.reshape(n.shape[:-1])


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
    seen = {kind: 0 for kind in KINDS}
    margins = []
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(top["embed"]).astype(F32)
        x = embed[jnp.asarray(tokens)]
        if mutate != "no_embed_mult":
            x = x * hf["embedding_multiplier"]
        for l, kind in enumerate(hf["layer_types"]):
            # the operator's leaves: entry (layers of this kind so far)
            ow = {k: jnp.asarray(v)[seen[kind]] for k, v in top.items()
                  if k.startswith(KINDS[kind])}
            seen[kind] += 1
            x, margin = layer(x, layer_weights(l), ow, kind)
            margins.append(margin)
        x = _rms(x, jnp.asarray(top["ln_f_scale"]), hf["rms_norm_eps"])
        logits = jnp.einsum("bse,ve->bsv", x, embed)
        if mutate != "no_logits_div":
            logits = logits / hf["logits_scaling"]
        return logits, jnp.stack(margins)


def router_margins(top, layer_weights, tokens, hf):
    """[layers, B, S]: the router's margin of every layer at every
    token of the model as published: what `benchmarks/logits_audit.py`
    sets beside the served logits' errors."""
    return _forward(top, layer_weights, tokens, hf, None)[1]


def loss(top, layer_weights, tokens, hf, mutate: Optional[str] = None) -> float:
    """Token-mean next-token cross-entropy of tokens [B, S + 1]."""
    tokens = np.asarray(tokens)
    logits = forward_logits(top, layer_weights, tokens[:, :-1], hf, mutate)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.asarray(tokens[:, 1:])
    return float(-jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1)))
