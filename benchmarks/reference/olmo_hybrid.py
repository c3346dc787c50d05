"""Plain float32 reference of the Olmo-Hybrid decoder (`model_type:
olmo_hybrid`): Gated DeltaNet layers whose write strength reaches 2 and
multi-head attention layers with no positions, each followed by a dense
SwiGLU, every RMSNorm on its sublayer's OUTPUT.

Straight `jax.numpy`, one layer at a time, no kernels, no cache, no
state slot, no chunking; independent of `deepspeed_tpu/`. With `x` the
residual stream and `N(x; s) = x * rsqrt(mean x^2 + rms_norm_eps) * s`
in float32 (a plain scale):

    layer i:  a = x + N(Op(x); s1);  x <- a + N(F(a); s2)
    (the OLMo 2 / OLMo 3 placement: the norm on the sublayer's output,
    none before it.) Op is attention where `layer_types[i]` is
    `full_attention`, the Gated DeltaNet where `linear_attention`.

    Gated DeltaNet on x (H heads; a head's q, k in R^Dk, v in R^Dv):
        [q; k; v; z] = W_qkvz x;  [b; a] = W_ba x (one of each a head);
        [q; k; v] <- silu(causal depthwise convolution of
        linear_conv_kernel_dim taps, no bias, zeros before the sequence
        starts: HERE an explicit sum over shifted copies of the WHOLE
        sequence); beta = 2 sigmoid(b) where `linear_allow_neg_eigval`
        (else sigmoid(b)); g = -exp(A_log) * softplus(a + dt_bias);
        each head's q and k L2-normalised (x * rsqrt(sum x^2 + 1e-6)),
        q scaled by Dk^-0.5 (key heads fewer than value heads would be
        repeated; the published model has as many);
        a head carries S in R^{Dk x Dv}, zero at the sequence's start;
        for each token t, as a `lax.scan` over tokens (the RECURRENCE,
        not the chunked form):
            S <- exp(g_t) S;  m = S^T k_t;  d = beta_t (v_t - m);
            S <- S + k_t d^T;  o_t = S^T q_t
        o <- N(o; w_norm) * silu(z) over each head's Dv values (the norm
        first, then the gate);  out = W_o o
    Attention on x (H query and KV key-value heads of D = E / H):
        q = N(W_q x; s_q), k = N(W_k x; s_k), each norm over ALL the
        H D (KV D) projected values of a token with one scale a value;
        v = W_v x; NO rotation (the published `rope_parameters.rope_theta`
        is null: the layer has no positions of its own); causal
        softmax(q k^T / sqrt(D)) v; W_o. No bias.
    F on a: W_down (silu(W_gate a) * W_up a)
    logits = W_head N(x; s_out)

Departures from the publisher, each where it is made; the configuration
file lists them under `assumed`:

- the publisher projects q, k, v, z, b and a by six matrices and
  convolves q, k and v apart; here `W_qkvz`'s columns are [q; k; v; z],
  `W_ba`'s [b; a], each in head order, and ONE depthwise convolution
  runs over the channels [q; k; v]: the same sums, concatenated.
- the publisher keeps the state in float32 and so does this; its
  kernels run the chunked form of the same recurrence (chunk 64).
- every product with a weight matrix is taken a BLOCK of the matrix at
  a time (`_times`: 4,096 columns, or rows where the rows are summed
  over), the block widened to float32 and dropped, and the logits
  leave the device a block of the vocabulary at a time: the same sums,
  and what they buy is room. At the published widths the operators'
  stacks, the embedding and the head are 3.5 GB that the caller holds
  on the device beside the engine's 12.7, and a whole float32 copy of
  one FFN (0.5 GB) or of the head (1.5 GB) does not fit beside them.

Weights arrive in the names and shapes of `models/transformer.init`:
`top` holds `embed` [V, E], `lm_head` [E, V], `ln_f_scale` [E]; the
operators' stacks by kind `gdn_in` [Ng, E, 2 H Dk + 2 H Dv], `gdn_ba`
[Ng, E, 2 H], `gdn_taps` [Ng, 2 H Dk + H Dv, K] (oldest tap first),
`gdn_a_log` / `gdn_dt_bias` [Ng, H], `gdn_norm_scale` [Ng, Dv],
`gdn_out` [Ng, H Dv, E] and `attn_wq` [Na, E, H, D], `attn_wk` /
`attn_wv` [Na, E, KV, D], `attn_wo` [Na, H, D, E], `attn_q_norm_scale`
[Na, H, D] / `attn_k_norm_scale` [Na, KV, D], layer l taking the entry
of its place among the layers of its kind. `layer_weights(l)` returns
layer l's: ln1_post_scale, ln2_post_scale [E]; w_gate / w_in [E, F],
w_out [F, E]. They come in whatever dtype the system holds and are
widened to float32 HERE. Every matmul runs under
default_matmul_precision("highest"). `forward_logits` returns a numpy
array.

`forward_logits(..., mutate=)` computes deliberately WRONG models (the
tests and the limits of the benchmark's logits check are set against
them): MUTANTS below.
"""

import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MUTANTS = (
    "beta_not_doubled",       # beta = sigmoid(b), in (0, 1)
    "pre_norm",               # x + Op(N(x)): each norm BEFORE its sublayer
    "per_head_qk_norm",       # the QK-norm's statistic a head at a time
    "rotary_on_full_layers",  # rope (theta 10,000) on q and k
    "no_decay",               # g = 0: nothing is ever forgotten
    "no_state_carry",         # every token sees a zero matrix and no past input
    "state_bf16",             # the matrix rounded to bf16 after every token
)
KINDS = {"linear_attention": "gdn_", "full_attention": "attn_"}
BLOCK = 4096   # columns (or summed rows) of a weight widened at a time


def _rms(x, scale, eps, axes=-1):
    var = jnp.mean(jnp.square(x), axis=axes, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _block(x, w, index, lo, hi, rows):
    """x @ (columns lo..hi of w), or x[..., lo:hi] @ (rows lo..hi of w):
    w a matrix [n, ...] (trailing dims flattened) or entry `index` of a
    stack of them, the block widened to float32 here."""
    w = w if index is None else w[index]
    if rows:
        w = w.reshape(w.shape[0], -1) if w.ndim == 2 else w.reshape(
            -1, w.shape[-1])
        return x[..., lo:hi] @ w[lo:hi].astype(F32)
    w = w.reshape(w.shape[0], -1)
    return x @ w[:, lo:hi].astype(F32)


def _times(x, w, index=None, rows=False):
    """x [..., n] @ w [n, ...] -> [..., the trailing dims flattened]
    (or, `rows`: x [..., the leading dims flattened] @ w [..., m], its
    leading dims summed over), a block of w at a time."""
    shape = w.shape[1:] if index is not None else w.shape
    n = int(np.prod(shape[:-1])) if rows else int(np.prod(shape[1:]))
    parts = [_block(x, w, index, lo, min(lo + BLOCK, n), rows)
             for lo in range(0, n, BLOCK)]
    return sum(parts[1:], parts[0]) if rows else jnp.concatenate(parts, -1)


def _rope(x, theta):
    """x [B, S, H, D] rotated at positions 0..S-1, split-halves pairing
    (the mutant's alone: the model has none)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(5,))
def _delta_rule(q, k, v, g, beta, mutate):
    """The recurrence, token by token: q, k [B, S, H, Dk], v
    [B, S, H, Dv], g, beta [B, S, H] -> o [B, S, H, Dv]."""
    B, _, H, Dk = q.shape

    def token(state, x):
        qt, kt, vt, gt, bt = x                 # [B, H, D], [B, H]
        if mutate == "no_state_carry":
            state = jnp.zeros_like(state)
        state = state * jnp.exp(gt)[..., None, None]
        m = jnp.einsum("bhkv,bhk->bhv", state, kt)
        d = bt[..., None] * (vt - m)
        state = state + kt[..., :, None] * d[..., None, :]
        if mutate == "state_bf16":
            # an explicit rounding: a cast there and back is one XLA may
            # drop on a TPU (excess precision)
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(token, jnp.zeros((B, H, Dk, v.shape[-1]), F32), xs)
    return jnp.moveaxis(o, 0, 1)


def gated_delta_net(h, top, i, hf, mutate=None):
    """The Gated DeltaNet on h [B, S, E] with entry i of the `gdn_`
    stacks, token by token."""
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    Dk, Dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    K = hf["linear_conv_kernel_dim"]
    B, S, _ = h.shape
    C = 2 * Hk * Dk + Hv * Dv
    mixed = _times(h, top["gdn_in"], i)
    u, z = mixed[..., :C], mixed[..., C:]
    ba = _times(h, top["gdn_ba"], i)
    b, a = ba[..., :Hv], ba[..., Hv:]
    taps = top["gdn_taps"][i].astype(F32)                      # [C, K]
    c = u * taps[:, K - 1]
    for j in range(K - 1):                 # tap j multiplies u_{t-(K-1)+j}
        back = K - 1 - j
        shifted = jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :S]
        if mutate == "no_state_carry":
            shifted = jnp.zeros_like(shifted)
        c = c + shifted * taps[:, j]
    c = jax.nn.silu(c)
    q = c[..., :Hk * Dk].reshape(B, S, Hk, Dk)
    k = c[..., Hk * Dk:2 * Hk * Dk].reshape(B, S, Hk, Dk)
    v = c[..., 2 * Hk * Dk:].reshape(B, S, Hv, Dv)
    beta = jax.nn.sigmoid(b)
    if hf.get("linear_allow_neg_eigval") and mutate != "beta_not_doubled":
        beta = 2.0 * beta
    g = -jnp.exp(top["gdn_a_log"][i].astype(F32)) * jax.nn.softplus(
        a + top["gdn_dt_bias"][i].astype(F32))
    if mutate == "no_decay":
        g = jnp.zeros_like(g)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(q, Hv // Hk, axis=2) * Dk ** -0.5
    k = jnp.repeat(k, Hv // Hk, axis=2)
    o = _delta_rule(q, k, v, g, beta, mutate)                  # [B, S, Hv, Dv]
    o = _rms(o, top["gdn_norm_scale"][i], hf["rms_norm_eps"]) * jax.nn.silu(
        z.reshape(B, S, Hv, Dv))
    return _times(o.reshape(B, S, Hv * Dv), top["gdn_out"], i)


@jax.jit
def _attend(q, k, v):
    """Causal softmax(q k^T / sqrt(D)) v over [B, S, H, D], the KV
    heads repeated to the query heads."""
    S, D, G = q.shape[1], q.shape[-1], q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def attention(h, top, i, hf, mutate=None):
    """Multi-head attention with no positions on h [B, S, E] with entry
    i of the `attn_` stacks."""
    eps = hf["rms_norm_eps"]
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf["hidden_size"] // H
    B, S, _ = h.shape
    q = _times(h, top["attn_wq"], i).reshape(B, S, H, D)
    k = _times(h, top["attn_wk"], i).reshape(B, S, KV, D)
    v = _times(h, top["attn_wv"], i).reshape(B, S, KV, D)
    # over the whole projection: every head's values under ONE statistic
    axes = -1 if mutate == "per_head_qk_norm" else (-2, -1)
    q = _rms(q, top["attn_q_norm_scale"][i], eps, axes)
    k = _rms(k, top["attn_k_norm_scale"][i], eps, axes)
    if mutate == "rotary_on_full_layers":
        q, k = _rope(q, 10000.0), _rope(k, 10000.0)
    o = _attend(q, k, v)
    return _times(o.reshape(B, S, H * D), top["attn_wo"], i, rows=True)


def _swiglu(n, lw):
    inner = jax.nn.silu(_times(n, lw["w_gate"])) * _times(n, lw["w_in"])
    return _times(inner, lw["w_out"], rows=True)


def _layer(x, lw, top, i, kind, hf, mutate=None):
    """One layer on x [B, S, E] float32. `lw`: its norms and FFN; entry
    i of `top`'s stacks of its kind: its operator's leaves."""
    eps = hf["rms_norm_eps"]
    op = gated_delta_net if kind == "linear_attention" else attention
    s1, s2 = lw["ln1_post_scale"], lw["ln2_post_scale"]
    if mutate == "pre_norm":
        x = x + op(_rms(x, s1, eps), top, i, hf, mutate)
        return x + _swiglu(_rms(x, s2, eps), lw)
    x = x + _rms(op(x, top, i, hf, mutate), s1, eps)
    return x + _rms(_swiglu(x, lw), s2, eps)


def forward_logits(top: Dict[str, Any], layer_weights: Callable[[int], Dict],
                   tokens, hf: Dict[str, Any], mutate: Optional[str] = None):
    """Logits [B, S, V] float32 (numpy) of tokens [B, S] (see the module
    docstring for `top` and `layer_weights`). `mutate` is None or one of
    MUTANTS."""
    if mutate is not None and mutate not in MUTANTS:
        raise ValueError(f"unknown mutant {mutate!r}; there are {MUTANTS}")
    top = {k: jnp.asarray(v) for k, v in top.items()}
    seen = {kind: 0 for kind in KINDS}
    with jax.default_matmul_precision("highest"):
        x = top["embed"][jnp.asarray(tokens)].astype(F32)
        for l, kind in enumerate(hf["layer_types"]):
            # the operator's leaves: entry (layers of this kind so far)
            x = _layer(x, layer_weights(l), top, seen[kind], kind, hf, mutate)
            seen[kind] += 1
        x = _rms(x, top["ln_f_scale"], hf["rms_norm_eps"])
        head, V = top["lm_head"], top["lm_head"].shape[-1]
        # a block of the vocabulary at a time, each to the host
        return np.concatenate(
            [np.asarray(_block(x, head, None, lo, min(lo + BLOCK, V), False))
             for lo in range(0, V, BLOCK)], axis=-1)


def loss(top, layer_weights, tokens, hf, mutate: Optional[str] = None) -> float:
    """Token-mean next-token cross-entropy of tokens [B, S + 1]."""
    tokens = np.asarray(tokens)
    logits = forward_logits(top, layer_weights, tokens[:, :-1], hf, mutate)
    logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    tgt = jnp.asarray(tokens[:, 1:])
    return float(-jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1)))
