"""Plain float32 reference of the Qwen3-Next decoder (`model_type:
qwen3_next`): Gated DeltaNet layers and gated softmax attention layers,
each followed by routed experts beside a gated shared expert.

Straight `jax.numpy`, one layer at a time, no kernels, no cache, no
state slot, no chunking; independent of `deepspeed_tpu/`. With `x` the
residual stream and `N(x; s) = x * rsqrt(mean x^2 + rms_norm_eps) * s`
in float32:

    layer i:  x <- x + Op(N(x; s1));  x <- x + F(N(x; s2))
    Op is gated attention where (i + 1) % full_attention_interval == 0
    (or as `layer_types` names it), else the Gated DeltaNet.

    Gated DeltaNet on h (Hk key heads, Hv value heads of Dk = Dv):
        [q; k; v; z] = W_qkvz h;  [b; a] = W_ba h (one of each a value
        head); [q; k; v] <- silu(causal depthwise convolution of
        linear_conv_kernel_dim taps, no bias, zeros before the sequence
        starts: HERE an explicit sum over shifted copies of the WHOLE
        sequence); beta = sigmoid(b);
        g = -exp(A_log) * softplus(a + dt_bias);
        q, k repeated Hv / Hk times along heads (key head j serves value
        heads j Hv/Hk ... ), each head's q and k L2-normalised
        (x * rsqrt(sum x^2 + 1e-6)), q scaled by Dk^-0.5;
        a value head carries S in R^{Dk x Dv}, zero at the sequence's
        start; for each token t, as a `lax.scan` over tokens (the
        RECURRENCE, not the chunked form):
            S <- exp(g_t) S;  m = S^T k_t;  d = beta_t (v_t - m);
            S <- S + k_t d^T;  o_t = S^T q_t
        o <- N(o; w_norm) * silu(z) over each head's Dv values (the norm
        first, then the gate);  out = W_o o
    Gated attention on h (H query / KV key-value heads of D):
        W_q h gives each head [q; gate]; k = W_k h, v = W_v h; q <-
        N(q; s_q), k <- N(k; s_k) over each head's D values, one scale
        of D for all heads, BEFORE rope; rope (split halves, theta
        `rope_theta`, no scaling) on the first partial_rotary_factor x D
        values of each head, the rest pass; causal softmax, scale
        D^-0.5, GQA; att <- att * sigmoid(gate); W_o. No bias.
    F on m: p = softmax(W_r m) over all experts, float32; the top
        `num_experts_per_tok`, weights p_i / sum of the chosen
        (`norm_topk_prob`); y = sum w_i E_i(m), E_i SwiGLU of
        `moe_intermediate_size`; y += sigmoid(w_sg . m) * E_shared(m).
        Every HELD expert is computed for every token and masked by the
        router's choice.
    logits = W_head N(x; s_out)

Departures from the publisher, each where it is made; the configuration
file lists them under `assumed`:

- the publisher's norms are zero-centred (x * (1 + w)) except the
  DeltaNet's output norm; the scales arrive here as the factor that
  multiplies (1 + w, or w for that one): what an importer stores.
- `W_qkvz`'s and `W_ba`'s columns are [q; k; v; z] and [b; a], each in
  head order; the publisher interleaves them by key-head group (a
  permutation of columns).
- the publisher keeps the state in float32 and so does this; its
  kernels run the chunked form of the same recurrence (chunk 64).
- no multi-token-prediction block (not part of the next-token logits).
- a file that holds a SHARE of the experts (`num_experts` under
  `reduced`, `experts_held.start`): the router keeps its published
  width and top-k, the held experts add their part, what the absent
  ones would add is left out. The vocabulary is the file's.
- ties in the top-k go to the lowest expert index (`lax.top_k`).

Weights arrive in the names and shapes of `models/transformer.init`:
`top` holds `embed` [V, E], `lm_head` [E, V], `ln_f_scale` [E]; the
operators' stacks by kind `gdn_in` [Ng, E, 2 Hk Dk + 2 Hv Dv], `gdn_ba`
[Ng, E, 2 Hv], `gdn_taps` [Ng, 2 Hk Dk + Hv Dv, K] (oldest tap first),
`gdn_a_log` / `gdn_dt_bias` [Ng, Hv], `gdn_norm_scale` [Ng, Dv],
`gdn_out` [Ng, Hv Dv, E] and `attn_wq` / `attn_wq_gate` [Na, E, H, D],
`attn_wk` / `attn_wv` [Na, E, KV, D], `attn_wo` [Na, H, D, E],
`attn_q_norm_scale` / `attn_k_norm_scale` [Na, D], layer l taking the
entry of its place among the layers of its kind. `layer_weights(l)`
returns layer l's: ln1_scale, ln2_scale [E]; w_router [E, X]; w_gate /
w_in [Xh, E, F], w_out [Xh, F, E] (Xh the held experts); ws_gate / ws_in
[E, Fs], ws_out [Fs, E], ws_sgate [E, 1]. They come in whatever dtype
the system holds and are widened to float32 HERE. Every matmul runs
under default_matmul_precision("highest").

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
    "no_state_carry",    # every token sees a zero matrix and no past input
    "no_decay",          # g = 0: nothing is ever forgotten
    "beta_one",          # beta = 1: every write replaces in full
    "no_l2norm",         # q and k as the convolution leaves them
    "state_bf16",        # the matrix rounded to bf16 after every token
    "no_attn_gate",      # attention's output gate left out
    "no_shared_gate",    # the shared expert unweighted
    "plain_norm_scale",  # the DeltaNet's output norm read as zero-centred
    "rope_all_256",      # rope over the whole head
    "no_topk_renorm",    # the chosen experts' raw softmax mass
)
KINDS = {"linear_attention": "gdn_", "full_attention": "attn_"}


def layer_types(hf):
    return hf.get("layer_types") or [
        "full_attention" if (i + 1) % hf["full_attention_interval"] == 0
        else "linear_attention" for i in range(hf["num_hidden_layers"])]


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta, rot):
    """x [B, S, H, D]: its first `rot` values rotated at positions
    0..S-1, split-halves pairing; the rest pass."""
    S = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]      # [S, rot/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., : rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _swiglu(n, w_gate, w_in, w_out):
    return (jax.nn.silu(n @ w_gate.astype(F32)) * (n @ w_in.astype(F32))
            ) @ w_out.astype(F32)


def gated_delta_net(h, ow, hf, mutate=None):
    """The Gated DeltaNet on normed h [B, S, E], token by token."""
    Hk, Hv = hf["linear_num_key_heads"], hf["linear_num_value_heads"]
    Dk, Dv = hf["linear_key_head_dim"], hf["linear_value_head_dim"]
    K = hf["linear_conv_kernel_dim"]
    B, S, _ = h.shape
    C = 2 * Hk * Dk + Hv * Dv
    mixed = h @ ow["gdn_in"].astype(F32)
    u, z = mixed[..., :C], mixed[..., C:]
    ba = h @ ow["gdn_ba"].astype(F32)
    b, a = ba[..., :Hv], ba[..., Hv:]
    taps = ow["gdn_taps"].astype(F32)                          # [C, K]
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
    beta = jnp.ones_like(b) if mutate == "beta_one" else jax.nn.sigmoid(b)
    g = -jnp.exp(ow["gdn_a_log"].astype(F32)) * jax.nn.softplus(
        a + ow["gdn_dt_bias"].astype(F32))
    if mutate == "no_decay":
        g = jnp.zeros_like(g)
    if mutate != "no_l2norm":
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(q, Hv // Hk, axis=2) * Dk ** -0.5
    k = jnp.repeat(k, Hv // Hk, axis=2)

    def token(state, x):
        qt, kt, vt, gt, bt = x                 # [B, Hv, D], [B, Hv]
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
    _, o = jax.lax.scan(token, jnp.zeros((B, Hv, Dk, Dv), F32), xs)
    o = jnp.moveaxis(o, 0, 1)                                  # [B, S, Hv, Dv]
    scale = ow["gdn_norm_scale"].astype(F32)
    if mutate == "plain_norm_scale":
        scale = 1.0 + scale
    o = _rms(o, scale, hf["rms_norm_eps"]) * jax.nn.silu(
        z.reshape(B, S, Hv, Dv))
    return o.reshape(B, S, Hv * Dv) @ ow["gdn_out"].astype(F32)


def attention(h, ow, hf, mutate=None):
    """Gated grouped-query attention on normed h [B, S, E]."""
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    H, KV, D = (hf["num_attention_heads"], hf["num_key_value_heads"],
                hf["head_dim"])
    S = h.shape[1]
    q = jnp.einsum("bse,ehd->bshd", h, ow["attn_wq"].astype(F32))
    gate = jnp.einsum("bse,ehd->bshd", h, ow["attn_wq_gate"].astype(F32))
    k = jnp.einsum("bse,ehd->bshd", h, ow["attn_wk"].astype(F32))
    v = jnp.einsum("bse,ehd->bshd", h, ow["attn_wv"].astype(F32))
    q = _rms(q, ow["attn_q_norm_scale"], eps)
    k = _rms(k, ow["attn_k_norm_scale"], eps)
    rot = D if mutate == "rope_all_256" else int(
        hf.get("partial_rotary_factor", 1.0) * D)
    q, k = _rope(q, theta, rot), _rope(k, theta, rot)
    G = H // KV
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    if mutate != "no_attn_gate":
        o = o * jax.nn.sigmoid(gate)
    return jnp.einsum("bshd,hde->bse", o, ow["attn_wo"].astype(F32))


def held_experts(hf):
    """(first held expert, experts held, the router's width)."""
    held = hf["num_experts"]
    routed = (hf.get("reduced") or {}).get("num_experts", {}).get(
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
    if hf.get("norm_topk_prob", True) and mutate != "no_topk_renorm":
        w = w / jnp.sum(w, -1, keepdims=True)
    return w, (top[..., k - 1] - top[..., k]) / top[..., k - 1]


def moe(n, lw, hf, mutate=None):
    """The FFN on normed n [T, E]: every held expert applied to every
    token, one at a time, weighted by its column; the shared expert
    times its gate."""
    w, margin = route(n, lw, hf, mutate)
    start, held, _ = held_experts(hf)

    def expert(acc, xs):
        w_gate, w_in, w_out, col = xs
        return acc + col[:, None] * _swiglu(n, w_gate, w_in, w_out), None

    out, _ = jax.lax.scan(
        expert, jnp.zeros_like(n),
        (lw["w_gate"], lw["w_in"], lw["w_out"], w[:, start:start + held].T))
    if "ws_gate" in lw:
        y = _swiglu(n, lw["ws_gate"], lw["ws_in"], lw["ws_out"])
        if mutate != "no_shared_gate":
            y = y * jax.nn.sigmoid(n @ lw["ws_sgate"].astype(F32))
        out = out + y
    return out, margin


def _layer(x, lw, ow, kind, hf, mutate=None):
    """One layer on x [B, S, E] float32 -> (x, the router's margin
    [B, S]). `lw`: its norms and FFN; `ow`: its operator's leaves."""
    eps = hf["rms_norm_eps"]
    op = gated_delta_net if kind == "linear_attention" else attention
    x = x + op(_rms(x, lw["ln1_scale"], eps), ow, hf, mutate)
    m = _rms(x, lw["ln2_scale"], eps)
    y, margin = moe(m.reshape(-1, m.shape[-1]), lw, hf, mutate)
    return x + y.reshape(m.shape), margin.reshape(m.shape[:-1])


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
        x = jnp.asarray(top["embed"])[jnp.asarray(tokens)].astype(F32)
        for l, kind in enumerate(layer_types(hf)):
            # the operator's leaves: entry (layers of this kind so far)
            ow = {k: jnp.asarray(v)[seen[kind]] for k, v in top.items()
                  if k.startswith(KINDS[kind])}
            seen[kind] += 1
            x, margin = layer(x, layer_weights(l), ow, kind)
            margins.append(margin)
        x = _rms(x, jnp.asarray(top["ln_f_scale"]), hf["rms_norm_eps"])
        if "lm_head" in top:
            logits = jnp.einsum("bse,ev->bsv", x,
                                jnp.asarray(top["lm_head"]).astype(F32))
        else:
            logits = jnp.einsum("bse,ve->bsv", x,
                                jnp.asarray(top["embed"]).astype(F32))
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
