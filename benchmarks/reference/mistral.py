"""Plain float32 reference of the Mistral decoder (MistralForCausalLM).

Straight `jax.numpy`, one layer at a time, no kernels, no cache, no
batching tricks; independent of models/transformer.py and
inference/model.py. It follows the published block:

    h = x + Attn(RMSNorm(x));  y = h + W_down(silu(W_gate n) * W_up n),
    n = RMSNorm(h)

with rotary embeddings in the split-halves (rotate_half) pairing over
the whole head, grouped-query attention (query head h reads KV head
h // (H / KV)), causal masking restricted to the last `sliding_window`
keys (key j visible to query i iff i - window < j <= i), RMSNorm in
float32, an untied output head, and next-token cross-entropy as the
token mean.

Weights arrive one layer at a time in the names and shapes of the
training layout (wq [E,H,D], wk/wv [E,KV,D], wo [H,D,E], w_gate/w_in
[E,F], w_out [F,E], ln1_scale/ln2_scale [E]) in whatever dtype the
system holds them, and are widened to float32 here, so a system that
stores bf16 is compared against exact arithmetic on its own values.
Every matmul runs under default_matmul_precision("highest"): on a TPU
a float32 matmul is otherwise a single bf16 pass.

Departures from the published description: none in the mathematics;
biases do not exist in this family and are not read.
"""

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


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


def _layer(x, lw, hf):
    """One decoder layer on x [B, S, E] float32."""
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    window = hf.get("sliding_window") or 0
    n = _rms(x, lw["ln1_scale"], eps)
    q = jnp.einsum("bse,ehd->bshd", n, lw["wq"].astype(F32))
    k = jnp.einsum("bse,ehd->bshd", n, lw["wk"].astype(F32))
    v = jnp.einsum("bse,ehd->bshd", n, lw["wv"].astype(F32))
    q, k = _rope(q, theta), _rope(k, theta)
    B, S, H, D = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    mask = j <= i
    if window:
        mask = mask & (j > i - window)
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    h = x + jnp.einsum("bshd,hde->bse", a, lw["wo"].astype(F32))
    n = _rms(h, lw["ln2_scale"], eps)
    gate = jnp.einsum("bse,ef->bsf", n, lw["w_gate"].astype(F32))
    up = jnp.einsum("bse,ef->bsf", n, lw["w_in"].astype(F32))
    return h + jnp.einsum("bsf,fe->bse", jax.nn.silu(gate) * up,
                          lw["w_out"].astype(F32))


def forward_logits(top: Dict[str, Any], layer_weights: Callable[[int], Dict],
                   tokens, hf: Dict[str, Any]):
    """Logits [B, S, V] float32 of tokens [B, S]. `top` holds `embed`
    [V, E], `ln_f_scale` [E] and `lm_head` [E, V]; `layer_weights(l)`
    returns layer l's weights (so a 7B-width model never sits on the
    device twice)."""
    layer = jax.jit(lambda x, lw: _layer(x, lw, hf))
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(top["embed"])[jnp.asarray(tokens)].astype(F32)
        for l in range(hf["num_hidden_layers"]):
            x = layer(x, layer_weights(l))
        x = _rms(x, jnp.asarray(top["ln_f_scale"]), hf["rms_norm_eps"])
        return jnp.einsum("bse,ev->bsv", x,
                          jnp.asarray(top["lm_head"]).astype(F32))


def loss(top, layer_weights, tokens, hf) -> float:
    """Token-mean next-token cross-entropy of tokens [B, S + 1]."""
    tokens = np.asarray(tokens)
    logits = forward_logits(top, layer_weights, tokens[:, :-1], hf)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tgt = jnp.asarray(tokens[:, 1:])
    return float(-jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1)))


def loss_and_grads(params: Dict[str, Any], tokens, hf):
    """Loss and gradients w.r.t. a whole training-layout tree (layers
    stacked on dim 0) — for the tiny CPU test; at published widths the
    tree does not fit beside the system's own state."""
    tokens = jnp.asarray(tokens)

    def f(p):
        with jax.default_matmul_precision("highest"):
            x = p["embed"].astype(F32)[tokens[:, :-1]]
            for l in range(hf["num_hidden_layers"]):
                x = _layer(x, jax.tree.map(lambda a: a[l], p["layers"]), hf)
            x = _rms(x, p["ln_f_scale"], hf["rms_norm_eps"])
            logits = jnp.einsum("bse,ev->bsv", x, p["lm_head"].astype(F32))
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:][..., None], -1))

    return jax.value_and_grad(f)(params)
