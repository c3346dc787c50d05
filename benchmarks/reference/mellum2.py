"""Plain float32 reference of the Mellum 2 decoder (`model_type` `mellum`,
JetBrains/Mellum2-12B-A2.5B-Instruct).

Straight `jax.numpy`, one layer at a time, no kernels, no cache, no
ring, no batching of requests; independent of models/transformer.py and
inference/. It follows the block as the published `config.json` gives
it (every number below is a key of that file):

    h = x + Attn_l(RMSNorm(x));  y = h + MLP_l(RMSNorm(h))
    RMSNorm(x) = x * rsqrt(mean x^2 + rms_norm_eps) * w, in float32
    a final RMSNorm and an untied head.

`Attn_l`: q = x W_q as `num_attention_heads` heads of `head_dim`, k and
v as `num_key_value_heads`, no bias, no norm on q or k; rotary over all
of a head, split-halves pairing, with the table of the layer's TYPE
(`layer_types[l]`); scores times head_dim^-0.5; query i sees key j iff
0 <= i - j, and in a `sliding_attention` layer also i - j <
`sliding_window`; softmax in float32; W_o. Query head h reads K/V head
h // (heads / kv heads).

Rotary of a `sliding_attention` layer (`rope_parameters` of that type,
`rope_type` default): inv_freq_d = theta^(-2d / D), cos and sin as they
are. Of a `full_attention` layer (`rope_type` yarn): extrap_d as above,
interp_d = extrap_d / factor; c(n) = D ln(L / (2 pi n)) / (2 ln theta)
with L `original_max_position_embeddings`; low = floor(c(beta_fast)),
high = ceil(c(beta_slow)), both clipped to [0, D / 2 - 1] (`truncate`,
ASSUMED true: the file has no such key); ramp_d = clip((d - low) /
(high - low), 0, 1); inv_freq_d = interp_d ramp_d + extrap_d (1 -
ramp_d); cos and sin BOTH times `attention_factor`. Static in the
sequence's length.

`MLP_l`, `mlp_layer_types[l]` `sparse`: p = softmax(x W_r) over
`num_experts` in float32; the `num_experts_per_tok` largest (ties to
the lowest index: `lax.top_k`); weights p_e over the sum of the chosen
p (`norm_topk_prob`); sum_e w_e W_down_e (silu(W_gate_e x) * W_up_e x),
experts of `moe_intermediate_size`; no shared expert, no scaling
factor, no bias on the choice. `dense`: a SwiGLU of `intermediate_size`
(a leading layer: its leaves arrive as `dense_<name>` in `top`).

Everything is computed in BLOCKS so that two prompts of 2,327 tokens at
the published widths fit beside a serving engine: one prompt at a time,
attention QUERY_BLOCK queries against all keys, the routed block
EXPERTS_AT_A_TIME experts against every token (every expert is applied
to every token and a [tokens, experts] weight matrix, zero outside the
chosen, combines them), the head QUERY_BLOCK tokens against VOCAB_SLAB
rows, and the logits are returned on the HOST (numpy).

Weights arrive in the names and shapes of `models/transformer.init`
(wq [E,H,D], wk / wv [E,KV,D], wo [H,D,E], w_router [E,X], w_gate /
w_in [X,E,F], w_out [X,F,E], ln1_scale / ln2_scale [E]; `top`: embed
[V,E], ln_f_scale [E], lm_head [E,V]) in whatever dtype the system
holds and are widened to float32 here. Every matmul runs under
default_matmul_precision("highest").

`forward_logits(..., mutate=)` computes deliberately WRONG models (the
tests' tolerance and the limits of the benchmark's logits check are set
against them): "all_full" (no layer has a window), "all_windowed"
(every layer has one, and the plain table), "plain_rope" (the full
layers rotate by the plain table), "no_attention_factor" (YaRN's
frequencies without its factor on cos and sin), "stale_ring" (a
windowed layer reads, for the oldest block of each query's window,
what the ring held one turn earlier: at the published sizes the keys
and values of 128 positions from 1,280 positions before; `stale_turn`) and "k_minus_1" (one expert
fewer).

Departures from the published description: none in the mathematics.
`max_window_layers` 0 and `use_sliding_window` true decide nothing
where `layer_types` is explicit and are not read; the multi-token-
prediction head the model card mentions has no key in the file and is
not computed.
"""

import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MUTANTS = ("all_full", "all_windowed", "plain_rope", "no_attention_factor",
           "stale_ring", "k_minus_1")
QUERY_BLOCK = 512   # scores of 32 heads x 512 queries x 2,327 keys: 0.15 GB
VOCAB_SLAB = 16384  # rows of the head widened to float32 at a time
EXPERTS_AT_A_TIME = 8  # 2,327 tokens x 8 experts x 2,304 outputs: 0.17 GB
DENSE_PREFIX = "dense_"


def stale_turn(hf: Dict[str, Any]):
    """(block, ring) in tokens of the "stale_ring" mutant: the served
    block size, and the ring the engine derives from it and the window
    (ceil((window + block - 1) / block) + 1 blocks)."""
    bs = int(hf.get("serve", {}).get("engine", {}).get("kv_block_size", 128))
    return bs, (-(-(int(hf["sliding_window"]) + bs - 1) // bs) + 1) * bs


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rotary_table(rope: Dict[str, Any], D: int, factor_on: bool = True):
    """(inv_freq [D / 2], the factor on cos and sin) of one layer type's
    `rope_parameters` entry."""
    theta = float(rope["rope_theta"])
    extrap = theta ** (-jnp.arange(0, D, 2, dtype=F32) / D)
    if rope.get("rope_type", "default") == "default":
        return extrap, 1.0
    L = float(rope["original_max_position_embeddings"])

    def band(turns):
        return D * math.log(L / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(band(rope["beta_fast"])), 0)
    high = min(math.ceil(band(rope["beta_slow"])), D // 2 - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv = extrap / float(rope["factor"]) * ramp + extrap * (1.0 - ramp)
    return inv, float(rope["attention_factor"]) if factor_on else 1.0


def _rope(x, inv, factor):
    """x [S, H, D] at positions 0..S-1; rotate_half pairing."""
    S, D = x.shape[0], x.shape[-1]
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]      # [S, D/2]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def routed_mlp(n, lw, hf, mutate: Optional[str] = None):
    """The routed block on normed activations n [S, E]: every expert
    applied to every token, EXPERTS_AT_A_TIME experts widened to
    float32 at a time, combined by a [tokens, experts] weight matrix
    that is zero outside the chosen."""
    top_k = hf["num_experts_per_tok"] - (mutate == "k_minus_1")
    p = jax.nn.softmax((n @ lw["w_router"].astype(F32)).astype(F32), axis=-1)
    _, chosen = jax.lax.top_k(p, top_k)
    w = jnp.sum(jax.nn.one_hot(chosen, p.shape[-1], dtype=F32), -2) * p
    if hf.get("norm_topk_prob"):
        w = w / jnp.sum(w, -1, keepdims=True)

    def some(args):
        w_gate, w_in, w_out, wx = args        # [G,E,F] x 2, [G,F,E], [G,S]
        gate = jnp.einsum("se,xef->sxf", n, w_gate.astype(F32))
        up = jnp.einsum("se,xef->sxf", n, w_in.astype(F32))
        each = jnp.einsum("sxf,xfe->sxe", jax.nn.silu(gate) * up,
                          w_out.astype(F32))
        return jnp.einsum("xs,sxe->se", wx, each)

    G = min(EXPERTS_AT_A_TIME, p.shape[-1])
    groups = lambda a: a.reshape(a.shape[0] // G, G, *a.shape[1:])
    return jnp.sum(jax.lax.map(some, (
        groups(lw["w_gate"]), groups(lw["w_in"]), groups(lw["w_out"]),
        groups(w.T))), axis=0)


def router_margin(n, lw, hf):
    """[S]: the gap between the smallest chosen probability and the
    largest one left out, as a share of the former (1 for a dense
    layer): under bf16's resolution the system may swap the two."""
    if "w_router" not in lw:
        return jnp.ones(n.shape[:1], F32)
    k = hf["num_experts_per_tok"]
    p, _ = jax.lax.top_k(jax.nn.softmax(
        (n @ lw["w_router"].astype(F32)).astype(F32), axis=-1), k + 1)
    return (p[..., k - 1] - p[..., k]) / p[..., k - 1]


def _attention(q, k, v, window: int, stale=None):
    """q [S, H, D], k / v [S, KV, D] -> [S, H, D]. stale: None, or
    stale_turn's pair."""
    S, H, D = q.shape
    KV = k.shape[1]
    qg = q.reshape(S, KV, H // KV, D)
    keys = jnp.arange(S)

    def scores(qb, kk, live):
        s = jnp.einsum("qkgd,skd->kgqs", qb, kk) / np.sqrt(D)
        return jnp.where(live[None, None], s, -jnp.inf)

    def block(args):
        qb, rows = args                                   # [Q,KV,G,D], [Q]
        live = keys[None, :] <= rows[:, None]
        if window:
            live &= rows[:, None] - keys[None, :] < window
        s = scores(qb, k, live)
        if stale is None:
            return jnp.einsum("kgqs,skd->qkgd", jax.nn.softmax(s, -1), v)
        # the oldest block of each query's window comes from one turn
        # of the ring earlier (where that is a position)
        block, turn = stale
        old = live & (rows[:, None] - keys[None, :] >= window - block) \
            & (keys[None, :] >= turn)
        k_old, v_old = jnp.roll(k, turn, axis=0), jnp.roll(v, turn, axis=0)
        s = jnp.where(old[None, None], scores(qb, k_old, live), s)
        p = jax.nn.softmax(s, -1)
        return (jnp.einsum("kgqs,skd->qkgd", jnp.where(old[None, None], 0, p), v)
                + jnp.einsum("kgqs,skd->qkgd",
                             jnp.where(old[None, None], p, 0), v_old))

    n = -(-S // QUERY_BLOCK)
    pad = n * QUERY_BLOCK - S
    out = jax.lax.map(block, (
        jnp.pad(qg, [(0, pad), (0, 0), (0, 0), (0, 0)]).reshape(
            n, QUERY_BLOCK, KV, H // KV, D),
        jnp.pad(keys, (0, pad), constant_values=S - 1).reshape(
            n, QUERY_BLOCK)))
    return out.reshape(n * QUERY_BLOCK, H, D)[:S]


def _layer(x, lw, hf, kind: str, mutate: Optional[str] = None):
    """One decoder layer of type `kind` on one prompt x [S, E] float32,
    and its router's margin [S]."""
    eps, D = hf["rms_norm_eps"], hf["head_dim"]
    windowed = {"all_full": False, "all_windowed": True}.get(
        mutate, kind == "sliding_attention")
    rope_of = "sliding_attention" if (
        windowed or mutate == "plain_rope") else "full_attention"
    inv, factor = rotary_table(hf["rope_parameters"][rope_of], D,
                               mutate != "no_attention_factor")
    n = _rms(x, lw["ln1_scale"], eps)
    q = jnp.einsum("se,ehd->shd", n, lw["wq"].astype(F32))
    k = jnp.einsum("se,ehd->shd", n, lw["wk"].astype(F32))
    v = jnp.einsum("se,ehd->shd", n, lw["wv"].astype(F32))
    a = _attention(_rope(q, inv, factor), _rope(k, inv, factor), v,
                   int(hf["sliding_window"]) if windowed else 0,
                   stale_turn(hf) if windowed and mutate == "stale_ring"
                   else None)
    h = x + jnp.einsum("shd,hde->se", a, lw["wo"].astype(F32))
    n2 = _rms(h, lw["ln2_scale"], eps)
    if "w_router" in lw:
        y = routed_mlp(n2, lw, hf, mutate)
    else:
        y = (jax.nn.silu(n2 @ lw["w_gate"].astype(F32))
             * (n2 @ lw["w_in"].astype(F32))) @ lw["w_out"].astype(F32)
    return h + y, router_margin(n2, lw, hf)


def forward_logits(top: Dict[str, Any], layer_weights: Callable[[int], Dict],
                   tokens, hf: Dict[str, Any], mutate: Optional[str] = None):
    """Logits [B, S, V] float32 (numpy, on the host) of tokens [B, S]
    (see the module docstring for `top` and `layer_weights`). `mutate`
    is None or one of MUTANTS."""
    if mutate is not None and mutate not in MUTANTS:
        raise ValueError(f"unknown mutant {mutate!r}; there are {MUTANTS}")
    return _forward(top, layer_weights, tokens, hf, mutate)[0]


def _head(x, top, hf):
    """Logits [S, V] of one prompt's last hidden states x [S, E], on the
    HOST: QUERY_BLOCK tokens against VOCAB_SLAB rows of the head at a
    time, so that neither the float32 head (0.9 GB at the published
    widths) nor a prompt's logits (2,327 x 98,304 x 4 B = 0.9 GB) sit on
    the device beside a serving engine."""
    n = _rms(x, jnp.asarray(top["ln_f_scale"]), hf["rms_norm_eps"])
    lm_head = jnp.asarray(top["lm_head"])
    slab = jax.jit(lambda nb, w: nb @ w.astype(F32))
    return np.concatenate([
        np.concatenate([np.asarray(slab(n[r:r + QUERY_BLOCK],
                                        lm_head[:, c:c + VOCAB_SLAB]))
                        for c in range(0, lm_head.shape[1], VOCAB_SLAB)], 1)
        for r in range(0, n.shape[0], QUERY_BLOCK)], 0)


def _forward(top, layer_weights, tokens, hf, mutate):
    layers = {kind: jax.jit(
        lambda x, lw, kind=kind: _layer(x, lw, hf, kind, mutate))
        for kind in set(hf["layer_types"])}
    n_dense = list(hf["mlp_layer_types"]).count("dense")
    with jax.default_matmul_precision("highest"):
        xs = [jnp.asarray(top["embed"])[jnp.asarray(row)].astype(F32)
              for row in np.asarray(tokens)]
        margins = []
        for l in range(hf["num_hidden_layers"]):
            if l < n_dense:
                lw = {name[len(DENSE_PREFIX):]: jnp.asarray(leaf)[l]
                      for name, leaf in top.items()
                      if name.startswith(DENSE_PREFIX)}
            else:
                lw = layer_weights(l - n_dense)
            outs = [layers[hf["layer_types"][l]](x, lw) for x in xs]
            xs = [x for x, _ in outs]
            margins.append(jnp.stack([m for _, m in outs]))
        return np.stack([_head(x, top, hf) for x in xs]), jnp.stack(margins)


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
