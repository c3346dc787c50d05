"""Plain float32 reference of the Arcee Trinity decoder (`afmoe`,
AfmoeForCausalLM), its loss and, by `jax.grad` of that loss, its
gradients.

Straight `jax.numpy`, one layer at a time, no kernels, no scan over
layers, no sorting, no cache; independent of models/transformer.py,
moe/dropless.py and inference/model.py. It follows the published block
(config.json keys; what is no key is the family's public modelling
code, `transformers` models/afmoe, and is listed under `assumed` in the
configuration's file). E hidden, H query and KV key/value heads of D,
window W:

    x0 = embed[token] * sqrt(E)                          (mup_enabled)
    a  = x + N_post_attn(Attn_l(N_in(x)))
    x' = a + N_post_mlp(F_l(N_pre_mlp(a)))               four RMSNorms
    logits = N_f(x_L) @ head                             untied

    Attn_l(h): q = h Wq, k = h Wk, v = h Wv; an RMSNorm over each HEAD's
      D values of q and of k (one learned scale of D for q, one for k);
      rotary (rotate_half pairing over all D) on q and k ONLY where
      layer_types[l] is sliding_attention; causal softmax(q k^T /
      sqrt(D)) v with key j visible to query i iff 0 <= i - j < W on a
      sliding layer, iff j <= i on a full one (which has no positions
      at all); out = (att * sigmoid(h Wg)) Wo.
    F_l, l < num_dense_layers: (silu(h Wgate) * (h Wup)) Wdown.
    F_l otherwise: s = sigmoid(h Wr) in float32 over ALL the router's
      experts; the num_experts_per_tok chosen are the largest of s + b
      (b = expert_bias; ties to the lowest index; groups of one);
      w_e = route_scale * s_e / (sum of the chosen s + 1e-20)
      (route_norm; b is NOT in the weights);
      F = Shared(h) + sum over chosen e of w_e Expert_e(h), each a
      SwiGLU.

Weights arrive one layer at a time in the names and shapes of the
training layout (models/transformer.init: wq / wq_gate [E,H,D], wk / wv
[E,KV,D], wo [H,D,E], q_norm_scale / k_norm_scale [D], ln1_scale,
ln1_post_scale, ln2_scale, ln2_post_scale [E]; a routed layer's
w_router [E,X], expert_bias [X], w_gate / w_in [Xh,E,F], w_out
[Xh,F,E], ws_gate / ws_in [E,Fs], ws_out [Fs,E]; a leading dense
layer's under `dense_<name>` [num_dense_layers, ...] in `top`, its MLP
w_gate / w_in [E,F], w_out [F,E]) in whatever dtype the system holds
them, and are widened to float32 here. Every matmul runs under
default_matmul_precision("highest").

Departures from the published description, each by the cut and none in
the mathematics of what is held:
  - a chip that holds a SHARE of the experts (the stacks hold Xh of the
    router's X; `experts_held.start` names the first) adds the outputs
    of the held experts alone: a pair routed to an expert held
    elsewhere adds nothing here (its chip adds it), while its score
    stays in the sum the weights are divided by. Nothing stands in for
    the exchange or for the absent experts.
  - the embedding and the head hold a slice of the vocabulary
    (`vocab_size` rows); the softmax of the loss runs over that slice.
  - the experts are a loop over the held ones, each multiplied by every
    token and weighted by a one-hot of the choice (no sorting, no
    grouped product): the same sum, Xh / k times the operations.
  - attention runs a block of heads at a time (HEAD_BLOCK), so that the
    scores of 8,192 positions fit beside the system's own state: the
    same numbers head by head.
"""

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# heads whose [S, S] scores are held at once
HEAD_BLOCK = 2


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


# The parts of the layer, each a function of this module that the layer
# calls by name: a control of the comparison (benchmarks/afmoe_audit.py,
# tests/test_trinity.py) replaces ONE of them by a wrong one and must
# come out not correct.

def sliding(hf: Dict[str, Any], li: int) -> bool:
    """Whether model layer li is a sliding_attention layer."""
    return hf["layer_types"][li] == "sliding_attention"


def window_of(hf, li: int):
    """Model layer li's window: key j visible to query i iff
    0 <= i - j < window; None: every j <= i."""
    return hf["sliding_window"] if sliding(hf, li) else None


def rotates(hf, li: int) -> bool:
    """Rotary on the sliding layers alone: a full layer has no
    positions at all."""
    return sliding(hf, li)


def head_norm(x, scale, eps):
    """An RMSNorm a HEAD of x [B, S, heads, D]: the statistic over its
    D values, one learned scale of D for all the heads."""
    return _rms(x, scale, eps)


def output_gate(att, g):
    return att * jax.nn.sigmoid(g)


def post_norm(x, scale, eps):
    """The second norm, on a sub-layer's OUTPUT before the residual."""
    return _rms(x, scale, eps)


def embed_scale(hf) -> float:
    return np.sqrt(hf["hidden_size"]) if hf.get("mup_enabled") else 1.0


def attention(h, lw, hf, li: int):
    """Attn_l over the normed h [B, S, E] of model layer li."""
    eps, W = hf["rms_norm_eps"], window_of(hf, li)
    q = jnp.einsum("bse,ehd->bshd", h, lw["wq"].astype(F32))
    k = jnp.einsum("bse,ehd->bshd", h, lw["wk"].astype(F32))
    v = jnp.einsum("bse,ehd->bshd", h, lw["wv"].astype(F32))
    g = jnp.einsum("bse,ehd->bshd", h, lw["wq_gate"].astype(F32))
    q = head_norm(q, lw["q_norm_scale"], eps)
    k = head_norm(k, lw["k_norm_scale"], eps)
    if rotates(hf, li):
        q, k = _rope(q, hf["rope_theta"]), _rope(k, hf["rope_theta"])
    B, S, H, D = q.shape
    rep = H // k.shape[2]
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    mask = (j <= i) & ((i - j < W) if W else True)
    outs = []
    for h0 in range(0, H, HEAD_BLOCK):
        hs = slice(h0, h0 + HEAD_BLOCK)
        kv = [n // rep for n in range(H)][hs]  # query head n reads KV n // rep
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, :, hs],
                       k[:, :, jnp.asarray(kv)]) / np.sqrt(D)
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :, jnp.asarray(kv)]))
    att = output_gate(jnp.concatenate(outs, axis=2), g)
    return jnp.einsum("bshd,hde->bse", att, lw["wo"].astype(F32))


def _swiglu(h, w_gate, w_in, w_out):
    gate = jnp.einsum("...e,ef->...f", h, w_gate.astype(F32))
    up = jnp.einsum("...e,ef->...f", h, w_in.astype(F32))
    return jnp.einsum("...f,fe->...e", jax.nn.silu(gate) * up,
                      w_out.astype(F32))


def router_scores(h, lw):
    """s = sigmoid(h Wr) in float32, over ALL the router's experts."""
    return jax.nn.sigmoid(jnp.einsum("...e,ex->...x", h.astype(F32),
                                     lw["w_router"].astype(F32)))


def chosen_weights(s, b, chosen, hf, held):
    """The chosen experts' weights [.., k]: their UNBIASED scores over
    the sum of all k of them (held here or not: `held` [.., k] says
    which are, and is not read), times route_scale."""
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if hf["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * hf["route_scale"]


def route(h, lw, hf):
    """(chosen [.., k] expert ids over the router's whole width, their
    weights [.., k]) of the normed h."""
    s = router_scores(h, lw)
    b = lw["expert_bias"].astype(F32)
    # the k largest of s + b, ties to the lowest index (a stable sort)
    chosen = jnp.argsort(-(s + b), axis=-1,
                         stable=True)[..., :hf["num_experts_per_tok"]]
    start, count = held_slice(lw, hf)
    held = (chosen >= start) & (chosen < start + count)
    return chosen, chosen_weights(s, b, chosen, hf, held)


def held_slice(lw, hf):
    """(first, count) of the router's experts whose weights are here."""
    return (int((hf.get("experts_held") or {}).get("start", 0)),
            lw["w_in"].shape[0])


def routed_experts(h, lw, hf):
    """sum over the chosen experts HELD here of w_e Expert_e(h): the
    stacks hold experts [start, start + Xh) of the router's."""
    chosen, w = route(h, lw, hf)
    start, _ = held_slice(lw, hf)
    out = jnp.zeros_like(h)
    for e in range(lw["w_in"].shape[0]):
        # this expert's weight a token: w where it was chosen, else 0
        w_e = jnp.sum(jnp.where(chosen == start + e, w, 0.0), axis=-1)
        out = out + w_e[..., None] * _swiglu(
            h, lw["w_gate"][e], lw["w_in"][e], lw["w_out"][e])
    return out


def shared_expert(h, lw):
    return _swiglu(h, lw["ws_gate"], lw["ws_in"], lw["ws_out"])


def attention_half(x, lw, hf, li: int):
    """a = x + N_post_attn(Attn_l(N_in(x)))."""
    eps = hf["rms_norm_eps"]
    return x + post_norm(attention(_rms(x, lw["ln1_scale"], eps), lw, hf, li),
                         lw["ln1_post_scale"], eps)


def mlp_input(a, lw, hf):
    """h = N_pre_mlp(a): what F_l, and so its router, reads."""
    return _rms(a, lw["ln2_scale"], hf["rms_norm_eps"])


def mlp_half(a, lw, hf, li: int):
    """x' = a + N_post_mlp(F_l(N_pre_mlp(a)))."""
    h = mlp_input(a, lw, hf)
    if li < hf["num_dense_layers"]:
        f = _swiglu(h, lw["w_gate"], lw["w_in"], lw["w_out"])
    else:
        f = shared_expert(h, lw) + routed_experts(h, lw, hf)
    return a + post_norm(f, lw["ln2_post_scale"], hf["rms_norm_eps"])


def layer(x, lw, hf, li: int):
    """Model layer li on x [B, S, E] float32; lw its weights under the
    plain names (a leading dense layer's `dense_` prefix taken off)."""
    return mlp_half(attention_half(x, lw, hf, li), lw, hf, li)


def _dense_layer(top, d):
    return {k[len("dense_"):]: jnp.asarray(v)[d] for k, v in top.items()
            if k.startswith("dense_")}


def forward_logits(top: Dict[str, Any], layer_weights: Callable[[int], Dict],
                   tokens, hf: Dict[str, Any], routed_inputs=None):
    """Logits [B, S, V] float32 of tokens [B, S]. `top` holds `embed`
    [V, E], `ln_f_scale` [E], `lm_head` [E, V] and the leading dense
    layers' `dense_<name>`; `layer_weights(l)` returns ROUTED layer l's
    weights (model layer num_dense_layers + l), one at a time. A list
    `routed_inputs` gains each routed layer's `mlp_input` [B, S, E]."""
    nd = hf["num_dense_layers"]
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(top["embed"])[jnp.asarray(tokens)].astype(F32)
        x = x * embed_scale(hf)
        for li in range(hf["num_hidden_layers"]):
            lw = _dense_layer(top, li) if li < nd else layer_weights(li - nd)
            a = jax.jit(lambda x, lw, li=li:
                        attention_half(x, lw, hf, li))(x, lw)
            if routed_inputs is not None and li >= nd:
                routed_inputs.append(mlp_input(a, lw, hf))
            x = jax.jit(lambda a, lw, li=li: mlp_half(a, lw, hf, li))(a, lw)
        x = _rms(x, jnp.asarray(top["ln_f_scale"]), hf["rms_norm_eps"])
        return jnp.einsum("bse,ev->bsv", x,
                          jnp.asarray(top["lm_head"]).astype(F32))


def _ce(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))


def loss(top, layer_weights, tokens, hf) -> float:
    """Token-mean next-token cross-entropy of tokens [B, S + 1]."""
    tokens = np.asarray(tokens)
    logits = forward_logits(top, layer_weights, tokens[:, :-1], hf)
    return float(_ce(logits, jnp.asarray(tokens[:, 1:])))


def loss_and_grads(params: Dict[str, Any], tokens, hf):
    """Loss and gradients w.r.t. a whole training-layout tree (layers
    stacked on dim 0) — for the tiny CPU test; at published widths the
    tree does not fit beside the system's own state. `expert_bias`
    moves the choice alone: its gradient is zero."""
    tokens = jnp.asarray(tokens)
    nd = hf["num_dense_layers"]

    def f(p):
        with jax.default_matmul_precision("highest"):
            x = p["embed"].astype(F32)[tokens[:, :-1]] * embed_scale(hf)
            for li in range(hf["num_hidden_layers"]):
                lw = (_dense_layer(p, li) if li < nd else
                      jax.tree.map(lambda a: a[li - nd], p["layers"]))
                x = layer(x, lw, hf, li)
            x = _rms(x, p["ln_f_scale"], hf["rms_norm_eps"])
            logits = jnp.einsum("bse,ev->bsv", x, p["lm_head"].astype(F32))
        return _ce(logits, tokens[:, 1:])

    return jax.value_and_grad(f)(params)
