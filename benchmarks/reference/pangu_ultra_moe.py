"""Plain float32 reference of the openPangu-Ultra-MoE decoder
(`model_type: pangu_ultra_moe`), as one chip's share of an
expert-parallel deployment sees it.

Straight `jax.numpy`, one layer at a time, no kernels, no cache, no
absorbed form, no sorting; independent of `deepspeed_tpu/`. With `x`
the residual stream and every `N_*` an RMSNorm (float32, eps
`rms_norm_eps`) with its own scale:

    layer (sandwich norm, four norms):
        x <- x + N_post_attn(MLA(N_in(x)))
        x <- x + N_post_mlp(F(N_pre_mlp(x)))
    F, the first `first_k_dense_replace` layers: a dense SwiGLU of width
        `intermediate_size`
    F, the others: shared(m) + routed_scaling_factor * sum over the
        chosen experts e of w_e * expert_e(m), `shared` and every
        `expert_e` SwiGLUs of width `moe_intermediate_size`
    router: s = sigmoid(W_g m) over ALL routed experts, float32; the
        `num_experts_per_tok` largest s; w = s_chosen / (sum s_chosen +
        1e-20) (`norm_topk_prob`)
    MLA on h = N_in(x), the NAIVE form:
        c_q = N_q(W_dq h);  q_i = W_uq,i c_q = [q_nope,i ; q_rope,i]
        [c_kv ; k_r] = W_dkv h;  c_kv <- N_kv(c_kv);  k_r <- RoPE(k_r)
        [k_nope,i ; v_i] = W_ukv,i c_kv        (up-projected, every head)
        score_i = (q_nope,i . k_nope,i + RoPE(q_rope,i) . k_r)
                  / sqrt(qk_nope_head_dim + qk_rope_head_dim)
        causal, softmax in float32; o_i = sum p v_i; out = W_o [o_1..o_H]

Departures from the published description, each where it is made:

- config.json states neither the router's scoring function nor groups
  nor a correction bias: sigmoid scores with plain top-k over all
  routed experts (the family's convention for a scaling factor with
  norm_topk_prob) is ASSUMED, as is the split-halves (rotate_half)
  pairing of the rotary dimensions, a permutation of W_uq / W_dkv
  columns. Both are listed in the configuration file's `assumed`.
- the multi-token-prediction block (`num_nextn_predict_layers`) is not
  part of the next-token logits and is not here.
- THE SHARE. `n_routed_experts` in `hf` is what this chip HOLDS (its
  expert stacks have that many), `reduced.n_routed_experts.published`
  the router's width and `experts_held.start` the first held expert.
  The router scores and chooses among all of them; a chosen expert
  that is held elsewhere adds nothing HERE (its chip adds it), so the
  layer's output is partial, and that partial result goes on to the
  next layer, in the program and here alike. `vocab_size` is the slice
  of the vocabulary this chip holds: a smaller vocabulary.
- ties in the top-k go to the lowest expert index (`lax.top_k`).

Weights arrive in the names and shapes of `models/transformer.init`:
`top` holds `embed` [V, E], `ln_f_scale` [E], `lm_head` [E, V] and the
leading dense layers' leaves `dense_<name>` [n_dense, ...];
`layer_weights(l)` returns routed layer l's: ln1_scale, ln1_post_scale,
ln2_scale, ln2_post_scale [E]; wq_a [E, Rq], q_a_scale [Rq], wq_b
[Rq, H, Dn+Dr]; wkv_a [E, Rkv+Dr], kv_a_scale [Rkv], wkv_b
[Rkv, H, Dn+Dv]; wo [H, Dv, E]; w_router [E, X]; w_gate / w_in
[Xheld, E, F], w_out [Xheld, F, E]; ws_gate / ws_in [E, Fs], ws_out
[Fs, E] (a dense layer: w_gate / w_in [E, Fd], w_out [Fd, E]). They come
in whatever dtype the system holds and are widened to float32 HERE, a
few heads, one expert or one slab of a dense MLP at a time, so that a
1.25 GB bf16 layer never becomes a 2.5 GB float32 one. Every matmul runs
under default_matmul_precision("highest").

`forward_logits(..., mutate=)` computes deliberately WRONG models (the
tests and the limits of the benchmark's logits check are set against
them): "no_scaling" (routed_scaling_factor left out), "softmax_router"
(softmax over the experts for sigmoid), "k_minus_1" (one expert
fewer), "no_post_norm" (both post-sublayer norms left out) and
"float8_cache" (what a cache holds, the normed latent and the rotated
key, rounded to float8_e4m3: a cache below the bf16 the file states).
"""

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MUTANTS = ("no_scaling", "softmax_router", "k_minus_1", "no_post_norm",
           "float8_cache")
HEADS_AT_A_TIME = 8       # scores of 8 heads x 2 prompts x 2,327^2: 0.35 GB
SLAB = 2048               # columns of a dense MLP widened at a time
DENSE_PREFIX = "dense_"


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def _rope(x, theta):
    """x [B, S, ..., D] rotated at positions 0..S-1 over all D dims,
    split-halves pairing (ASSUMED, see the module docstring)."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]      # [S, D/2]
    shape = (1, S) + (1,) * (x.ndim - 3) + (D // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def share(hf):
    """(router width, first held expert, experts held) of this cut."""
    held = int(hf["n_routed_experts"])
    routed = int((hf.get("reduced") or {}).get("n_routed_experts", {})
                 .get("published", held))
    return routed, int((hf.get("experts_held") or {}).get("start", 0)), held


def _swiglu(n, w_gate, w_in, w_out):
    """silu(n W_gate) * (n W_in) W_out, a slab of columns at a time."""
    Fd = w_gate.shape[-1]
    slab = min(SLAB, Fd)
    assert Fd % slab == 0, (Fd, slab)

    def body(i, acc):
        cut = lambda w, axis: jax.lax.dynamic_slice_in_dim(
            w, i * slab, slab, axis).astype(F32)
        inner = jax.nn.silu(n @ cut(w_gate, 1)) * (n @ cut(w_in, 1))
        return acc + inner @ cut(w_out, 0)

    return jax.lax.fori_loop(0, Fd // slab, body, jnp.zeros_like(n))


def route(n, lw, hf, mutate=None):
    """Normed activations n [..., E] -> the [..., X] combine weights
    over ALL routed experts (zero outside the chosen), and the router's
    margin: how far the smallest chosen score lies above the largest
    left out, as a share of the former."""
    k = hf["num_experts_per_tok"] - (mutate == "k_minus_1")
    logits = jnp.einsum("...e,ex->...x", n, lw["w_router"].astype(F32))
    s = (jax.nn.softmax(logits, axis=-1) if mutate == "softmax_router"
         else jax.nn.sigmoid(logits))
    top, chosen = jax.lax.top_k(s, k + 1)
    w = jnp.sum(jax.nn.one_hot(chosen[..., :k], s.shape[-1], dtype=F32), -2) * s
    if hf.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    if mutate != "no_scaling":
        w = w * float(hf.get("routed_scaling_factor", 1.0))
    return w, (top[..., k - 1] - top[..., k]) / top[..., k - 1]


def moe_parts(n, lw, hf, mutate=None):
    """The routed block on normed activations n [T, E]: (what THIS
    share's held experts add, what the shared expert adds, the
    router's margin). Every held expert is applied to every token, one
    expert at a time, weighted by its column of the combine weights."""
    w, margin = route(n, lw, hf, mutate)
    _, start, held = share(hf)
    w_held = jax.lax.dynamic_slice_in_dim(w, start, held, axis=-1)  # [T, Xh]

    def expert(acc, xs):
        w_gate, w_in, w_out, col = xs
        return acc + col[:, None] * _swiglu(n, w_gate, w_in, w_out), None

    routed, _ = jax.lax.scan(
        expert, jnp.zeros_like(n),
        (lw["w_gate"], lw["w_in"], lw["w_out"], w_held.T))
    shared = _swiglu(n, lw["ws_gate"], lw["ws_in"], lw["ws_out"])
    return routed, shared, margin


def _attention(h, lw, hf, mutate=None):
    """MLA, the naive form, on normed h [B, S, E]: heads a few at a
    time, each group's K and V up-projected from the latent."""
    eps, theta = hf["rms_norm_eps"], float(hf["rope_theta"])
    H, Rkv = hf["num_attention_heads"], hf["kv_lora_rank"]
    Dn, Dr = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
    B, S, _ = h.shape
    c_q = _rms(h @ lw["wq_a"].astype(F32), lw["q_a_scale"], eps)
    ckv = h @ lw["wkv_a"].astype(F32)
    c_kv = _rms(ckv[..., :Rkv], lw["kv_a_scale"], eps)
    k_r = _rope(ckv[..., Rkv:], theta)                       # [B, S, Dr]
    if mutate == "float8_cache":
        c_kv = c_kv.astype(jnp.float8_e4m3fn).astype(F32)
        k_r = k_r.astype(jnp.float8_e4m3fn).astype(F32)
    hg = min(H, HEADS_AT_A_TIME)
    assert H % hg == 0, (H, hg)
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def group(out, g):
        cut = lambda w, axis: jax.lax.dynamic_slice_in_dim(
            w, g * hg, hg, axis).astype(F32)
        q = jnp.einsum("bsr,rhd->bshd", c_q, cut(lw["wq_b"], 1))
        kv = jnp.einsum("bsc,chd->bshd", c_kv, cut(lw["wkv_b"], 1))
        s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :Dn], kv[..., :Dn])
             + jnp.einsum("bqhd,bkd->bhqk", _rope(q[..., Dn:], theta), k_r)
             ) / np.sqrt(Dn + Dr)
        p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., Dn:])
        return out + jnp.einsum("bshd,hde->bse", o, cut(lw["wo"], 0)), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(h), jnp.arange(H // hg))
    return out


def _layer(x, lw, hf, mutate=None):
    """One layer on x [B, S, E] float32 -> (x, the router's margin
    [B, S], ones for a dense layer). A layer is routed if it has a
    router."""
    eps = hf["rms_norm_eps"]
    post = (lambda y, name: y) if mutate == "no_post_norm" or not hf.get(
        "sandwich_norm") else (lambda y, name: _rms(y, lw[name], eps))
    x = x + post(_attention(_rms(x, lw["ln1_scale"], eps), lw, hf, mutate),
                 "ln1_post_scale")
    m = _rms(x, lw["ln2_scale"], eps)
    flat = m.reshape(-1, m.shape[-1])
    if "w_router" in lw:
        routed, shared, margin = moe_parts(flat, lw, hf, mutate)
        y, margin = routed + shared, margin.reshape(m.shape[:-1])
    else:
        y = _swiglu(flat, lw["w_gate"], lw["w_in"], lw["w_out"])
        margin = jnp.ones(m.shape[:-1], F32)
    return x + post(y.reshape(m.shape), "ln2_post_scale"), margin


def forward_logits(top: Dict[str, Any], layer_weights: Callable[[int], Dict],
                   tokens, hf: Dict[str, Any], mutate: Optional[str] = None):
    """Logits [B, S, V] float32 of tokens [B, S] (see the module
    docstring for `top` and `layer_weights`). `mutate` is None or one of
    MUTANTS."""
    if mutate is not None and mutate not in MUTANTS:
        raise ValueError(f"unknown mutant {mutate!r}; there are {MUTANTS}")
    return _forward(top, layer_weights, tokens, hf, mutate)[0]


def _forward(top, layer_weights, tokens, hf, mutate):
    layer = jax.jit(lambda x, lw: _layer(x, lw, hf, mutate))
    n_dense = int(hf.get("first_k_dense_replace", 0))
    margins = []
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(top["embed"])[jnp.asarray(tokens)].astype(F32)
        for l in range(hf["num_hidden_layers"]):
            if l < n_dense:
                lw = {k[len(DENSE_PREFIX):]: jnp.asarray(v)[l]
                      for k, v in top.items() if k.startswith(DENSE_PREFIX)}
            else:
                lw = layer_weights(l - n_dense)
            x, margin = layer(x, lw)
            margins.append(margin)
        x = _rms(x, jnp.asarray(top["ln_f_scale"]), hf["rms_norm_eps"])
        return jnp.einsum("bse,ev->bsv", x,
                          jnp.asarray(top["lm_head"]).astype(F32)), \
            jnp.stack(margins)


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
