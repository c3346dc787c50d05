"""Plain float32 reference of the Phi-4-mini-flash decoder (`model_type:
phi4flash`; SambaY, arXiv:2507.06607, with differential attention,
arXiv:2410.05258, on the Mamba-1 mixer of arXiv:2312.00752 and the
cross-decoder of YOCO, arXiv:2405.05254).

Straight `jax.numpy`, one layer at a time, no kernels, no cache, no
ring, no state slot, no chunking; independent of `deepspeed_tpu/`. With
`x` the residual stream and LN(x) = (x - mean) rsqrt(var + eps) * s + b
in float32 (`layer_norm_eps`):

    layer l:  x <- x + Mixer_l(LN1(x));  x <- x + F(LN2(x))
    F(h) = W_out (silu(W_gate h) * W_in h);  logits = LN_f(x) Emb^T

Which mixer, by `mixers(hf)` from `mb_per_layer` 2 and the depth L
(half = L / 2): l even and l <= half the SELECTIVE SCAN; l odd and
l < half attention in a WINDOW of `sliding_window` (a list of one entry
a layer is taken as it is); l = half + 1 FULL attention; l even above
that the GATED MEMORY UNIT; l odd above that CROSS attention. No layer
has positions.

    Selective scan on h (I = 2 E channels, N = 16, 4 taps, R = ceil(E / 16)):
        [x; z] = W_in h;  x <- silu(causal depthwise conv_4(x) + b_conv)
        (HERE an explicit sum over shifted copies of the whole sequence);
        [r; B; C] = W_x x;  dt = softplus(W_dt r + b_dt);  A = -exp(A_log);
        a sequence carries s in R^{I x N}, zero at its start; for each
        token, as a `lax.scan` over tokens (the RECURRENCE):
            s[c, n] <- exp(dt[c] A[c, n]) s[c, n] + dt[c] B[n] x[c]
            y[c] = sum_n C[n] s[c, n] + D[c] x[c]
        out = W_out (y * silu(z)).  The LAST scan layer also hands on
        m = y, before the gate, to the units below it.
    Gated memory unit on h:  out = W_out^g (silu(W_in^g h) * m), m the
        last scan layer's for the same token.
    Differential attention on h (H query heads, KV key-value heads of
    D = E / H; pairs in order): query pair p = heads (2p, 2p + 1) =
    (q1, q2); K/V pair g = heads (2g, 2g + 1) = (k1, k2), V_g = [v1; v2]
    in R^{2D}; query pair p reads K/V pair p // (H / KV). With
    s = D^-0.5 and the layer's mask M (causal; in a windowed layer a key
    is seen while query - key < window), EACH map a dense [T, T]
    softmax, computed apart:
        a1 = softmax(s q1 k1^T + M) V_g;  a2 = softmax(s q2 k2^T + M) V_g
        lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l),
        lam0(l) = 0.8 - 0.6 exp(-0.3 l), l the layer's index in the stack
        o_p = RMS(a1 - lam a2; g_2D, eps) * (1 - lam0(l));
        out = W_o [o_0; ...] + b_o
    A layer that owns K/V: [q; k; v] from W_q, W_k, W_v with biases. A
    cross layer: q = W_q h + b_q alone, k and v the FULL layer's of the
    same pass, the mask causal and full.

Departures from the publisher, each an `assumed` entry of the
configuration: the catalog row holds no Mamba sizes (the family's
defaults: expand 2, d_state 16, d_conv 4, dt_rank ceil(E / 16)), no
rotary key (none is applied), no norm kind (LayerNorm with a bias, the
Phi family's) and no biases' keys (q/k/v/o biased as the Phi family,
the mixers' other projections not); m is taken WITH the skip D x and
before the gate; the gated unit has no bias; the importer's weight names
wait for a checkpoint.

Weights arrive in the names and shapes of `models/transformer.init`:
`top` holds `embed` [V, E], `ln_f_scale` / `ln_f_bias` [E], and the
mixers' stacks by kind, layer l taking the entry of its place among the
layers of its kind: `sscan_in` [n, E, 2I], `sscan_taps` [n, I, 4]
(oldest tap first), `sscan_conv_bias` [n, I], `sscan_x` [n, I, R + 2N],
`sscan_dt` [n, R, I], `sscan_dt_bias` [n, I], `sscan_a_log` [n, I, N],
`sscan_d` [n, I], `sscan_out` [n, I, E]; `attn_wq` [n, E, H, D],
`attn_wk` / `attn_wv` [n, E, KV, D], `attn_wo` [n, H, D, E], `attn_bq`
/ `attn_bk` / `attn_bv`, `attn_bo` [n, E], `attn_diff_lq1` .. `lk2`
[n, D], `attn_diff_norm_scale` [n, 2D]; `gmu_in` [n, E, I], `gmu_out`
[n, I, E]; `xattn_wq`, `xattn_wo`, `xattn_bq`, `xattn_bo`,
`xattn_diff_*`. `layer_weights(l)` returns layer l's: ln1_scale /
ln1_bias / ln2_scale / ln2_bias [E], w_gate / w_in [E, F], w_out
[F, E]. They come in whatever dtype the system holds and are widened to
float32 HERE, a block of a matrix at a time, and the logits leave the
device a block of the vocabulary at a time (the same sums: what they
buy is room beside a live engine). Every matmul runs under
default_matmul_precision("highest"). `forward_logits` returns a numpy
array.

`forward_logits(..., mutate=)` computes deliberately WRONG models (the
tests and the limits of the benchmark's logits check are set against
them): MUTANTS below.
"""

import functools
import math
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MUTANTS = (
    "state_bf16",         # the scan's state rounded to bf16 after every token
    "no_differential",    # lam = 0: the second map is never subtracted
    "scalar_decay",       # A averaged over the state: one rate a channel
    "all_full",           # no layer has a window
    "all_windowed",       # every attending layer has one, cross layers too
    "cross_reads_own",    # a cross layer's K/V from its OWN input (donor's W)
    "stale_ring",         # a window's oldest block from one ring turn earlier
    "memory_after_gate",  # m taken after the gate
    "no_memory",          # m = 1
    "zero_state",         # the scan's state zeroed before every token
)
BLOCK = 4096   # columns (or summed rows) of a weight widened at a time


def mixers(hf: Dict[str, Any]):
    """Layer l's mixer, 'scan' | 'window' | 'full' | 'unit' | 'cross',
    and each layer's window (0: none), by the publisher's rule."""
    L, half = hf["num_hidden_layers"], hf["num_hidden_layers"] // 2
    window = hf["sliding_window"]
    kinds, windows = [], []
    for l in range(L):
        if l % 2 == 0:
            kinds.append("scan" if l <= half else "unit")
        else:
            kinds.append("window" if l < half else
                         "full" if l == half + 1 else "cross")
        w = window[l] if isinstance(window, (list, tuple)) else (
            window if kinds[-1] == "window" else 0)
        windows.append(int(w or 0))
    return kinds, windows


def stale_turn(hf: Dict[str, Any]):
    """(block, ring) in tokens of the "stale_ring" mutant: the served
    block size, and the ring the engine derives from it and the window
    (ceil((window + block - 1) / block) + 1 blocks)."""
    bs = int(hf.get("serve", {}).get("engine", {}).get("kv_block_size", 128))
    window = max(mixers(hf)[1])
    return bs, (-(-(window + bs - 1) // bs) + 1) * bs


def mamba_sizes(hf: Dict[str, Any]):
    """(I, N, K, R): the scan's channels, state width, taps and step
    rank, from the keys where the file has them, else the family's
    defaults."""
    E = hf["hidden_size"]
    rank = hf.get("mamba_dt_rank", "auto")
    return (int(hf.get("mamba_expand", 2)) * E, int(hf.get("mamba_d_state", 16)),
            int(hf.get("mamba_d_conv", 4)),
            math.ceil(E / 16) if rank == "auto" else int(rank))


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale.astype(F32) \
        + bias.astype(F32)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _block(x, w, index, lo, hi, rows):
    """x @ (columns lo..hi of w), or x[..., lo:hi] @ (rows lo..hi of w):
    w a matrix [n, ...] (trailing dims flattened) or entry `index` of a
    stack of them, the block widened to float32 here."""
    w = w if index is None else w[index]
    if rows:
        w = w.reshape(-1, w.shape[-1])
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


@functools.partial(jax.jit, static_argnums=(5,))
def _recurrence(x, dt, A, Bm, Cm, mutate):
    """The scan, token by token: x, dt [B, S, I], A [I, N], Bm, Cm
    [B, S, N] -> y [B, S, I] (without the skip)."""
    B, _, I = x.shape

    def token(s, xs):
        xt, dtt, bt, ct = xs
        if mutate == "zero_state":  # a slot that never held this sequence
            s = jnp.zeros_like(s)
        s = s * jnp.exp(dtt[..., None] * A) + (dtt * xt)[..., None] * bt[:, None, :]
        if mutate == "state_bf16":
            # an explicit rounding: a cast there and back is one XLA may
            # drop on a TPU (excess precision)
            s = jax.lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7)
        return s, jnp.sum(s * ct[:, None, :], axis=-1)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, Bm, Cm))
    _, y = jax.lax.scan(token, jnp.zeros((B, I, A.shape[-1]), F32), xs)
    return jnp.moveaxis(y, 0, 1)


def selective_scan(h, top, i, hf, mutate=None):
    """The Mamba-1 mixer on h [B, S, E] with entry i of the `sscan_`
    stacks -> (out [B, S, E], y [B, S, I]: the scan's output with the
    skip, before the gate)."""
    I, N, K, R = mamba_sizes(hf)
    S = h.shape[1]
    xz = _times(h, top["sscan_in"], i)
    u, z = xz[..., :I], xz[..., I:]
    taps = top["sscan_taps"][i].astype(F32)                     # [I, K]
    c = u * taps[:, K - 1]
    for j in range(K - 1):                 # tap j multiplies u_{t-(K-1)+j}
        back = K - 1 - j
        c = c + jnp.pad(u, ((0, 0), (back, 0), (0, 0)))[:, :S] * taps[:, j]
    x = jax.nn.silu(c + top["sscan_conv_bias"][i].astype(F32))
    rbc = _times(x, top["sscan_x"], i)
    r, Bm, Cm = rbc[..., :R], rbc[..., R:R + N], rbc[..., R + N:]
    dt = jax.nn.softplus(_times(r, top["sscan_dt"], i)
                         + top["sscan_dt_bias"][i].astype(F32))
    A = -jnp.exp(top["sscan_a_log"][i].astype(F32))
    if mutate == "scalar_decay":   # the Mamba-2 form: one rate a channel
        A = jnp.broadcast_to(jnp.mean(A, axis=-1, keepdims=True), A.shape)
    y = _recurrence(x, dt, A, Bm, Cm, mutate) \
        + top["sscan_d"][i].astype(F32) * x
    gated = y * jax.nn.silu(z)
    out = _times(gated, top["sscan_out"], i, rows=True)
    return out, (gated if mutate == "memory_after_gate" else y)


def gated_memory(h, m, top, i, mutate=None):
    """The gated memory unit on h [B, S, E] with the last scan layer's
    m [B, S, I] and entry i of the `gmu_` stacks."""
    if mutate == "no_memory":
        m = jnp.ones_like(m)
    return _times(jax.nn.silu(_times(h, top["gmu_in"], i)) * m,
                  top["gmu_out"], i, rows=True)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _one_map(q, k, v, window, stale, scale):
    """softmax(scale q k^T + M) v, ONE head: q, k [B, S, D], v
    [B, S, 2D] -> [B, S, 2D], the map a dense [S, S]. stale: None, or
    stale_turn's pair (the "stale_ring" mutant)."""
    S = q.shape[1]
    rows, keys = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    live = keys <= rows
    if window:
        live &= rows - keys < window
    s = jnp.where(live[None], jnp.einsum("bqd,bkd->bqk", q, k) * scale,
                  -jnp.inf)
    if stale is None:
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)
    # the oldest block of each query's window comes from one turn of
    # the ring earlier (where that is a position)
    block, turn = stale
    old = live & (rows - keys >= window - block) & (keys >= turn)
    k_old, v_old = jnp.roll(k, turn, axis=1), jnp.roll(v, turn, axis=1)
    s = jnp.where(old[None], jnp.einsum("bqd,bkd->bqk", q, k_old) * scale, s)
    p = jax.nn.softmax(s, -1)
    return (jnp.einsum("bqk,bkd->bqd", jnp.where(old[None], 0, p), v)
            + jnp.einsum("bqk,bkd->bqd", jnp.where(old[None], p, 0), v_old))


def differential_attention(h, kv, top, prefix, i, l, hf, window, mutate=None):
    """Differential attention on h [B, S, E] with entry i of the
    `prefix` stacks, layer l of the stack. kv: None (the layer projects
    its own k and v, and returns them) or the (k, v) [B, S, KV, D] of
    the layer it reads. -> (out [B, S, E], (k, v))."""
    H, KV = hf["num_attention_heads"], hf["num_key_value_heads"]
    E = hf["hidden_size"]
    D = E // H
    B, S, _ = h.shape
    leaf = lambda name: top[prefix + name][i]
    q = (_times(h, top[prefix + "wq"], i)
         + leaf("bq").astype(F32).reshape(-1)).reshape(B, S, H, D)
    if kv is None:
        kv = tuple(
            (_times(h, top[prefix + w], i)
             + leaf(b).astype(F32).reshape(-1)).reshape(B, S, KV, D)
            for w, b in (("wk", "bk"), ("wv", "bv")))
    k, v = kv
    stale = stale_turn(hf) if mutate == "stale_ring" and window else None
    per = (H // 2) // (KV // 2)   # query pairs a pair of K/V
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * l)
    dot = lambda a, b: jnp.sum(leaf(a).astype(F32) * leaf(b).astype(F32))
    lam = (jnp.exp(dot("diff_lq1", "diff_lk1"))
           - jnp.exp(dot("diff_lq2", "diff_lk2")) + lam0)
    if mutate == "no_differential":
        lam = 0.0
    scale = leaf("diff_norm_scale").astype(F32)
    pairs = []
    for p in range(H // 2):       # each pair's two maps, computed apart
        g = p // per
        vg = jnp.concatenate([v[:, :, 2 * g], v[:, :, 2 * g + 1]], axis=-1)
        a1 = _one_map(q[:, :, 2 * p], k[:, :, 2 * g], vg, window, stale,
                      D ** -0.5)
        a2 = _one_map(q[:, :, 2 * p + 1], k[:, :, 2 * g + 1], vg, window,
                      stale, D ** -0.5)
        o = a1 - lam * a2
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + hf["layer_norm_eps"]) * scale
        pairs.append(o * (1.0 - lam0))
    o = jnp.concatenate(pairs, axis=-1)                        # [B, S, H D]
    out = _times(o, top[prefix + "wo"], i, rows=True) + leaf("bo").astype(F32)
    return out, kv


def _ffn(n, lw):
    inner = jax.nn.silu(_times(n, lw["w_gate"])) * _times(n, lw["w_in"])
    return _times(inner, lw["w_out"], rows=True)


def forward_logits(top: Dict[str, Any], layer_weights: Callable[[int], Dict],
                   tokens, hf: Dict[str, Any], mutate: Optional[str] = None):
    """Logits [B, S, V] float32 (numpy) of tokens [B, S] (see the module
    docstring for `top` and `layer_weights`). `mutate` is None or one of
    MUTANTS."""
    if mutate is not None and mutate not in MUTANTS:
        raise ValueError(f"unknown mutant {mutate!r}; there are {MUTANTS}")
    top = {k: jnp.asarray(v) for k, v in top.items()}
    eps = hf["layer_norm_eps"]
    kinds, windows = mixers(hf)
    widest = max(windows)
    seen = {"sscan_": 0, "attn_": 0, "gmu_": 0, "xattn_": 0}
    memory = full_kv = full_at = None
    with jax.default_matmul_precision("highest"):
        x = top["embed"][jnp.asarray(tokens)].astype(F32)
        for l, kind in enumerate(kinds):
            lw = layer_weights(l)
            h = _layer_norm(x, lw["ln1_scale"], lw["ln1_bias"], eps)
            window = windows[l]
            if mutate == "all_full":
                window = 0
            elif mutate == "all_windowed" and kind != "scan" and kind != "unit":
                window = widest
            if kind == "scan":
                out, memory = selective_scan(h, top, seen["sscan_"], hf, mutate)
                seen["sscan_"] += 1
            elif kind == "unit":
                out = gated_memory(h, memory, top, seen["gmu_"], mutate)
                seen["gmu_"] += 1
            elif kind == "cross":
                kv = full_kv
                if mutate == "cross_reads_own":
                    # the full layer's W_k and W_v on THIS layer's input
                    _, kv = differential_attention(
                        h, None, top, "attn_", full_at, l, hf, 0)
                out, _ = differential_attention(
                    h, kv, top, "xattn_", seen["xattn_"], l, hf, window, mutate)
                seen["xattn_"] += 1
            else:
                out, kv = differential_attention(
                    h, None, top, "attn_", seen["attn_"], l, hf, window, mutate)
                if kind == "full":
                    full_kv, full_at = kv, seen["attn_"]
                seen["attn_"] += 1
            x = x + out
            x = x + _ffn(_layer_norm(x, lw["ln2_scale"], lw["ln2_bias"], eps),
                         lw)
        x = _layer_norm(x, top["ln_f_scale"], top["ln_f_bias"], eps)
        emb, V = top["embed"], top["embed"].shape[0]
        # a block of the vocabulary at a time, each to the host
        return np.concatenate(
            [np.asarray(_vocab_block(x, emb, lo, min(lo + BLOCK, V)))
             for lo in range(0, V, BLOCK)], axis=-1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _vocab_block(x, emb, lo, hi):
    return x @ emb[lo:hi].astype(F32).T


def loss(top, layer_weights, tokens, hf, mutate: Optional[str] = None) -> float:
    """Token-mean next-token cross-entropy of tokens [B, S + 1]."""
    tokens = np.asarray(tokens)
    logits = forward_logits(top, layer_weights, tokens[:, :-1], hf, mutate)
    logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    tgt = jnp.asarray(tokens[:, 1:])
    return float(-jnp.mean(jnp.take_along_axis(logp, tgt[..., None], -1)))
