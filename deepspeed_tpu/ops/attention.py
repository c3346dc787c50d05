"""Attention ops.

TPU-native analog of the reference's fused attention kernels
(ref: csrc/transformer/ softmax/transform kernels for training,
csrc/transformer/inference/csrc/softmax.cu for decode). Two paths:

- `_xla_attention`: pure-jnp reference — the numerics oracle in tests
  (the analog of the reference's torch-reference checks in
  tests/unit/ops), and what runs where no kernel can (CPU without an
  interpret request) or is wanted (`use_flash=False`, S < 256).
- Pallas flash attention (ops/pallas/flash_attention.py): the TPU hot
  path, flash-style tiling in VMEM; selected by `use_flash=True`
  wherever kernels run (ops/pallas.kernels_runnable: a TPU, or an
  explicit interpret request).

Layout is [batch, seq, heads, head_dim]. GQA: the flash kernel consumes
KV heads in place via BlockSpec index maps — callers must NOT pre-repeat
KV heads; only the XLA fallback materializes the repeat.
"""

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .pallas import kernels_runnable

_NEG_INF = -1e30


def alibi_slopes(n_heads: int) -> np.ndarray:
    """Per-head ALiBi slopes [H] (Press et al., arXiv 2108.12409 — the
    rule the reference bakes into its Bloom containers, ref:
    deepspeed/module_inject/containers/bloom.py + csrc softmax alibi
    path). Power-of-two head counts use the geometric ladder from
    2^(-8/n); other counts take the closest power's ladder plus every
    other entry of the doubled ladder."""
    def ladder(n: int):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start ** (i + 1) for i in range(n)]

    if math.log2(n_heads).is_integer():
        s = ladder(n_heads)
    else:
        c = 2 ** math.floor(math.log2(n_heads))
        s = ladder(c) + ladder(2 * c)[0::2][: n_heads - c]
    return np.asarray(s, np.float32)


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    B, S, KV, D = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (B, S, KV, n_rep, D)).reshape(B, S, KV * n_rep, D)


def _xla_attention(q, k, v, causal: bool = True, window: int = 0,
                   alibi: Optional[jnp.ndarray] = None):
    B, S, H, D = q.shape
    scale = 1.0 / (D**0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    Sk = k.shape[1]
    if alibi is not None:
        # ALiBi: score[h, i, j] += slope_h * (j - i); non-positive under
        # the causal mask, 0 on the diagonal
        rel = (jnp.arange(Sk)[None, :] - jnp.arange(Sk - S, Sk)[:, None])
        logits = logits + alibi[None, :, None, None] * rel[None, None]
    if causal:
        mask = jnp.tril(jnp.ones((S, Sk), bool), k=Sk - S)
        if window > 0:
            # token-exact sliding window (Mistral-class): q attends only
            # to the last `window` positions including itself
            mask &= jnp.triu(jnp.ones((S, Sk), bool), k=Sk - S - window + 1)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def uses_flash(q, use_flash: bool) -> bool:
    """THE flash-vs-reference selection, from what the call can observe:
    the caller's flag, the sequence length (short sequences do not fill
    a tile), and whether kernels run here at all."""
    return use_flash and q.shape[1] >= 256 and kernels_runnable()


def causal_attention(q, k, v, use_flash: bool = True, window: int = 0,
                     block_q: int = 512, block_k: int = 1024,
                     alibi: Optional[jnp.ndarray] = None):
    """Causal self-attention, [B,S,H,D] x [B,S,KV,D] -> [B,S,H,D].

    GQA KV heads are consumed in-place by the flash kernel (index maps,
    no HBM repeat); only the XLA fallback materializes the repeat.

    window > 0 enables a token-exact sliding window (Mistral-class);
    the flash kernels prune out-of-window blocks from compute AND DMA.

    alibi: optional [H] per-head ALiBi slopes (Bloom-class); the bias
    slope_h * (key_pos - query_pos) enters the flash kernels' online
    softmax in-tile and the XLA fallback's logits identically.

    block_q/block_k tune the flash tiling (TransformerConfig
    flash_block_q/k). The training cells run 1024x1024 (measured on a
    v5e, PERF.md section 6, PR 56, the three kernels of one layer: 18.9
    ms at B 2 / H 32 / KV 4 / S 8,192 / D 128 under a window of 2,048,
    33.3 under none, 19.2 at B 4 / KV 8 / S 4,096). Square tiles that
    divide the sequence and the window let the kernels skip the mask on
    interior tiles and walk edge tiles by their triangle
    (flash_attention.tile_census counts them); any other tiling runs
    the mask over every tile it visits. Other tile sizes were not
    measured on this installation."""
    if uses_flash(q, use_flash):
        # imported where it runs: jax.experimental.pallas costs ~1.5 s
        # that a training-only or CPU process should not pay at import
        from .pallas.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=block_q, block_k=block_k, alibi=alibi)
    n_rep = q.shape[2] // k.shape[2]
    return _xla_attention(q, _repeat_kv(k, n_rep), _repeat_kv(v, n_rep),
                          causal=True, window=window, alibi=alibi)


def block_causal_attention(q, k, v, block_length: int, q_tile: int = 256):
    """Self-attention under the BLOCK-causal mask of a block-diffusion
    model, [B,S,H,D] x [B,S,KV,D] -> [B,S,H,D]: position i sees
    position j iff j // block_length <= i // block_length
    (bidirectional inside a block, causal across blocks). Plain XLA: a
    query tile's float32 scores against every key, one tile of q_tile
    rows at a time (lax.map), so that what is live is
    [B, H, q_tile, S] and not [B, H, S, S]; GQA heads read their KV
    head in place. The whole-prompt prefill of such a model runs it
    (inference/model.py prefill_batch); its steady state is the paged
    walk."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    tile = min(q_tile, S)
    pad = -S % tile
    qg = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        B, (S + pad) // tile, tile, KV, H // KV, D)
    see = (jnp.arange(S, dtype=jnp.int32) // block_length)[None, :]

    def one(args):
        qt, start = args  # [B, tile, KV, G, D]
        rows = start + jnp.arange(tile, dtype=jnp.int32)
        scores = jnp.einsum("bqkgd,bskd->bkgqs", qt, k).astype(
            jnp.float32) * (1.0 / D ** 0.5)
        keep = see <= (rows // block_length)[:, None]
        probs = jax.nn.softmax(
            jnp.where(keep[None, None, None], scores, _NEG_INF),
            axis=-1).astype(q.dtype)
        return jnp.einsum("bkgqs,bskd->bqkgd", probs, v)

    starts = jnp.arange(0, S + pad, tile, dtype=jnp.int32)
    out = jax.lax.map(one, (jnp.moveaxis(qg, 1, 0), starts))
    return jnp.moveaxis(out, 0, 1).reshape(B, S + pad, H, D)[:, :S]
