"""Inference-side transformer forward over a paged KV cache.

TPU-native redesign of the FastGen model layer
(ref: inference/v2/model_implementations/inference_model_base.py:45
DSInferenceModelBase + inference_transformer_base.py — there, per-layer
CUDA kernels write QKV into the paged cache (linear_blocked_kv_rotary)
and run blocked flash; here the same dataflow is a fused Pallas
write+attend kernel over the paged arena).

Weights are the SAME pytree as models/transformer, passed through
`prepare()` into the SERVING layout (one model family, two execution
modes — the reference needs a separate inference module zoo because its
training and inference kernels differ; here both consume the functional
params dict):

- layers are UNSTACKED into a python list of per-layer dicts. The
  training layout stacks layers [L, ...] for `lax.scan`; serving decode
  unrolls layers, and XLA materializes a per-step HBM copy of every
  static slice of a stacked array inside the decode loop (measured
  0.36 ms/step of pure slice copies on the 350M flagship — 16% of the
  step). Separate per-layer arrays stream straight into their GEMMs.
- Q/K/V projections fuse into one [E, H+2KV, D] GEMM and the llama
  gate/up pair into one [E, 2F] GEMM (decode is launch-bound at small
  batch; fewer, fatter MXU ops). Under a TP mesh weights stay UNFUSED:
  splitting a 'model'-sharded fused output would insert collectives.
- weights may be per-channel int8 (quantization.ChannelQuantWeight):
  the matmul consumes the codes directly (XLA fuses the dequant convert
  into the dot — int8 bytes from HBM) and scales the output.

Cache: per layer, k and v as [num_blocks, block_size, KV_heads,
head_dim] — one cache page is a contiguous (block_size, KV, D) tile
(single large DMA in the kernels); TP shards the KV dim. All cache
mutation goes through Pallas kernels on donated, aliased buffers so
the arena is updated in place. A model whose layers are of several kinds
(cfg.layer_types) has K/V pools for its attention layers ALONE and,
for each of the others, STATE pools [slots, ...]: one entry a tracked
sequence, of fixed size whatever the sequence's length (PagedCache).
"""

import contextlib
import math
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import transformer as T
from ..ops.attention import (
    block_causal_attention,
    causal_attention,
    uses_flash,
)
from ..ops.pallas.expert_stream import (
    expert_grouped_mlp,
    expert_stream_mlp,
    expert_stream_ungated_mlp,
    group_rows,
    grouped_f_tile,
    stream_f_tile,
)
from ..ops.pallas.conv_carry import carry_facts, carry_fits, conv_carry
from ..ops.pallas.gated_delta import (
    gated_delta_chunked,
    gated_delta_step,
    gated_delta_step_xla,
    pack_heads,
    step_fits,
)
from ..ops.pallas.selective_scan import pool_view as sscan_pool_view
from ..ops.pallas.selective_scan import (
    sscan_chunked,
    sscan_step,
    sscan_step_fits,
    sscan_step_xla,
)
from ..ops.pallas.ssm_state import (
    pack_state,
    ssm_chunked,
    ssm_step,
    ssm_step_fits,
    ssm_step_xla,
)
from ..ops.pallas.paged_attention import (
    fused_write_fits,
    kv_pack,
    kv_pair_fold,
    kv_write_path,
    latent_lanes,
    paged_decode_attention,
    paged_decode_attention_xla,
    paged_kv_write,
    paged_latent_attention,
    paged_latent_attention_xla,
    paged_latent_write,
    paged_latent_write_xla,
    paged_scale_write,
    quantize_kv_rows,
)
from .quantization import ChannelQuantWeight, channel_quantize


# ---------------------------------------------------------------------------
# serving weight layout
# ---------------------------------------------------------------------------

def is_prepared(params) -> bool:
    return isinstance(params.get("layers"), (list, tuple))


def prepare(params: Dict[str, Any], cfg: T.TransformerConfig,
            fuse: bool = True) -> Dict[str, Any]:
    """Training layout -> serving layout (see module docstring).

    fuse=False keeps wq/wk/wv and w_gate/w_in separate — required under
    a TP mesh where the fused output dim would be 'model'-sharded and
    the split would reshard. Call once (e.g. under jit at
    refresh_params time), NOT inside a per-token compiled step: the
    concats copy the weight tree."""
    if is_prepared(params):
        return params
    out = {k: v for k, v in params.items() if k != "layers"}
    st = params["layers"]
    lead = jax.tree.leaves(st)[0]
    L = cfg.n_layers
    if lead.shape[0] != L:
        raise ValueError(
            f"serving expects flat [n_layers, ...] stacked layers "
            f"(got leading dim {lead.shape[0]} != {L}; merge pipeline "
            "partitions before serving)"
        )
    # operators of several kinds: each kind's top-level stacks hand
    # layer li the entry of its place among its kind, under the leaf's
    # own name (conv, gdn and ssm leaves carry their prefix, clear of
    # the FFN's w_in / w_out)
    ops = {kind: {} for kind, _, _ in T.operator_stacks(cfg)}
    for top, kind, name, _, _ in T._operator_leaves(cfg):
        ops[kind][name] = out.pop(top)

    def layer(leaves, li):
        mine = ops.get(cfg.layer_kind(li), {})
        return prepare_layer(dict(
            leaves, **{name: w[cfg.op_index(li)] for name, w in mine.items()}),
            cfg, fuse, cfg.layer_kind(li))

    nd = cfg.n_dense_layers
    out["layers"] = [layer({name: w[l] for name, w in st.items()}, nd + l)
                     for l in range(L)]
    # leading dense layers: their top-level `dense_<name>` stacks become
    # a list of per-layer dicts beside `layers`
    pre = T.DENSE_PREFIX
    dense = {k[len(pre):]: out.pop(k) for k in list(out) if k.startswith(pre)}
    if dense:
        out["dense_layers"] = [
            layer({name: w[l] for name, w in dense.items()}, l)
            for l in range(nd)]
    return out


# the kinds of layer whose dense gated FFN reads gate and up as ONE
# matmul (w_gi) whatever its mixer is. An attention layer's rule is
# older and its own: the pair fuses where its q, k and v did. The older
# state kinds keep the pair apart, as their cells were measured: a kind
# joins this table with a measurement (ROADMAP.md B-I 17 (e))
_GATE_UP_FUSED = frozenset({"selective_scan", "gated_memory",
                            "cross_attention"})


def prepare_layer(lp: Dict[str, Any], cfg: T.TransformerConfig,
                  fuse: bool = True, kind: str = "attention"
                  ) -> Dict[str, Any]:
    """One layer's training-layout dict -> serving layout (the per-layer
    body of prepare(); offload serving stages layers through this one at
    a time so a bigger-than-HBM model never materializes whole). kind:
    the layer's, one of T.LAYER_KINDS."""
    lp = dict(lp)
    if "wkv_b" in lp:
        # latent attention: the up-projection splits into the halves the
        # two forms read apart (absorbed decode multiplies q by w_uk and
        # the attended latent by w_uv), once, not per compiled step
        Dn = cfg.qk_nope_head_dim
        wkv_b = lp.pop("wkv_b")
        lp["w_uk"], lp["w_uv"] = wkv_b[..., :Dn], wkv_b[..., Dn:]
    if fuse and "wk" in lp:
        # the output gate's projection (cfg.attn_output_gate) rides the
        # same GEMM, after v
        lp["w_qkv"] = jnp.concatenate(
            [lp.pop("wq"), lp.pop("wk"), lp.pop("wv")]
            + ([lp.pop("wq_gate")] if "wq_gate" in lp else []), axis=1)
        if "bq" in lp:
            lp["b_qkv"] = jnp.concatenate(
                [lp.pop("bq"), lp.pop("bk"), lp.pop("bv")], axis=0)
    if fuse and ("w_qkv" in lp or kind in _GATE_UP_FUSED) \
            and "w_router" not in lp and cfg.is_gated and "w_gate" in lp:
        lp["w_gi"] = jnp.concatenate(
            [lp.pop("w_gate"), lp.pop("w_in")], axis=1)
    if "w_router" in lp and "w_gate" not in lp and "b_in" not in lp:
        lp["w_in"], lp["w_out"] = _whole_lane_experts(lp["w_in"], lp["w_out"])
    return lp


def _whole_lane_experts(w_in, w_out):
    """The stacks of a routed block WITHOUT a gate, [X, E, F] and
    [X, F, E], with F padded to whole 128-lane tiles where E fills them
    and F does not (1,856 = 14.5 tiles to 1,920): zero columns of w_in
    against zero rows of w_out, which add nothing whatever the
    activation makes of 0, so that the one pipelined pass takes the
    stacks (expert_stream's tiles are whole lanes of F). 3.4% more
    bytes at those widths; a block that fills its lanes, or whose E
    fills none (the pass would refuse it anyway), stays as it is."""
    X, E, F = w_in.shape
    pad = -F % 128
    if E % 128 or not pad:
        return w_in, w_out
    return (jnp.pad(w_in, ((0, 0), (0, 0), (0, pad))),
            jnp.pad(w_out, ((0, 0), (0, pad), (0, 0))))


# per-layer serving weight name -> (contract_ndim, logical axes) for
# per-channel quantization and TP sharding of the PREPARED layout
_SERVING_SPECS = {
    "w_qkv": (1, ("embed", "heads", "head_dim")),
    "wq": (1, ("embed", "heads", "head_dim")),
    "wq_gate": (1, ("embed", "heads", "head_dim")),
    "wk": (1, ("embed", "heads", "head_dim")),
    "wv": (1, ("embed", "heads", "head_dim")),
    "wo": (2, ("heads", "head_dim", "embed")),
    "w_gi": (1, ("embed", "mlp")),
    "w_gate": (1, ("embed", "mlp")),
    "w_in": (1, ("embed", "mlp")),
    "w_out": (1, ("mlp", "embed")),
    "b_qkv": (None, ("heads", "head_dim")),
    "bq": (None, ("heads", "head_dim")),
    "bk": (None, ("heads", "head_dim")),
    "bv": (None, ("heads", "head_dim")),
    "bo": (None, ("embed",)),
    "b_in": (None, ("mlp",)),
    "b_out": (None, ("embed",)),
    "ln1_scale": (None, ("embed",)),
    "ln1_bias": (None, ("embed",)),
    "ln2_scale": (None, ("embed",)),
    "ln2_bias": (None, ("embed",)),
    "q_norm_scale": (None, ("heads", "head_dim")),
    "k_norm_scale": (None, ("heads", "head_dim")),
    # MoE expert stacks (never per-channel-quantized; X leading dim)
    "w_router": (None, ("embed", None)),
    # PR-MoE residual dense expert + mixing coefficient
    "wr_in": (1, ("embed", "mlp")),
    "wr_gate": (1, ("embed", "mlp")),
    "wr_out": (1, ("mlp", "embed")),
    "br_in": (None, ("mlp",)),
    "br_out": (None, ("embed",)),
    "w_coef": (None, ("embed", None)),
    "b_coef": (None, (None,)),
}
_MOE_SPECS = {
    "w_in": ("expert", "embed", "expert_mlp"),
    "w_out": ("expert", "expert_mlp", "embed"),
    "w_gate": ("expert", "embed", "expert_mlp"),
    "b_in": ("expert", "expert_mlp"),
    "b_out": ("expert", "embed"),
}


def quantize_prepared(prepared: Dict[str, Any],
                      cfg: T.TransformerConfig) -> Dict[str, Any]:
    """Per-channel int8 over the prepared tree (the decode SPEED path;
    see ChannelQuantWeight). Embedding quantizes per ROW so one scale
    serves both the lookup and the tied-logits contraction. Norm
    scales, biases, the position table, and MoE expert stacks stay full
    precision."""
    out = dict(prepared)
    out["embed"] = channel_quantize(prepared["embed"], 1, scale_first=True)
    if "lm_head" in prepared:
        out["lm_head"] = channel_quantize(prepared["lm_head"], 1)
    out["layers"] = [quantize_layer(lp, cfg) for lp in prepared["layers"]]
    return out


def quantize_layer(lp: Dict[str, Any],
                   cfg: T.TransformerConfig) -> Dict[str, Any]:
    """Per-channel int8 for one prepared layer (see quantize_prepared).

    MoE expert stacks [X, ...] ride the GROUPWISE int8 path instead
    (QuantizedWeight — the N004 machinery): a per-output-channel scale
    does not survive the expert-stacked leading dim, but group scales
    do, so the stacks park as int8 codes (w/ the offload tiers) and
    dequantize transiently where the grouped GEMM consumes them
    (_mlp). Resident expert bytes halve; the router stays fp32."""
    moe = cfg.n_experts > 0
    nlp = dict(lp)
    for name, w in lp.items():
        spec = _SERVING_SPECS.get(name)
        if moe and name in ("w_gate", "w_in", "w_out"):
            from ..ops.quantization import quantize_groupwise
            from .quantization import QuantizedWeight

            q, s = quantize_groupwise(w, 128, 8)
            nlp[name] = QuantizedWeight(q=q, scale=s, bits=8,
                                        dtype_name=str(w.dtype))
            continue
        if spec is None or spec[0] is None:
            continue
        nlp[name] = channel_quantize(w, spec[0])
    return nlp


def _wmm(eq: str, x, w):
    """einsum with a weight that may be per-channel int8: codes feed the
    dot (convert fuses into the MXU operand stream — int8 HBM bytes),
    the per-output-channel scale is an elementwise epilogue."""
    if isinstance(w, ChannelQuantWeight):
        y = jnp.einsum(eq, x, w.q.astype(x.dtype))
        return y * w.scale.astype(x.dtype)
    return jnp.einsum(eq, x, w.astype(x.dtype))


def _embed_rows(embed, tokens, multiplier: float = 1.0):
    """The tokens' rows of the embedding, times cfg.embedding_multiplier
    where the model has one."""
    if isinstance(embed, ChannelQuantWeight):
        dt = jnp.dtype(embed.dtype_name)
        return (embed.q[tokens].astype(dt)
                * embed.scale[tokens][..., None].astype(dt))
    rows = embed[tokens]
    return rows if multiplier == 1.0 else rows * multiplier


def _lm_logits(x, params, cfg: T.TransformerConfig):
    """Final-norm'd activations [.., E] -> f32 logits [.., V]. Tied
    embeddings contract WITHOUT materializing embed.T (ref r3 profile:
    the transpose showed up as per-step HBM copies)."""
    if cfg.tie_embeddings:
        emb = params["embed"]
        if isinstance(emb, ChannelQuantWeight):
            y = jnp.einsum("...e,ve->...v", x, emb.q.astype(x.dtype))
            return y.astype(jnp.float32) * emb.scale
        y = jnp.einsum("...e,ve->...v", x, emb.astype(x.dtype)
                       ).astype(jnp.float32)
        # Granite divides its logits (a model with that scalar is
        # tied, 16-bit or float32, on one device)
        return y if cfg.logits_scaling == 1.0 else y / cfg.logits_scaling
    head = params["lm_head"]
    if isinstance(head, ChannelQuantWeight):
        y = jnp.einsum("...e,ev->...v", x, head.q.astype(x.dtype))
        y = y.astype(jnp.float32) * head.scale
    else:
        y = jnp.einsum("...e,ev->...v", x, head.astype(x.dtype)
                       ).astype(jnp.float32)
    if "lm_head_b" in params:
        y = y + params["lm_head_b"].astype(jnp.float32)
    return y


# ---------------------------------------------------------------------------
# tensor-parallel serving helpers
#
# The reference's inference engine is TP-first: it builds an mp group and
# row/col-slices every Linear (ref: inference/engine.py:254
# _create_model_parallel_group; v2 sharding helpers
# inference/v2/model_implementations/sharding/qkv.py). TPU-native, TP is
# a mesh 'model' axis: weights carry the SAME logical specs as training
# (models/transformer.logical_specs + parallel/sharding rules), the paged
# KV cache shards over its KV-head dim, and XLA inserts the Megatron
# collectives (psum after the row-parallel wo/w_out matmuls). The only
# ops XLA cannot partition are the Pallas custom calls — those run under
# shard_map over the head dims, which the cache layout was designed for
# ("TP shards the KV dim", ops/pallas/paged_attention.py:15).
# ---------------------------------------------------------------------------


def _tp_size(mesh: Optional[Mesh]) -> int:
    if mesh is None:
        return 1
    return int(mesh.shape.get("model", 1))


def _heads_shardable(mesh: Optional[Mesh], cfg: T.TransformerConfig) -> bool:
    """Pallas kernels may run per-shard only when Q and KV heads both
    split evenly over 'model' (contiguous-block GQA grouping then stays
    device-local: local q group g pairs with local kv head g)."""
    tp = _tp_size(mesh)
    return tp > 1 and cfg.n_heads % tp == 0 and cfg.kv_heads % tp == 0


def _cons(x, mesh: Optional[Mesh], *spec):
    """with_sharding_constraint, shape-guarded: any dim whose mesh-axis
    product does not divide it falls back to replicated."""
    if mesh is None:
        return x
    out = []
    for i, ax in enumerate(spec):
        if ax is None:
            out.append(None)
            continue
        size = mesh.shape.get(ax, 1)
        out.append(ax if size > 1 and x.shape[i] % size == 0 else None)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*out)))


def cache_pspec(mesh: Optional[Mesh], kv_heads: int) -> P:
    """PartitionSpec for one [NBLK, bs, KV, D] cache arena."""
    tp = _tp_size(mesh)
    if tp > 1 and kv_heads % tp == 0:
        return P(None, None, "model", None)
    return P()


def _shard_map_kernel(fn, mesh: Mesh, in_specs, out_specs):
    # fully-manual map (every mesh axis)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class PagedCache(NamedTuple):
    """Per-layer lists of [NBLK, bs, KV, D] arrays, one entry for each
    layer that holds K/V (cfg.n_kv_layers: every layer, or the
    attention layers of a model of two kinds, in their order). A pool
    is PACKED, [NBLK, bs, KV / f, f D] (kv_pack: the same bytes, f
    heads side by side in one), at head dim 64 (two heads a 128-lane
    row), where more than 8 heads are no whole tiles of the layout
    (30 heads of 128 in 16 bits: 2 heads of 1,920) and where fewer
    than 8 are (4 heads of 128, 8 of 64: 2 heads of 256).

    A model of mixed windows (cfg.mixed_windows) holds pools of TWO
    sizes in these lists: a full layer's [NBLK, ...] paged by a
    sequence's block table as every other model's, a windowed layer's
    [rings * R + 1, ...] (ring_blocks) where the sequence that holds
    ring r (ragged.SequenceDescriptor.ring) keeps its last R blocks of
    tokens in blocks r * R .. r * R + R - 1, position p in block
    (p // bs) % R of them: bounded per-sequence state that happens to
    be walked by the paged kernels, through a table the step makes from
    the ring's number (_ring_tables). Not paged either: what moves or
    shares pages is refused for it (engine._POOL_CANNOT 'ring').

    A latent-attention model (cfg.is_latent) caches ONE row a token a
    layer, [normed latent; rotary key]: `k` holds its pools
    [NBLK, bs, C] (C the row padded to whole lanes, latent_lanes) and
    `v` is empty.

    int8-quantized caches (kv_quant) additionally carry per-layer
    [NBLK, bs, KV] f32 scale-tile pools: block i's codes dequantize by
    k_scale[i] — the scales are part of the page, so every path that
    moves pages (COW, export/import, spill) moves them together."""

    k: List[jnp.ndarray]
    v: List[jnp.ndarray] = ()
    k_scale: Optional[List[jnp.ndarray]] = None
    v_scale: Optional[List[jnp.ndarray]] = None
    # recurrent state: for each layer that carries fixed-size state
    # from token to token (cfg.n_state_layers, in their order) a tuple
    # of pools [slots, ...] (cfg.state_shapes of the layer's kind);
    # entry s of each is the state of the tracked sequence that holds
    # slot s (ragged.SequenceDescriptor.slot). What an entry holds is
    # the layer's business: a conv layer ONE pool [slots, conv_kernel
    # - 1, channels / lanes, lanes], its last conv_kernel - 1 inputs,
    # oldest first, a slot whole tiles; a linear-attention
    # layer a float32 pool [slots + 1, heads, Dk, Dv] of its heads'
    # matrices (the last slot is the pad rows', ops/pallas/
    # gated_delta.py) and such a pool of carried inputs beside it; a
    # state-space layer the same two, its matrices transposed and
    # packed [slots + 1, heads / pack, N, pack P] (ops/pallas/
    # ssm_state.py).
    # Another kind of state is another shape here, not another manager.
    # Not paged: pages travel (COW, handoff, spill) WITHOUT it, which is
    # why the engine refuses those for a model that has any.
    state: List[Tuple[jnp.ndarray, ...]] = ()

    @property
    def block_size(self) -> int:
        return self.k[0].shape[1]

    @property
    def num_blocks(self) -> int:
        """Blocks of a PAGED layer's pool (the largest: a windowed
        layer's ring pool is sized apart)."""
        return max(pool.shape[0] for pool in self.k)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None


def ring_blocks(cfg: T.TransformerConfig, block_size: int,
                blocks_per_seq: int) -> int:
    """R, the blocks a sequence's ring holds in a windowed layer of a
    model of mixed windows (0: the model has no rings): the widest
    window plus the rows of one sequence a step may write before it
    attends (a chunk: at most block_size, engine.put refuses more),
    in whole blocks, plus one because neither end is aligned:
    ceil((window + block_size - 1) / block_size) + 1, so that no
    position a row of the chunk still sees shares a block with one the
    chunk writes. Never more than a table's blocks_per_seq, where the
    ring is the whole context."""
    if not cfg.mixed_windows:
        return 0
    return min(-(-(cfg.widest_window + block_size - 1) // block_size) + 1,
               blocks_per_seq)


def kv_pool_shape(cfg: T.TransformerConfig) -> Tuple[int, int]:
    """(heads, values a head) of K (and of V) a token has in a layer's
    cache: the model's kv_heads of head_dim, or, of a model of paired
    heads (cfg.differential_attention: a pair is one head of 2
    head_dim), its pairs folded side by side by the kernels' tile rule
    (paged_attention.kv_pair_fold): the same values in the same order."""
    if not cfg.differential_attention:
        return cfg.kv_heads, cfg.head_dim
    pairs, width = cfg.kv_heads // 2, 2 * cfg.head_dim
    fold = kv_pair_fold(pairs, width)
    return pairs // fold, width * fold


def kv_pool_pack(cache: "PagedCache", cfg: T.TransformerConfig) -> int:
    """KV heads a head of this cache's K/V pools holds side by side
    (paged_attention.kv_pack as init_cache asked it; 1: not packed),
    read off the allocated pool's lanes as the kernels' entry reads it."""
    return cache.k[0].shape[3] // kv_pool_shape(cfg)[1]


def init_cache(
    cfg: T.TransformerConfig, num_blocks: int, block_size: int, dtype=jnp.bfloat16,
    mesh: Optional[Mesh] = None, kv_quant: bool = False,
    state_slots: int = 0, ring_pool_blocks: int = 0,
) -> PagedCache:
    """kv_quant=True allocates int8 code pools + f32 per-block scale
    tiles instead of `dtype` pools — half (vs bf16) or a quarter (vs
    f32) the resident KV bytes plus KV*8 scale bytes per token.
    state_slots: rows of each state pool (one a tracked sequence) of a
    model with recurrent state. ring_pool_blocks: blocks of a WINDOWED
    layer's pool in a model of mixed windows (rings x ring_blocks + the
    pad rows' one); its full layers' pools hold num_blocks."""
    (KV, D), L = kv_pool_shape(cfg), cfg.n_kv_layers
    if (cfg.is_latent or cfg.n_state_layers or cfg.mixed_windows
            or cfg.differential_attention) and (
            kv_quant or mesh is not None):
        raise NotImplementedError(
            "a latent cache, a cache beside recurrent state, a cache "
            "with rings and one of paired heads is bf16/f32 on one device: "
            "no int8 pool and no mesh")
    def state_pools(kind):
        # the carried inputs, and before them the heads' matrices, whose
        # pool holds one slot more: the pad rows' (state_step_call)
        *matrices, (carried, _) = cfg.state_shapes(kind)
        return (*(jnp.zeros((state_slots + 1, *shape), dt)
                  for shape, dt in matrices),
                jnp.zeros((state_slots, *carried), dtype))

    state = [state_pools(kind) for kind in cfg.state_layer_kinds]
    if cfg.is_latent:
        shape = (num_blocks, block_size, latent_lanes(cfg.latent_dim))
        return PagedCache(k=[jnp.zeros(shape, dtype) for _ in range(L)],
                          v=[])
    # unquantised pools on one device lay heads side by side where the
    # layout would pad them (two of 64 a lane row; 30 of 128 as 2 x 1,920)
    # and where a block of fewer than 8 heads is cheaper to walk in two
    # (4 of 128 as 2 x 256)
    pack = 1
    if not kv_quant and mesh is None:
        pack = kv_pack(KV, D, jnp.dtype(dtype).itemsize)
    shape = (num_blocks, block_size, KV // pack, D * pack)
    if kv_quant:
        dtype = jnp.int8
    if mesh is not None:
        sharding = NamedSharding(mesh, cache_pspec(mesh, KV))
        mk = lambda: jax.device_put(jnp.zeros(shape, dtype), sharding)
        sc_sharding = NamedSharding(
            mesh, P(*cache_pspec(mesh, KV)[:3]))  # scales shard with KV
        mks = lambda: jax.device_put(
            jnp.ones(shape[:3], jnp.float32), sc_sharding)
    else:
        mk = lambda: jnp.zeros(shape, dtype)
        mks = lambda: jnp.ones(shape[:3], jnp.float32)
    if cfg.mixed_windows:
        mk = lambda ring: jnp.zeros(
            (ring_pool_blocks if ring else num_blocks, *shape[1:]), dtype)
        cache = PagedCache(k=[mk(ring) for ring in cfg.ring_layers],
                           v=[mk(ring) for ring in cfg.ring_layers])
        return cache._replace(state=state) if state else cache
    if not kv_quant:
        cache = PagedCache(k=[mk() for _ in range(L)],
                           v=[mk() for _ in range(L)])
        return cache._replace(state=state) if state else cache
    return PagedCache(
        k=[mk() for _ in range(L)], v=[mk() for _ in range(L)],
        k_scale=[mks() for _ in range(L)], v_scale=[mks() for _ in range(L)])


def _rope_at(x, positions, cfg: T.TransformerConfig, scaled: bool = True):
    """Rotary embedding of x [..., T, H, D] at per-token positions [T]
    (decode needs a different position per row, unlike training's
    contiguous offset; prefill's prompts share one [Tp]).
    Frequencies come from T.rope_inv_freq so long-context scaling
    (linear / llama3) and partial rotary (Phi) match the training
    forward exactly. scaled: the layer's table is the scaled one
    (cfg.rope_scaled_at), whose cos and sin YaRN also multiplies by
    cfg.rope_attention_factor."""
    freqs = T.rope_inv_freq(cfg, scaled)
    R = T.rope_dim(cfg)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]  # [T, R/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaled and cfg.rope_attention_factor != 1.0:
        cos, sin = (t * cfg.rope_attention_factor for t in (cos, sin))
    xr, xp = x[..., :R], x[..., R:]
    c, s = cos[:, None, :], sin[:, None, :]
    if cfg.rope_interleaved:
        # GPT-J rotate_every_two pairing — must match T._rope exactly
        xf = xr.astype(jnp.float32).reshape(*xr.shape[:-1], R // 2, 2)
        x1, x2 = xf[..., 0], xf[..., 1]
        out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                        axis=-1).reshape(xr.shape)
    else:
        x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)  # [T, H, R/2]
        out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)


def _flat_slot_index(positions, block_table, block_size):
    """Token position → flat slot in the [KV, NBLK*bs, D] cache view.

    positions: [T] int32 absolute positions of one sequence (prefill) or
    per-row positions with per-row tables (decode handled by caller)."""
    return block_table[positions // block_size] * block_size + positions % block_size


def kv_write_how(pool, mesh=None, use_kernel: bool = True) -> str:
    """How _write_kv reaches a [NBLK, bs, KV, D] pool: "xla" (the jnp
    scatter: decode_impl='xla', or KV heads a `model` mesh does not
    divide), else what paged_kv_write does with the shard ONE device
    holds, "rows" or "blocks" (ops/pallas/paged_attention.
    kv_write_path). Static, from shapes; _write_kv branches on it and
    the engine's init.pool span reports it (kv_write_ids)."""
    NBLK, bs, KV, D = pool.shape
    tp = _tp_size(mesh)
    if not use_kernel or (tp > 1 and KV % tp):
        return "xla"
    return kv_write_path((NBLK, bs, KV // tp, D), pool.dtype)


def kv_write_ids(cache: "PagedCache", cfg: T.TransformerConfig, mesh=None,
                 use_kernel: bool = True) -> Dict[str, str]:
    """init.pool's ids of how a step's new rows reach this cache's
    pools (kv_write_how): `kv_write` the paged K/V pools, `ring_kv_write`
    a windowed layer's rings where the model holds them (cfg.ring_layers),
    `scale_kv_write` an int8 cache's scale pools (paged_scale_write's
    [NBLK, bs, 1, KV] view). None for a latent cache, whose one pool
    paged_latent_write reaches."""
    if cfg.is_latent:
        return {}
    ids = {}
    for name, ring in (("kv_write", False), ("ring_kv_write", True)):
        pools = [k for k, r in zip(cache.k, cfg.ring_layers) if r == ring]
        if pools:
            ids[name] = kv_write_how(pools[0], mesh, use_kernel)
    if cache.k_scale:  # _write_kv_quant: the scatter where the codes take it
        NBLK, bs, KV = cache.k_scale[0].shape
        ids["scale_kv_write"] = (
            "xla" if kv_write_how(cache.k[0], mesh, use_kernel) == "xla"
            else kv_write_path((NBLK, bs, 1, KV // _tp_size(mesh)),
                               cache.k_scale[0].dtype))
    return ids


def _write_kv(cache_k, cache_v, k_new, v_new, flat_idx, mesh=None,
              use_kernel: bool = True):
    """Write [T, KV, D] new KV into [NBLK, bs, KV, D] caches at flat
    slots [T] with paged_kv_write: a row goes to its slot by one DMA of
    its own bytes (or, at a pool shape Mosaic refuses that of, by a
    read-modify-write of the slot's block). Under a TP
    mesh with the KV dim sharded, each device writes its own KV slice
    (shard_map; slots are replicated). use_kernel=False
    (decode_impl='xla') takes the jnp scatter oracle, so the oracle
    engine shares no Pallas program with the engine it checks.
    kv_write_how names the case."""
    if kv_write_how(cache_k, mesh, use_kernel) == "xla":
        # decode_impl='xla', or KV not divisible by the mesh: cache/k/v
        # are replicated, but a raw pallas_call cannot run under the
        # multi-device program (SPMD partitions the scatter instead)
        return _write_kv_xla(cache_k, cache_v, k_new, v_new, flat_idx)
    if _tp_size(mesh) > 1:
        kv = P(None, None, "model", None)
        new = P(None, "model", None)
        return _shard_map_kernel(
            paged_kv_write, mesh,
            in_specs=(kv, kv, new, new, P(None)),
            out_specs=(kv, kv),
        )(cache_k, cache_v, k_new, v_new, flat_idx)
    return paged_kv_write(cache_k, cache_v, k_new, v_new, flat_idx)


def _write_kv_xla(cache_k, cache_v, k_new, v_new, flat_idx):
    """jnp scatter oracle for paged_kv_write (tests + CPU/TP fallback).

    -1 slots must be DROPPED: jax wraps negative indices even under
    mode="drop" (only out-of-bounds drops), so map them past the arena
    first — otherwise pad rows would overwrite the last cache slot."""
    NBLK, bs, KV, D = cache_k.shape
    idx = jnp.where(flat_idx < 0, NBLK * bs, flat_idx)
    # rows in the pool's own row shape (a packed pool's: kv_pack)
    k_new, v_new = (r.reshape(r.shape[0], KV, D) for r in (k_new, v_new))
    ck = cache_k.reshape(NBLK * bs, KV, D).at[idx].set(k_new, mode="drop")
    cv = cache_v.reshape(NBLK * bs, KV, D).at[idx].set(v_new, mode="drop")
    return ck.reshape(NBLK, bs, KV, D), cv.reshape(NBLK, bs, KV, D)


def _write_scales_xla(k_scale, v_scale, ks_new, vs_new, flat_idx):
    """jnp scatter of [T, KV] per-row quant scales into the
    [NBLK, bs, KV] scale pools (oracle + TP-degenerate fallback for
    paged_scale_write; same -1-drops contract as _write_kv_xla)."""
    NBLK, bs, KV = k_scale.shape
    idx = jnp.where(flat_idx < 0, NBLK * bs, flat_idx)
    ks = k_scale.reshape(NBLK * bs, KV).at[idx].set(ks_new, mode="drop")
    vs = v_scale.reshape(NBLK * bs, KV).at[idx].set(vs_new, mode="drop")
    return ks.reshape(NBLK, bs, KV), vs.reshape(NBLK, bs, KV)


def _write_kv_quant(cache_k, cache_v, k_scale, v_scale, k_new, v_new,
                    flat_idx, mesh=None, use_kernel: bool = True):
    """Quantize [T, KV, D] new rows (quantize_kv_rows — THE rounding
    authority, shared with the fused kernel) and write codes + scale
    rows into the int8 pools. Codes ride the same Pallas write as
    bf16 (_write_kv is dtype-generic); scales ride paged_scale_write
    (or the XLA scatter on the degenerate TP layout)."""
    qk, ks, qv, vs = quantize_kv_rows(k_new, v_new)
    ck, cv = _write_kv(cache_k, cache_v, qk, qv, flat_idx, mesh, use_kernel)
    if kv_write_how(cache_k, mesh, use_kernel) == "xla":
        cks, cvs = _write_scales_xla(k_scale, v_scale, ks, vs, flat_idx)
    elif _tp_size(mesh) > 1:
        sp = P(None, None, "model")
        new = P(None, "model")
        cks, cvs = _shard_map_kernel(
            paged_scale_write, mesh,
            in_specs=(sp, sp, new, new, P(None)),
            out_specs=(sp, sp),
        )(k_scale, v_scale, ks, vs, flat_idx)
    else:
        cks, cvs = paged_scale_write(k_scale, v_scale, ks, vs, flat_idx)
    return ck, cv, cks, cvs


def _layer_pools(cache: PagedCache, li: int) -> tuple:
    """One layer's pools in PagedCache's field order: (k, v), and
    (k, v, k_scale, v_scale) of a quantised cache. What `attend` hands
    back per layer and _forward zips into the new PagedCache."""
    return tuple(pool[li] for pool in cache[:4] if pool)


def _write_pools(pools: tuple, k_new, v_new, flat_idx, mesh=None,
                 use_kernel: bool = True) -> tuple:
    """One layer's pools (_layer_pools) with [T, KV, D] new rows written
    at flat slots [T], every pool constrained to its KV-head sharding.
    Both serving sites write through here (decode unless it fuses the
    write into the attention call). A latent cache is one pool: k_new
    holds its [T, C] rows and v_new nothing."""
    if len(pools) == 1:
        write = paged_latent_write if use_kernel else paged_latent_write_xla
        return (write(pools[0], k_new, flat_idx),)
    if len(pools) == 4:
        ck, cv, *scales = _write_kv_quant(*pools, k_new, v_new, flat_idx,
                                          mesh, use_kernel)
        scales = [_cons(sc, mesh, None, None, "model") for sc in scales]
    else:
        ck, cv = _write_kv(*pools, k_new, v_new, flat_idx, mesh, use_kernel)
        scales = []
    return (_cons(ck, mesh, None, None, "model", None),
            _cons(cv, mesh, None, None, "model", None), *scales)


# Rows an expert sees (T x k / X, static in a compiled program) between
# which serving multiplies every token by EVERY expert so as to stream
# each expert's weights once; at or outside these it takes the ragged
# wire. One pair of bounds for each way of streaming: the one pipelined
# pass over the stack ('stream', ops/pallas/expert_stream.py) and the
# `lax.scan` over experts that stays for what the pass cannot take.
# Measured on a TPU v5e at OLMoE-1B-7B's widths (64 experts of
# 2048 x 1024, top-8, bf16, 8 layers whose weights alone stream in
# 7.87 ms; PERF.md section 6, PR 34), ms for the routed block of 8
# layers, stream / scan / ragged: T 8: 8.7 / 11.3 / 6.3,
# 16: 8.7 / 11.4 / 9.3, 32: 8.8 / 11.6 / 12.8, 64: 9.1 / 12.3 / 20.8,
# 128: 8.8 / 14.7 / 21.4, 256: 9.1 / 16.5 / 22.8, 512: 17.7 / 24.9 / 25.7,
# 1024: 35.2 / 39.9 / 32.9. All-expert streaming does X / k times the
# needed operations: best while the weight stream binds (the pass: to
# 256 tokens; from 512 on its matmuls bind, at 94% of the chip's peak,
# which is where its grouped entry takes over: _STREAM_RIDGE_TOKENS
# below, with that column of the table).
# The ragged wire does the needed operations only, at a fixed cost of
# its sort, gather and `lax.ragged_dot` (flat at ~2.6 ms a layer from 8
# to 32 rows an expert), and skips experts no token reached, so it wins
# at both ends.
_STREAM_ROWS_PER_EXPERT = (1, 128)
_SCAN_ROWS_PER_EXPERT = (2, 128)
# Rows (T, static) past which the one pipelined pass, where it would be
# taken, multiplies an expert's weight tile by that expert's OWN rows
# ('grouped'): the chip's ridge. All-expert streaming does T x X x 6EF
# operations over X x 3EF x 2 bytes of 16-bit stacks, T operations a
# byte whatever X, k, E and F are, so its matmuls bind from
# 197e12 / 819e9 = ~240 rows on a v5e; 256 is the table's next column.
# Same chip, the routed block alone, ms, stream / grouped / ragged
# (PERF.md section 6, PR 37). LFM2-8B-A1B's widths (32 experts of
# 2048 x 1792, top-4, 12 layers whose weights alone stream in 10.32 ms):
# T 128: 11.88 / 11.58 / 31.2, 192: 12.22 / 11.66 / 24.4,
# 256: 12.17 / 11.74 / 32.7, 288: 13.11 / 11.88 / 24.8,
# 320: 14.47 / 11.90 / 26.2, 384: 17.24 / 11.99 / 34.1,
# 512: 22.83 / 12.27 / 35.6, 768: 33.96 / 13.52 / 38.6. OLMoE's (64 of
# 2048 x 1024, top-8, 8 layers, 7.87 ms): 128: 9.69 / 9.94 / 21.4,
# 192: 9.78 / 10.22 / 22.2, 256: 9.91 / 10.43 / 22.8,
# 288: 10.26 / 10.63 / 16.9, 320: 11.21 / 11.42 / 23.5,
# 384: 13.37 / 11.55 / 24.2, 512: 17.71 / 11.92 / 25.6,
# 768: 26.44 / 12.95 / 28.4. Under the ridge both forms wait for the
# same weight stream and are within 5% of each other, the grouped form
# ahead at one pair of widths and behind at the other (it moves
# ~12 B x E a buffer row to lay the pairs out and sum them back; the
# all-expert form reduces a [T, X] combine matrix a grid step). The two
# widths' first wins, 128 and 384, are three columns apart, so the bound
# is the computed ridge and not a column read off either: past it the
# grouped form is 10-150% ahead at the first and from 4% behind (288,
# 320: inside what OLMoE's own floor moved between two machines, 8.77
# to 9.69 at 128) to 16-104% ahead at the second.
_STREAM_RIDGE_TOKENS = 256


def expert_path(n_tokens: int, cfg: T.TransformerConfig, lp=None,
                use_kernel: bool = False, mesh=None) -> str:
    """Which expert path a compiled serving program over `n_tokens`
    rows takes: 'stream', 'grouped', 'scan' or 'ragged'. THE place that
    chooses, from what the call can observe and nothing else (no flag
    selects it): the static rows, the layer's leaves `lp` (arrays or
    shapes: their types, dtypes and sizes), whether kernels run
    (`use_kernel`: decode_impl resolved 'pallas') and the mesh.

    Every expert is streamed AND multiplied by every row ('stream' or
    'scan') between the measured bounds of rows an expert, and whatever
    the rows on a chip that holds a SHARE of the experts
    (cfg.experts_held): the rows a held expert sees are the same
    T x k / X an expert, but how many pairs reach the held ones varies
    by iteration and is known on the device alone, so a path that sorts
    or groups would have to lay out every pair, of which held / X stay.
    Streaming reads each held expert once and drops the pairs routed
    elsewhere in its weight matrix.

    Of the two, the one pipelined pass wherever its kernel takes the
    inputs: kernels on and one device (under a mesh of several, as for
    attention in _decode_attention, a raw pallas_call cannot consume
    sharded operands), a block without biases, gated (three stacks) or
    not (two: act(h W_in) W_out, the pass's ungated entry), plain
    16-bit stacks whose E and F fill whole lanes (prepare pads an
    ungated block's F to them) and tokens whose resident buffers fit
    VMEM (expert_stream.stream_f_tile). A QuantizedWeight stack
    dequantises transiently and keeps the scan.

    Where that pass would be taken and the rows are past the chip's
    ridge (_STREAM_RIDGE_TOKENS: its matmuls would bind, not its
    stream), the pass takes its second entry ('grouped': the same
    stacks through the same weight pipeline, each expert against its
    OWN rows) if the buffer of the T x k pairs fits VMEM
    (expert_stream.grouped_f_tile); where it does not, 'stream' as
    before. Nothing else moved: a held share, what the pass cannot take
    and the bounds of rows an expert answer as they did."""
    streams = (
        lp is not None and use_kernel
        and (mesh is None or mesh.devices.size == 1)
        and "b_in" not in lp
        and stream_f_tile(n_tokens, lp.get("w_gate"), lp["w_in"],
                          lp["w_out"]) is not None)
    every = "stream" if streams else "scan"
    if cfg.experts_held is not None:
        return every
    rows = n_tokens * cfg.moe_top_k / cfg.n_experts
    lo, hi = _STREAM_ROWS_PER_EXPERT if streams else _SCAN_ROWS_PER_EXPERT
    if not lo < rows < hi:
        return "ragged"
    # (the grouped entry is the gated block's alone)
    if (streams and cfg.is_gated and n_tokens > _STREAM_RIDGE_TOKENS
            and grouped_f_tile(n_tokens, cfg.moe_top_k, lp["w_gate"],
                               lp["w_in"], lp["w_out"]) is not None):
        return "grouped"
    return every


def _mlp(h, lp, cfg: T.TransformerConfig, census_cb=None,
         use_kernel: bool = False, mesh=None):
    """FFN over [T, E] tokens — dense or MoE (Mixtral / OLMoE serving).

    Dense llama uses the fused [E, 2F] gate|up GEMM when the prepared
    layout carries it (see prepare()).

    MoE serving is CAPACITY-FREE exact top-k for ANY k: every token
    gets its full expert mix — no train-time capacity drops (those are
    a training-throughput artifact; ref: sharded_moe.py topk_gating
    keeps the drops only because the fixed [X, C] buffers feed the
    all-to-all). Gate weights reproduce the training combine weights
    exactly (cfg.moe_norm_topk_prob; unset: top-1 the softmax gate,
    k>=2 renormalized), so serving matches the training forward
    wherever training dropped nothing.

    Four expert paths share the gating authority
    (moe.dropless.dropless_topk_gating); expert_path() picks one from
    the static rows (T, and the rows an expert sees, T x k / X) and
    from what it is handed here (the layer's leaves, use_kernel, mesh:
    as _layer hands them to `attend`):
    - 'ragged': per-expert token batching — the ragged batch's rows
      stable-sort by expert id and run as ONE grouped (ragged) GEMM per
      projection inside this same compiled program
      (moe/dropless.py dropless_apply), FLOPs proportional to T*k.
    - 'stream': ONE pipelined pass over the stacked expert weights with
      a per-expert combine column (ops/pallas/expert_stream.py) — X/k
      times the needed FLOPs, no [T,X,C] dispatch tensor, every weight
      streamed once and the stream never drained between experts.
    - 'grouped': that pass's second entry, past the chip's ridge
      (_STREAM_RIDGE_TOKENS rows, where the all-expert matmuls would
      bind): the same stacks through the same weight pipeline, each
      expert's tile against its OWN rows, the T x k pairs laid out by
      expert in one static, capacity-free buffer
      (expert_stream.group_rows) - the needed FLOPs to a row block,
      every weight still streamed once, reached or not.
    - 'scan': the same sum as a `lax.scan` over the experts, a loop
      trip and three separately started dots an expert, for what the
      pass cannot take (int8 stacks, biases, a mesh, decode_impl 'xla').

    Device time is told apart by scope: `moe_route` (router matmul,
    softmax, top-k, and the sort, the pairs' layout and gather, or the
    weight matrix), `moe_experts` (the expert matmuls and activation;
    on the stream and scan paths the combine column too, inside the
    kernel or fused by XLA into the output matmul; on the grouped path
    the weighted float32 sum over a token's k rows), `moe_combine` (the
    ragged wire's weighting and segment-sum).

    Expert stacks may arrive as groupwise-int8 QuantizedWeight (the
    N004 machinery; quantize_layer): codes dequantize transiently here,
    so resident HBM holds int8 codes + group scales.

    census_cb: when set, per-expert routed-token counts [X] of this
    application stream out via jax.debug.callback — the scheduler's
    expert-utilization/imbalance counters (scheduler.metrics())."""
    act = T._act_fn(cfg)  # one dispatch table for train + serve
    if "w_router" not in lp:  # a dense model, or a leading dense layer
        if cfg.is_gated:
            if "w_gi" in lp:
                gi = _wmm("te,ef->tf", h, lp["w_gi"])
                F = gi.shape[-1] // 2
                inner = act(gi[:, :F]) * gi[:, F:]
            else:
                inner = act(_wmm("te,ef->tf", h, lp["w_gate"])) \
                    * _wmm("te,ef->tf", h, lp["w_in"])
        else:
            inner = _wmm("te,ef->tf", h, lp["w_in"])
            if "b_in" in lp:
                inner = inner + lp["b_in"].astype(h.dtype)
            inner = act(inner)
        out = _wmm("tf,fe->te", inner, lp["w_out"])
        if "b_out" in lp:
            out = out + lp["b_out"].astype(h.dtype)
        return out

    from ..moe.dropless import (
        dropless_apply,
        dropless_topk_gating,
        expert_counts,
    )
    from .quantization import QuantizedWeight

    def deq(w):
        # groupwise-int8 expert stacks (N004 machinery) dequantize
        # transiently at use; plain arrays pass through
        return w.dequantize() if isinstance(w, QuantizedWeight) else w

    X = cfg.n_experts
    T_ = h.shape[0]
    path = expert_path(T_, cfg, lp, use_kernel, mesh)
    with jax.named_scope("moe_route"):
        logits = h.astype(jnp.float32) @ lp["w_router"].astype(jnp.float32)
        if cfg.moe_scoring == "sigmoid":
            idx, wts = _sigmoid_topk_gating(logits, cfg,
                                            lp.get("expert_bias"))
        else:
            # eval gate: no noise; one authority with the training paths
            idx, wts, _, _ = dropless_topk_gating(
                logits, cfg.moe_top_k, renormalize=cfg.moe_norm_topk_prob)
        if census_cb is not None:
            jax.debug.callback(census_cb, expert_counts(idx, X))
        if cfg.experts_held is not None:
            # this chip's share: the router chose among all X; a pair
            # routed to an expert held elsewhere lands on column Xh,
            # which the weight matrix does not have, and is dropped
            start, Xh = cfg.experts_held
            held = (idx >= start) & (idx < start + Xh)
            weights = jnp.zeros((T_, Xh), jnp.float32).at[
                jnp.arange(T_)[:, None], jnp.where(held, idx - start, Xh)
            ].add(wts, mode="drop")
            wcols = weights.T.astype(h.dtype)
        elif path in ("stream", "scan"):
            # combine-weight matrix [T, X] from the top-k decisions
            weights = jnp.zeros((T_, X), jnp.float32).at[
                jnp.arange(T_)[:, None], idx].add(wts)
            wcols = weights.T.astype(h.dtype)

    has_gate = cfg.is_gated
    has_bias = "b_in" in lp
    if path == "ragged":
        # per-expert token batching across the ragged batch: ONE
        # grouped GEMM per projection in this same compiled program
        # (its three scopes are dropless_apply's own)
        out = dropless_apply(
            h, idx, wts, expert_counts(idx, X),
            deq(lp["w_in"]), deq(lp["w_out"]),
            w_gate=deq(lp["w_gate"]) if has_gate else None,
            b_in=lp.get("b_in"), b_out=lp.get("b_out"), act=act)
        # (the shared expert too: a model that holds every expert AND a
        # shared one reaches this path at few rows an expert; until PR 55
        # no served family did, and the path left the shared expert out)
        return _moe_shared(out, h, lp, cfg, act)

    if path == "grouped":
        with jax.named_scope("moe_route"):
            # the pairs in the order a stable sort by expert gives, each
            # expert's run from a 16-row boundary of one static buffer
            row_token, pair_row, starts, counts = group_rows(idx, X)
            xs = h[row_token]
        with jax.named_scope("moe_experts"):
            ys = expert_grouped_mlp(xs, starts, counts, lp["w_gate"],
                                    lp["w_in"], lp["w_out"], act)
            # float32 across a token's k experts up to the one cast; a
            # [T, E] gather a choice, which XLA fuses into the sum
            out = sum(ys[pair_row[:, j]] * wts[:, j, None]
                      for j in range(cfg.moe_top_k)).astype(h.dtype)
        return _moe_shared(out, h, lp, cfg, act)

    if path == "stream":
        with jax.named_scope("moe_experts"):
            out = (expert_stream_mlp(h, lp["w_gate"], lp["w_in"],
                                     lp["w_out"], wcols, act) if has_gate
                   else expert_stream_ungated_mlp(h, lp["w_in"], lp["w_out"],
                                                  wcols, act))
        return _moe_shared(out, h, lp, cfg, act)

    xs = [deq(lp["w_in"]), deq(lp["w_out"]), wcols]
    if has_gate:
        xs.append(deq(lp["w_gate"]))
    if has_bias:
        xs += [lp["b_in"], lp["b_out"]]

    def expert(acc, ws):
        if has_gate:
            w_in, w_out, wcol, w_gate = ws[:4]
            inner = act(h @ w_gate.astype(h.dtype)) * (
                h @ w_in.astype(h.dtype)
            )
            y = inner @ w_out.astype(h.dtype)
        else:
            w_in, w_out, wcol = ws[:3]
            b_in, b_out = ws[3:] if has_bias else (None, None)
            inner = h @ w_in.astype(h.dtype)
            if b_in is not None:
                inner = inner + b_in.astype(h.dtype)
            y = act(inner) @ w_out.astype(h.dtype)
            if b_out is not None:
                y = y + b_out.astype(h.dtype)
        return acc + wcol[:, None] * y, None

    with jax.named_scope("moe_experts"):
        out, _ = jax.lax.scan(expert, jnp.zeros_like(h), tuple(xs))
    return _moe_shared(out, h, lp, cfg, act)


def _moe_shared(out, h, lp, cfg: T.TransformerConfig, act):
    """The tail of the all-expert paths: the shared expert (every
    token, on every chip alike; gated as the routed experts are, or
    act(h ws_in) ws_out where they have no gate; unweighted, or times a
    sigmoid gate of its own, one scalar a token:
    cfg.shared_expert_gate) under its own scope, then the PR-MoE
    residual."""
    if cfg.n_shared_experts:
        with jax.named_scope("moe_shared"):
            up = lambda: _wmm("te,ef->tf", h, lp["ws_in"])
            inner = (act(_wmm("te,ef->tf", h, lp["ws_gate"])) * up()
                     if "ws_gate" in lp else act(up()))
            y = _wmm("tf,fe->te", inner, lp["ws_out"])
            if cfg.shared_expert_gate:
                y = y * jax.nn.sigmoid(
                    h.astype(jnp.float32)
                    @ lp["ws_sgate"].astype(jnp.float32)).astype(y.dtype)
            out = out + y
    return _moe_residual(out, h, lp, cfg, act)


def _sigmoid_topk_gating(logits, cfg: T.TransformerConfig, bias=None):
    """The sigmoid-scored router by cfg's settings (top-k, whether the
    chosen scores are divided by their sum, the routed scale; `bias`
    a layer's `expert_bias`, for the choice alone): ONE authority with
    the training gate, moe/dropless.py sigmoid_topk_gating.
    logits [T, X] f32 -> (idx [T, k] int32, weights [T, k] f32)."""
    from ..moe.dropless import sigmoid_topk_gating

    return sigmoid_topk_gating(logits, cfg.moe_top_k, bias,
                               cfg.moe_norm_topk_prob,
                               cfg.routed_scaling_factor)


def _ffn_residual(x, attn_out, h1, lp, cfg: T.TransformerConfig,
                  census_cb=None, use_kernel: bool = False, mesh=None):
    """The tail of one layer over [..., E] activations: the operator's
    residual (and no more in a model whose layers are one sublayer
    each, cfg.mixer_only), norm2 and the FFN (sequential, or
    the Falcon/Phi parallel form where the FFN reads ln2(x) or the
    shared ln1 output h1), under the scopes the training forward
    names (`norm2`, `mlp`)."""
    m = cfg.residual_multiplier
    if cfg.output_norm:  # the operator's norm stands on its output
        with jax.named_scope("norm1_post"):
            attn_out = T._norm(attn_out, lp["ln1_post_scale"], None, cfg)
    if m != 1.0:  # Granite: both branches of every layer, before the add
        attn_out = attn_out * m
    if cfg.mixer_only:  # the layer is its operator: no FFN behind it
        return x + attn_out
    if not cfg.parallel_residual:
        x = x + attn_out
    if cfg.parallel_residual and cfg.shared_ln:
        h2 = h1
    elif cfg.output_norm:  # the FFN reads the stream as it is
        h2 = T._act_quant(x, cfg)
    else:
        with jax.named_scope("norm2"):
            h2 = T._act_quant(
                T._norm(x, lp["ln2_scale"], lp.get("ln2_bias"), cfg), cfg)
    with jax.named_scope("mlp"):
        y = _mlp(h2.reshape(-1, h2.shape[-1]), lp, cfg, census_cb,
                 use_kernel, mesh).reshape(x.shape)
        if cfg.sandwich_norm:
            y = T._norm(y, lp["ln2_post_scale"], None, cfg)
        if m != 1.0:
            y = y * m
    if cfg.output_norm:
        with jax.named_scope("norm2_post"):
            y = T._norm(y, lp["ln2_post_scale"], None, cfg)
    return x + attn_out + y if cfg.parallel_residual else x + y


def _moe_residual(out, h, lp, cfg: T.TransformerConfig, act):
    """PR-MoE serving tail: dense residual expert + learned mix,
    matching the training combine exactly (ref: moe/layer.py
    use_residual). No-op unless cfg.moe_use_residual."""
    if not cfg.moe_use_residual:
        return out
    if cfg.is_gated:
        inner = act(_wmm("te,ef->tf", h, lp["wr_gate"])) \
            * _wmm("te,ef->tf", h, lp["wr_in"])
    else:
        inner = _wmm("te,ef->tf", h, lp["wr_in"])
        if "br_in" in lp:
            inner = inner + lp["br_in"].astype(h.dtype)
        inner = act(inner)
    dense = _wmm("tf,fe->te", inner, lp["wr_out"])
    if "br_out" in lp:
        dense = dense + lp["br_out"].astype(h.dtype)
    coef = jax.nn.softmax(
        h.astype(jnp.float32) @ lp["w_coef"].astype(jnp.float32)
        + lp["b_coef"].astype(jnp.float32), axis=-1)
    return (out * coef[:, 0:1].astype(h.dtype)
            + dense * coef[:, 1:2].astype(h.dtype))


def _paired_heads(q, k, v, cfg: T.TransformerConfig):
    """Differential attention's pairs as heads of twice the width, in
    the order the projections leave them: q [..., H, D] -> [..., H, 2D],
    an even head (q1 of its pair) over the first D lanes and zeros, an
    odd one (q2) zeros and the last D, so that against a K/V pair's
    [k1; k2] each scores with its own key alone; k, v [..., KV, D] ->
    [..., KV / 2, 2D], a reshape (None stays None: a cross layer's).
    Every path below then computes a1 and a2 as H query heads over
    KV / 2 heads of 2D, at twice the needed products and the needed
    bytes. q is scaled by 2^0.5: what the kernels' own (2D)^-0.5 lacks
    of D^-0.5 (as cfg.attention_multiplier's multiply)."""
    D = q.shape[-1]
    odd = (jnp.arange(q.shape[-2]) % 2 == 1)[:, None]
    q = q * jnp.asarray(2 ** 0.5, q.dtype)
    q = jnp.concatenate([jnp.where(odd, 0, q), jnp.where(odd, q, 0)], axis=-1)
    pair = lambda a: a if a is None else a.reshape(
        *a.shape[:-2], a.shape[-2] // 2, 2 * D)
    return q, pair(k), pair(v)


def _diff_combine(att, lp, li: int, cfg: T.TransformerConfig):
    """att [..., H, 2D], the maps of _paired_heads' queries over their
    pair's V -> [..., H, D], what W_o reads: for each pair p of heads,
    a1 - lam a2 (a1 = att[2p], a2 = att[2p + 1]) normed over its 2D
    values (RMS, the layer's learned scale) times 1 - lam0, float32;
    lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
    lam0 = 0.8 - 0.6 exp(-0.3 li) by the layer's index in the stack."""
    f32 = jnp.float32
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * li)
    dot = lambda a, b: jnp.sum(lp[a].astype(f32) * lp[b].astype(f32))
    lam = (jnp.exp(dot("diff_lq1", "diff_lk1"))
           - jnp.exp(dot("diff_lq2", "diff_lk2")) + lam0)
    o = att[..., 0::2, :].astype(f32) - lam * att[..., 1::2, :].astype(f32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    o = o * lp["diff_norm_scale"].astype(f32) * (1.0 - lam0)
    *lead, H, W = att.shape
    return o.reshape(*lead, H, W // 2).astype(att.dtype)


def _fold_pairs(q, pool):
    """Paired queries q [S, H, W] laid against a pool whose heads hold
    `f` K/V pairs side by side (kv_pool_shape: [.., pairs / f, f W]):
    (q [S, H, f W], each head's W values over its own pair's lanes and
    zeros elsewhere, so that it scores against its pair alone, times
    f^0.5: what the kernels' own (f W)^-0.5 lacks of W^-0.5;
    unfold(att [S, H, f W]) -> [S, H, W], the lanes of its pair's V).
    q as it is where the pool folds nothing."""
    S, H, W = q.shape
    f = pool.shape[-1] // W
    if f == 1:
        return q, lambda att: att
    # heads in their order: H / pairs a pair, f pairs a pool head
    slot = jnp.arange(H) // (H // (pool.shape[-2] * f)) % f
    mine = (slot[:, None] == jnp.arange(f)[None, :])[None, :, :, None]
    wide = jnp.where(mine, q[:, :, None, :] * jnp.asarray(f ** 0.5, q.dtype),
                     0).reshape(S, H, f * W)
    return wide, lambda att: jnp.sum(
        jnp.where(mine, att.reshape(S, H, f, W), 0), axis=2)


def _pool_rows(rows, cfg: T.TransformerConfig):
    """New K or V rows [T, heads, W] in the row shape of the cache's
    pools: a model of paired heads' pairs folded side by side
    (kv_pool_shape), any other model's as they are."""
    if not cfg.differential_attention:
        return rows
    return rows.reshape(rows.shape[0], *kv_pool_shape(cfg))


def _decode_attention(q, pools: tuple, table, ctx, use_kernel: bool,
                      window: int = 0, mesh=None, alibi=None,
                      k_new=None, v_new=None, slots=None):
    """WHERE one decode attention call over a layer's pools
    (_layer_pools) runs. Which Pallas kernel serves it is
    paged_decode_attention's business, read there from the same
    arguments (k_new/v_new/slots: the write fused into the call, the
    pools PRE-write and the result (att, *updated pools); scale pools:
    int8 KV; window; alibi, the [H] per-head slopes). One of three
    places:

    - one device (use_kernel, no 'model' axis): the kernel entry as it
      is, the only place a fused write can run;
    - per shard under a 'model' mesh whose Q and KV heads both divide:
      ONE shard_map over the head dims (scale tiles and slopes shard
      with their heads) around the kernel entry, or around the oracle
      when use_kernel is false;
    - the XLA oracle: use_kernel false, or heads that do not divide (a
      raw pallas_call cannot consume sharded operands; SPMD partitions
      the gather freely)."""
    ck, cv, *scales = pools
    opt = dict(zip(("k_scale", "v_scale"), scales))  # what is present
    if alibi is not None:
        opt["alibi_slopes"] = jnp.asarray(alibi, jnp.float32)
    tp = _tp_size(mesh)
    if use_kernel and tp <= 1:
        return paged_decode_attention(q, ck, cv, table, ctx, window=window,
                                      k_new=k_new, v_new=v_new, slots=slots,
                                      **opt)
    assert k_new is None, "the fused write runs on one device only"
    fn = partial(paged_decode_attention if use_kernel
                 else paged_decode_attention_xla, window=window)
    if tp > 1 and q.shape[1] % tp == 0 and ck.shape[2] % tp == 0:
        qs = P(None, "model", None)
        kv = P(None, None, "model", None)
        specs = dict(k_scale=P(None, None, "model"),
                     v_scale=P(None, None, "model"),
                     alibi_slopes=P("model"))
        return _shard_map_kernel(
            lambda q_, k_, v_, t_, c_, *rest: fn(
                q_, k_, v_, t_, c_, **dict(zip(opt, rest))),
            mesh,
            in_specs=(qs, kv, kv, P(None, None), P(None),
                      *(specs[n] for n in opt)),
            out_specs=qs,
        )(q, ck, cv, table, ctx, *opt.values())
    return paged_decode_attention_xla(q, ck, cv, table, ctx, window=window,
                                      **opt)


# ---------------------------------------------------------------------------
# the serving forward: one layer body, one prologue, one epilogue
# ---------------------------------------------------------------------------

def _layer(x, lp, li: int, positions, cfg: T.TransformerConfig, mesh, attend,
           alibi, census_cb=None, use_kernel: bool = False, carry=None,
           recur=None, handed=None):
    """One serving layer over [..., E] activations (decode rows [S, E],
    prefill prompts [B, Tp, E]): norm1, then the layer's operator by its
    kind (cfg.layer_kind(li)) and the FFN tail. A layer that carries
    state runs its kind's operator (_STATE_OPERATORS) under its kind's
    scope. An 'experts' layer (cfg.mixer_only) runs the routed block on
    norm1's output under the scope `mlp` and holds nothing. A 'conv'
    layer: the gated short convolution (_short_conv)
    with `carry(u, li)` handed in by the caller, as `attend` is: where
    the inputs before this one come from and how the sequence's state
    row is left. A 'linear_attention' layer: the Gated DeltaNet
    (_gated_delta_net) with the same `carry` for its convolution and
    `recur(args, li)`, how the heads' matrices advance (a step over
    ragged rows, or a whole prompt's chunked scan). A 'state_space'
    layer: the Mamba-2 mixer (_state_space), with both. An attention
    layer: the QKV projection (fused w_qkv
    or split, bias or none; with the output gate's, cfg.attn_output_gate),
    QK-norm, rope at `positions` (the
    second-to-last axis of q/k: [S] or [Tp]; none where the model has
    no positions), a softmax scale of its own folded into q, the head
    constraints,
    `attend(q, k, v, li, alibi, lp) -> (att, layer_cache)` handed in by the
    caller (the ONE thing the two sites differ in: what attention runs
    and how the new rows reach the cache), the output projection and
    the FFN tail, whose routed block asks expert_path with `use_kernel`
    and the mesh. Returns (x, layer_cache): the layer's K/V pools, or
    its state pools.

    `handed` is _forward's, what a layer of this pass leaves for later
    ones: the layer cfg.memory_donor its scan's output ('memory'), which
    a 'gated_memory' layer gates its own projection with (it holds
    nothing); the layer cfg.kv_donor its keys, values and pools as its
    `attend` left them ('kv'), which a 'cross_attention' layer, a query
    and an output projection alone, hands to `attend` as `donor`. Where
    cfg.differential_attention, heads are paired before `attend`
    (_paired_heads) and their two maps subtracted after it
    (_diff_combine)."""
    H, KV = cfg.n_heads, cfg.kv_heads
    if cfg.output_norm:  # no norm before the operator: _ffn_residual's
        h1 = T._act_quant(x, cfg)
    else:
        with jax.named_scope("norm1"):
            h1 = T._act_quant(
                T._norm(x, lp["ln1_scale"], lp.get("ln1_bias"), cfg), cfg)
    kind = cfg.layer_kind(li)
    if kind == "experts":  # the routed block as the layer's operator
        with jax.named_scope("mlp"):
            out = _mlp(h1.reshape(-1, h1.shape[-1]), lp, cfg, census_cb,
                       use_kernel, mesh).reshape(x.shape)
        return _ffn_residual(x, out, h1, lp, cfg), None
    if kind == "gated_memory":  # holds nothing: the donor's scan gates it
        with jax.named_scope("gated_memory"):
            gate = jax.nn.silu(_wmm("...e,ef->...f", h1, lp["gmu_in"]))
            out = _wmm("...f,fe->...e",
                       gate * handed["memory"].astype(gate.dtype),
                       lp["gmu_out"])
        return _ffn_residual(x, out, h1, lp, cfg, census_cb, use_kernel,
                             mesh), None
    if kind in _STATE_OPERATORS:
        scope, operator = _STATE_OPERATORS[kind]
        with jax.named_scope(scope):
            out, state, *gives = operator(h1, lp, cfg, partial(carry, li=li),
                                          partial(recur, li=li))
        if li == cfg.memory_donor:
            handed["memory"], = gives
        return _ffn_residual(x, out, h1, lp, cfg, census_cb, use_kernel,
                             mesh), state
    if cfg.is_latent:
        with jax.named_scope("attention"):
            with jax.named_scope("mla_project"):
                q, row = _latent_project(h1, lp, positions, cfg)
            # k: the row the cache holds for each token; no v
            att, layer_cache = attend(q, row, None, li, None, lp)
            with jax.named_scope("mla_out"):
                out = _wmm("...hd,hde->...e", att, lp["wo"])
                if cfg.sandwich_norm:
                    out = T._norm(out, lp["ln1_post_scale"], None, cfg)
        return _ffn_residual(x, out, h1, lp, cfg, census_cb, use_kernel,
                             mesh), layer_cache
    cross = kind == "cross_attention"
    with jax.named_scope("attention"):
        if cross:  # the donor's keys and values: a query alone
            q = _wmm("...e,ehd->...hd", h1, lp["wq"])
            k, v, gate = None, None, []
            if "bq" in lp:
                q = q + lp["bq"].astype(x.dtype)
        elif "w_qkv" in lp:
            qkv = _wmm("...e,ehd->...hd", h1, lp["w_qkv"])
            if "b_qkv" in lp:
                qkv = qkv + lp["b_qkv"].astype(x.dtype)
            q, k, v, *gate = jnp.split(
                qkv, [H, H + KV] + [H + 2 * KV] * cfg.attn_output_gate,
                axis=-2)
        else:
            q = _wmm("...e,ehd->...hd", h1, lp["wq"])
            k = _wmm("...e,ehd->...hd", h1, lp["wk"])
            v = _wmm("...e,ehd->...hd", h1, lp["wv"])
            if "bq" in lp:
                q = q + lp["bq"].astype(x.dtype)
                k = k + lp["bk"].astype(x.dtype)
                v = v + lp["bv"].astype(x.dtype)
            gate = ([_wmm("...e,ehd->...hd", h1, lp["wq_gate"])]
                    if cfg.attn_output_gate else [])
        q, k = T.qk_norm(q, k, lp, cfg)
        if cfg.rope_at(li):
            scaled = cfg.rope_scaled_at(li)
            q = _rope_at(q, positions, cfg, scaled)
            k = _rope_at(k, positions, cfg, scaled)
        if cfg.attention_multiplier is not None:
            # a softmax scale that is not head_dim^-0.5 (Granite's
            # 1/128): q is scaled by what the kernels' own head_dim^-0.5
            # lacks, ONE multiply on the step's [rows, H, D], so that
            # flash attention (which hard-codes its scale), the paged
            # walk, both oracles and every other family's program text
            # stay as they are; the cached K is the unscaled one
            q = q * (cfg.attention_multiplier * cfg.head_dim ** 0.5)
        if cfg.differential_attention:
            q, k, v = _paired_heads(q, k, v, cfg)
        heads = (None,) * (q.ndim - 2) + ("model", None)
        q = _cons(q, mesh, *heads)
        k = _cons(k, mesh, *heads)
        v = _cons(v, mesh, *heads)
        # a model of mixed windows: device time by the layer's window, or
        # `attn_cross` for a walk of another layer's pool
        # (metadata alone; no other model's program carries the scope)
        with (jax.named_scope("attn_cross" if cross else "attn_window"
                              if cfg.window_for_layer(li) else "attn_full")
              if cfg.mixed_windows else contextlib.nullcontext()):
            if cross:
                att, layer_cache = attend(q, k, v, li, alibi, lp,
                                          donor=handed["kv"])
            else:
                att, layer_cache = attend(q, k, v, li, alibi, lp)
        if li == cfg.kv_donor:
            handed["kv"] = (k, v, layer_cache)
        if cfg.differential_attention:
            with jax.named_scope("diff_combine"):
                att = _diff_combine(att, lp, li, cfg)
        if gate:
            with jax.named_scope("attn_gate"):
                att = att * jax.nn.sigmoid(
                    gate[0].astype(jnp.float32)).astype(att.dtype)
        out = _wmm("...hd,hde->...e", att, lp["wo"])
        if "bo" in lp:
            out = out + lp["bo"].astype(x.dtype)
        if cfg.sandwich_norm:
            out = T._norm(out, lp["ln1_post_scale"], None, cfg)
    return _ffn_residual(x, out, h1, lp, cfg, census_cb, use_kernel,
                         mesh), layer_cache


# ---------------------------------------------------------------------------
# the gated short convolution, and the state its sequences carry
# ---------------------------------------------------------------------------

def _short_conv(h1, lp, cfg, carry, recur=None):
    """Normed activations h1 [..., E] -> (the operator's output
    [..., E], the layer's state pool). [B; C; X] = conv_in h1;
    u = B * X; v_t = sum_j taps[:, j] * u_{t-(K-1)+j} (depthwise,
    causal, no bias, no activation; the sum in float32); out =
    conv_out (C * v). `carry(u, taps)` gives (v in float32; the state
    pool with each sequence's last K - 1 inputs written): where the
    K - 1 inputs before each position come from is the one thing a step
    over ragged rows and a whole-prompt prefill differ in."""
    with jax.named_scope("conv_project"):
        b, c, xg = jnp.split(_wmm("...e,ef->...f", h1, lp["conv_in"]), 3,
                             axis=-1)
        u = b * xg
    with jax.named_scope("conv_state"):
        v, pool = carry(u, lp["conv_taps"])
    with jax.named_scope("conv_out"):
        out = _wmm("...e,ef->...f", c * v.astype(u.dtype), lp["conv_out"])
    return out, (pool,)


def _depthwise(past, u, taps):
    """sum_j taps[:, j] * (the input K - 1 - j places back), in float32:
    `past` the K - 1 inputs before each position, oldest first, `u` the
    current one, taps [channels, K], oldest first."""
    taps = taps.astype(jnp.float32)
    return sum(up.astype(jnp.float32) * taps[:, j]
               for j, up in enumerate([*past, u]))


def _gated_delta_net(h1, lp, cfg: T.TransformerConfig, carry, recur):
    """The Gated DeltaNet operator: normed activations h1 [..., E] ->
    (its output [..., E], the layer's state pools (the heads' matrices,
    the convolution's carried inputs)).

    [q; k; v; z] = gdn_in h1, [b; a] = gdn_ba h1 (one of each a value
    head); [q; k; v] <- silu(causal depthwise convolution of
    conv_kernel taps, no bias, zeros before the sequence starts);
    beta = sigmoid(b) (twice that where cfg.gdn_neg_eigval);
    g = -exp(a_log) softplus(a + dt_bias), float32;
    q and k of the key heads repeated to the value heads, each head's
    L2-normalised (x rsqrt(sum x^2 + 1e-6)), q scaled by Dk^-0.5; the
    delta rule per value head (ops/pallas/gated_delta.py) through
    `recur((q, k, v, g, beta))` -> (o float32, the matrices' pool): the
    one thing a step over ragged rows and a whole-prompt prefill differ
    in, beside `carry` (as _short_conv's); then o <- rms(o) *
    gdn_norm_scale * silu(z) over each head's Dv values (a PLAIN scale;
    the norm first, then the gate) and gdn_out."""
    Hk, Hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    Dk, Dv = cfg.gdn_key_dim, cfg.gdn_value_dim
    C, f32 = cfg.gdn_conv_dim, jnp.float32
    with jax.named_scope("gdn_project"):
        mixed = _wmm("...e,ef->...f", h1, lp["gdn_in"])
        u, z = mixed[..., :C], mixed[..., C:]
        b, a = jnp.split(
            _wmm("...e,ef->...f", h1, lp["gdn_ba"]).astype(f32), 2, axis=-1)
        beta = jax.nn.sigmoid(b)
        if cfg.gdn_neg_eigval:  # in (0, 2): the state may reflect along k
            beta = 2.0 * beta
        g = -jnp.exp(lp["gdn_a_log"].astype(f32)) * jax.nn.softplus(
            a + lp["gdn_dt_bias"].astype(f32))
    with jax.named_scope("gdn_conv"):
        conv, conv_pool = carry(u, lp["gdn_taps"])
        # the convolution's output in the activations' dtype, as the
        # publisher's; the heads' norms and the rule in float32
        c = jax.nn.silu(conv).astype(u.dtype)
        q, k, v = jnp.split(c.astype(f32), [Hk * Dk, 2 * Hk * Dk], axis=-1)
        lead = c.shape[:-1]

        def unit(x):  # [..., Hk * Dk] -> [..., Hv, Dk], each head's norm 1
            x = x.reshape(*lead, Hk, Dk)
            x = x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
            return jnp.repeat(x, Hv // Hk, axis=-2)

        q, k = unit(q) * Dk ** -0.5, unit(k)
        v = v.reshape(*lead, Hv, Dv)
    with jax.named_scope("gdn_state"):
        o, pool = recur((q, k, v, g, beta))
    with jax.named_scope("gdn_out"):
        o = o * jax.lax.rsqrt(
            jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
        o = (o * lp["gdn_norm_scale"].astype(f32)
             * jax.nn.silu(z.reshape(o.shape).astype(f32))).astype(h1.dtype)
        out = _wmm("...f,fe->...e", o.reshape(*lead, Hv * Dv), lp["gdn_out"])
    return out, (pool, conv_pool)


def _state_space(h1, lp, cfg: T.TransformerConfig, carry, recur):
    """The state-space (Mamba-2) mixer: normed activations h1 [..., E]
    -> (its output [..., E], the layer's state pools (the heads'
    matrices, the convolution's carried inputs)).

    [z; x; B; C; dt] = ssm_in h1 (no bias), B and C one vector of
    ssm_state_dim a GROUP of heads (cfg.ssm_groups, side by side);
    [x; B; C] <- silu(causal
    depthwise convolution of conv_kernel taps + ssm_conv_bias, zeros
    before the sequence starts), in the activations' dtype as the
    publisher's; dt <- softplus(dt + dt_bias), A = -exp(a_log),
    float32, one of each a head, no clamp; the recurrence per head
    (ops/pallas/ssm_state.py: S <- exp(dt A) S + (dt x) B^T, y = S C,
    B and C its group's) through `recur((x, dt, A, B, C))` -> (y
    float32, the matrices' pool), with `carry` as _short_conv's;
    y += D x (ssm_d); then y <- rms(y * silu(z)) * ssm_norm_scale, the
    statistic over each GROUP's values apart (all the heads' together
    where there is one group; the gate BEFORE the norm: the DeltaNet's
    order is the other way round) and ssm_out."""
    Hs, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_dim
    I, G, f32 = cfg.ssm_inner, cfg.ssm_groups, jnp.float32
    with jax.named_scope("ssm_project"):
        mixed = _wmm("...e,ef->...f", h1, lp["ssm_in"])
        z, u, dt = jnp.split(mixed, [I, I + cfg.ssm_conv_dim], axis=-1)
        dt = jax.nn.softplus(dt.astype(f32) + lp["ssm_dt_bias"].astype(f32))
        A = -jnp.exp(lp["ssm_a_log"].astype(f32))
    with jax.named_scope("ssm_conv"):
        conv, conv_pool = carry(u, lp["ssm_taps"])
        c = jax.nn.silu(conv + lp["ssm_conv_bias"].astype(f32)
                        ).astype(u.dtype).astype(f32)
        x, Bm, Cm = jnp.split(c, [I, I + G * N], axis=-1)
        x = x.reshape(*x.shape[:-1], Hs, P)
    with jax.named_scope("ssm_state"):
        y, pool = recur((x, dt, A, Bm, Cm))
        y = y + lp["ssm_d"].astype(f32)[:, None] * x
    with jax.named_scope("ssm_gate_norm"):
        y = y.reshape(z.shape) * jax.nn.silu(z.astype(f32))
        if G > 1:  # each group's statistic its own
            y = y.reshape(*z.shape[:-1], G, I // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                              + cfg.norm_eps)
        y = (y.reshape(z.shape) * lp["ssm_norm_scale"].astype(f32)
             ).astype(h1.dtype)
    with jax.named_scope("ssm_out"):
        out = _wmm("...f,fe->...e", y, lp["ssm_out"])
    return out, (pool, conv_pool)


def _selective_scan(h1, lp, cfg: T.TransformerConfig, carry, recur):
    """The selective-scan (Mamba-1) mixer: normed activations h1
    [..., E] -> (its output [..., E], the layer's state pools (the
    state, the convolution's carried inputs), and what a later layer
    may read of it: the scan's output y, skip included, BEFORE the
    gate).

    [x; z] = sscan_in h1 (no bias); x <- silu(causal depthwise
    convolution of conv_kernel taps + sscan_conv_bias, zeros before the
    sequence starts), in the activations' dtype as the publisher's;
    [r; B; C] = sscan_x x (r of cfg.ssm_dt_rank, B and C of
    ssm_state_dim, one of each a token); dt = softplus(sscan_dt r +
    sscan_dt_bias), A = -exp(sscan_a_log), float32, a rate a (channel,
    state) pair, no clamp; the recurrence (ops/pallas/selective_scan.py:
    h <- exp(dt A) h + dt B x, y = h C) through
    `recur((x, dt, A, B, C))` -> (y float32, the state's pool), with
    `carry` as _short_conv's; y += D x (sscan_d); out = sscan_out
    (y * silu(z)): no norm behind the gate."""
    N, R, f32 = cfg.ssm_state_dim, cfg.ssm_dt_rank, jnp.float32
    with jax.named_scope("sscan_project"):
        u, z = jnp.split(_wmm("...e,ef->...f", h1, lp["sscan_in"]), 2,
                         axis=-1)
    with jax.named_scope("sscan_conv"):
        conv, conv_pool = carry(u, lp["sscan_taps"])
        x = jax.nn.silu(conv + lp["sscan_conv_bias"].astype(f32)
                        ).astype(u.dtype)
    with jax.named_scope("sscan_project"):
        r, Bm, Cm = jnp.split(_wmm("...f,fr->...r", x, lp["sscan_x"]),
                              [R, R + N], axis=-1)
        dt = jax.nn.softplus(
            _wmm("...r,rf->...f", r, lp["sscan_dt"]).astype(f32)
            + lp["sscan_dt_bias"].astype(f32))
        A = -jnp.exp(lp["sscan_a_log"].astype(f32))
    with jax.named_scope("sscan_state"):
        x = x.astype(f32)
        y, pool = recur((x, dt, A, Bm.astype(f32), Cm.astype(f32)))
        y = y + lp["sscan_d"].astype(f32) * x
    with jax.named_scope("sscan_gate"):
        gated = (y * jax.nn.silu(z.astype(f32))).astype(h1.dtype)
    with jax.named_scope("sscan_out"):
        out = _wmm("...f,fe->...e", gated, lp["sscan_out"])
    return out, (pool, conv_pool), y.astype(h1.dtype)


# a layer that carries state, by its kind: its scope, its operator
# (h1, lp, cfg, carry, recur) -> (out, the layer's state pools, and
# where the kind hands something on to later layers, that)
_STATE_OPERATORS = {
    "conv": ("short_conv", _short_conv),
    "linear_attention": ("linear_attention", _gated_delta_net),
    "state_space": ("state_space", _state_space),
    "selective_scan": ("selective_scan", _selective_scan),
}


def _recur_rows(kind: str, args, pool, slots, positions, use_kernel: bool):
    """`recur` of a step over ragged rows [S, H, ...] (the rows of
    _carry_rows), for a layer of `kind` whose heads carry a matrix:
    each run advances its sequence's matrices from its slot, from zero
    where the run starts at position 0; through the kind's kernel
    where kernels run and it takes the shapes, else its loop in XLA."""
    fits, kernel, xla = _STEP_OF[kind]
    step = kernel if use_kernel and fits(args[0].shape[0], pool) else xla
    return step(*args, pool, slots, positions)


def _recur_prompts(kind: str, args, pool, slots, n_real, cfg):
    """`recur` of a whole-prompt prefill [B, Tp, H, ...]: the kind's
    chunked scan from a zero state, the padding after each prompt's
    n_real tokens made to leave the state as it is, and each prompt's
    last state copied into its sequence's slot (pad prompts' into the
    pool's last)."""
    B, Tp = args[0].shape[:2]
    real = (jnp.arange(Tp)[None, :] < n_real[:, None])[..., None]
    o, last = _SCAN_OF[kind](args, real, cfg, pool)
    where = jnp.where((n_real > 0) & (slots >= 0), slots, pool.shape[0] - 1)
    for i in range(B):  # megabytes an entry: slice updates, no scatter
        pool = jax.lax.dynamic_update_slice(
            pool, last[i][None].astype(pool.dtype), (where[i], 0, 0, 0))
    return o, pool


def _gdn_scan(args, real, cfg, pool):
    """The delta rule's chunked scan over whole prompts, its last
    states in the pool's layout (the heads side by side that a lane row
    of `pool` holds); a pad token (not `real` [B, Tp, 1]) has k = 0,
    g = 0, beta = 0."""
    q, k, v, g, beta = args
    o, last = gated_delta_chunked(
        q, jnp.where(real[..., None], k, 0.0), v, jnp.where(real, g, 0.0),
        jnp.where(real, beta, 0.0))
    return o, pack_heads(last, last.shape[1] // pool.shape[1])


def _ssm_scan(args, real, cfg, pool):
    """The state-space layer's chunked scan over whole prompts, its
    last states in the pool's layout; a pad token has dt = 0."""
    x, dt, A, Bm, Cm = args
    y, last = ssm_chunked(x, jnp.where(real, dt, 0.0), A, Bm, Cm,
                          chunk=cfg.ssm_chunk, groups=cfg.ssm_groups)
    return y, pack_state(last, cfg.ssm_pack)


def _sscan_scan(args, real, cfg, pool):
    """The selective scan's chunked form over whole prompts, its last
    states in the pool's layout; a pad token has dt = 0."""
    x, dt, A, Bm, Cm = args
    y, last = sscan_chunked(x, jnp.where(real, dt, 0.0), A, Bm, Cm,
                            chunk=cfg.ssm_chunk)
    return y, sscan_pool_view(last, pool.shape[-1])


# a kind whose heads carry a matrix: whether its step kernel takes
# (rows, pool), the kernel, the same step in XLA; its whole-prompt scan
_STEP_OF = {
    "linear_attention": (step_fits, gated_delta_step, gated_delta_step_xla),
    "state_space": (ssm_step_fits, ssm_step, ssm_step_xla),
    "selective_scan": (sscan_step_fits, sscan_step, sscan_step_xla),
}
_SCAN_OF = {"linear_attention": _gdn_scan, "state_space": _ssm_scan,
            "selective_scan": _sscan_scan}


def _state_write(pool, slots, rows, keep):
    """State pool [slots, K - 1, ...] with rows [N, (K - 1) E] written
    at slots [N] where `keep` [N]; the others (pad rows, rows that are
    not their sequence's last of the step) are dropped."""
    idx = jnp.where(keep & (slots >= 0), slots, pool.shape[0])
    return pool.at[idx].set(
        rows.reshape(-1, *pool.shape[1:]).astype(pool.dtype), mode="drop")


def _slot_wide(carry, cache: "PagedCache", cfg: T.TransformerConfig):
    """`carry(u, taps, li)` of a serving site from its `carry(u, taps,
    pool)` over layer li's pool of carried inputs. Where a slot of that
    pool is wider than the layer's channels (cfg.state_shapes pads it
    to whole tiles: Granite's 8,448 in 9,216), the inputs and the taps
    are padded to the slot with zeros and the sum is cut back, so that
    everything between works at ONE width, the slot's."""
    def at_layer(u, taps, li):
        pool = cache.state[cfg.state_index(li)][-1]
        E, wide = u.shape[-1], pool.shape[-2] * pool.shape[-1]
        if wide == E:
            return carry(u, taps, pool)
        pad = lambda a, axis: jnp.pad(
            a, [(0, wide - E) if i == axis else (0, 0) for i in range(a.ndim)])
        conv, pool = carry(pad(u, u.ndim - 1), pad(taps, 0), pool)
        return conv[..., :E], pool
    return at_layer


def _carry_rows(u, pool, slots, positions):
    """The K - 1 inputs before each row of a step over ragged rows
    u [S, E], oldest first, and the pool those rows leave: row r is
    the token at positions[r] of the sequence holding state slot
    slots[r] (-1: batch padding); rows of one sequence are adjacent and
    in order (a prefill chunk), any other row is another sequence. The
    k-th input before row r is row r - k where that is the same
    sequence's token k places back, else it was left in the sequence's
    slot by an earlier step; before the sequence's first token it is
    ZERO, whatever the slot holds, so a slot needs no clearing between
    the sequences that take it in turn, nor after a flush. Each
    sequence's last row of the step leaves its last K - 1 inputs in the
    slot. With _depthwise, what ops/pallas/conv_carry.py does in one
    pass, and its oracle."""
    S, E = u.shape
    K1 = pool.shape[1]  # K - 1 inputs carried, oldest first
    held = pool[jnp.maximum(slots, 0)].reshape(S, K1, E)
    # rows of its own sequence before row r in this step, up to K - 1
    run, last = carry_facts(slots, positions, K1)
    past = []
    for k in range(K1, 0, -1):  # oldest first
        here = jnp.roll(u, k, axis=0)
        there = jnp.take_along_axis(
            held, jnp.clip(K1 - k + run, 0, K1 - 1)[:, None, None], axis=1
        )[:, 0].astype(u.dtype)
        past.append(jnp.where(
            (positions >= k)[:, None],
            jnp.where((run >= k)[:, None], here, there), 0))
    rows = jnp.concatenate([*past[1:], u], axis=-1)
    return past, _state_write(pool, slots, rows, last)


def _carry_prompts(u, pool, slots, n_real):
    """_carry_rows of a whole-prompt prefill u [B, Tp, E]: a plain
    causal shift (zeros before the prompt starts), and each prompt's
    last K - 1 real inputs written to its sequence's slot."""
    B, Tp, E = u.shape
    K1 = pool.shape[1]
    up = jnp.pad(u, ((0, 0), (K1, 0), (0, 0)))  # up[:, t + K1] = u_t
    past = [up[:, K1 - k:K1 - k + Tp] for k in range(K1, 0, -1)]
    # inputs n_real - K1 .. n_real - 1: up[:, n_real .. n_real + K1 - 1]
    tail = jnp.take_along_axis(
        up, (n_real[:, None] + jnp.arange(K1))[:, :, None], axis=1)
    return past, _state_write(pool, slots, tail.reshape(B, K1 * E),
                              n_real > 0)


# ---------------------------------------------------------------------------
# latent attention (MLA): the projections, and the two forms
# ---------------------------------------------------------------------------

def _latent_project(h1, lp, positions, cfg: T.TransformerConfig):
    """normed activations h1 [..., E] -> (q [..., H, Dn + Dr] with its
    last Dr rotated, row [..., Rkv + Dr]: what the cache holds for the
    token, the RMSNorm'd latent and the rotated shared key)."""
    Dn, Rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    cq = T._norm(_wmm("...e,er->...r", h1, lp["wq_a"]),
                 lp["q_a_scale"], None, cfg)
    q = _wmm("...r,rhd->...hd", cq, lp["wq_b"])
    q = jnp.concatenate(
        [q[..., :Dn], _rope_at(q[..., Dn:], positions, cfg)], axis=-1)
    ckv = _wmm("...e,ec->...c", h1, lp["wkv_a"])
    c = T._norm(ckv[..., :Rkv], lp["kv_a_scale"], None, cfg)
    # one rotary key for all heads: a head axis of 1 for _rope_at
    kr = _rope_at(ckv[..., None, Rkv:], positions, cfg)[..., 0, :]
    return q, jnp.concatenate([c, kr], axis=-1)


def _pad_lanes(x, width: int):
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def _latent_absorbed(q, row, lp, pool, tables, ctx_lens, flat_idx,
                     cfg: T.TransformerConfig, use_kernel: bool):
    """Decode and chunk rows, the ABSORBED form: rows [S] written to
    the latent pool, then every head's query moved into the latent
    space (q~ = W_uk^T q_nope, so score = q~ . latent + q_rope . k_rope)
    and attended as multi-query attention over the ONE cached row a
    token; the attended latent goes through W_uv. No key or value of
    any head is ever materialised. -> (att [S, H, Dv], (pool,))"""
    Dn, Rkv = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    C = pool.shape[-1]
    with jax.named_scope("mla_cache_write"):
        (pool,) = _write_pools((pool,), _pad_lanes(row, C), None, flat_idx,
                               None, use_kernel)
    with jax.named_scope("mla_project"):
        qt = _wmm("shn,chn->shc", q[..., :Dn], lp["w_uk"])
        scale = 1.0 / (q.shape[-1] ** 0.5)
        ql = _pad_lanes(jnp.concatenate([qt, q[..., Dn:]], axis=-1) * scale,
                        C).astype(q.dtype)
    with jax.named_scope("mla_attend"):
        walk = (paged_latent_attention if use_kernel
                else paged_latent_attention_xla)
        u = walk(ql, pool, tables, ctx_lens, Rkv)
    with jax.named_scope("mla_out"):
        att = _wmm("shc,chv->shv", u, lp["w_uv"])
    return att, (pool,)


# heads a whole-prompt latent prefill up-projects and attends at a time:
# K and V of every head of every prompt token at once are gigabytes at
# 128 heads (B 2 x T 4096: 3 x 0.5 GB, twice over inside the flash call)
_LATENT_PREFILL_HEADS = 32


def _latent_naive(q, row, lp, pool, flat_idx, cfg: T.TransformerConfig,
                  use_kernel: bool):
    """Whole-prompt prefill, the NAIVE form: rows [B, Tp] written to
    the latent pool, then every head's key [nope; shared rope] and
    value up-projected from the latent and attended causally. Chosen
    for whole prompts from the shapes alone: it multiplies (Dn + Dr +
    Dv) per score against the absorbed form's (2 Rkv + Dr), fewer at
    every length, and its rows need no per-row block table. The flash
    kernel takes one head dim for q, k and v, a lane multiple: all
    three are zero-padded to it and q pre-scaled so that the kernel's
    1/sqrt(padded) comes out as 1/sqrt(Dn + Dr).
    -> (att [B, Tp, H, Dv], (pool,))"""
    B, Tp, H, Dq = q.shape
    Rkv, Dv = cfg.kv_lora_rank, cfg.v_head_dim
    with jax.named_scope("mla_cache_write"):
        (pool,) = _write_pools(
            (pool,), _pad_lanes(row, pool.shape[-1]).reshape(B * Tp, -1),
            None, flat_idx, None, use_kernel)
    c, kr = row[..., :Rkv], row[..., Rkv:]
    flash = uses_flash(q, use_kernel and cfg.use_flash)
    Dp = latent_lanes(max(Dq, Dv)) if flash else Dq
    hg = min(H, _LATENT_PREFILL_HEADS)
    assert H % hg == 0, (H, hg)

    def heads(args):
        qg, w_uk, w_uv = args  # [B, Tp, hg, Dq], [Rkv, hg, Dn], [Rkv, hg, Dv]
        k = jnp.concatenate(
            [_wmm("btc,chn->bthn", c, w_uk),
             jnp.broadcast_to(kr[:, :, None, :], (B, Tp, hg, kr.shape[-1]))],
            axis=-1)
        v = _wmm("btc,chv->bthv", c, w_uv)
        if not flash:
            return causal_attention(qg, k, v, use_flash=False)
        qs = (qg * ((Dp / Dq) ** 0.5)).astype(qg.dtype)
        return causal_attention(
            _pad_lanes(qs, Dp), _pad_lanes(k, Dp), _pad_lanes(v, Dp),
            use_flash=True)[..., :Dv]

    def split(w, axis):  # the head axis -> [H / hg, ..., hg, ...] leading
        w = w.reshape(*w.shape[:axis], H // hg, hg, *w.shape[axis + 1:])
        return jnp.moveaxis(w, axis, 0)

    with jax.named_scope("mla_attend"):
        att = jax.lax.map(heads, (split(q, 2), split(lp["w_uk"], 1),
                                  split(lp["w_uv"], 1)))
    # [H / hg, B, Tp, hg, Dv] -> [B, Tp, H, Dv]
    return jnp.moveaxis(att, 0, 2).reshape(B, Tp, H, Dv), (pool,)


def _forward(params, tokens, positions, cfg: T.TransformerConfig, mesh,
             attend, fetch_layer=None, census_cb=None, head_rows=None,
             use_kernel: bool = False, carry=None, recur=None):
    """The serving forward both sites run: tokens [...] int32 at
    `positions` (their last axis) -> (f32 logits, the PagedCache the
    layers' `attend` calls returned). Prologue (embedding, learned
    positions, embedding norm, ALiBi slopes), _layer per layer (weights
    through fetch_layer when offloaded), epilogue (head_rows picks the
    rows the head runs on — prefill's last real tokens — then the final
    norm and the head)."""
    if not is_prepared(params):
        params = prepare(params, cfg, fuse=mesh is None)
    with jax.named_scope("embed"):
        x = _embed_rows(params["embed"], tokens, cfg.embedding_multiplier)
        if cfg.use_learned_pos:
            x = x + params["pos_embed"][positions].astype(x.dtype)
        if cfg.embedding_layernorm:
            x = T._norm(x, params["embed_ln_scale"],
                        params.get("embed_ln_bias"), cfg)
    alibi = (jnp.asarray(T.model_alibi_slopes(cfg)) if cfg.alibi
             else None)

    pools = []  # per layer that holds K/V, as _layer_pools
    states = []  # per layer that holds state, its pool
    handed = {}  # what a layer leaves for later ones of this pass (_layer)
    x_hist = []  # layer outputs; fetch l is barriered on output l-2
    # leading dense layers first (cache layers 0..n_dense-1), then the
    # stacked ones
    for li, lp in enumerate(list(params.get("dense_layers", ()))
                            + list(params["layers"])):
        if fetch_layer is not None:
            lp = fetch_layer(lp, x_hist[-2] if len(x_hist) >= 2 else None,
                             li)
        x, layer_cache = _layer(x, lp, li, positions, cfg, mesh, attend,
                                alibi, census_cb, use_kernel, carry, recur,
                                handed)
        # a layer holds K/V, state or nothing, by its kind
        if cfg.layer_kind(li) == "attention":
            pools.append(layer_cache)
        elif layer_cache is not None:
            states.append(layer_cache)
        x_hist.append(x)

    with jax.named_scope("lm_head"):
        if head_rows is not None:
            x = head_rows(x)
        x = T._norm(x, params["ln_f_scale"], params.get("ln_f_bias"), cfg)
        logits = _lm_logits(x, params, cfg)
        logits = _cons(logits, mesh, None, None)
    new = PagedCache(*(list(pool) for pool in zip(*pools)))
    if states:
        new = new._replace(state=states)
    return logits, new._replace(v=[]) if cfg.is_latent else new


# ---------------------------------------------------------------------------
# decode: a batch of sequences, one new token each
# ---------------------------------------------------------------------------

def decode_step(
    params, cache: PagedCache, tokens, tables, ctx_lens, cfg: T.TransformerConfig,
    use_kernel: bool = True, mesh: Optional[Mesh] = None,
    unique_rows: bool = False, fetch_layer=None, census_cb=None,
    slots=None, rings=None, positions=None,
):
    """tokens [S] int32, tables [S, NB] int32, ctx_lens [S] int32 (context
    length INCLUDING the new token) → (logits [S, V], new cache).

    positions [S] int32: each row's POSITION (rotary, the slot its K/V
    is written to) where that is not its visible length less one: a
    model that generates by diffusion over blocks (cfg.block_length B)
    and no other. A row at position p sees through the END of its block,
    ctx_lens = (p // B + 1) * B, and a block's rows are fed several
    times at the SAME positions, each pass writing the block's K/V over
    the last one's: the write goes before the walk, so the rows of one
    dispatch see each other (docs/paged_attention.md). Absent:
    ctx_lens - 1, every other family's program text.

    rings [S] int32: each row's sequence's ring of the windowed layers'
    pools (-1: batch padding), for a model of mixed windows and no
    other: its windowed layers write and walk through _ring_tables,
    its full layers through `tables`.

    slots [S] int32: each row's sequence's state slot (-1: batch
    padding), for a model with recurrent state (cache.state) and no
    other; rows of one sequence (a prefill chunk) are adjacent and in
    order (_carry_rows).

    ref: engine_v2.py put→model.forward decode path; one compiled program
    per (S, NB) shape. mesh: TP serving — params/cache arrive sharded
    over 'model' and constraints keep activations head-sharded between
    the column-parallel QKV and row-parallel output projections.

    unique_rows=True asserts every row is a distinct sequence (no
    chunked-continuation rows sharing a block table) — this enables the
    fused write+attend kernel, halving Pallas launches per layer. The
    caller must also guarantee padding rows' tables point at a reserved
    scratch block (engine: pad_block), since the fused kernel's
    write-back touches each row's target block.

    fetch_layer: ZeRO-Inference offload serving — a per-layer transform
    (in-jit pinned_host→HBM device_put) applied as each layer's weights
    are consumed, so HBM holds O(one layer) of weights instead of the
    model (ref: docs/_posts/2022-09-10-zero-inference.md full-offload
    mode; the engine builds it)."""
    bs = cache.block_size
    # rows with ctx_lens == 0 are batch padding: their KV write is dropped
    # and their (garbage) logits are sliced off by the engine
    valid = ctx_lens > 0
    if positions is None:
        positions = jnp.maximum(ctx_lens - 1, 0)  # [S] this token's position
    elif unique_rows:
        raise ValueError(
            "rows given `positions` are a block's: they share a table and "
            "see each other, which the fused write+attend program "
            "(unique_rows) does not do")
    # per-row flat slot: each row has its own table; padding rows
    # scatter to -1 which mode="drop" discards
    flat_idx = (
        jnp.take_along_axis(tables, (positions // bs)[:, None], axis=1)[:, 0]
        * bs + positions % bs
    )
    flat_idx = jnp.where(valid, flat_idx, jnp.int32(-1))
    by_window = {False: (tables, flat_idx)}  # does a ring hold the layer
    if cfg.mixed_windows:
        _need_rings(rings)
        ring_tables = _ring_tables(rings, cache, cfg, tables.shape[1])
        by_window[True] = (ring_tables, jnp.where(valid, jnp.take_along_axis(
            ring_tables, (positions // bs)[:, None], axis=1)[:, 0] * bs
            + positions % bs, jnp.int32(-1)))
    # the write fuses into the attention call only on the single-device
    # kernel path (the shard_map TP path and the XLA oracle keep the
    # separate write), and only at widths whose rows' write semaphores
    # the core has
    fuse_write = (unique_rows and use_kernel and _tp_size(mesh) <= 1
                  and fused_write_fits(tokens.shape[0]))

    def attend(q, k, v, li, alibi, lp, donor=None):
        if cfg.is_latent:  # k: the rows the cache holds
            return _latent_absorbed(q, k, lp, *_layer_pools(cache, li),
                                    tables, ctx_lens, flat_idx, cfg,
                                    use_kernel)
        unfold = lambda att: att
        if cfg.differential_attention:  # the pairs as the pools hold them
            q, unfold = _fold_pairs(q, cache.k[0])
        if donor is not None:
            # a cross layer: the donor's pools as its own attend left
            # them, this step's rows in them; a full walk, and no write
            att = _decode_attention(q, donor[2], tables, ctx_lens,
                                    use_kernel, 0, mesh, alibi)
            return unfold(att), None
        window = cfg.window_for_layer(li)
        table, flat = by_window[cfg.ring_layers[cfg.op_index(li)]]
        where = (table, ctx_lens, use_kernel, window, mesh, alibi)
        pools = _layer_pools(cache, cfg.op_index(li))
        k, v = _pool_rows(k, cfg), _pool_rows(v, cfg)
        if fuse_write:
            att, *pools = _decode_attention(q, pools, *where, k_new=k,
                                            v_new=v, slots=flat)
            return unfold(att), pools
        pools = _write_pools(pools, k, v, flat, mesh, use_kernel)
        return unfold(_decode_attention(q, pools, *where)), pools

    @partial(_slot_wide, cache=cache, cfg=cfg)
    def carry(u, taps, pool):
        if use_kernel and carry_fits(u.shape[0], u.dtype, pool):
            return conv_carry(u, taps, pool, slots, positions)
        past, pool = _carry_rows(u, pool, slots, positions)
        return _depthwise(past, u, taps), pool

    def recur(args, li):
        return _recur_rows(cfg.layer_kind(li), args,
                           cache.state[cfg.state_index(li)][0], slots,
                           positions, use_kernel)

    return _forward(params, tokens, positions, cfg, mesh, attend,
                    fetch_layer, census_cb, use_kernel=use_kernel,
                    carry=carry, recur=recur)


def _need_rings(rings):
    if rings is None:
        raise ValueError(
            "a model of mixed windows holds its windowed layers' K/V in "
            "rings: the step takes `rings`, each row's sequence's ring "
            "(ragged.SequenceDescriptor.ring; the engine passes it)")


def _ring_tables(rings, cache: PagedCache, cfg: T.TransformerConfig,
                 n_slots: int):
    """[S, n_slots] int32: the block table of a WINDOWED layer, made
    from each row's ring number: table slot j (token positions
    j * bs ..) is block (j % R) of the row's ring, R = ring_blocks, so
    the paged kernels write and walk it as they do any table and never
    learn that slots R apart name one block: of two such slots the
    window keeps at most one live. Pad rows (ring -1) get the pool's
    last block, which no ring holds."""
    pool = cache.k[cfg.ring_layers.index(True)]
    R = ring_blocks(cfg, cache.block_size, n_slots)
    rings = rings.astype(jnp.int32)[:, None]
    return jnp.where(
        rings >= 0, rings * R + jnp.arange(n_slots, dtype=jnp.int32)[None] % R,
        jnp.int32(pool.shape[0] - 1))


def decode_multi(
    params, cache: PagedCache, tokens, tables, ctx_lens,
    cfg: T.TransformerConfig, n_steps: int, use_kernel: bool = True,
    mesh: Optional[Mesh] = None, unique_rows: bool = True,
    sampling=None, keys=None, step0=None, presence=None,
    fetch_layer=None, census_cb=None, slots=None, rings=None,
):
    """Fused decode: n_steps tokens per compiled program.

    One `lax.scan` over decode_step with the next token fed back — the
    host dispatches once per n_steps instead of per token, amortizing
    dispatch/scheduling latency (the SplitFuse-era "fixed work per
    forward" idea applied along time). Block tables must already cover
    ctx_lens + n_steps positions. Rows are by construction distinct
    sequences (each advances its own context), so the fused
    write+attend kernel applies (see decode_step unique_rows).

    sampling: optional sampling.SamplingConfig — the full on-device
    chain (penalty/temperature/top-k/top-p + gumbel-max draw); None =
    greedy argmax. keys [S] per-sequence PRNG keys and step0 [S] int32
    draw counters feed the per-(sequence, step) streams; presence
    [S, V] uint8 rides the carry for the repetition penalty (pass only
    when the config needs it — it is 2 MB at batch 64).

    Returns (generated [n_steps, S] int32, final logits [S, V], cache,
    final presence or None).
    """
    from .sampling import sample_tokens, update_presence

    S = tokens.shape[0]
    V = cfg.vocab_size
    if not is_prepared(params):
        params = prepare(params, cfg, fuse=mesh is None)
    with_presence = presence is not None

    def body(carry, i):
        toks, ctx, _, cache, pres = carry
        logits, cache = decode_step(params, cache, toks, tables, ctx, cfg,
                                    use_kernel, mesh=mesh,
                                    unique_rows=unique_rows,
                                    fetch_layer=fetch_layer,
                                    census_cb=census_cb, slots=slots,
                                    rings=rings)
        if sampling is None:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            nxt = sample_tokens(logits, sampling, keys,
                                None if step0 is None else step0 + i,
                                presence=pres)
        if with_presence:
            pres = update_presence(pres, nxt)
        # logits ride the CARRY (overwritten per step): stacking them in ys
        # would keep a dead [n_steps, S, V] accumulator live in HBM
        return (nxt, ctx + 1, logits, cache, pres), nxt

    init = (tokens, ctx_lens, jnp.zeros((S, V), jnp.float32), cache,
            presence)
    (_, _, last_logits, cache, presence), gen = jax.lax.scan(
        body, init, jnp.arange(n_steps, dtype=jnp.int32)
    )
    return gen, last_logits, cache, presence


# ---------------------------------------------------------------------------
# prefill: one sequence's whole prompt
# ---------------------------------------------------------------------------

def prefill_step(
    params, cache: PagedCache, tokens, n_real, table, cfg: T.TransformerConfig,
    use_kernel: bool = True, mesh: Optional[Mesh] = None,
):
    """tokens [Tp] int32 (padded), n_real scalar int32, table [NB] int32 →
    (last-token logits [V], new cache) — single-prompt prefill (the B=1
    view of prefill_batch)."""
    n_real = jnp.asarray(n_real, jnp.int32).reshape(1)
    logits, cache = prefill_batch(
        params, cache, tokens[None], n_real, table[None], cfg, use_kernel,
        mesh=mesh,
    )
    return logits[0], cache


def prefill_batch(
    params, cache: PagedCache, tokens, n_real, tables,
    cfg: T.TransformerConfig, use_kernel: bool = True,
    mesh: Optional[Mesh] = None, fetch_layer=None, census_cb=None,
    slots=None, rings=None,
):
    """Cross-prompt batched prefill: tokens [B, Tp] int32 (padded),
    n_real [B] int32, tables [B, NB] int32 → (last-real-token logits
    [B, V], new cache). slots [B] int32: each prompt's sequence's state
    slot, for a model with recurrent state (decode_step). rings [B]
    int32: each prompt's sequence's ring, for a model of mixed windows:
    a windowed layer keeps the prompt's last ring_blocks blocks alone
    (older positions are outside every later row's window).

    ONE compiled program runs B concurrent prompts — the ragged-batch
    idea of SplitFuse applied to prefill (ref: inference/v2/kernels/
    ragged_ops/ mixed prefill batches; VERDICT r2 W4: per-prompt calls
    made TTFT degrade linearly under concurrent arrivals). Attention
    over each prompt is plain causal flash (batch dim is natural); new
    KV rows from every prompt scatter into the paged cache in one write
    call. Rows with n_real == 0 are batch padding (garbage logits,
    sliced by the caller; their KV writes drop)."""
    B, Tp = tokens.shape
    bs = cache.block_size
    positions = jnp.arange(Tp, dtype=jnp.int32)
    # per-row flat cache slots for the real tokens; -1 rows drop
    flat_idx = jnp.where(
        positions[None, :] < n_real[:, None],
        jnp.take_along_axis(
            tables, positions[None, :] // bs, axis=1
        ) * bs + positions[None, :] % bs,
        jnp.int32(-1),
    ).reshape(B * Tp)
    flat_by_window = {False: flat_idx}  # does a ring hold the layer
    if cfg.mixed_windows:
        _need_rings(rings)
        ring_tables = _ring_tables(rings, cache, cfg, tables.shape[1])
        R = ring_blocks(cfg, bs, tables.shape[1])
        kept = ((positions[None, :] < n_real[:, None])
                & (positions[None, :] // bs
                   > ((n_real - 1) // bs)[:, None] - R))
        flat_by_window[True] = jnp.where(
            kept,
            jnp.take_along_axis(ring_tables, jnp.minimum(
                positions[None, :] // bs, tables.shape[1] - 1), axis=1) * bs
            + positions[None, :] % bs,
            jnp.int32(-1),
        ).reshape(B * Tp)

    def attend(q, k, v, li, alibi, lp, donor=None):
        if cfg.is_latent:  # k: the rows the cache holds
            return _latent_naive(q, k, lp, *_layer_pools(cache, li),
                                 flat_idx, cfg, use_kernel)
        if donor is not None:
            # a cross layer: the donor's keys and values of this pass,
            # causal and full; it writes nothing
            return causal_attention(
                q, donor[0], donor[1],
                use_flash=use_kernel and cfg.use_flash), None
        # the prompt's in-flight attention stays full precision (it
        # never reads the cache); only the RESIDENT copy quantizes —
        # later decode steps read these codes
        pools = _write_pools(
            _layer_pools(cache, cfg.op_index(li)),
            _pool_rows(k.reshape(B * Tp, *k.shape[2:]), cfg),
            _pool_rows(v.reshape(B * Tp, *v.shape[2:]), cfg),
            flat_by_window[cfg.ring_layers[cfg.op_index(li)]],
            mesh, use_kernel)
        if cfg.block_length:
            # the block-causal mask, in XLA (the flash kernel's tiles
            # know the causal and the windowed mask alone); this family
            # is refused a mesh by the engine
            return block_causal_attention(q, k, v, cfg.block_length), pools
        flash = partial(causal_attention, window=cfg.window_for_layer(li))
        if _heads_shardable(mesh, cfg):
            # flash kernel per head-shard; GQA grouping stays
            # device-local, slopes shard with their heads
            hs = P(None, None, "model", None)
            ab = () if alibi is None else (alibi,)
            att = _shard_map_kernel(
                lambda q_, k_, v_, *ab_: flash(
                    q_, k_, v_, use_flash=use_kernel and cfg.use_flash,
                    alibi=ab_[0] if ab_ else None),
                mesh, in_specs=(hs, hs, hs) + (P("model"),) * len(ab),
                out_specs=hs,
            )(q, k, v, *ab)
        else:
            att = flash(
                q, k, v,
                # a raw pallas_call cannot consume TP-sharded operands
                use_flash=(use_kernel and cfg.use_flash
                           and _tp_size(mesh) <= 1),
                alibi=alibi)
        return att, pools

    def last_real(x):
        # logits for each prompt's last REAL token only (logits_gather):
        # gather before the vocab matmul so the head runs on B tokens,
        # not B*Tp
        last = jnp.maximum(n_real - 1, 0)  # [B]; padding rows read pos 0
        if cfg.block_length:
            # every position of the last block predicts ITSELF: the
            # block's rows, [B, block_length, E]
            rows = (last // cfg.block_length * cfg.block_length)[:, None] \
                + jnp.arange(cfg.block_length, dtype=jnp.int32)[None]
            return jnp.take_along_axis(
                x, jnp.minimum(rows, Tp - 1)[:, :, None], axis=1)
        return jnp.take_along_axis(
            x, last[:, None, None].astype(jnp.int32).repeat(x.shape[-1], axis=2),
            axis=1)[:, 0]

    @partial(_slot_wide, cache=cache, cfg=cfg)
    def carry(u, taps, pool):
        past, pool = _carry_prompts(u, pool, slots, n_real)
        return _depthwise(past, u, taps), pool

    def recur(args, li):
        return _recur_prompts(cfg.layer_kind(li), args,
                              cache.state[cfg.state_index(li)][0], slots,
                              n_real, cfg)

    return _forward(params, tokens, positions, cfg, mesh, attend,
                    fetch_layer, census_cb, head_rows=last_real,
                    use_kernel=use_kernel, carry=carry, recur=recur)
