"""Inference engine: continuous batching over a paged KV cache.

TPU-native redesign of FastGen's InferenceEngineV2
(ref: inference/v2/engine_v2.py:30 — put:107, query:158, flush:242;
config ref: inference/v2/ragged/manager_configs.py
RaggedInferenceEngineConfig:137). Differences driven by XLA:

- static shapes: prompts and decode batches are padded to power-of-two
  buckets; each bucket is one compiled program, cached (the reference
  re-runs eager CUDA kernels on exact ragged sizes; here the SplitFuse
  "fixed token budget per step" idea becomes "fixed compiled buckets").
- the ragged batch never exists as a device-side struct: the device sees
  dense padded token buffers + block tables + context lengths; all
  raggedness lives in the host-side StateManager (inference/ragged.py).
- one forward pass per put() for the decode set (all sequences advance
  one token in a single compiled program); concurrent prefills run as
  compiled WAVES — one program per (batch-bucket, token-bucket), capped
  at max_batch_size prompts (the SplitFuse mixed-batch idea).

v1-engine parity (ref: deepspeed/inference/engine.py:39): init_inference
constructs this engine; greedy `generate` is provided for parity with
the wrapped-module generate path.
"""

import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pydantic import Field

from ..config.config import ConfigModel, PrefixCacheConfig
from ..resilience.faults import fault_point
from ..resilience.integrity import (
    HandoffIntegrityError,
    corrupt_payload,
    payload_digest,
)
from ..models import transformer as T
from ..ops.pallas import kernel_traces, kernels_runnable
from ..ops.pallas.expert_stream import grouped_rows
from ..ops.pallas.paged_attention import latent_lanes, latent_walk_fits
from ..utils import profiler
from ..utils.frames import on_one_chunk
from ..utils.logging import log_dist
from ..utils.sync import host_sync, serving_readback
from . import model as M
from .ragged import StateManager


class InferenceConfig(ConfigModel):
    """ref: inference/v2/ragged/manager_configs.py DSStateManagerConfig +
    RaggedInferenceEngineConfig (max_tracked_sequences,
    max_ragged_batch_size, KVCacheConfig) — flattened to what the TPU
    engine needs. tp_size: tensor-parallel degree (the v1 engine's
    tensor_parallel.tp_size, ref: inference/config.py DeepSpeedTPConfig) —
    weights shard by the training rules table, the KV cache shards over
    its KV-head dim."""

    max_tracked_sequences: int = 256
    max_batch_size: int = 64          # decode sequences per step
    max_seq_len: int = 4096           # per-sequence context cap
    kv_block_size: int = 128
    num_kv_blocks: int = 512          # total paged-cache blocks
    # rings of the windowed layers' pool of a model of mixed windows
    # (model.ring_blocks blocks each, one a tracked sequence from
    # admission to flush); 0 = one for every tracked sequence. No other
    # model reads it.
    num_kv_rings: int = 0
    min_prefill_bucket: int = 64
    tp_size: int = 1                  # tensor-parallel degree
    # KV-cache residency dtype: 'auto' = the engine compute dtype;
    # 'int8' = per-block quantized pools (int8 codes + [bs, KV] f32
    # scale tiles per block; docs/paged_attention.md) — ~2x (bf16) /
    # ~4x (f32) more resident tokens per HBM byte, and export/spill
    # payloads shrink by the same factor
    kv_cache_dtype: str = "auto"
    # serving attention + KV-write implementation: 'auto' = the Pallas
    # kernels wherever they run (ops/pallas.kernels_runnable: a TPU, or
    # an explicit interpret request), the jnp oracle elsewhere — the
    # RESOLVED choice is InferenceEngine.resolved_impl, logged at init
    # and surfaced as the scheduler's `decode_kernel` metric; 'pallas'
    # forces the kernels (off-TPU that needs ops/pallas.
    # interpret_kernels, else pallas_call raises); 'xla' forces the
    # oracle (no Pallas program anywhere in the engine)
    decode_impl: str = "auto"
    # MoE expert-utilization census: every compiled decode/prefill
    # application streams its per-expert routed-token counts to the
    # engine (jax.debug.callback — one tiny [X] host transfer per
    # layer), surfaced as engine.moe_expert_census() and the
    # scheduler's moe_expert_* / moe_imbalance metrics. Off by default
    # (a per-layer callback is not free); no effect on dense models.
    moe_census: bool = False
    # automatic prefix caching (config/config.py PrefixCacheConfig):
    # hash-matched block reuse + COW tails in the ragged control plane
    prefix_cache: PrefixCacheConfig = Field(default_factory=PrefixCacheConfig)

    @property
    def blocks_per_seq(self) -> int:
        return -(-self.max_seq_len // self.kv_block_size)


class KvCacheDtypeError(ValueError):
    """KV pages cannot move between engines whose cache dtypes differ:
    an int8 payload's codes+scales mean nothing to a bf16 pool and vice
    versa, and silently dequantizing would break the token-identity
    contract of the recompute fallback. Typed (a ValueError subclass)
    so the router's fleet-construction check and direct import_kv
    callers can reject mixed-dtype fleets explicitly — mirroring the
    heterogeneous-fleet geometry rejection."""


# What a served model's cache holds, by kind of pool, and what each
# kind cannot do yet. A paged K/V pool does everything. A latent pool
# (one row a token, ops/pallas latent kernels) has no int8 form, no
# sharding and no page transfer. A state pool (model.PagedCache.state:
# one fixed-size row a tracked sequence) is not paged at all: whatever
# moves or shares PAGES (a prefix-cache credit, COW, handoff, spill)
# would move them without the state that goes with their last token,
# and whatever rolls a sequence back (a rejected speculative draft)
# would leave the state ahead of it. Until a snapshot of the state per
# cached block exists these are refused HERE, where the engine or the
# scheduler is built, never computed wrongly. A ring pool (a windowed
# layer's, in a model of mixed windows: model.ring_blocks blocks a
# tracked sequence, turned over as the window passes them) is walked by
# the paged kernels and is not paged either: a cached or exported PAGE
# of the full layers has no counterpart in it once the ring has turned
# (prefix_credit, page_transfer: credit, COW, handoff, spill wait for a
# ring's contents to travel with the last pages), a rejected draft may
# already have overwritten the block it would roll back to
# (speculation), and its pools have no int8 form and no sharding yet
# (int8_kv, mesh: init_cache builds neither). Weight quantization and
# offload touch no cache and stay served.
_POOL_CANNOT = {
    "kv": frozenset(),
    "latent": frozenset({"mesh", "weight_quantization", "offload",
                         "int8_kv", "page_transfer"}),
    "state": frozenset({"mesh", "weight_quantization", "offload",
                        "int8_kv", "page_transfer", "prefix_credit",
                        "speculation"}),
    "ring": frozenset({"mesh", "int8_kv", "page_transfer", "prefix_credit",
                       "speculation"}),
}


def pool_kinds(cfg: T.TransformerConfig) -> Tuple[str, ...]:
    """The kinds of pool this model's cache holds: ('kv',), ('latent',),
    'ring' beside 'kv' where layers of two windows are mixed, and
    'state' beside any where some layers carry recurrent state."""
    return (("latent" if cfg.is_latent else "kv",)
            + (("ring",) if cfg.mixed_windows else ())
            + (("state",) if cfg.n_state_layers else ()))


def pools_can(cfg: T.TransformerConfig, feature: str) -> bool:
    return not any(feature in _POOL_CANNOT[k] for k in pool_kinds(cfg))


def refuse_for_pools(cfg: T.TransformerConfig, feature: str) -> None:
    """THE refusal of what a model's pools cannot do: raises naming the
    pool kinds present and which of them cannot do `feature`."""
    kinds = pool_kinds(cfg)
    cannot = [k for k in kinds if feature in _POOL_CANNOT[k]]
    if cannot:
        raise NotImplementedError(
            f"{feature} is not served for a model whose cache holds "
            f"{' + '.join(kinds)} pools: the {' and the '.join(cannot)} "
            f"pool cannot do it yet (inference/engine.py _POOL_CANNOT)")


# What a model that generates by diffusion over blocks (cfg.block_length)
# is not served with, and why: each is refused where the engine or the
# scheduler is built, by refuse_block_diffusion.
_BLOCK_DIFFUSION_CANNOT = {
    "speculation": "a draft continues a causal sequence token by token; a "
                   "block is revealed in the order of its confidences",
    "decode_multi": "the fused multi-step program feeds ONE sampled token a "
                    "row from step to step (decode_chunk > 1); a denoising "
                    "pass feeds a block's rows at the same positions",
    "wave": "a whole-prompt wave samples one token a prompt; a prompt's "
            "whole blocks yield none, and its remainder opens the first "
            "block (prefill_mode='chunked')",
    "presence": "the repetition penalty needs each token on the host before "
                "the next draw; a block's tokens stay on the device between "
                "passes",
    "handoff": "a request parked after its first TOKEN has no counterpart: "
               "the first commit yields a block",
}


def refuse_block_diffusion(cfg: T.TransformerConfig, feature: str) -> None:
    if cfg.block_length:
        raise NotImplementedError(
            f"{feature} is not served for a model that generates by "
            f"diffusion over blocks (block_length {cfg.block_length}): "
            f"{_BLOCK_DIFFUSION_CANNOT[feature]}")


def ring_geometry(cfg: T.TransformerConfig, config) -> Tuple[int, int]:
    """(rings, blocks a ring) of the windowed layers' pool an engine of
    `config` holds for this model; (0, 0) for a model without rings."""
    R = M.ring_blocks(cfg, config.kv_block_size, config.blocks_per_seq)
    return (config.num_kv_rings or config.max_tracked_sequences if R else 0,
            R)


def pool_bytes(cfg: T.TransformerConfig, config, dtype) -> Dict[str, int]:
    """Bytes of the pools an engine of `config` would allocate for this
    model, from shapes alone: {'kv': ..., 'state': ...}, and for a
    model of mixed windows 'ring', its windowed layers' pools, which
    'kv' then leaves out."""
    rings, R = ring_geometry(cfg, config)
    shapes = jax.eval_shape(lambda: M.init_cache(
        cfg, config.num_kv_blocks + 1, config.kv_block_size, dtype,
        kv_quant=config.kv_cache_dtype == "int8",
        state_slots=config.max_tracked_sequences,
        ring_pool_blocks=rings * R + 1))
    size = lambda tree: sum(x.size * x.dtype.itemsize
                            for x in jax.tree.leaves(tree))
    ring = size([pool for pools in (shapes.k, shapes.v)
                 for pool, held in zip(pools, cfg.ring_layers) if held])
    return {"kv": size(shapes._replace(state=())) - ring,
            "state": size(shapes.state), **({"ring": ring} if R else {})}


def refuse_pools_beyond(limit: Optional[int], weights: int,
                        pools: Dict[str, int]) -> None:
    """A model with state pools whose weights + K/V + state exceed the
    device's memory is refused where the engine is built, with the
    three numbers: a slot costs megabytes there (a float32 matrix a
    head), so max_tracked_sequences is not free, and the alternative is
    an allocation failure in warm-up that names none of them. `limit`
    None (a backend that states no limit: the CPU) refuses nothing."""
    kv = pools["kv"] + pools.get("ring", 0)
    total = weights + kv + pools["state"]
    if limit is not None and pools["state"] and total > limit:
        gb = lambda n: f"{n / 1e9:.2f} GB"
        raise ValueError(
            f"this engine does not fit the device: weights {gb(weights)} + "
            f"K/V pools {gb(kv)} + state pools "
            f"{gb(pools['state'])} = {gb(total)} of {gb(limit)}; lower "
            f"max_tracked_sequences (a state slot costs "
            f"{gb(pools['state'])} / slots) or num_kv_blocks")


def _bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _layer_logical_specs(lp: Any, cfg: T.TransformerConfig) -> Dict[str, Any]:
    """Logical-axis specs for ONE prepared layer dict (the single
    source for both park-time and fetch-time sharding)."""
    moe = cfg.n_experts > 0
    return {
        name: (M._MOE_SPECS[name] if moe and name in M._MOE_SPECS
               else M._SERVING_SPECS[name][1])
        for name in lp
    }


def _leaf_sharding(pspec, leaf, mesh: Mesh, memory_kind: str = "device"):
    """Sharding(s) for one prepared leaf: plain leaves take the rules-
    table spec; quantized leaves shard their int codes by that spec and
    replicate the scales (small, and a sharded-scale/packed-codes
    pairing is not worth the bookkeeping)."""
    from .quantization import ChannelQuantWeight, QuantizedWeight

    mk = NamedSharding(mesh, pspec, memory_kind=memory_kind)
    repl = NamedSharding(mesh, P(), memory_kind=memory_kind)
    if isinstance(leaf, QuantizedWeight):
        return QuantizedWeight(q=mk, scale=repl, bits=leaf.bits,
                               dtype_name=leaf.dtype_name)
    if isinstance(leaf, ChannelQuantWeight):
        return ChannelQuantWeight(q=mk, scale=repl,
                                  dtype_name=leaf.dtype_name)
    return mk


def _prepared_specs(prepared: Any, cfg: T.TransformerConfig) -> Any:
    """Logical-axis tree matching a PREPARED serving tree (M.prepare
    layout: per-layer list, unfused under TP)."""
    # top-level entries come from the training spec table (one source of
    # truth; prepare() leaves them untouched)
    top = {k: v for k, v in T.logical_specs(cfg).items() if k != "layers"}
    specs: Dict[str, Any] = {k: top[k] for k in prepared if k != "layers"}
    specs["layers"] = [_layer_logical_specs(lp, cfg)
                       for lp in prepared["layers"]]
    return specs


def _shard_serving_params(params: Any, cfg: T.TransformerConfig,
                          mesh: Mesh) -> Any:
    """device_put the PREPARED weight tree with the training rules table
    (parallel/sharding.py — heads/mlp/vocab over 'model'), shape-guarded
    per leaf so e.g. 2 GQA kv-heads under tp=8 replicate instead of
    failing. Quantized leaves shard their int codes by the same logical
    spec (scales replicate — they are small and the pairing of a sharded
    scale dim with packed codes is not worth the bookkeeping).
    ref: inference/engine.py:331 sharded checkpoint load + AutoTP slicing
    — here sharding is a placement, not a tensor-surgery pass."""
    from ..parallel import sharding as Sh
    from .quantization import ChannelQuantWeight, QuantizedWeight

    is_q = lambda x: isinstance(x, (QuantizedWeight, ChannelQuantWeight))
    specs = _prepared_specs(params, cfg)
    # shape-guard against the ARRAY actually placed (int4 codes pack the
    # last dim 2-per-byte, so the guard must see the packed shape)
    shapes = jax.tree.map(
        lambda leaf: leaf.q.shape if is_q(leaf) else leaf.shape,
        params, is_leaf=is_q,
    )
    pspecs = Sh.tree_logical_to_mesh(specs, Sh.make_rules(), mesh,
                                     shapes=shapes)
    shardings = jax.tree.map(
        lambda ps, leaf: _leaf_sharding(ps, leaf, mesh),
        pspecs, params,
        is_leaf=lambda x: isinstance(x, P))
    return jax.tree.map(jax.device_put, params, shardings)


class InferenceEngine:
    """put/query/flush over (params, TransformerConfig)."""

    def __init__(
        self,
        model_config: T.TransformerConfig,
        params: Any,
        config: Optional[InferenceConfig] = None,
        dtype=jnp.bfloat16,
        quantization: Optional[Dict[str, Any]] = None,
        mesh: Optional[Mesh] = None,
        offload: Optional[Dict[str, Any]] = None,
    ):
        """quantization: ZeRO-Inference weight-only PTQ, e.g.
        {"bits": 8, "group_size": 128} — weights stay int8/int4 in HBM
        and dequantize transiently inside each compiled step
        (ref: deepspeed/inference/quantization/).

        offload: ZeRO-Inference FULL-offload serving — {"device": "cpu"}
        parks every LAYER's weights in host DRAM (pinned_host) and
        streams them into HBM inside the compiled step, one layer at a
        time, so models larger than a chip's HBM serve on one chip
        (ref: docs/_posts/2022-09-10-zero-inference.md:52 — the 43 tok/s
        OPT-30B full-offload case; batch-size-first policy applies: the
        per-step cost is dominated by the fixed weight stream, so
        throughput scales with batch until HBM/compute bind).
        Embeddings / lm_head / final norm stay HBM-resident (they are
        the hot constant set). Composes with per-channel int8
        quantization (halves the streamed bytes) AND with a TP mesh
        (each device parks + streams its own weight SHARD — per-device
        stream shrinks by 1/tp). {"device": "nvme", "path": ...} parks
        layers in per-leaf NVMe files instead (bigger-than-DRAM models;
        ref partitioned_param_swapper.py:36): each step's layer fetch
        is an in-program io_callback over the aio read-ahead window
        (inference/offload_store.py); single-chip only.

        mesh: explicit serving mesh; when absent and config.tp_size > 1,
        a {'model': tp_size} mesh is built over the first tp_size devices
        (ref: inference/engine.py:254 _create_model_parallel_group)."""
        self.cfg = model_config
        self.config = config or InferenceConfig()
        if mesh is not None and self.config.tp_size > 1 and \
                int(mesh.shape.get("model", 1)) != self.config.tp_size:
            raise ValueError(
                f"explicit mesh has model={mesh.shape.get('model', 1)} but "
                f"config.tp_size={self.config.tp_size}; drop one of the two"
            )
        if mesh is None and self.config.tp_size > 1:
            from ..platform.mesh import build_mesh

            devs = jax.devices()
            if len(devs) < self.config.tp_size:
                raise ValueError(
                    f"tp_size {self.config.tp_size} > {len(devs)} devices"
                )
            mesh = build_mesh({"model": self.config.tp_size},
                              devices=devs[: self.config.tp_size])
        # a mesh whose axes are all size 1 is the single-device path
        self.mesh = (
            mesh if mesh is not None and any(s > 1 for s in mesh.shape.values())
            else None
        )
        if self.mesh is not None:
            tp = int(self.mesh.shape.get("model", 1))
            if model_config.n_heads % tp != 0:
                raise ValueError(
                    f"n_heads {model_config.n_heads} not divisible by "
                    f"tp_size {tp} (ref AutoTP requires head divisibility, "
                    "module_inject/auto_tp.py)"
                )
        for feature, asked in (
                ("mesh", self.mesh is not None),
                ("weight_quantization", bool(quantization)),
                ("offload", offload is not None),
                ("int8_kv", self.config.kv_cache_dtype != "auto")):
            if asked:
                refuse_for_pools(model_config, feature)
        B = model_config.block_length
        if B and self.mesh is not None:
            raise NotImplementedError(
                "a model that generates by diffusion over blocks is served "
                "on one device: its whole-prompt prefill masks in XLA and "
                "its look-ahead feeds a block's tokens from a committed "
                "device array, neither of which a mesh program takes yet")
        if B and (self.config.kv_block_size % B
                  or self.config.min_prefill_bucket % B):
            raise ValueError(
                f"block_length {B} must divide kv_block_size "
                f"{self.config.kv_block_size} and min_prefill_bucket "
                f"{self.config.min_prefill_bucket}: a diffusion block never "
                "straddles two KV blocks, so pages move and are shared in "
                "whole diffusion blocks")
        if self.config.decode_impl not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"decode_impl must be 'auto', 'pallas' or 'xla' "
                f"(got {self.config.decode_impl!r})")
        # under TP the kernels run per head-shard, which needs Q and KV
        # heads to split evenly over 'model'; a layout that cannot is
        # the oracle's — said here, not discovered per call
        tp = M._tp_size(self.mesh)
        kernel_layout = tp <= 1 or M._heads_shardable(self.mesh, model_config)
        if self.config.decode_impl == "pallas" and not kernel_layout:
            raise ValueError(
                f"decode_impl='pallas' needs n_heads {model_config.n_heads} "
                f"and kv_heads {model_config.kv_heads} divisible by tp_size "
                f"{tp}; use decode_impl='auto' or 'xla'")
        self._use_kernel = kernel_layout and (
            self.config.decode_impl == "pallas"
            or (self.config.decode_impl == "auto" and kernels_runnable()))
        if model_config.is_latent and self._use_kernel:
            # the latent walk holds a table's live blocks in VMEM twice
            # over: a context it cannot take is refused here, not handed
            # to the XLA oracle call by call
            row = jax.ShapeDtypeStruct(
                (1, self.config.kv_block_size,
                 latent_lanes(model_config.latent_dim)), dtype)
            if not latent_walk_fits(self.config.blocks_per_seq, row):
                raise ValueError(
                    f"max_seq_len {self.config.max_seq_len} = "
                    f"{self.config.blocks_per_seq} blocks of "
                    f"{self.config.kv_block_size} tokens is more than the "
                    "latent walk's VMEM buffers hold; lower max_seq_len or "
                    "use decode_impl='xla'")
        if model_config.use_learned_pos:
            # prefill pads prompts up to a power-of-two bucket, and every
            # padded position indexes the learned position table — so the
            # largest BUCKET (not just max_seq_len) must fit
            worst = _bucket(self.config.max_seq_len, self.config.min_prefill_bucket)
            if worst > model_config.max_seq:
                raise ValueError(
                    f"gpt2 learned positions ({model_config.max_seq}) are "
                    f"shorter than the largest prefill bucket ({worst}); "
                    "lower max_seq_len so its bucket fits"
                )
        self._offload = None
        self._nvme_store = None
        if offload is not None:  # {} is a config error, not "disabled"
            dev = offload.get("device")
            if dev not in ("cpu", "nvme"):
                raise ValueError(
                    f"offload.device must be 'cpu' or 'nvme' (got {dev!r})")
            if dev == "nvme":
                # bigger-than-DRAM tier (ref: partitioned_param_swapper
                # :36 + the 30 tok/s OPT-30B NVMe case, zero-inference
                # post:52): layers live in per-leaf NVMe files and each
                # step's layer fetch is an in-program io_callback over
                # the aio read-ahead window (inference/offload_store.py)
                if self.mesh is not None:
                    raise NotImplementedError(
                        "nvme offload serving under a TP mesh: the "
                        "io_callback fetch is single-process; use the "
                        "cpu tier with TP, or nvme single-chip"
                    )
                if not offload.get("path"):
                    raise ValueError(
                        "offload={'device': 'nvme'} requires 'path' "
                        "(an NVMe-backed directory)")
                self._offload = {
                    "device": "nvme",
                    "path": offload["path"],
                    "n_threads": int(offload.get("n_threads", 4)),
                    "block_size": int(offload.get("block_size", 1 << 20)),
                    "read_ahead": int(offload.get("read_ahead", 2)),
                }
            else:
                # cpu tier composes with a TP mesh: each device's weight
                # SHARD parks in its pinned_host and streams to its own
                # HBM inside the step (the per-device stream shrinks by
                # 1/tp, so offload TP scales the weight-stream roofline)
                self._offload = {"device": "cpu"}
        self._dtype = dtype
        self._quantization = dict(quantization) if quantization else None
        self._per_channel = bool(self._quantization
                                 and self._quantization.pop("per_channel",
                                                            False))
        if self._quantization is not None:
            unknown = set(self._quantization) - {"bits", "group_size",
                                                 "min_ndim"}
            if unknown:
                raise TypeError(
                    f"unknown quantization keys {sorted(unknown)}; expected "
                    "bits / group_size / min_ndim / per_channel"
                )
        if self._per_channel and int(quantization.get("bits", 8)) != 8:
            raise ValueError(
                "per_channel quantization is int8-only (int4 uses the "
                "groupwise memory path)"
            )
        if quantization and not self._per_channel:
            from .quantization import dequantize_tree

            self._dequant = dequantize_tree
        else:
            # per-channel codes feed the matmuls directly (M._wmm); no
            # step-entry dequant pass
            self._dequant = lambda p: p
        self._prepare_fn = None
        self._expert_paths: Dict[int, str] = {}  # by program width
        self._carry_kernels: Dict[int, bool] = {}  # by program width
        self._step_kernels: Dict[int, bool] = {}  # by program width
        self._layer_xform = None
        self._top_xform = None
        # awaited, so each set-up phase's span carries its own time and
        # the device memory it left behind (docs/tracing.md)
        with profiler.span("init.transform", always=True):
            self.refresh_params(params)
            host_sync(self.params)
        rings, ring_blocks = ring_geometry(model_config, self.config)
        self.state = StateManager(
            num_blocks=self.config.num_kv_blocks,
            block_size=self.config.kv_block_size,
            max_tracked=self.config.max_tracked_sequences,
            enable_prefix_cache=self.config.prefix_cache.enabled,
            cache_pool_blocks=self.config.prefix_cache.pool_blocks,
            # a model with recurrent state is given no prefix credit
            # (the index still fills: admissions that would have been
            # credited are counted, PrefixMatch.declined)
            credit_prefix=pools_can(model_config, "prefix_credit"),
            num_rings=rings, ring_blocks=ring_blocks,
        )
        self._cow_fn = None  # compiled (cache, src, dst) -> cache page copy
        # compiled block-table transfer pair (disaggregated serving):
        # gather a sequence's KV pages out / scatter them into another
        # engine's cache. Fixed [blocks_per_seq] index width, so ONE
        # program each regardless of sequence length.
        self._kv_gather = None
        self._kv_scatter = None
        # one RESERVED scratch block past the allocator's range: fused
        # write+attend RMWs every decode row's newest block, so padding
        # rows need a target that can never alias a live sequence
        self.pad_block = self.config.num_kv_blocks
        if self.config.kv_cache_dtype not in ("auto", "int8"):
            raise ValueError(
                f"kv_cache_dtype must be 'auto' or 'int8' "
                f"(got {self.config.kv_cache_dtype!r})")
        self.kv_quant = self.config.kv_cache_dtype == "int8"
        if model_config.n_state_layers:
            stats = jax.local_devices()[0].memory_stats() or {}
            refuse_pools_beyond(
                stats.get("bytes_limit"),
                sum(x.nbytes for x in jax.tree.leaves(self.params)),
                pool_bytes(model_config, self.config, dtype))
        with profiler.span("init.pool", always=True) as sp:
            self.cache = host_sync(M.init_cache(
                model_config, self.config.num_kv_blocks + 1,
                self.config.kv_block_size, dtype, mesh=self.mesh,
                kv_quant=self.kv_quant,
                state_slots=self.config.max_tracked_sequences,
                ring_pool_blocks=rings * ring_blocks + 1,
            ))
            sp.set(**M.kv_write_ids(self.cache, model_config, self.mesh,
                                    self._use_kernel))
            if not model_config.is_latent:
                # KV heads a pool's head holds side by side (paged_
                # attention.kv_pack), and heads held beyond the model's
                pack = M.kv_pool_pack(self.cache, model_config)
                sp.set(kv_pack=pack, kv_heads_padded=(
                    self.cache.k[0].shape[2] * pack
                    - M.kv_pool_shape(model_config)[0]))
            if rings:
                pools = pool_bytes(model_config, self.config, dtype)
                held = model_config.ring_layers
                sp.set(full_layers=len(held) - sum(held),
                       full_blocks=self.config.num_kv_blocks,
                       full_bytes=pools["kv"], window_layers=sum(held),
                       rings=rings, ring_blocks=ring_blocks,
                       ring_bytes=pools["ring"])
            # bytes one tracked sequence's slot holds over all the state
            # layers' pools (0 for a model without recurrent state)
            self.state_slot_bytes = sum(
                x.nbytes // x.shape[0]
                for x in jax.tree.leaves(self.cache.state))
            if model_config.n_kv_reader_layers:
                # who owns what: layers with pages of their own, with
                # rings, and those that walk a pool they do not own
                held = model_config.ring_layers
                sp.set(kv_owner_layers=len(held) - sum(held),
                       kv_ring_layers=sum(held),
                       kv_reader_layers=model_config.n_kv_reader_layers)
            if self.cache.state:
                sp.set(kv_layers=len(self.cache.k),
                       state_layers=len(self.cache.state),
                       state_slots=self.config.max_tracked_sequences,
                       state_bytes=sum(
                           x.nbytes for x in jax.tree.leaves(self.cache.state)))
        self._prefill_batch_fns: Dict[Tuple[int, int], Any] = {}
        # keyed (batch_width, unique_rows)
        self._decode_fns: Dict[Tuple[int, bool], Any] = {}
        # always-on S003 tracker (analysis/sanitizer.py): the serving
        # scheduler and warmup() record each dispatch's operand
        # signature per compiled-program name — a finding after warmup
        # means steady-state serving is recompiling (weak-type drift /
        # shape churn), the exact hazard AOT warmup exists to kill
        from ..analysis.sanitizer import RecompileTracker

        self.recompile_tracker = RecompileTracker()
        # MoE expert-utilization census (config.moe_census): per-expert
        # routed-token counts accumulated from every compiled MoE FFN
        # application. debug.callback fires on runtime threads, so the
        # accumulator is lock-guarded (the R003 race class).
        self._census_enabled = (self.config.moe_census
                                and model_config.n_experts > 0)
        self._census = np.zeros((max(model_config.n_experts, 1),),
                                np.int64)
        self._census_lock = threading.Lock()
        # per-bucket static footprints captured by warmup(footprint=True)
        # ({width: {peak_hbm_bytes, ...}} — analysis/costmodel.py)
        self.warmup_footprints: Dict[int, Dict[str, float]] = {}
        kv_bytes = sum(x.nbytes for x in self.cache.k + self.cache.v)
        if self.kv_quant:
            kv_bytes += sum(x.nbytes
                            for x in self.cache.k_scale + self.cache.v_scale)
        log_dist(
            f"inference engine: {self.config.num_kv_blocks} KV blocks x "
            f"{self.config.kv_block_size} tokens ({kv_bytes/2**30:.2f} GiB "
            f"{'int8' if self.kv_quant else str(dtype.__name__ if hasattr(dtype, '__name__') else dtype)} cache), "
            f"max_batch {self.config.max_batch_size}, "
            f"decode_impl {self.resolved_impl}",
            ranks=[0],
        )

    @property
    def resolved_impl(self) -> str:
        """The RESOLVED serving implementation ('pallas' | 'xla') —
        what config.decode_impl='auto' picked on this backend."""
        return "pallas" if self._use_kernel else "xla"

    def expert_path(self, width: int) -> Optional[str]:
        """The expert path (M.expert_path: 'stream', 'grouped', 'scan' or
        'ragged') of a compiled program over `width` token rows, None for
        a dense model: asked with what the program's routed layers will see
        (the leaves' shapes and types after the step's own dequant and
        fetch, the resolved kernel choice, the mesh). What the
        scheduler counts engaged steps by and the set-up spans name."""
        if self.cfg.n_experts == 0:
            return None
        if width not in self._expert_paths:
            fetch = self._fetch_layer() or (lambda lp, dep, li: lp)
            n_dense = len(self.params.get("dense_layers", ()))
            # the first routed layer (the first of `layers`, but where a
            # layer is one mixer: the first 'experts' layer)
            li = next(i for i, lp in enumerate(self.params["layers"])
                      if "w_router" in lp)
            lp = jax.eval_shape(
                lambda p: fetch(self._dequant(p)["layers"][li], None,
                                n_dense + li),
                self.params)
            self._expert_paths[width] = M.expert_path(
                width, self.cfg, lp, self._use_kernel, self.mesh)
        return self._expert_paths[width]

    def carry_kernel(self, width: int) -> bool:
        """Whether a compiled step over `width` token rows runs every
        state layer's short convolution as the one-pass kernel
        (ops/pallas/conv_carry.py; M.decode_step's `carry` asks the same
        of the same shapes): the resolved kernel choice and carry_fits
        of each layer's pool of carried inputs. False for a model
        without recurrent state. What the scheduler counts
        state_carry_kernel_steps by."""
        if width not in self._carry_kernels:
            self._carry_kernels[width] = (
                bool(self.cache.state) and self._use_kernel and all(
                    M.carry_fits(width, self._dtype, pools[-1])
                    for pools in self.cache.state))
        return self._carry_kernels[width]

    def step_kernel(self, width: int) -> bool:
        """Whether a compiled step over `width` token rows advances
        every matrix a head carries (linear attention, state space)
        through its kind's step kernel and not the loop over rows in
        XLA (M._recur_rows asks the same of the same shapes): the
        resolved kernel choice and the kind's fit of each such layer's
        pool. False for a model without such layers. What the scheduler
        counts state_step_kernel_steps by."""
        if width not in self._step_kernels:
            cfg = self.cfg
            fits = [M._STEP_OF[kind][0](
                width, self.cache.state[cfg.state_index(li)][0])
                for li, kind in enumerate(cfg.layer_types or ())
                if kind in M._STEP_OF]
            self._step_kernels[width] = (
                bool(fits) and self._use_kernel and all(fits))
        return self._step_kernels[width]

    def refresh_params(self, params: Any) -> None:
        """(Re)point the served weight tree — the hybrid-engine shared-
        weights path (ref: runtime/hybrid_engine.py): after training
        steps, generation serves the updated arrays (quantized engines
        re-quantize). The tree is cast and converted to the SERVING
        layout (M.prepare: per-layer unstacked, fused GEMMs — see
        inference/model.py docstring) in one compiled transform.

        Offload engines stage LAYER BY LAYER instead: a bigger-than-HBM
        model must never materialize whole on device, so each layer is
        cast/fused/quantized in its own compiled transform whose outputs
        land directly in pinned_host (device HBM holds one layer
        transiently)."""
        if self._offload is not None:
            self.params = self._refresh_offload(params)
            return
        if self._host_tree_too_large_twice(params):
            # the compiled transform holds its input AND its output on
            # the device; a host tree of over half the device's memory
            # is laid out on the host instead (numpy views a layer, the
            # few fused projections made on the device) and sent once
            dtype = self._dtype
            cast = jax.tree.map(
                lambda x: x.astype(dtype)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
            self.params = jax.device_put(M.prepare(cast, self.cfg, fuse=True))
            return
        if self._prepare_fn is None:
            cfg, dtype = self.cfg, self._dtype
            fuse = self.mesh is None
            per_channel = self._per_channel
            qz = self._quantization

            def xform(p):
                cast = jax.tree.map(
                    lambda x: x.astype(dtype)
                    if jnp.issubdtype(x.dtype, jnp.floating) else x,
                    p,
                )
                prep = M.prepare(cast, cfg, fuse=fuse)
                if per_channel:
                    prep = M.quantize_prepared(prep, cfg)
                elif qz:
                    from .quantization import quantize_for_inference

                    prep = quantize_for_inference(prep, **qz)
                return prep

            self._prepare_fn = jax.jit(xform)
        prepared = self._prepare_fn(params)
        if self.mesh is not None:
            prepared = _shard_serving_params(prepared, self.cfg, self.mesh)
        self.params = prepared

    def _host_tree_too_large_twice(self, params: Any) -> bool:
        """Whether `params` is a plain tree of HOST arrays that the
        device could not hold twice over in the serving dtype (one
        device, no quantization: the cases refresh_params' compiled
        transform is not needed for). Read from the arrays and the
        device's own memory limit; a backend that states none (the
        CPU) says no."""
        leaves = jax.tree.leaves(params)
        if self.mesh is not None or self._quantization or self._per_channel \
                or not all(isinstance(x, np.ndarray) for x in leaves):
            return False
        stats = jax.local_devices()[0].memory_stats() or {}
        if "bytes_limit" not in stats:
            return False
        itemsize = jnp.dtype(self._dtype).itemsize
        nbytes = sum(x.size * (itemsize if jnp.issubdtype(
            x.dtype, jnp.floating) else x.dtype.itemsize) for x in leaves)
        return 2 * nbytes > stats["bytes_limit"] - stats.get("bytes_in_use", 0)

    def _layer_pspec_sharding(self, lp: Any, memory_kind: str):
        """Per-leaf NamedShardings for ONE prepared layer under the TP
        mesh — the same rules/packing _shard_serving_params uses
        (_layer_logical_specs + _leaf_sharding), restricted to a layer
        subtree, in the given memory kind."""
        from ..parallel import sharding as Sh
        from .quantization import ChannelQuantWeight, QuantizedWeight

        is_q = lambda x: isinstance(x, (QuantizedWeight, ChannelQuantWeight))
        specs = _layer_logical_specs(lp, self.cfg)
        shapes = jax.tree.map(
            lambda leaf: leaf.q.shape if is_q(leaf) else leaf.shape,
            lp, is_leaf=is_q)
        pspecs = Sh.tree_logical_to_mesh(specs, Sh.make_rules(), self.mesh,
                                         shapes=shapes)
        return jax.tree.map(
            lambda ps, leaf: _leaf_sharding(ps, leaf, self.mesh,
                                            memory_kind),
            pspecs, lp, is_leaf=lambda x: isinstance(x, P))

    def _refresh_offload(self, params: Any) -> Any:
        """Layer-at-a-time staging into the offload tier: pinned_host
        (cpu — per-device SHARDS under a TP mesh) or per-leaf NVMe files
        (nvme — inference/offload_store.py)."""
        cfg, dtype = self.cfg, self._dtype
        if self._quantization and not self._per_channel:
            raise NotImplementedError(
                "offload serving with GROUPWISE quantization would "
                "dequantize the whole tree on device each step; use "
                "per_channel int8 (streams codes, scales on output)"
            )
        nvme = self._offload["device"] == "nvme"
        host = jax.sharding.SingleDeviceSharding(
            jax.devices()[0], memory_kind="pinned_host")

        from .quantization import ChannelQuantWeight

        is_cq = lambda x: isinstance(x, ChannelQuantWeight)

        def cast(p):
            # quantized leaves pass through whole (their f32 scales must
            # NOT cast to the serving dtype)
            return jax.tree.map(
                lambda x: x if is_cq(x) else (
                    x.astype(dtype)
                    if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)
                    else jnp.asarray(x)),
                p, is_leaf=is_cq)

        per_channel = self._per_channel
        fuse = self.mesh is None  # TP keeps QKV/gate-up unfused

        def layer_xform(lp):
            lp = M.prepare_layer(cast(lp), cfg, fuse=fuse)
            if per_channel and not any(is_cq(v) for v in lp.values()):
                lp = M.quantize_layer(lp, cfg)
            return lp

        if self._layer_xform is None:
            # one compiled transform per layer; the result is parked to
            # pinned_host eagerly (in-jit host out_shardings is not
            # lowered on every backend), so HBM holds a single layer
            # transiently
            self._layer_xform = jax.jit(layer_xform)
            self._top_xform = jax.jit(
                lambda t: M.quantize_prepared(
                    {**cast(t), "layers": []}, cfg)
                if per_channel else cast(t))
        st = params["layers"]
        if isinstance(st, dict):  # training layout: stacked [L, ...]
            layer_dicts = ({name: w[l] for name, w in st.items()}
                           for l in range(cfg.n_layers))
        else:
            # per-layer list, or the lazy HF import's single-use
            # generator (import_external(lazy_layers=True))
            layer_dicts = st

        if nvme:
            from .offload_store import NvmeLayerStore

            if self._nvme_store is not None:
                # params refresh: reclaim the previous model's NVMe
                # footprint before staging the new one
                self._nvme_store.close()
            self._nvme_store = NvmeLayerStore(
                self._offload["path"], cfg.n_layers,
                n_threads=self._offload["n_threads"],
                block_size=self._offload["block_size"],
                read_ahead=self._offload["read_ahead"])

            def park(l, lp):
                # pull to host and release the device copy immediately
                lp_host = jax.tree.map(
                    lambda w: np.asarray(jax.device_get(w)), lp)
                self._nvme_store.stage_layer(l, lp_host)
                # the served tree carries NO arrays for this layer —
                # the step's io_callback materializes them per use,
                # selected by the static loop index
                return {}
        elif self.mesh is not None:
            def park(l, lp):
                sh = self._layer_pspec_sharding(lp, "pinned_host")
                return jax.tree.map(jax.device_put, lp, sh)
        else:
            def park(l, lp):
                return jax.tree.map(lambda w: jax.device_put(w, host), lp)

        layers = [park(l, self._layer_xform(lp))
                  for l, lp in enumerate(layer_dicts)]
        if len(layers) != cfg.n_layers:
            raise ValueError(
                f"offload staging got {len(layers)} layers for a "
                f"{cfg.n_layers}-layer model — an exhausted single-use "
                "lazy import generator (re-import for a second engine) "
                "or a pipeline-partitioned stack (merge partitions first)"
            )
        if nvme:
            self._nvme_store.finish_staging()
        top_in = {k: v for k, v in params.items() if k != "layers"}
        top = self._top_xform(top_in)
        if self.mesh is not None:
            top = _shard_serving_params({**top, "layers": []}, cfg,
                                        self.mesh)
        top.pop("layers", None)
        top["layers"] = layers
        return top

    def _fetch_layer(self):
        """In-jit offload-tier→HBM fetch for one layer's weights (None
        when weights are HBM-resident).

        The fetch is scheduling-barriered on the activations from TWO
        layers back: without the barrier XLA's scheduler hoists every
        layer's host stream (or NVMe callback) to the program start —
        for a bigger-than-HBM model that is an immediate OOM (observed
        on the 19 GiB 70B-width slice). The 2-layer window still
        overlaps layer l+1's stream with layer l's compute."""
        if self._offload is None:
            return None

        def barrier(lp, dep):
            if dep is None:
                return lp
            return jax.tree.map(
                lambda w: jax.lax.optimization_barrier((w, dep))[0], lp)

        if self._offload["device"] == "nvme":
            from jax.experimental import io_callback

            store = self._nvme_store

            def fetch(lp, dep=None, idx=None):
                # the layer entry carries no arrays; the STATIC loop
                # index selects the manifest row at trace time
                specs = store.layer_specs(idx)
                l = idx
                # the dep rides as a callback ARGUMENT: the runtime may
                # not start the read before the activations two layers
                # back exist, so reads stay inside the rolling window
                # (read_ahead submits the NEXT layers on each wait)
                token = (jnp.zeros((), jnp.int32) if dep is None
                         else jnp.sum(jnp.ravel(dep)[:1]).astype(jnp.int32))
                return io_callback(
                    lambda _tok, _l=l: store.read_layer(int(_l)),
                    specs, token)

            return fetch

        if self.mesh is not None:
            def fetch(lp, dep=None, idx=None):
                lp = barrier(lp, dep)
                # shardings recomputed from leaf names+shapes at trace
                # time: the same rules table that parked the shards
                sh = self._layer_pspec_sharding(lp, "device")
                return jax.tree.map(jax.device_put, lp, sh)

            return fetch

        dev_s = jax.sharding.SingleDeviceSharding(
            jax.devices()[0], memory_kind="device")

        def fetch(lp, dep=None, idx=None):
            lp = barrier(lp, dep)
            return jax.tree.map(lambda w: jax.device_put(w, dev_s), lp)

        return fetch

    # -- compiled-step caches -------------------------------------------
    def _prefill_batch_fn(self, bp: int, tp: int):
        """Compiled cross-prompt prefill for batch bucket bp x token
        bucket tp — ONE program runs all concurrent prompts (ref:
        inference/v2 ragged mixed-prefill batches; fixes the per-prompt
        TTFT pile-up under concurrent arrivals)."""
        key = (bp, tp)
        if key not in self._prefill_batch_fns:
            cfg, use_kernel, deq = self.cfg, self._use_kernel, self._dequant
            mesh = self.mesh
            fetch = self._fetch_layer()

            census = self._census_cb()

            def step(params, cache, tokens, n_real, tables, *rows):
                return M.prefill_batch(
                    deq(params), cache, tokens, n_real, tables, cfg,
                    use_kernel, mesh=mesh, fetch_layer=fetch,
                    census_cb=census, **self._row_kwargs(rows),
                )

            # donated: the paged KV cache aliases the returned cache
            # (same PagedCache layout in and out); compile caches below
            # are only ever touched by the host dispatch thread
            self._prefill_batch_fns[key] = jax.jit(step, donate_argnums=(1,))
        return self._prefill_batch_fns[key]

    def _census_cb(self):
        """The per-application expert-census sink compiled into MoE
        programs (None when disabled — the compiled program then
        carries no callback at all)."""
        if not self._census_enabled:
            return None

        def add(counts):
            with self._census_lock:
                self._census += np.asarray(counts, np.int64)

        return add

    def moe_expert_census(self) -> np.ndarray:
        """[X] int64 cumulative per-expert routed-token counts (counts
        accumulate over layers and steps; config.moe_census)."""
        with self._census_lock:
            return self._census.copy()

    def _decode_fn(self, s: int, unique_rows: bool = False):
        """The compiled step over `s` rows: (params, cache, tokens,
        tables, ctx, *position_args, *state_args) -> (logits, cache). A
        model that generates by diffusion over blocks has the one
        program whatever `unique_rows` says: a block's rows share a
        table and see each other."""
        unique_rows = unique_rows and not self.cfg.block_length
        key = (s, unique_rows)
        if key not in self._decode_fns:
            cfg, use_kernel, deq = self.cfg, self._use_kernel, self._dequant
            mesh = self.mesh
            fetch = self._fetch_layer()

            census = self._census_cb()

            def step(params, cache, tokens, tables, ctx, *rows):
                where = {}
                if cfg.block_length:  # position_args' operand comes first
                    where["positions"], *rows = rows
                return M.decode_step(
                    deq(params), cache, tokens, tables, ctx, cfg, use_kernel,
                    mesh=mesh, unique_rows=unique_rows, fetch_layer=fetch,
                    census_cb=census, **where, **self._row_kwargs(rows),
                )

            # donated: the KV cache aliases the returned cache in-place
            self._decode_fns[key] = jax.jit(step, donate_argnums=(1,))
        return self._decode_fns[key]

    def decode_multi_fn(self, s: int, n_steps: int, sampling=None,
                        with_presence: bool = False):
        """Compiled fused decode (model.decode_multi) for batch width
        `s` — the one construction site that applies the engine's
        dequant wrapper, mirroring _decode_fn. sampling: a
        sampling.SamplingConfig compiled into the program (None =
        greedy); with_presence adds the [s, vocab] repetition-penalty
        bitmap to the carried state."""
        refuse_block_diffusion(self.cfg, "decode_multi")
        key = (s, n_steps, None if sampling is None else sampling.key(),
               with_presence)
        if not hasattr(self, "_decode_multi_fns"):
            self._decode_multi_fns = {}
        if key not in self._decode_multi_fns:
            cfg, use_kernel, deq = self.cfg, self._use_kernel, self._dequant
            mesh = self.mesh
            fetch = self._fetch_layer()
            census = self._census_cb()

            if sampling is None:
                def step(params, cache, tokens, tables, ctx, *rows):
                    return M.decode_multi(
                        deq(params), cache, tokens, tables, ctx, cfg,
                        n_steps=n_steps, use_kernel=use_kernel, mesh=mesh,
                        fetch_layer=fetch, census_cb=census,
                        **self._row_kwargs(rows),
                    )
            elif with_presence:
                def step(params, cache, tokens, tables, ctx, keys, step0,
                         presence, *rows):
                    return M.decode_multi(
                        deq(params), cache, tokens, tables, ctx, cfg,
                        n_steps=n_steps, use_kernel=use_kernel, mesh=mesh,
                        sampling=sampling, keys=keys, step0=step0,
                        presence=presence, fetch_layer=fetch,
                        census_cb=census, **self._row_kwargs(rows),
                    )
            else:
                def step(params, cache, tokens, tables, ctx, keys, step0,
                         *rows):
                    return M.decode_multi(
                        deq(params), cache, tokens, tables, ctx, cfg,
                        n_steps=n_steps, use_kernel=use_kernel, mesh=mesh,
                        sampling=sampling, keys=keys, step0=step0,
                        fetch_layer=fetch, census_cb=census,
                        **self._row_kwargs(rows),
                    )

            # donated: the KV cache aliases the carried cache output
            self._decode_multi_fns[key] = jax.jit(step, donate_argnums=(1,))
        return self._decode_multi_fns[key]

    def _sample_fn(self, scfg, with_presence: bool):
        """Compiled sampling epilogue over a [n, V] logits batch (the
        put()/prefill token-return path)."""
        from .sampling import sample_tokens

        key = (scfg.key(), with_presence)
        if not hasattr(self, "_sample_fns"):
            self._sample_fns = {}
        if key not in self._sample_fns:
            if with_presence:
                fn = lambda lg, keys, steps, pres: sample_tokens(
                    lg, scfg, keys, steps, presence=pres)
            else:
                fn = lambda lg, keys, steps: sample_tokens(
                    lg, scfg, keys, steps)
            self._sample_fns[key] = jax.jit(fn)
        return self._sample_fns[key]

    def _next_tokens_fn(self):
        """Compiled composition of a decode step's token input ON the
        device (the serving scheduler's look-ahead): row i takes row
        src[i] of the previous step's sampled tokens where src[i] >= 0
        and the host's toks[i] elsewhere, so a sampled token feeds the
        next step without a host round trip. One tiny program per
        (previous width, width) pair; warmup() compiles the pairs."""
        if not hasattr(self, "_next_tokens"):
            self._next_tokens = jax.jit(
                lambda prev, toks, src: jnp.where(
                    src >= 0, prev[jnp.maximum(src, 0)], toks))
        return self._next_tokens

    def position_args(self, positions: Optional[np.ndarray],
                      width: int = 0) -> tuple:
        """The operand a compiled step over rows takes before
        state_args' for a model that generates by diffusion over blocks:
        each row's position, apart from its visible length
        (model.decode_step; None: `width` pad rows). Empty for every
        other model, whose programs take no such operand."""
        if not self.cfg.block_length:
            return ()
        if positions is None:
            positions = np.zeros((width,), np.int32)
        return (self._dev(np.asarray(positions, np.int32)),)

    def block_ctx(self, positions: np.ndarray) -> np.ndarray:
        """What rows at `positions` see under the block-causal mask:
        through the end of their block."""
        B = self.cfg.block_length
        return (np.asarray(positions) // B + 1) * B

    def _block_unmask_fn(self, scfg, reveal: int):
        """Compiled sample-and-reveal epilogue of a denoising pass
        (sampling.block_unmask) over a [n, V] logits batch: `reveal`
        positions a block."""
        from .sampling import block_unmask

        key = (scfg.key(), reveal)
        if not hasattr(self, "_block_unmask_fns"):
            self._block_unmask_fns = {}
        if key not in self._block_unmask_fns:
            B, mask_id = self.cfg.block_length, self.cfg.mask_token_id
            self._block_unmask_fns[key] = jax.jit(
                lambda lg, toks, active, keys, steps: block_unmask(
                    lg, toks, active, scfg, keys, steps, block_length=B,
                    mask_id=mask_id, reveal=reveal))
        return self._block_unmask_fns[key]

    def _dev(self, x):
        """Host array → device, replicated over the serving mesh (so the
        compiled step's non-weight operands carry a committed sharding)."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(jnp.asarray(x), NamedSharding(self.mesh, P()))

    def state_args(self, slots: np.ndarray,
                   rings: Optional[np.ndarray] = None) -> tuple:
        """The operands a compiled step takes after the others for what
        its rows' sequences hold beside their pages, on the device: each
        row's state slot (a model with recurrent state), then each row's
        ring (a model of mixed windows); -1: a pad row, and `rings`
        None: all pad rows. Empty for every other model, whose programs
        take no such operand."""
        args = ()
        if self.cache.state:
            args += (self._dev(np.asarray(slots, np.int32)),)
        if self.state.num_rings:
            args += (self._dev(np.full(len(slots), -1, np.int32)
                               if rings is None
                               else np.asarray(rings, np.int32)),)
        return args

    def _row_kwargs(self, extra: tuple) -> Dict[str, Any]:
        """state_args' operands as a step function's keywords."""
        names = (("slots",) if self.cache.state else ()) + (
            ("rings",) if self.state.num_rings else ())
        return dict(zip(names, extra, strict=True))

    def _copy_block(self, src: int, dst: int) -> None:
        """Host-issued cache-page copy (the COW half of prefix caching):
        clone block src's K/V rows into block dst across every layer, in
        ONE compiled program reused for all copies (src/dst are traced
        scalars, so the first copy pays the only compile)."""
        if self._cow_fn is None:
            def cp(cache, s, d):
                # scale tiles are part of the page: a quantized COW
                # clones them with their codes
                return M.PagedCache(
                    k=[ck.at[d].set(ck[s]) for ck in cache.k],
                    v=[cv.at[d].set(cv[s]) for cv in cache.v],
                    k_scale=(None if cache.k_scale is None else
                             [ks.at[d].set(ks[s]) for ks in cache.k_scale]),
                    v_scale=(None if cache.v_scale is None else
                             [vs.at[d].set(vs[s]) for vs in cache.v_scale]),
                    state=cache.state,
                )

            # donated: cache aliases the returned PagedCache (in-place
            # page write, no second cache allocation)
            self._cow_fn = jax.jit(cp, donate_argnums=(0,))
        self.cache = self._cow_fn(self.cache, jnp.int32(src),
                                  jnp.int32(dst))

    def kv_bytes_per_token(self) -> int:
        """Resident KV bytes one token costs across all layers — codes
        (+ per-block scale tiles when quantized). The capacity number
        the ds_budget gate pins the int8/bf16 ratio on (>= 1.8x)."""
        per_tok = 0
        n_pools = 2 if self.cache.v else 1  # a latent cache has no V
        for l in range(len(self.cache.k)):
            # one token slot of one block: [KV, D] in the pool dtype
            per_tok += n_pools * self.cache.k[l][0, 0].nbytes
            if self.cache.quantized:
                per_tok += 2 * self.cache.k_scale[l][0, 0].nbytes
        return per_tok

    def prefix_cache_stats(self) -> Dict[str, float]:
        """Per-engine prefix-cache counters: lookup hits/misses,
        cached-token ratio, LRU evictions, COW copies (ragged.py
        StateManager.cache_stats) — plus the KV-pool residency
        numbers: kv_bytes_per_token (codes + scale tiles),
        kv_pool_bytes (whole resident pool incl. the scratch block),
        and kv_quantized (1.0 on the int8 pools)."""
        s = self.state.cache_stats()
        pool = sum(x.nbytes for x in self.cache.k + self.cache.v)
        if self.cache.quantized:
            pool += sum(x.nbytes
                        for x in self.cache.k_scale + self.cache.v_scale)
        s["kv_bytes_per_token"] = float(self.kv_bytes_per_token())
        s["kv_pool_bytes"] = float(pool)
        s["kv_quantized"] = 1.0 if self.cache.quantized else 0.0
        return s

    # -- paged-KV block transfer (prefill/decode disaggregation) ---------
    def _pages_travel(self) -> None:
        """Handoff and spill move pages between caches."""
        refuse_for_pools(self.cfg, "page_transfer")

    def _kv_gather_fn(self):
        """Compiled gather of [blocks_per_seq] cache pages across every
        layer: (cache, idx) -> ([L, B, bs, KV, D] k, same v). Pad slots
        index the reserved scratch block, so one program serves every
        sequence length."""
        self._pages_travel()
        if self._kv_gather is None:
            def gather(cache, idx):
                out = (jnp.stack([ck[idx] for ck in cache.k]),
                       jnp.stack([cv[idx] for cv in cache.v]))
                if cache.k_scale is not None:
                    # quantized pages travel with their scale tiles
                    out += (jnp.stack([ks[idx] for ks in cache.k_scale]),
                            jnp.stack([vs[idx] for vs in cache.v_scale]))
                return out

            self._kv_gather = jax.jit(gather)
        return self._kv_gather

    def _kv_scatter_fn(self):
        """Compiled scatter of transferred pages into this cache:
        (cache, idx, k, v) -> cache with rows idx overwritten. Pad rows
        land on the reserved scratch block (never a live page)."""
        self._pages_travel()
        if self._kv_scatter is None:
            if self.kv_quant:
                def scatter(cache, idx, k, v, ks, vs):
                    return M.PagedCache(
                        k=[ck.at[idx].set(k[l])
                           for l, ck in enumerate(cache.k)],
                        v=[cv.at[idx].set(v[l])
                           for l, cv in enumerate(cache.v)],
                        k_scale=[p.at[idx].set(ks[l])
                                 for l, p in enumerate(cache.k_scale)],
                        v_scale=[p.at[idx].set(vs[l])
                                 for l, p in enumerate(cache.v_scale)],
                    )
            else:
                def scatter(cache, idx, k, v):
                    return M.PagedCache(
                        k=[ck.at[idx].set(k[l])
                           for l, ck in enumerate(cache.k)],
                        v=[cv.at[idx].set(v[l])
                           for l, cv in enumerate(cache.v)],
                    )

            # donated: the live cache aliases the returned one (an
            # in-place page write, no second cache allocation)
            self._kv_scatter = jax.jit(scatter, donate_argnums=(0,))
        return self._kv_scatter

    def _pad_block_idx(self, blocks: List[int]) -> np.ndarray:
        idx = np.full((self.config.blocks_per_seq,), self.pad_block,
                      np.int32)
        idx[:len(blocks)] = blocks
        return idx

    def kv_payload_nbytes(self, n_blocks: int) -> int:
        """Size in bytes of an export_kv payload's K+V page stacks —
        codes plus, for quantized pools, the per-block scale tiles —
        for a sequence holding `n_blocks` blocks: the spill tier's
        budget pre-check (scheduler._try_spill), computed WITHOUT
        paying the compiled gather + readback. A quantized pool's
        payload is ~2x (bf16) / ~4x (f32) smaller, so the same
        pinned-host spill budget parks that many more victims."""
        per_page = int(self.cache.k[0][0].nbytes)
        if self.cache.quantized:
            per_page += int(self.cache.k_scale[0][0].nbytes)
        return ((2 if self.cache.v else 1) * len(self.cache.k) * n_blocks
                * per_page)

    def export_kv(self, uid: int) -> Dict[str, Any]:
        """Serialize one sequence's paged KV for a cross-engine handoff
        (the DistServe/Splitwise prefill->decode transfer): gather its
        block pages in ONE compiled program and read them back as host
        numpy. The payload is self-describing — seen_tokens, the token
        record (for the receiver's prefix index), and the [L, n_blocks,
        bs, KV, D] K/V page stacks — and import_kv() on any
        geometry-identical engine reconstructs the sequence exactly.
        The readback routes through utils.sync.serving_readback: it is
        a deliberate transfer-boundary sync, sized in KV pages (never
        logits), and the only host crossing in the handoff path."""
        act = fault_point("engine.export_kv", uid=uid)
        if act is not None and act.kind == "delay":
            time.sleep(act.value)  # a hung transfer (timeout-guard tests)
        seq = self.state.get(uid)
        if seq is None:
            raise KeyError(f"unknown sequence uid {uid}")
        # export only the blocks holding WRITTEN KV: a preemption
        # victim (spill path) reserves blocks for its full recompute
        # target ahead of writing them, and import_kv's extend
        # allocates by seen_tokens — the unwritten reservation tail
        # carries no data and must not ride the payload
        nb = min(len(seq.blocks),
                 -(-seq.seen_tokens // self.state.block_size))
        idx = self._pad_block_idx(seq.blocks[:nb])
        self.recompile_tracker.record("kv_transfer_gather", (idx,))
        gathered = self._kv_gather_fn()(self.cache, self._dev(idx))
        k, v = gathered[0], gathered[1]
        payload = {
            "seen_tokens": int(seq.seen_tokens),
            "n_blocks": nb,
            # the receiver must lay the pages into a dtype-identical
            # pool (import_kv rejects mixed-dtype fleets typed)
            "kv_dtype": str(self.cache.k[0].dtype),
            "token_ids": (list(seq.tokens[:seq.seen_tokens])
                          if seq.tokens_valid else None),
            "k": serving_readback(k)[:, :nb],
            "v": serving_readback(v)[:, :nb],
        }
        if self.cache.quantized:
            # per-block scale tiles ship WITH their code pages — and
            # under the digest below, so a flipped scale byte is caught
            # exactly like a flipped code byte
            ks, vs = gathered[2], gathered[3]
            payload["k_scale"] = serving_readback(ks)[:, :nb]
            payload["v_scale"] = serving_readback(vs)[:, :nb]
        # integrity envelope (resilience/integrity.py): blake2b over
        # every field's bytes+dtype+shape (sorted keys — the quantized
        # payload's scale tensors are covered too), attached at the
        # sender — import_kv verifies it before a single page is
        # scattered, so a bit flipped in transit or in the receiver's
        # DRAM falls back to the token-identical recompute path
        # instead of serving corrupted KV
        payload["digest"] = payload_digest(payload)
        return payload

    def import_kv(self, uid: int, payload: Dict[str, Any]) -> None:
        """Adopt a sequence whose KV pages arrive from export_kv() on a
        peer engine: allocate blocks, scatter the pages in ONE compiled
        program, and commit the token record (which also registers the
        transferred prefix in THIS engine's hash-chain index, so later
        prompts sharing it route here for free). Raises RuntimeError
        when the pool cannot fit the sequence — callers fall back to
        recompute (token-identical: draws key on seed/stream/position,
        not on which replica runs them). Raises HandoffIntegrityError
        BEFORE any allocation when the payload's digest envelope does
        not verify (an in-transit/DRAM bit flip) — same fallback."""
        self._pages_travel()
        fault_point("engine.import_kv", uid=uid)
        # chaos point 'handoff.payload': kind='corrupt' flips one bit
        # in the K/V page stacks of a COPY of the payload (the
        # in-transit SDC model) — the digest check below must catch it
        act = fault_point("handoff.payload", uid=uid)
        if act is not None and act.kind == "corrupt":
            payload, flips = corrupt_payload(
                payload, act.seed, act.invocation)
            log_dist(f"chaos: corrupted KV handoff payload of uid "
                     f"{uid} ({flips})", ranks=[0])
        if "digest" in payload and \
                payload_digest(payload) != payload["digest"]:
            raise HandoffIntegrityError(
                f"KV handoff payload of uid {uid} failed digest "
                "verification — discarding (recompute fallback)")
        own_dtype = str(self.cache.k[0].dtype)
        sent_dtype = payload.get("kv_dtype", own_dtype)
        if sent_dtype != own_dtype:
            # typed BEFORE any allocation (mirrors the heterogeneous-
            # fleet geometry rejection): a quantized payload cannot
            # land in a full-precision pool — the caller's recompute
            # fallback stays token-identical, silent dequantization
            # would not
            raise KvCacheDtypeError(
                f"KV payload of uid {uid} carries {sent_dtype} pages but "
                f"this engine's pool is {own_dtype} — mixed-kv-dtype "
                "fleets are rejected; recompute the sequence instead")
        n_tok = int(payload["seen_tokens"])
        nb = int(payload["n_blocks"])
        k, v = payload["k"], payload["v"]
        want = self.cache.k[0].shape[1:]  # (bs, KV, D) per page
        if tuple(k.shape[2:]) != want or k.shape[0] != self.cfg.n_layers:
            raise ValueError(
                f"KV payload geometry {k.shape} does not match this "
                f"engine's cache pages {(self.cfg.n_layers, nb) + want} — "
                "disaggregated replicas must be model/geometry-identical")
        if self.kv_quant and ("k_scale" not in payload
                              or "v_scale" not in payload):
            raise KvCacheDtypeError(
                f"int8 KV payload of uid {uid} is missing its per-block "
                "scale tensors — refusing to scatter scaleless codes")
        seq = self.state.extend(uid, n_tok)  # may raise: pool exhausted
        assert len(seq.blocks) == nb, (len(seq.blocks), nb)
        idx = self._pad_block_idx(seq.blocks)
        B = self.config.blocks_per_seq
        dt = self.cache.k[0].dtype
        kp = np.zeros((k.shape[0], B) + tuple(k.shape[2:]), dt)
        vp = np.zeros_like(kp)
        kp[:, :nb], vp[:, :nb] = k, v
        args = [self._dev(kp), self._dev(vp)]
        if self.kv_quant:
            ksp = np.ones((k.shape[0], B) + tuple(k.shape[2:4]), np.float32)
            vsp = np.ones_like(ksp)
            ksp[:, :nb], vsp[:, :nb] = payload["k_scale"], payload["v_scale"]
            args += [self._dev(ksp), self._dev(vsp)]
        self.recompile_tracker.record("kv_transfer_scatter", (idx,))
        self.cache = self._kv_scatter_fn()(
            self.cache, self._dev(idx), *args)
        self.state.commit(uid, n_tok, token_ids=payload["token_ids"])

    def warmup_kv_transfer(self) -> None:
        """Precompile + signature-baseline the handoff gather/scatter
        pair over scratch-only indices, so the first real handoff in
        steady-state serving compiles nothing (the same zero-recompile
        contract warmup() gives the decode grid)."""
        idx = self._pad_block_idx([])
        self.recompile_tracker.record("kv_transfer_gather", (idx,))
        gathered = self._kv_gather_fn()(self.cache, self._dev(idx))
        self.recompile_tracker.record("kv_transfer_scatter", (idx,))
        self.cache = self._kv_scatter_fn()(
            self.cache, self._dev(idx), *gathered)

    def export_parked_kv(self, limit: int) -> List[Dict[str, Any]]:
        """Serialize up to `limit` of this engine's hottest PARKED
        prefix chains (StateManager.parked_chains — MRU-first, full
        token provenance) as export_kv-format payloads, one per chain:
        seen_tokens covers exactly the chain's full blocks, the page
        stacks ride the SAME compiled gather as a live handoff, and
        the blake2b digest envelope is attached. A joining replica
        (inference/router.py add_replica warm boot) import_kv()s each
        payload onto a scratch uid and flushes it, which parks the
        pages AND registers the prefix chain in its own hash index —
        the new replica's first same-prefix prompt scores a cache hit
        before it has served anything. Chains longer than
        blocks_per_seq are truncated to the transfer window (the
        leading blocks still form a valid chain). Read-only on the
        donor: nothing is acquired, flushed, or evicted."""
        payloads: List[Dict[str, Any]] = []
        bs = self.state.block_size
        for tokens, blocks in self.state.parked_chains(limit):
            nb = min(len(blocks), self.config.blocks_per_seq)
            idx = self._pad_block_idx(blocks[:nb])
            self.recompile_tracker.record("kv_transfer_gather", (idx,))
            gathered = self._kv_gather_fn()(self.cache, self._dev(idx))
            payload = {
                "seen_tokens": nb * bs,
                "n_blocks": nb,
                "kv_dtype": str(self.cache.k[0].dtype),
                "token_ids": list(tokens[:nb * bs]),
                "k": serving_readback(gathered[0])[:, :nb],
                "v": serving_readback(gathered[1])[:, :nb],
            }
            if self.cache.quantized:
                payload["k_scale"] = serving_readback(gathered[2])[:, :nb]
                payload["v_scale"] = serving_readback(gathered[3])[:, :nb]
            payload["digest"] = payload_digest(payload)
            payloads.append(payload)
        return payloads

    # -- scheduling queries (ref: engine_v2.py query:158/can_schedule:184)
    def query(self, uid: int) -> Dict[str, Any]:
        seq = self.state.get(uid)
        seen = seq.seen_tokens if seq else 0
        cached_cap = (len(seq.blocks) * self.state.block_size - seen) if seq else 0
        return {
            "seen_tokens": seen,
            "free_blocks": self.state.free_blocks,
            "max_new_tokens": min(
                cached_cap + self.state.free_blocks * self.state.block_size,
                self.config.max_seq_len - seen,
            ),
            "prefix_cache": self.state.cache_stats(),
        }

    def can_schedule(self, uids: Iterable[int], lengths: Iterable[int]) -> bool:
        need = new = 0
        for uid, n in zip(uids, lengths):
            seq = self.state.get(uid)
            seen = seq.seen_tokens if seq else 0
            if seen + n > self.config.max_seq_len:
                return False
            have = len(seq.blocks) if seq else 0
            need += max(0, -(-(seen + n) // self.state.block_size) - have)
            new += seq is None
        # both pools: the paged blocks, and a ring a sequence not tracked yet
        return need <= self.state.free_blocks and (
            not self.state.num_rings or new <= self.state.free_rings)

    # -- per-row PRNG streams: key = fold_in(base(seed), uid), draw
    # -- counter = the sampled token's POSITION (seen_tokens at draw
    # -- time) — batch composition never affects a sequence's stream
    def _row_keys(self, seed: int, uids_arr: np.ndarray):
        if not hasattr(self, "_key_fn"):
            self._key_fn = jax.jit(
                lambda base, u: jax.vmap(
                    jax.random.fold_in, in_axes=(None, 0))(base, u)
            )
        return self._key_fn(jax.random.PRNGKey(seed),
                            jnp.asarray(uids_arr, jnp.uint32))

    # -- the engine step (ref: engine_v2.py put:107) ---------------------
    def put(
        self, uids: Sequence[int], tokens: Sequence[np.ndarray],
        return_tokens: bool = False,
        sampling: Optional[Dict[str, Any]] = None,
        seed: int = 0,
        presence: Optional[np.ndarray] = None,
        strict: bool = True,
        sampling_streams: Optional[Sequence[int]] = None,
        commit: bool = True,
    ) -> Any:
        """Run one engine step over a ragged batch.

        A model that generates by diffusion over blocks
        (cfg.block_length B): every run is whole blocks and starts on a
        block boundary (a new uid's prompt, a known uid's next blocks),
        and the logits of EVERY row of each run's last block come back,
        [len(uids), B, vocab]: a position's logits are the distribution
        of the token at that position. commit=False is a DENOISING
        pass: the rows' K/V are written and seen_tokens stays, so the
        same positions are fed again; the pass that leaves the block's
        K/V for good is an ordinary put(). Sampling a block is the
        scheduler's (return_tokens is refused).

        New uids carry their whole prompt; known uids carry exactly one
        continuation token. Returns next-token logits [len(uids), vocab]
        in input order — or, with return_tokens=True, SAMPLED token ids
        [len(uids)] int32: the sampling chain runs on device and only
        the ids cross to the host (the reference gathers logits /
        samples device-side too: inference/v2 logits_gather + the MII
        sampling contract; round 3 shipped [batch, vocab] fp32 per step).

        sampling: SamplingConfig kwargs (do_sample/temperature/top_k/
        top_p/repetition_penalty); greedy when omitted. seed + stream +
        position define the draw (deterministic, batch-independent);
        the stream id defaults to the uid, overridable per input row
        via sampling_streams (generate() passes its slot indices so a
        fixed seed reproduces regardless of which uids were free).
        presence: optional [len(uids), vocab] uint8 seen-token bitmap,
        required when repetition_penalty != 1 (the engine tracks counts,
        not token sets — generate() builds it from its own history).

        strict=True (default) raises BEFORE any state mutation when the
        batch's new prompts don't fit the KV pool (decode rows in the
        same call are not run either — re-issue after freeing).
        strict=False instead admits prompts per-uid while capacity
        lasts (the v2 scheduler's defer-individual-prompts behavior,
        ref: inference/v2/scheduling_utils.py) and returns
        (results, rejected_uids); rejected prompts' rows are zeros and
        their sequences untouched."""
        uids = list(uids)
        tokens = [np.atleast_1d(np.asarray(t, np.int32)) for t in tokens]
        if len(uids) != len(set(uids)):
            raise ValueError("duplicate uids in one put()")
        if len(uids) != len(tokens):
            raise ValueError("uids and tokens length mismatch")
        B = self.cfg.block_length
        if not commit and not B:
            raise ValueError(
                "commit=False is a denoising pass of a model that generates "
                "by diffusion over blocks (cfg.block_length); this model "
                "is causal")
        if B and return_tokens:
            raise ValueError(
                "a block's tokens are sampled and revealed by the scheduler "
                "(ServingScheduler); put() of a block-diffusion model "
                "returns logits")
        if B and any(len(t) % B for t in tokens):
            raise ValueError(
                f"a run fed to a model of block_length {B} is whole blocks: "
                f"got runs of {[len(t) for t in tokens]} tokens")

        prefills: List[Tuple[int, int, np.ndarray]] = []  # (pos, uid, toks)
        # chunked continuation (SplitFuse/ragged analog): an in-flight
        # sequence's multi-token chunk becomes len(chunk) "virtual decode
        # rows" sharing one block table with per-row increasing context —
        # the same compiled decode program serves single-token decodes and
        # continuation prefills (only the last row's logits are surfaced)
        decodes: List[Tuple[int, int, np.ndarray]] = []  # (pos, uid, chunk)
        n_rows = 0
        for i, (uid, toks) in enumerate(zip(uids, tokens)):
            if len(toks) == 0:
                raise ValueError(f"uid {uid}: empty token array")
            seq = self.state.get(uid)
            if seq is not None and seq.seen_tokens > 0:
                if seq.seen_tokens + len(toks) > self.config.max_seq_len:
                    raise ValueError(
                        f"uid {uid}: {seq.seen_tokens}+{len(toks)} tokens "
                        "> max_seq_len"
                    )
                if self.state.num_rings and len(toks) > self.config.kv_block_size:
                    # a ring is sized for a chunk of one block of rows
                    # (model.ring_blocks): more would write over
                    # positions its own first rows still see
                    raise ValueError(
                        f"uid {uid}: a chunk of {len(toks)} rows is more "
                        f"than a ring takes in one step (kv_block_size "
                        f"{self.config.kv_block_size}); split the put()")
                decodes.append((i, uid, toks))
                n_rows += len(toks)
            else:
                if len(toks) > self.config.max_seq_len:
                    raise ValueError(f"prompt of {len(toks)} > max_seq_len")
                if not commit:
                    raise ValueError(
                        f"uid {uid}: a denoising pass (commit=False) feeds a "
                        "block of a sequence already in the cache")
                prefills.append((i, uid, toks))
        if n_rows > self.config.max_batch_size:
            raise RuntimeError(
                f"{n_rows} decode rows > max_batch_size "
                f"{self.config.max_batch_size}; split the put()"
            )

        scfg = None
        if return_tokens:
            from .sampling import SamplingConfig

            scfg = SamplingConfig(**(sampling or {}))
            if scfg.needs_presence and presence is None:
                raise ValueError(
                    "repetition_penalty needs the seen-token bitmap: pass "
                    "presence=[len(uids), vocab] uint8 (generate() builds "
                    "it from its own history)"
                )
            tok_out = np.zeros((len(uids),), np.int32)
            stream_of = {u: (sampling_streams[i]
                             if sampling_streams is not None else u)
                         for i, u in enumerate(uids)}

        def sample_rows(logits_all, rows, row_uids, row_steps, row_pos):
            """Sample the bucketed logits [bucket, V] in place: real
            rows listed in `rows`; pad rows sample garbage that is
            never read. Working on the BUCKET keeps one compiled
            epilogue per bucket width instead of one per exact row
            count (r4 review finding)."""
            bucket = logits_all.shape[0]
            streams = np.zeros((bucket,), np.uint32)
            steps = np.zeros((bucket,), np.int32)
            streams[np.asarray(rows)] = [stream_of[u] for u in row_uids]
            steps[np.asarray(rows)] = row_steps
            keys = self._row_keys(seed, streams)
            if presence is not None and scfg.needs_presence:
                pres = np.zeros((bucket, presence.shape[1]), presence.dtype)
                pres[np.asarray(rows)] = presence[np.asarray(row_pos)]
                toks = self._sample_fn(scfg, True)(
                    logits_all, keys, self._dev(steps), self._dev(pres))
            else:
                toks = self._sample_fn(scfg, False)(logits_all, keys,
                                                    self._dev(steps))
            tok_out[np.asarray(row_pos)] = np.asarray(toks)[np.asarray(rows)]

        out = np.zeros((len(uids),) + ((B,) if B else ())
                       + (self.cfg.vocab_size,), np.float32)

        rejected: List[int] = []
        if prefills:
            if not self.can_schedule([u for _, u, _ in prefills],
                                     [len(t) for _, _, t in prefills]):
                if strict:
                    # nothing has been mutated yet (decodes run after) —
                    # the caller can free sequences and re-issue the put
                    raise RuntimeError(
                        "insufficient KV blocks for this prefill wave; "
                        "free sequences, split the put(), or use "
                        "strict=False for per-prompt admission"
                    )
                # per-prompt admission (ref: the v2 scheduler defers
                # individual prompts rather than failing the batch):
                # admit in arrival order while capacity lasts
                admitted = []
                for pos, uid, toks in prefills:
                    if self.can_schedule(
                        [u for _, u, _ in admitted] + [uid],
                        [len(t) for _, _, t in admitted] + [len(toks)],
                    ):
                        admitted.append((pos, uid, toks))
                    else:
                        rejected.append(uid)
                prefills = admitted
        if prefills and self.state.enable_prefix_cache:
            # prefix-cache admission: a prompt whose leading full blocks
            # match the content-addressed index SHARES those blocks and
            # prefills only the suffix — routed through the chunked-
            # continuation decode path (it already handles arbitrary
            # start positions against the paged cache), bounded by the
            # decode-row budget. Capacity was checked above WITHOUT
            # cache credit, so a degraded match always still fits.
            missed: List[Tuple[int, int, np.ndarray]] = []
            for pos, uid, toks in prefills:
                budget = self.config.max_batch_size - n_rows
                _, match = self.state.extend(
                    uid, len(toks), token_ids=toks, max_suffix_rows=budget,
                    align=B or 1)
                if match.n_cached > 0:
                    if match.cow is not None:
                        # shared full-match tail: clone the page before
                        # the recomputed last token writes into it
                        self._copy_block(*match.cow)
                    suffix = toks[match.n_cached:]
                    decodes.append((pos, uid, suffix))
                    n_rows += len(suffix)
                else:
                    missed.append((pos, uid, toks))
            prefills = missed
        if prefills:
            # prompts run as compiled WAVES (a solo prompt is a bp=1
            # wave — one code path, one compile cache), bucketed in both
            # tokens (max prompt in the wave) and batch (power of 2) and
            # capped so one put() cannot compile an unbounded (bp, tp)
            # activation footprint. Waves are GROUPED BY TOKEN BUCKET
            # (length-sorted): prompts sharing a power-of-two bucket run
            # together, so one long straggler no longer inflates every
            # short prompt's padding to its bucket (r3 advisor finding —
            # the compute cost of a wave is bp * bucket(max member)).
            prefills.sort(key=lambda pu: len(pu[2]))
            groups: Dict[int, List[Tuple[int, int, np.ndarray]]] = {}
            for pu in prefills:
                groups.setdefault(
                    _bucket(len(pu[2]), self.config.min_prefill_bucket), []
                ).append(pu)
            # largest power of two <= max_batch_size, so the bp bucket
            # can never exceed the configured ceiling
            cap = 1 << (self.config.max_batch_size.bit_length() - 1)
            waves = [g[w0:w0 + cap] for _, g in sorted(groups.items())
                     for w0 in range(0, len(g), cap)]
            for wave in waves:
                tp = _bucket(max(len(t) for _, _, t in wave),
                             self.config.min_prefill_bucket)
                bp = _bucket(len(wave), 1)
                toks_b = np.zeros((bp, tp), np.int32)
                n_real = np.zeros((bp,), np.int32)
                tables = np.zeros((bp, self.config.blocks_per_seq), np.int32)
                slots = np.full((bp,), -1, np.int32)
                rings = np.full((bp,), -1, np.int32)
                for row, (pos, uid, toks) in enumerate(wave):
                    n = len(toks)
                    seq = self.state.extend(uid, n)
                    slots[row], rings[row] = seq.slot, seq.ring
                    toks_b[row, :n] = toks
                    n_real[row] = n
                    tables[row] = self.state.block_table(
                        [uid], self.config.blocks_per_seq)[0]
                logits, self.cache = self._prefill_batch_fn(bp, tp)(
                    self.params, self.cache, self._dev(toks_b),
                    self._dev(n_real), self._dev(tables),
                    *self.state_args(slots, rings),
                )
                for row, (pos, uid, toks) in enumerate(wave):
                    self.state.commit(uid, len(toks), token_ids=toks)
                if return_tokens:
                    sample_rows(
                        logits,
                        list(range(len(wave))),
                        [uid for _, uid, _ in wave],
                        [len(toks) for _, _, toks in wave],
                        [pos for pos, _, _ in wave],
                    )
                else:
                    logits = np.asarray(logits)
                    for row, (pos, uid, toks) in enumerate(wave):
                        out[pos] = logits[row]

        if decodes:
            sp = _bucket(n_rows, 8)
            toks = np.zeros((sp,), np.int32)
            ctx = np.zeros((sp,), np.int32)  # pad rows: ctx 0 = inert
            where = np.zeros((sp,), np.int32)  # a block model's positions
            tables = np.full((sp, self.config.blocks_per_seq),
                             self.pad_block, np.int32)
            slots = np.full((sp,), -1, np.int32)
            rings = np.full((sp,), -1, np.int32)
            last_row: List[int] = []  # each chunk's final row index
            row = 0
            for pos, uid, chunk in decodes:
                base = self.state.get(uid).seen_tokens
                seq = self.state.extend(uid, len(chunk))
                table = self.state.block_table(
                    [uid], self.config.blocks_per_seq, self.pad_block,
                )[0]
                for j, tok in enumerate(chunk):
                    toks[row] = int(tok)
                    ctx[row] = base + j + 1
                    where[row] = base + j
                    tables[row] = table
                    slots[row], rings[row] = seq.slot, seq.ring
                    row += 1
                last_row.append(row - 1)
            if B:  # a row sees through the end of its block
                ctx[:n_rows] = self.block_ctx(where[:n_rows])
            # single-token rows are all DISTINCT sequences → the fused
            # write+attend kernel applies; multi-token chunks share a
            # table across rows and keep the separate write kernel
            unique = all(len(c) == 1 for _, _, c in decodes)
            logits, self.cache = self._decode_fn(sp, unique)(
                self.params, self.cache, self._dev(toks),
                self._dev(tables), self._dev(ctx),
                *self.position_args(where),
                *self.state_args(slots, rings),
            )
            for (pos, uid, chunk), lr in zip(decodes, last_row):
                if commit:
                    self.state.commit(uid, len(chunk), token_ids=chunk)
            if return_tokens:
                sample_rows(
                    logits,
                    last_row,
                    [uid for _, uid, _ in decodes],
                    [self.state.get(uid).seen_tokens
                     for _, uid, _ in decodes],
                    [pos for pos, _, _ in decodes],
                )
            else:
                logits_np = np.asarray(logits[:n_rows])
                for (pos, uid, chunk), lr in zip(decodes, last_row):
                    out[pos] = (logits_np[lr + 1 - B:lr + 1] if B
                                else logits_np[lr])
        result = tok_out if return_tokens else out
        if not strict:
            return result, rejected
        return result

    def flush(self, uid: int) -> None:
        """Free a sequence's KV blocks (ref: engine_v2.py flush:242)."""
        self.state.flush(uid)

    # -- AOT warmup: precompile the serving shape-bucket grid ------------
    def warmup(
        self,
        sampling: Optional[Dict[str, Any]] = None,
        widths: Optional[Sequence[int]] = None,
        chunked: bool = True,
        decode_chunks: Sequence[int] = (),
        presence: bool = False,
        footprint: bool = True,
        reveals: Sequence[int] = (1,),
    ) -> Dict[str, Any]:
        """Precompile the (bucket width x chunk) decode/sample grid so
        steady-state serving triggers ZERO recompiles (S003): every
        program a ServingScheduler can dispatch at these widths is
        compiled here, by EXECUTING it once over inert padding rows —
        ctx 0 rows drop their KV writes (XLA path) or write the
        reserved pad_block scratch (fused kernel), so the live cache is
        untouched and the jit call cache (not just an AOT artifact) is
        populated on every jax version.

        widths: decode-row buckets (default: powers of two from 8 up to
        bucket(max_batch_size)). chunked=True additionally compiles the
        shared-table variant mixed prefill chunks need. decode_chunks:
        fused multi-step depths (model.decode_multi) to warm per width.
        sampling/presence select the sampling epilogue variant; a model
        that generates by diffusion over blocks warms its
        sample-and-reveal epilogue instead, once for each of `reveals`
        (positions revealed a pass: ceil(block_length /
        denoising_steps), which the scheduler passes). Off a
        mesh and without presence the scheduler's look-ahead composes a
        step's tokens on the device (`_next_tokens_fn`): its program is
        warmed for every (previous width, width) pair of `widths`.
        footprint=True additionally AOT-compiles the per-width decode
        program once more for its static cost report (the jit call
        cache and the AOT artifact are separate compilations), filling
        `self.warmup_footprints[width]` — the per-bucket HBM numbers
        the serving scheduler validates its admission config against
        and feeds to the monitor.

        Every program is AWAITED here (so its execution is charged to
        it, not to whatever runs next) and leaves an always-kept span
        `warmup.program` (ids kind, width, unique, and kernel_traces:
        the kernel bodies it traced, one a distinct signature whatever
        the layers, ops/pallas kernel_jit) with children
        `warmup.trace` / `warmup.lower` / `warmup.compile` (the stages
        jax.monitoring reports) and `warmup.execute` (docs/tracing.md).

        Logs a one-line summary and one line per program, and returns
        {programs, seconds, widths, chunks, hbm_per_bucket, split,
        per_program}: `split` divides `seconds` into trace_s, lower_s,
        compile_s, execute_s and other_s (host work that is none of
        them), `per_program` does the same for each program."""
        import time as _time

        from ..analysis.costmodel import build_cost_report
        from .sampling import SamplingConfig

        scfg = SamplingConfig(**(sampling or {}))
        if widths is None:
            widths, w = [], 8
            top = _bucket(self.config.max_batch_size, 8)
            while w <= top:
                widths.append(w)
                w *= 2
        widths = [int(w) for w in widths]
        t0 = _time.perf_counter()
        per_program: List[Dict[str, Any]] = []
        stage_names = ("trace", "lower", "compile")

        def warm(kind: str, w: int, call, **ids):
            """Run one program, await it, and account for its time."""
            with profiler.span("warmup.program", always=True, kind=kind,
                               width=w, **ids) as sp, \
                    profiler.compile_spans("warmup") as stages:
                before = kernel_traces()
                # tracing and lowering are millions of Python calls: on
                # one chunk of the frame stack (utils/frames.py)
                out = on_one_chunk(call)
                traced = kernel_traces() - before
                sp.set(kernel_traces=traced)
                t_run = _time.perf_counter_ns()
                host_sync(out)
                t_done = _time.perf_counter_ns()
                profiler.record("warmup.execute", t_run, t_done,
                                always=True)
            split = profiler.split_ns(sp.t0_ns, sp.t1_ns, [
                (st, [(r.t0_ns, r.t1_ns) for r in stages
                      if r.name == f"warmup.{st}"])
                for st in stage_names] + [("execute", [(t_run, t_done)])])
            per_program.append(dict(
                {"kind": kind, "width": w, **ids,
                 "kernel_traces": traced,
                 "seconds": (sp.t1_ns - sp.t0_ns) * 1e-9},
                **{f"{k}_s": v * 1e-9 for k, v in split.items()}))
            return out

        rt = self.recompile_tracker
        use_sampler = not (scfg.greedy and not scfg.needs_presence)
        with_pres = bool(presence and scfg.needs_presence)
        V = self.cfg.vocab_size
        for w in widths:
            toks = np.zeros((w,), np.int32)
            ctx = np.zeros((w,), np.int32)
            tables = np.full((w, self.config.blocks_per_seq),
                             self.pad_block, np.int32)
            steps = np.zeros((w,), np.int32)
            keys = self._row_keys(0, np.zeros((w,), np.uint32))
            logits = None
            state = self.state_args(np.full((w,), -1, np.int32))
            # a routed model's programs name their expert path (one that
            # multiplies an expert by its own rows, the static rows of
            # its buffer), a model of two kinds of layer its counts of each
            path = self.expert_path(w)
            named = {"moe_expert_path": path} if path else {}
            if path == "grouped":
                named["moe_grouped_rows"] = grouped_rows(
                    w, self.cfg.moe_top_k, self.cfg.n_experts)
            if state:
                named.update(kv_layers=len(self.cache.k),
                             state_layers=len(self.cache.state))
                # which implementation advances the heads' matrices: a
                # silent fall-back to the loop over rows is seen here
                if set(self.cfg.layer_types or ()) & set(M._STEP_OF):
                    named["state_step"] = ("kernel" if self.step_kernel(w)
                                           else "xla")
            B = self.cfg.block_length
            # a block-diffusion model has the shared-table program alone
            for uniq in ((False,) if B else
                         (True, False) if chunked else (True,)):
                rt.record(f"serving_decode[w{w},u{int(uniq)}]",
                          (toks, tables, ctx))
                logits, self.cache = warm(
                    "decode", w, lambda: self._decode_fn(w, uniq)(
                        self.params, self.cache, self._dev(toks),
                        self._dev(tables), self._dev(ctx),
                        *self.position_args(None, w), *state),
                    unique=int(uniq), **named)
            if B:
                # the sample-and-reveal epilogue of a denoising pass, at
                # every count of positions a pass may reveal
                active = self._dev(np.zeros((w,), bool))
                for reveal in reveals:
                    rt.record(f"serving_unmask[w{w},r{reveal}]", (steps,))
                    warm("unmask", w,
                         lambda: self._block_unmask_fn(scfg, reveal)(
                             logits, self._dev(toks), active, keys,
                             self._dev(steps)), reveal=reveal)
            elif with_pres:
                pres = np.zeros((w, V), np.uint8)
                rt.record(f"serving_sample[w{w}]", (steps, pres))
                warm("sample", w, lambda: self._sample_fn(scfg, True)(
                    logits, keys, self._dev(steps), self._dev(pres)))
            else:
                rt.record(f"serving_sample[w{w}]", (steps,))
                warm("sample", w, lambda: self._sample_fn(scfg, False)(
                    logits, keys, self._dev(steps)))
            if self.mesh is None and not with_pres:
                # the look-ahead's token composition (never engaged on
                # a mesh or with the presence bitmap): the previous
                # step may have had any warmed width
                src = self._dev(np.full((w,), -1, np.int32))
                for wp in widths:
                    rt.record(f"serving_tokens[w{wp},w{w}]", (toks,))
                    warm("tokens", w, lambda: self._next_tokens_fn()(
                        self._dev(np.zeros((wp,), np.int32)),
                        self._dev(toks), src), source=wp)
            for C in decode_chunks:
                C = int(C)
                if C < 1:
                    continue
                rt.record(f"serving_fused[w{w},c{C}]",
                          (toks, tables, ctx, steps))
                fn = self.decode_multi_fn(
                    w, C, sampling=scfg if use_sampler else None,
                    with_presence=with_pres)
                args = [self.params, self.cache, self._dev(toks),
                        self._dev(tables), self._dev(ctx)]
                if use_sampler:
                    args.append(keys)
                    args.append(self._dev(steps))
                    if with_pres:
                        args.append(self._dev(np.zeros((w, V), np.uint8)))
                args += state
                _, _, self.cache, _ = warm("fused", w, lambda: fn(*args),
                                           chunk=C, **named)
            if footprint:
                with profiler.span("warmup.footprint", always=True, width=w):
                    rep = build_cost_report(self.compiled_decode(w),
                                            label=f"serving_decode[w{w}]")
                if rep is not None:
                    self.warmup_footprints[w] = {
                        "peak_hbm_bytes": float(rep.peak_hbm_bytes),
                        "arg_bytes": float(rep.arg_bytes),
                        "temp_bytes": float(rep.temp_bytes),
                        "comm_bytes": float(rep.comm_bytes),
                        # schedule-aware S009 projection per bucket
                        # (analysis/schedule.py): the AOT step-time the
                        # ds_schedule gate pins for the decode buckets
                        "step_time_us": float(rep.step_time_s * 1e6),
                        "exposed_comm_us": float(
                            rep.exposed_comm_s * 1e6),
                    }
        dt = _time.perf_counter() - t0
        n = len(per_program)
        split = {f"{k}_s": sum(pp[f"{k}_s"] for pp in per_program)
                 for k in stage_names + ("execute",)}
        split["other_s"] = dt - sum(split.values())
        fp = self.warmup_footprints
        fp_note = (f", peak {max(f['peak_hbm_bytes'] for f in fp.values()) / 2**20:.0f} MiB"
                   if fp else "")
        log_dist(
            f"serving warmup: {n} compiled programs (decode widths "
            f"{widths}{' +chunked' if chunked else ''}, fused depths "
            f"{[int(c) for c in decode_chunks]}, "
            f"sampling={'on' if use_sampler else 'greedy'}) in {dt:.1f}s"
            f"{fp_note}: " + " ".join(
                f"{k} {v:.2f}" for k, v in split.items()),
            ranks=[0],
        )
        for pp in per_program:
            log_dist("serving warmup program: " + " ".join(
                f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
                for k, v in pp.items()), ranks=[0])
        return {"programs": n, "seconds": dt, "widths": widths,
                "chunks": [int(c) for c in decode_chunks],
                "hbm_per_bucket": {
                    w: f["peak_hbm_bytes"] for w, f in sorted(fp.items())},
                "split": split, "per_program": per_program}

    def compiled_decode(self, width: int, unique_rows: bool = True):
        """AOT-compiled decode program serving dispatches at this bucket
        width (unique_rows=False: the shared-table variant mixed prefill
        chunks run), lowered over inert padding rows — the inspectable
        artifact behind warmup's footprints and chip_smoke's
        which-kernels-compiled check. A separate compilation from the
        jit call cache (the persistent compile cache dedupes them)."""
        toks = np.zeros((width,), np.int32)
        tables = np.full((width, self.config.blocks_per_seq),
                         self.pad_block, np.int32)
        return self._aot(self._decode_fn(width, unique_rows),
                         self._dev(toks), self._dev(tables), self._dev(toks),
                         *self.position_args(None, width),
                         *self.state_args(toks - 1))

    def compiled_prefill(self, bp: int, tp: int):
        """AOT-compiled whole-prompt prefill wave for batch bucket bp x
        token bucket tp (compiled_decode's twin)."""
        return self._aot(
            self._prefill_batch_fn(bp, tp),
            self._dev(np.zeros((bp, tp), np.int32)),
            self._dev(np.zeros((bp,), np.int32)),
            self._dev(np.zeros((bp, self.config.blocks_per_seq), np.int32)),
            *self.state_args(np.full((bp,), -1, np.int32)))

    def _aot(self, fn, *operands):
        import warnings

        # the donated-cache warning is S001 business, not ours
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return fn.lower(self.params, self.cache, *operands).compile()

    def sanitize_numerics(self, widths: Optional[Sequence[int]] = None):
        """Numerics sanitizer (analysis/numerics.py) over the serving
        decode buckets: per width, the compiled decode program is
        checked against the engine's serving dtype — accumulation
        downcasts (N001: an additive reduce below fp32 that jax's
        upcast-by-default semantics would never emit means an explicit
        override snuck into the model) — plus the determinism
        analyzer's D001 on the pre-optimization HLO (a mesh-sharded
        threefry draw in a decode bucket would make served tokens a
        function of the TP layout). Compile-time only; defaults to
        the warmed bucket widths (or the smallest bucket before
        warmup). Returns a merged analysis.SanitizerReport."""
        import warnings as _warnings

        from ..analysis.determinism import check_rng_discipline
        from ..analysis.numerics import check_program_numerics
        from ..analysis.report import merge_reports
        from ..profiling.hlo import preopt_hlo_text
        from ..runtime.precision import PrecisionPolicy, hlo_dtype_name

        serving = hlo_dtype_name(self._dtype)
        policy = PrecisionPolicy(
            compute=serving, master=None, grad_accum="f32",
            grad_comm=serving, loss_scaled=False)
        if widths is None:
            widths = sorted(self.warmup_footprints) or [
                min(8, _bucket(self.config.max_batch_size, 8))]
        reports = []
        for w in (int(w) for w in widths):
            toks = np.zeros((w,), np.int32)
            ctx = np.zeros((w,), np.int32)
            tables = np.full((w, self.config.blocks_per_seq),
                             self.pad_block, np.int32)
            # the donated-cache warning is S001 business, not ours
            with _warnings.catch_warnings():
                _warnings.simplefilter("ignore")
                lowered = self._decode_fn(w, True).lower(
                    self.params, self.cache, self._dev(toks),
                    self._dev(tables), self._dev(ctx),
                    *self.position_args(None, w),
                    *self.state_args(toks - 1))
                compiled = lowered.compile()
            reports.append(check_program_numerics(
                compiled, policy, lowered=lowered,
                label=f"serving_decode[w{w}]"))
            pre = preopt_hlo_text(lowered)
            if pre:
                reports.append(check_rng_discipline(
                    pre, label=f"serving_decode[w{w}]"))
        return merge_reports("serving_decode", *reports)

    # -- speculative (multi-token-per-stream) decoding -------------------
    def _verify_chunks(
        self, uids: Sequence[int], chunks: Sequence[np.ndarray],
    ) -> List[np.ndarray]:
        """Run each in-flight uid's candidate chunk through ONE decode
        program and return EVERY row's logits ([len(chunk), V] per uid)
        — the verification half of speculative decoding. KV for all
        candidate rows is written, but seen_tokens is NOT committed:
        the caller commits only the accepted prefix (rejected rows'
        slots are simply overwritten by the next real tokens)."""
        refuse_for_pools(self.cfg, "speculation")
        refuse_block_diffusion(self.cfg, "speculation")
        rows = sum(len(c) for c in chunks)
        if rows > self.config.max_batch_size:
            raise RuntimeError(
                f"{rows} verify rows > max_batch_size "
                f"{self.config.max_batch_size}")
        sp = _bucket(rows, 8)
        toks = np.zeros((sp,), np.int32)
        ctx = np.zeros((sp,), np.int32)
        tables = np.full((sp, self.config.blocks_per_seq),
                         self.pad_block, np.int32)
        spans: List[Tuple[int, int]] = []
        row = 0
        for uid, chunk in zip(uids, chunks):
            base = self.state.get(uid).seen_tokens
            self.state.extend(uid, len(chunk))
            table = self.state.block_table(
                [uid], self.config.blocks_per_seq, self.pad_block)[0]
            spans.append((row, row + len(chunk)))
            for j, tok in enumerate(chunk):
                toks[row] = int(tok)
                ctx[row] = base + j + 1
                tables[row] = table
                row += 1
        logits, self.cache = self._decode_fn(sp, False)(
            self.params, self.cache, self._dev(toks),
            self._dev(tables), self._dev(ctx),
        )
        logits_np = np.asarray(logits[:rows])
        return [logits_np[a:b] for a, b in spans]

    @staticmethod
    def _ngram_draft(hist: List[int], ngram: int, k: int) -> List[int]:
        """Prompt-lookup drafting: the most recent earlier occurrence of
        the last `ngram` tokens proposes the k tokens that followed it
        (no draft model — the sequence drafts itself)."""
        if k <= 0 or len(hist) <= ngram:
            return []
        pat = hist[-ngram:]
        for i in range(len(hist) - ngram - 1, -1, -1):
            if hist[i:i + ngram] == pat:
                return hist[i + ngram: i + ngram + k]
        return []

    def generate_speculative(
        self, prompts: Sequence[Sequence[int]], max_new_tokens: int = 32,
        eos_token_id: Optional[int] = None, ngram: int = 3,
        draft_len: int = 4, return_stats: bool = False,
    ) -> Any:
        """Greedy generation with prompt-lookup self-speculation.

        Each step feeds [committed_next, draft_1..draft_k] through ONE
        forward and accepts the longest greedy-consistent prefix — so a
        run of k accepted tokens streams the weights ONCE instead of k
        times. For full-offload serving the step cost IS the weight
        stream (88% of the host-link estimate, measured on an earlier
        setup; not re-measured), so effective tok/s scales with the
        mean accepted length — the policy lever for bigger-than-HBM
        models.
        Exact: the output equals plain greedy decoding token for token
        (worst case accepts 1 token/step = standard decode).
        ref: the reference ecosystem's prompt-lookup/self-speculative
        decoding (MII generation path); arXiv 2304.04487-class
        draft-and-verify with the sequence as its own draft model.

        return_stats=True additionally returns a dict of per-run
        counters: steps, draft/accepted token totals, mean accepted
        length, draft_acceptance_rate (accepted draft tokens over
        proposed draft tokens), and draft_collapsed_steps — steps where the shared
        verify-row budget (max_batch_size // n_live) forced per_seq=1
        so k=0 and speculation degenerated to one-token decode. The
        first such step also logs a warning, so a silently-serial
        "speculative" run is visible to callers.

        Since the serving-scheduler PR the request lifecycle (admission,
        immediate EOS retirement + flush, preemption under KV pressure)
        runs through inference/scheduler.py ServingScheduler in
        speculative mode; verification still dispatches through
        self._verify_chunks. Exactness is unchanged."""
        from .scheduler import ServingScheduler, ServingSchedulerConfig

        if len(prompts) > self.config.max_batch_size:
            raise ValueError(
                f"{len(prompts)} prompts > max_batch_size "
                f"{self.config.max_batch_size} (every live sequence "
                "needs at least one verify row per step)")
        sched = ServingScheduler(
            self,
            ServingSchedulerConfig(prefill_mode="wave", warmup=False),
            seed=0,
            speculative={"ngram": int(ngram),
                         "draft_len": int(draft_len)})
        rids = [sched.submit(list(p), max_new_tokens, eos_token_id,
                             stream=i)
                for i, p in enumerate(prompts)]
        sched.run()
        outs = [sched.finished[r].output for r in rids]
        if return_stats:
            # one authority for the derived rates (mean_accepted,
            # draft_acceptance_rate): the scheduler's spec_summary —
            # the same numbers the router reports per replica
            return outs, sched.spec_summary()
        return outs

    # -- sampling (v1 generate inherits full HF sampling; here the same
    # -- knobs applied host-side over put() logits, ref:
    # -- inference/engine.py:613 generate → HF LogitsProcessor chain)
    @staticmethod
    def sample_token(
        logits: np.ndarray,
        *,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        repetition_penalty: float = 1.0,
        seen_tokens: Sequence[int] = (),
        rng: Optional[np.random.Generator] = None,
    ) -> int:
        """One next-token draw from a [V] float logits row.

        temperature <= 0 is greedy argmax. top_k/top_p filter before the
        softmax draw (both may combine). repetition_penalty follows the
        CTRL rule the reference inherits from HF: a seen token's logit is
        divided by the penalty when positive, multiplied when negative.
        """
        row = np.asarray(logits, np.float64).copy()
        if repetition_penalty != 1.0 and len(seen_tokens):
            idx = np.unique(np.asarray(list(seen_tokens), np.int64))
            pos = row[idx] > 0
            row[idx] = np.where(pos, row[idx] / repetition_penalty,
                                row[idx] * repetition_penalty)
        if temperature <= 0.0:
            return int(np.argmax(row))
        row = row / temperature
        if top_k and 0 < top_k < row.size:
            kth = np.partition(row, -top_k)[-top_k]
            row[row < kth] = -np.inf
        if 0.0 < top_p < 1.0:
            order = np.argsort(row)[::-1]
            probs = np.exp(row[order] - row[order[0]])
            probs /= probs.sum()
            keep = np.cumsum(probs) - probs < top_p  # always keep top-1
            row[order[~keep]] = -np.inf
        probs = np.exp(row - row.max())
        probs /= probs.sum()
        # v1-parity host sampler: callers that want replayable draws
        # pass `rng`; bare calls are explicitly best-effort
        # ds-lint: ok D004 best-effort path, rng param is the replayable route
        gen = rng if rng is not None else np.random.default_rng()
        return int(gen.choice(row.size, p=probs))

    # -- convenience generation (v1 engine.generate parity) --------------
    def generate(
        self, prompts: Sequence[Sequence[int]], max_new_tokens: int = 32,
        eos_token_id: Optional[int] = None,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: int = 0,
        top_p: float = 1.0,
        repetition_penalty: float = 1.0,
        seed: Optional[int] = None,
        chunk: int = 8,
    ) -> List[List[int]]:
        """Continuous-batch generation; returns new tokens per prompt
        (ref: inference/engine.py generate:613).

        Rides FUSED multi-step decode: after the prefill, tokens are
        produced in compiled chunks of `chunk` steps — sampling
        (temperature/top-k/top-p/repetition-penalty, gumbel-max draw)
        runs INSIDE the decode program with per-sequence PRNG streams
        (key = fold_in(seed, uid), counter = token position), so the
        host sees only [chunk, batch] token ids per dispatch — never
        [batch, vocab] logits (round 3's per-step serving tax). The
        draw for a given (seed, uid, position) is independent of batch
        composition; a fixed seed reproduces the sequence exactly
        (tests/test_sampling.py replays it with a host oracle).

        top-p nucleus mass is computed over the top-256 candidates
        (sampling.SamplingConfig.cand_width) — exact whenever the
        nucleus fits, which at serving temperatures it does.

        uids are allocated disjoint from in-flight sequences so calling
        generate() never hijacks another caller's context.

        Since the serving-scheduler PR this is a thin wrapper over
        inference/scheduler.py ServingScheduler (prefill_mode='wave',
        decode_chunk=chunk): one control plane serves batch generation
        and online serving. Observable upgrades over the old loop: a
        sequence hitting EOS/length is FLUSHED at the iteration it
        finishes (its KV blocks rejoin the pool mid-batch instead of
        stranding until the last sequence drains), more prompts than
        max_batch_size queue instead of raising, and KV-block pressure
        preempts the youngest sequence for recompute instead of
        raising RuntimeError. Tokens are unchanged: draws are keyed by
        (seed, stream=slot, position), independent of scheduling."""
        from .scheduler import ServingScheduler, ServingSchedulerConfig

        # seed=None asks for a FRESH session seed; the drawn value then
        # becomes the session's (seed, stream, position) root, so
        # replay-with-the-returned-seed is exact
        # ds-lint: ok D004 fresh-seed request; replay threads the drawn seed
        seed_val = (int(np.random.default_rng().integers(2**31))
                    if seed is None else int(seed))
        blocks = bool(self.cfg.block_length)  # neither fused nor waved
        sched = ServingScheduler(
            self,
            ServingSchedulerConfig(
                decode_chunk=1 if blocks else max(1, int(chunk)),
                prefill_mode="chunked" if blocks else "wave",
                max_num_batched_tokens=max(
                    self.config.max_batch_size,
                    ServingSchedulerConfig().max_num_batched_tokens),
                warmup=False),
            sampling=dict(do_sample=do_sample, temperature=temperature,
                          top_k=top_k, top_p=top_p,
                          repetition_penalty=repetition_penalty),
            seed=seed_val)
        rids = [sched.submit(list(p), max_new_tokens, eos_token_id,
                             stream=i)
                for i, p in enumerate(prompts)]
        sched.run()
        return [sched.finished[r].output for r in rids]


def init_inference(
    params: Any,
    model_config: T.TransformerConfig,
    config: Optional[Dict[str, Any]] = None,
    dtype=jnp.bfloat16,
    quantization: Optional[Dict[str, Any]] = None,
    mesh: Optional[Mesh] = None,
    offload: Optional[Dict[str, Any]] = None,
) -> InferenceEngine:
    """Build the inference engine (ref: deepspeed/__init__.py
    init_inference:268 → InferenceEngine; config keys follow
    InferenceConfig). quantization={"bits": 8|4, "group_size": N}
    enables ZeRO-Inference weight-only PTQ.

    Tensor parallelism: pass an explicit mesh, config["tp_size"]=N, or
    the reference's spelling config["tensor_parallel"]={"tp_size": N}
    (ref: inference/config.py DeepSpeedTPConfig).

    Reference v1 config keys (ref: inference/config.py
    DeepSpeedInferenceConfig) are understood: `dtype` maps to the engine
    dtype ('int8' additionally enables weight PTQ), `max_out_tokens` →
    max_seq_len, kernel-injection/CUDA-graph knobs are no-ops on TPU
    (kernels are always the Pallas/XLA path), and `checkpoint` points to
    init_inference_from_hf."""
    cfg = dict(config or {})
    if "checkpoint" in cfg:
        raise NotImplementedError(
            "config['checkpoint']: load external checkpoints with "
            "init_inference_from_hf(path, ...) (HF safetensors/bin), or "
            "pass params restored via the TRAINING engine's "
            "load_checkpoint (runtime/engine.py) into init_inference"
        )
    if "injection_policy" in cfg or "injection_policy_tuple" in cfg:
        raise NotImplementedError(
            "injection_policy: TPU sharding is a rules table, not module "
            "surgery — override parallel/sharding.py rules instead"
        )
    dt = cfg.pop("dtype", None)
    if dt is not None:
        try:
            # dtype OBJECTS (jnp.bfloat16, np.float16, np.dtype(...)) —
            # the natural spellings in a JAX codebase
            name = np.dtype(dt).name
        except TypeError:
            # strings ('fp16') and torch.dtype reprs ('torch.float16')
            name = str(dt).split(".")[-1].lower()
        if name in ("int8",):
            # ZeRO-Inference weight-only PTQ is the int8 serving path
            quantization = quantization or {"bits": 8, "group_size": 128}
            dtype = jnp.bfloat16
        elif name in ("float16", "fp16", "half", "bfloat16", "bf16"):
            # fp16 serving maps to bf16 (TPU's 16-bit matmul format)
            dtype = jnp.bfloat16
        elif name in ("float32", "fp32", "float", "float64", "double"):
            # float64 spellings (np.dtype('float') → 'float64', torch
            # double) clamp to f32 — TPU has no f64 serving path
            dtype = jnp.float32
        else:
            raise ValueError(f"unsupported inference dtype {dt!r}")
    if "max_out_tokens" in cfg:
        mot = int(cfg.pop("max_out_tokens"))
        if "max_seq_len" in cfg and int(cfg["max_seq_len"]) != mot:
            raise ValueError(
                f"conflicting max_out_tokens ({mot}) and max_seq_len "
                f"({cfg['max_seq_len']}) in the inference config; drop one"
            )
        cfg["max_seq_len"] = mot
    for noop in ("replace_with_kernel_inject", "replace_method",
                 "enable_cuda_graph", "triangular_masking",
                 "use_triton", "triton_autotune"):
        if cfg.pop(noop, None):
            log_dist(
                f"inference config '{noop}' is a no-op on TPU (the "
                "Pallas/XLA kernels are always the serving path)",
                ranks=[0],
            )
    tp = cfg.pop("tensor_parallel", None)
    if tp is not None:
        if isinstance(tp, dict):
            size = int(tp.get("tp_size", 1))
            if not tp.get("enabled", True):
                size = 1
        else:
            size = int(tp)
        if "tp_size" in cfg and int(cfg["tp_size"]) != size:
            raise ValueError(
                f"conflicting tensor_parallel ({size}) and tp_size "
                f"({cfg['tp_size']}) in the inference config; drop one"
            )
        cfg["tp_size"] = size
    if "offload" in cfg:
        off = cfg.pop("offload")
        if offload is not None and offload != off:
            raise ValueError("conflicting offload in config and kwarg")
        offload = off
    icfg = InferenceConfig(**cfg)
    with profiler.span("init.inference", always=True) as sp:
        engine = InferenceEngine(model_config, params, icfg, dtype,
                                 quantization=quantization, mesh=mesh,
                                 offload=offload)
        if model_config.n_experts:  # a routed model names its experts
            sp.set(n_experts=model_config.n_experts,
                   moe_top_k=model_config.moe_top_k,
                   moe_expert_path=engine.expert_path(icfg.max_batch_size))
        if engine.cache.state:  # and a model of two kinds its layers
            sp.set(kv_layers=len(engine.cache.k),
                   state_layers=len(engine.cache.state))
        return engine


def init_inference_from_hf(
    path: str,
    config: Optional[Dict[str, Any]] = None,
    dtype=jnp.bfloat16,
    quantization: Optional[Dict[str, Any]] = None,
    mesh: Optional[Mesh] = None,
    offload: Optional[Dict[str, Any]] = None,
    **config_overrides,
) -> InferenceEngine:
    """Serve an HF-format checkpoint directory: import + init_inference
    (the build_hf_engine analog, ref: inference/v2/engine_factory.py:67).
    config_overrides adjust the derived TransformerConfig (e.g.
    attention_impl, use_flash).

    With offload={"device": "cpu"} the import is LAZY: layers stream
    from the checkpoint files one at a time straight into the
    pinned_host tier, so a checkpoint larger than free host-RAM
    headroom (let alone HBM) never materializes whole anywhere."""
    from ..utils.hf_checkpoint import import_external

    lazy = offload is not None or bool((config or {}).get("offload"))
    model_cfg, params = import_external(path, lazy_layers=lazy,
                                        **config_overrides)
    return init_inference(params, model_cfg, config, dtype,
                          quantization=quantization, mesh=mesh,
                          offload=offload)
