"""Decoder-only transformer model family (GPT-2-class and Llama-class).

The in-tree reference models for the framework, playing the role of the
reference's test/bench models (ref: tests/unit/simple_model.py and the
model_implementations zoo). TPU-first design decisions:

- pure-functional params dict (no module system) with *logical axis
  names* per leaf — the sharding-rules table (parallel/sharding.py) maps
  these to mesh axes, which is this framework's AutoTP
  (ref: module_inject/auto_tp.py).
- layers stacked on a leading 'layers' dim and executed with `lax.scan`
  → O(1) compile time in depth, XLA-friendly.
- Ulysses sequence parallelism is two sharding constraints around
  attention (seq-sharded ↔ head-sharded); XLA inserts the all-to-all
  pair that the reference does by hand (ref: deepspeed/sequence/layer.py
  _SeqAllToAll:44, DistributedAttention:60).
- activation checkpointing = jax.checkpoint policy on the scanned layer
  body (ref: runtime/activation_checkpointing/checkpointing.py:989).
- GQA (n_kv_heads < n_heads), rotary embeddings, RMSNorm, SwiGLU for the
  Llama variant; learned positions, LayerNorm, gelu for GPT-2.
"""

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ops.attention import causal_attention, uses_flash
from ..utils.profiler import EXPERT_BIAS_UPDATE, LAYER_STACK, ZERO_GATHER

DP = ("data", "zero", "expert")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # GQA; None = MHA
    d_model: int = 512
    d_ff: Optional[int] = None  # default: 4x (gpt2) or llama 8/3 rounding
    max_seq: int = 2048
    variant: str = "llama"  # "llama" | "gpt2"
    # "ulysses": seq↔head all-to-all resharding around local attention
    # (deepspeed/sequence/layer.py); "ring": KV rotation over the 'seq'
    # ring with online softmax (parallel/ring_attention.py) — better for
    # very long sequences or heads < seq-parallel degree.
    attention_impl: str = "ulysses"
    # Token-exact sliding-window attention (Mistral-class; Mixtral = this
    # + n_experts). 0 disables. Applies to the ulysses impl; serving
    # masks the paged decode path to the same window.
    sliding_window: int = 0
    # Per-layer window pattern cycling over layers (GPT-Neo class:
    # attention_types [["global","local"], L/2] → (0, 256)). 0 entries
    # are global. Overrides sliding_window; the pattern length must
    # divide n_layers (the scan groups layers by one pattern period).
    attention_window_pattern: Optional[Tuple[int, ...]] = None
    dropout: float = 0.0
    # QAT activation quantization (ref: compression/basic_layer.py
    # LinearLayer_Compress activation_quantization — there a forward hook
    # on every compressed linear; here symmetric per-tensor fake-quant
    # with straight-through gradients on the normed activations feeding
    # the attention and FFN projections). 0 disables.
    activation_quant_bits: int = 0
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    # jax.checkpoint policy: none | full | dots | save_attn |
    # save_attn_qkv | save_attn_mlp | save_attn_dots (save_attn* keep the
    # flash residuals so the backward skips the attention re-forward)
    remat: str = "none"
    # Pallas flash attention wherever kernels run (a TPU, or an explicit
    # interpret request — ops/pallas.kernels_runnable) and S >= 256;
    # the jnp reference otherwise
    use_flash: bool = True
    # flash tiling (ops/attention.causal_attention says what was measured)
    flash_block_q: int = 512
    flash_block_k: int = 1024
    # MoE (ref: deepspeed/moe/layer.py MoE:17 knobs). n_experts > 0 turns
    # every MLP into an expert-parallel MoE FFN.
    n_experts: int = 0
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 4
    moe_aux_loss_coef: float = 0.01
    moe_noisy_gate_policy: Optional[str] = None  # None | RSample | Jitter
    # Dropless (capacity-factor-free) routing (moe/dropless.py,
    # MegaBlocks-style): sort-by-expert grouped batching at EP=1, the
    # explicit dispatch/combine all-to-all frame under an 'expert' mesh
    # axis. No token is ever dropped; moe_capacity_factor/min_capacity
    # are ignored. A TRAINING flag: serving is capacity-free always and
    # picks its expert path from the call's shape
    # (inference/model.py expert_path).
    moe_dropless: bool = False
    # Whether the top-k combine weights are renormalised to sum to 1 (HF
    # norm_topk_prob). None = the rule every path had: k > 1
    # renormalises (GShard / Mixtral), k = 1 keeps the raw softmax mass
    # (Switch). False = the raw softmax mass of the chosen experts
    # whatever k (OLMoE: top-8 of 64 sums to well under 1).
    moe_norm_topk_prob: Optional[bool] = None
    # Router z-loss coefficient (ST-MoE): penalizes large router logits
    # so the fp32 gate softmax stays numerically sharp. 0 disables.
    moe_z_loss_coef: float = 0.0
    # PR-MoE residual form (ref: moe/layer.py:29 use_residual, arXiv
    # 2201.05596): each MoE FFN gains a DENSE residual expert and a
    # learned 2-way mixing coefficient —
    # out = moe(h) * c0 + dense(h) * c1, c = softmax(h @ w_coef + b).
    moe_use_residual: bool = False
    # Pipeline parallelism (ref: runtime/pipe/module.py PipelineModule).
    # >1 stores layers stage-partitioned [P, L/P, ...] and routes the
    # forward through runtime/pipe.pipeline_apply.
    pipeline_stages: int = 1
    # Interleaved (virtual-stage) pipelining: v > 1 stores layers
    # chunk-partitioned [v, P, L/(vP), ...] and runs the circular
    # schedule (runtime/pipe.pipeline_apply_circular) — warmup/drain
    # bubble shrinks ~v (the Megatron interleaved-1F1B analog).
    pipeline_virtual_stages: int = 1
    # Random-LTD (ref: data_pipeline/data_routing/basic_layer.py
    # RandomLayerTokenDrop:107): layers in [start, end) process only the
    # batch-supplied 'random_ltd' token subset; dropped tokens skip them
    # and are re-inserted in order. None disables.
    random_ltd_layer_range: Optional[Tuple[int, int]] = None
    # RoPE frequency scaling for long-context checkpoints (HF
    # rope_scaling): "none" | "linear" (positions / factor) | "llama3"
    # (NTK-style per-band wavelength remap, the Llama-3.x rule) | "yarn"
    # (SERVING ONLY: bands that turn more than rope_yarn_beta_fast times
    # over rope_original_max_seq keep their frequency, those that turn
    # fewer than rope_yarn_beta_slow times divide it by the factor, a
    # linear ramp between; cos and sin times rope_attention_factor).
    rope_scaling_type: str = "none"
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_seq: int = 8192
    rope_yarn_beta_fast: float = 32.0
    rope_yarn_beta_slow: float = 1.0
    rope_attention_factor: float = 1.0
    # SERVING ONLY. The scaling above is the FULL-attention layers'
    # alone: a layer with a window (window_for_layer > 0) rotates by the
    # plain rope_theta table (Mellum-class rope_parameters by layer type)
    rope_scaling_full_only: bool = False
    # Explicit head dim for families where head_dim != d_model / n_heads
    # (Mistral-Nemo / Gemma-class); None derives it.
    head_dim_override: Optional[int] = None
    # ---- model-family knobs (serving-zoo breadth: Falcon / OPT / Phi /
    # Qwen — ref: inference/v2/model_implementations/{falcon,opt,phi,
    # qwen,qwen_v2}/model.py; each family is a small delta on the ONE
    # functional family here, not a separate module zoo). The `variant`
    # stays the base preset: "llama" = rotary family, "gpt2" =
    # learned-positions family; None knobs inherit the preset.
    qkv_bias: Optional[bool] = None       # Qwen/Qwen2/Phi: q/k/v biases
    attn_out_bias: Optional[bool] = None  # bo (OPT/Phi yes, Qwen no)
    mlp_bias: Optional[bool] = None       # b_in/b_out
    activation: Optional[str] = None      # silu | gelu | relu (OPT) | relu2
    norm_type: Optional[str] = None       # rms | layer (Falcon/Phi: layer)
    gated_mlp: Optional[bool] = None      # SwiGLU pair vs single w_in
    # Falcon/Phi parallel form: x + attn(ln1 x) + mlp(ln2 x); shared_ln
    # feeds BOTH branches from ln1 (Falcon-7B / Phi) and drops ln2.
    parallel_residual: bool = False
    shared_ln: bool = False
    rotary_pct: float = 1.0               # Phi partial rotary
    lm_head_bias: bool = False            # Phi-2
    # ALiBi positional bias (Bloom / falcon-rw; ref:
    # module_inject/containers/bloom.py + the CUDA softmax alibi path).
    # Replaces rope AND learned positions: per-head slopes bias every
    # attention score by slope_h * (key_pos - query_pos).
    alibi: bool = False
    # Falcon's HF modeling applies the bias BEFORE the 1/sqrt(D) score
    # scaling (bloom adds it after) — falcon-rw checkpoints therefore
    # need slopes scaled by 1/sqrt(head_dim) to reproduce HF numerics.
    alibi_slope_scale: float = 1.0
    # GPT-J rope pairing: rotate_every_two (dims 2i/2i+1 form a rotation
    # pair) instead of the Llama/NeoX split-halves convention.
    rope_interleaved: bool = False
    # Bloom: LayerNorm over the embedding output before the first block
    embedding_layernorm: bool = False
    # QK-norm (OLMoE / OLMo-2): an RMSNorm over the WHOLE projected q
    # and k vectors (all heads together, n_heads * head_dim values) with
    # a learned scale of that length, before the head split's rope.
    qk_norm: bool = False
    # ---- latent attention + shared/held experts (DeepSeek-V2 class;
    # openPangu-Ultra-MoE). SERVING ONLY: init() makes the tree and
    # inference/model.py runs it; the training forward refuses it.
    # kv_lora_rank > 0 turns attention into multi-head latent attention:
    # queries through a q_lora_rank bottleneck with its own RMSNorm,
    # keys/values through ONE kv_lora_rank latent (RMSNorm'd) plus ONE
    # rotary key of qk_rope_head_dim shared by all heads; a head's query
    # and key are [nope (qk_nope_head_dim); rope (qk_rope_head_dim)],
    # its value v_head_dim. What serving caches is the latent and the
    # rotary key: kv_lora_rank + qk_rope_head_dim values a token a layer.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # a second RMSNorm on each sub-layer's OUTPUT, before the residual
    # add: x + N_post(f(N_pre(x))) (four norms a layer)
    sandwich_norm: bool = False
    # SERVING ONLY. The RMSNorm on each sub-layer's OUTPUT alone, none
    # before it: x + N(f(x)) (the OLMo 2 placement; two norms a layer,
    # under the names sandwich_norm gives its second pair:
    # `ln1_post_scale`, `ln2_post_scale`; no `ln1_scale` / `ln2_scale`)
    output_norm: bool = False
    # shared experts beside the routed ones: one dense MLP of
    # n_shared_experts * d_ff every token passes through, gated or not
    # as the routed experts are (is_gated: an ungated block has no
    # `ws_gate`); unweighted, or (shared_expert_gate) times
    # sigmoid(ws_sgate . h), a scalar a token
    n_shared_experts: int = 0
    shared_expert_gate: bool = False
    # router scores: "softmax" over the experts, or "sigmoid" of each
    # logit (then the chosen weights are divided by their sum when
    # moe_norm_topk_prob, and multiplied by routed_scaling_factor)
    moe_scoring: str = "softmax"
    routed_scaling_factor: float = 1.0
    # leading dense layers BEFORE the stacked ones: n_layers counts the
    # stacked (routed) layers alone, the model's depth is
    # n_dense_layers + n_layers. Their weights are top-level leaves
    # `dense_<name>` [n_dense_layers, ...] (the tree keeps ONE
    # homogeneous `layers` stack); their MLP is dense of width dense_d_ff
    n_dense_layers: int = 0
    dense_d_ff: Optional[int] = None
    # (start, count): the slice of the n_experts routed experts THIS
    # chip holds under expert parallelism. The router keeps all
    # n_experts outputs; the expert stacks hold `count`; pairs routed
    # elsewhere add nothing here (their chips add it). None: all held.
    experts_held: Optional[Tuple[int, int]] = None
    # ---- layers of several kinds (LFM2 / Qwen3-Next class hybrids).
    # SERVING ONLY. layer_types names the operator of each of the
    # model's `depth` layers, leading dense ones included, one of
    # LAYER_KINDS: "attention"; "conv", the gated short convolution:
    # [B; C; X] = W_in h, u = B * X, a causal depthwise convolution of
    # conv_kernel taps over u, out = W_out (C * conv); or
    # "linear_attention", the Gated DeltaNet (inference/model.py
    # _gated_delta_net): gdn_value_heads heads that each carry a
    # float32 [gdn_key_dim, gdn_value_dim] matrix rewritten by every
    # token (S <- exp(g) S; S <- S + k (beta (v - S^T k))^T), q and k
    # of gdn_key_heads heads repeated to the value heads, behind a
    # causal depthwise convolution of conv_kernel taps (then silu) over
    # the channels of [q; k; v]; or "state_space", the Mamba-2 mixer
    # (inference/model.py _state_space): ssm_heads heads of ssm_head_dim
    # that each carry a float32 [ssm_head_dim, ssm_state_dim] matrix
    # (S <- exp(dt A) S + (dt x) B^T, y = S C + D x: ONE scalar decay a
    # head a token, B and C one vector a token for all the heads of a
    # group, ssm_groups),
    # behind a causal depthwise convolution of conv_kernel taps WITH a
    # bias (then silu) over the channels of [x; B; C]; or "experts", the
    # routed block as a layer of its own (mixer_only); or
    # "selective_scan", the Mamba-1 mixer (ssm_dt_rank), and the two
    # kinds that read another layer, "cross_attention" and
    # "gated_memory" (differential_attention). A sequence carries
    # fixed-size state from token to token in a conv, linear-attention
    # or state-space layer, whatever its length (state_shapes), K/V
    # in the attention layers alone, and nothing in an experts layer.
    # The operators' weights are top-level stacks by kind (`conv_<name>`
    # [n conv layers, ...], `attn_<name>`, `gdn_<name>`, `ssm_<name>`);
    # `layers` (and `dense_<name>`) keep what every layer has, its norms
    # and FFN.
    layer_types: Optional[Tuple[str, ...]] = None
    conv_kernel: int = 0
    gdn_key_heads: int = 0
    gdn_value_heads: int = 0
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    # SERVING ONLY. The delta rule's write strength beta = 2 sigmoid(b),
    # in (0, 2), where it is sigmoid(b): I - beta k k^T then has the
    # eigenvalue 1 - beta in (-1, 1) along k (the publishers'
    # `allow_neg_eigval`)
    gdn_neg_eigval: bool = False
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_dim: int = 0
    # groups of a state-space layer's heads: a token has ONE B and ONE C
    # vector a group (ssm_heads / ssm_groups heads read each), and the
    # gated norm's statistic is a group's own
    ssm_groups: int = 1
    # tokens the whole-prompt scan of a state-space layer takes at a time
    ssm_chunk: int = 256
    # SERVING ONLY. The rank of a "selective_scan" layer's step: the
    # Mamba-1 mixer (arXiv:2312.00752; inference/model.py
    # _selective_scan), whose decay is a MATRIX: a sequence carries
    # h in R^{channels x ssm_state_dim}, float32, every (channel, state)
    # pair with a rate of its own, h <- exp(dt_c A_{c,n}) h + dt_c B_n x_c,
    # y_c = sum_n C_n h_{c,n} + D_c x_c, dt = softplus(W_dt r + b_dt)
    # through a bottleneck r of this rank, and NO norm behind the gate.
    # Its channels are ssm_heads x ssm_head_dim (ssm_inner), a "head"
    # here being one 128-lane row of channels of the state pool; its
    # convolution (conv_kernel taps, a bias, then silu) runs over x alone.
    ssm_dt_rank: int = 0
    # SERVING ONLY. Every layer is ONE sublayer, x + op(norm1 x), with
    # one norm: no FFN tail behind a mixer, and the routed block is a
    # layer of its own, the kind "experts" of LAYER_KINDS (its leaves a
    # top-level stack `moe_<name>` like any operator's; such a layer
    # holds neither K/V nor state). `layers` then holds the norm alone.
    mixer_only: bool = False
    # attention's output gate: W_q projects each head to [q; gate]
    # (leaf `wq_gate` beside `wq`), att <- att * sigmoid(gate) before W_o
    attn_output_gate: bool = False
    # QK-norm a HEAD at a time: the RMS statistic over each head's
    # head_dim values, ONE learned scale of head_dim shared by all the
    # heads of q (another for k). qk_norm must be set too.
    qk_norm_per_head: bool = False
    # a learned per-expert bias added to the router's scores for the
    # CHOICE of the top-k alone (the weights stay the unbiased scores):
    # leaf `expert_bias` [n_experts] in every routed layer
    moe_expert_bias: bool = False
    # TRAINING. What the step moves `expert_bias` by (loss-free
    # balancing, arXiv 2408.15664, as torchtitan writes it): after each
    # optimizer step, with c the step's count of tokens that chose each
    # expert, d = rate * sign(mean(c) - c), b <- b + d - mean(d). No
    # gradient, no moment, no decay (step_state_rule). 0: b stays.
    expert_bias_update_rate: float = 0.0
    # ---- Granite's scalars and its attention without positions.
    # SERVING ONLY. position_embedding "none": no rotary and no learned
    # positions, nothing (None: the variant's). The embedding's rows
    # times embedding_multiplier; both branches of every layer times
    # residual_multiplier before they are added (x + m op(norm1 x),
    # then x + m ffn(norm2 x)); the logits DIVIDED by logits_scaling;
    # attention's softmax scale (None: head_dim^-0.5).
    position_embedding: Optional[str] = None
    # rotary on the WINDOWED layers alone (window_for_layer > 0): a
    # full-attention layer of a model of mixed windows has no positions
    # at all (Trinity-class `afmoe`). Training and serving both honour
    # it (rope_at); it needs the rotary family and a window pattern.
    rope_windowed_only: bool = False
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: Optional[float] = None
    # ---- a decoder that reads another layer's cache (SambaY class,
    # arXiv:2507.06607). SERVING ONLY. Two more kinds of layer_types that
    # hold NOTHING: "cross_attention" (W_q and W_o alone: it attends,
    # causal and full, over the K/V that the layer `kv_donor` cached for
    # the sequence, this step's rows included) and "gated_memory"
    # (out = W_out (silu(W_in h) * m), m what the layer `memory_donor`'s
    # selective scan read for the SAME token, before its gate). Which
    # layers donate is DERIVED from layer_types, not set beside it.
    # differential_attention (arXiv:2410.05258), in every layer that
    # attends: the heads pair up in order, query pair p = heads
    # (2p, 2p + 1) reads the K/V pair p // (pairs of q a pair of K/V):
    # a1 = softmax(s q1 k1^T) V, a2 = softmax(s q2 k2^T) V over the
    # pair's V = [v1; v2] of 2 head_dim, s = head_dim^-0.5;
    # lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0(l),
    # lam0(l) = 0.8 - 0.6 exp(-0.3 l) by the layer's index in the whole
    # stack; o = RMSNorm(a1 - lam a2; a learned scale of 2 head_dim a
    # layer) * (1 - lam0(l)). A pair of K heads IS one K head of twice
    # the width in the order the projection leaves them: what a cache
    # holds of them is the serving model's (inference/model.py
    # kv_pool_shape).
    differential_attention: bool = False
    # ---- generation by diffusion over blocks (block-diffusion language
    # models, SDAR class). SERVING ONLY. block_length B > 0: position i
    # sees position j iff j // B <= i // B (bidirectional inside a block,
    # causal across blocks), the ONE difference of a layer from a causal
    # one; the logits at a position are the distribution of the token AT
    # that position, and a position still to be generated is fed as
    # mask_token_id. How a block is denoised (the passes, the reveal) is
    # the scheduler's (inference/scheduler.py, docs/serving_scheduler.md).
    # 0: a causal model, every other family.
    block_length: int = 0
    mask_token_id: int = 0

    def __post_init__(self):
        if self.layer_types is not None:
            object.__setattr__(self, "layer_types", tuple(self.layer_types))
            if len(self.layer_types) != self.depth or not set(
                    self.layer_types) <= set(LAYER_KINDS):
                raise ValueError(
                    f"layer_types names one of {LAYER_KINDS} for each of "
                    f"the {self.depth} layers (got {self.layer_types})")
            if self.state_layer_kinds and self.conv_kernel < 2:
                raise ValueError(
                    "layers that carry state (conv, linear_attention, "
                    "state_space, selective_scan) need conv_kernel >= 2")
            if "state_space" in self.layer_types and not (
                    self.ssm_heads > 0 and self.ssm_head_dim > 0
                    and self.ssm_state_dim > 0 and self.ssm_chunk > 0):
                raise ValueError(
                    "state_space layers need ssm_heads, ssm_head_dim, "
                    "ssm_state_dim and ssm_chunk")
            if "state_space" in self.layer_types and (
                    self.ssm_groups < 1 or self.ssm_heads % self.ssm_groups
                    or self.ssm_heads // self.ssm_groups % self.ssm_pack):
                raise ValueError(
                    f"ssm_groups {self.ssm_groups} must divide ssm_heads "
                    f"{self.ssm_heads} into groups of whole lane rows of "
                    f"the state pool ({self.ssm_pack} heads a row)")
            if ("experts" in self.layer_types) != (
                    self.mixer_only and self.n_experts > 0):
                raise ValueError(
                    "an 'experts' layer is the routed block of a model "
                    "whose layers are one sublayer each: it needs "
                    "mixer_only and n_experts, and they need it")
            if "linear_attention" in self.layer_types and not (
                    self.gdn_key_heads > 0 and self.gdn_key_dim > 0
                    and self.gdn_value_dim > 0 and self.gdn_value_heads > 0
                    and self.gdn_value_heads % self.gdn_key_heads == 0):
                raise ValueError(
                    "linear_attention layers need gdn_key_heads, "
                    "gdn_key_dim, gdn_value_dim and gdn_value_heads, a "
                    "multiple of gdn_key_heads")
            if "selective_scan" in self.layer_types and not (
                    self.ssm_heads > 0 and self.ssm_head_dim > 0
                    and self.ssm_state_dim > 0 and self.ssm_chunk > 0
                    and self.ssm_dt_rank > 0):
                raise ValueError(
                    "selective_scan layers need ssm_heads x ssm_head_dim "
                    "channels, ssm_state_dim, ssm_chunk and ssm_dt_rank")
            for reader, donor, what in (
                    ("cross_attention", self.kv_donor,
                     "a full-attention layer before the first of them, "
                     "whose K/V they read"),
                    ("gated_memory", self.memory_donor,
                     "a selective_scan layer before the first of them, "
                     "whose output they gate with")):
                if reader in self.layer_types and donor is None:
                    raise ValueError(f"{reader} layers need {what}")
            if "cross_attention" in self.layer_types and (
                    self.use_rope or self.use_learned_pos or self.alibi
                    or self.qk_norm or self.attn_output_gate
                    or self.mixer_only):
                raise NotImplementedError(
                    "cross_attention layers project no key: they are served "
                    "without positions (position_embedding 'none'), QK-norm, "
                    "an output gate, or layers of one sublayer each")
            if self.kv_lora_rank > 0:
                raise NotImplementedError(
                    "layers of two kinds with latent attention: a latent "
                    "pool beside state pools is not served")
        if self.mixer_only and (
                self.layer_types is None or self.n_dense_layers
                or self.parallel_residual or self.sandwich_norm
                or self.output_norm or self.moe_use_residual):
            raise ValueError(
                "mixer_only layers are named by layer_types, and have no "
                "leading dense layers, parallel or sandwich form, no norm "
                "on the output alone, nor a PR-MoE residual")
        if self.output_norm and (
                self.sandwich_norm or self.parallel_residual
                or self.norm_has_bias or self.kv_lora_rank > 0
                or self.residual_multiplier != 1.0):
            raise ValueError(
                "output_norm is the RMSNorm on each sub-layer's output "
                "ALONE: it excludes sandwich_norm (which has it beside the "
                "norm before), the parallel form, a norm with a bias, "
                "latent attention and a residual multiplier")
        if self.differential_attention and (
                self.kv_lora_rank > 0 or self.mixer_only or self.alibi
                or self.attn_output_gate or self.qk_norm
                or self.n_heads % 2 or self.kv_heads % 2
                or (self.n_heads // 2) % (self.kv_heads // 2)):
            raise ValueError(
                "differential_attention subtracts the maps of PAIRS of "
                f"heads: n_heads {self.n_heads} and kv_heads {self.kv_heads} "
                "must be even, a whole number of query pairs a pair of K/V; "
                "and it stands beside no latent attention, mixer_only, "
                "ALiBi, output gate or QK-norm")
        if self.shared_expert_gate and not self.n_shared_experts:
            raise ValueError("shared_expert_gate gates n_shared_experts: "
                             "set both")
        if self.attn_output_gate and (self.kv_lora_rank > 0
                                     or self.has_qkv_bias):
            raise NotImplementedError(
                "attn_output_gate with latent attention or q/k/v biases")
        if self.qk_norm_per_head and not self.qk_norm:
            raise ValueError("qk_norm_per_head is a form of qk_norm: set both")
        if self.block_length and (
                self.block_length < 0 or self.layer_types is not None
                or self.kv_lora_rank > 0 or self.sliding_window
                or self.alibi or self.differential_attention
                or not 0 <= self.mask_token_id < self.vocab_size):
            raise ValueError(
                "block_length is the block of a block-causal mask over "
                "plain attention layers (no layer_types, latent, windowed, "
                "ALiBi or differential attention), and mask_token_id an id "
                f"of the vocabulary (got block_length {self.block_length}, "
                f"mask_token_id {self.mask_token_id})")
        if self.position_embedding not in (None, "none"):
            raise ValueError(
                f"unknown position_embedding {self.position_embedding!r} "
                "(None: the variant's; 'none')")
        if self.rope_windowed_only and not (
                self.use_rope and self.attention_window_pattern is not None):
            raise ValueError(
                "rope_windowed_only rotates the windowed layers of a model "
                "that has rotary positions and an attention_window_pattern: "
                f"it contradicts position_embedding "
                f"{self.position_embedding!r}, variant {self.variant!r}, "
                f"alibi {self.alibi} or a model with no pattern")
        if self.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown moe_scoring {self.moe_scoring!r} (softmax|sigmoid)")
        if self.kv_lora_rank > 0 and not (
                self.q_lora_rank > 0 and self.qk_nope_head_dim > 0
                and self.qk_rope_head_dim > 0 and self.v_head_dim > 0
                and self.qk_rope_head_dim % 2 == 0):
            raise ValueError(
                "latent attention (kv_lora_rank > 0) needs q_lora_rank, "
                "qk_nope_head_dim, v_head_dim and an even qk_rope_head_dim")
        if self.experts_held is not None:
            start, count = self.experts_held
            if not (0 <= start and count >= 1
                    and start + count <= self.n_experts):
                raise ValueError(
                    f"experts_held {self.experts_held} is no slice of "
                    f"{self.n_experts} experts")
        if self.n_dense_layers and self.dense_d_ff is None:
            raise ValueError("n_dense_layers needs dense_d_ff")
        if self.rope_scaling_type not in ("none", "linear", "llama3",
                                          "yarn"):
            raise ValueError(
                f"unsupported rope_scaling_type '{self.rope_scaling_type}' "
                "(supported: none|linear|llama3|yarn)"
            )
        if self.pipeline_virtual_stages > 1 and self.pipeline_stages <= 1:
            raise ValueError(
                "pipeline_virtual_stages > 1 requires pipeline_stages > 1"
            )
        if self.remat not in REMAT_MODES:
            raise ValueError(
                f"unknown remat '{self.remat}' (expected one of {REMAT_MODES})"
            )
        if self.attention_impl not in ("ulysses", "ring"):
            raise ValueError(
                f"unknown attention_impl '{self.attention_impl}' "
                "(expected ulysses|ring)"
            )
        if self.sliding_window > 0 and self.attention_impl != "ulysses":
            raise ValueError(
                "sliding_window requires attention_impl='ulysses' (ring "
                "rotates full KV)"
            )
        if self.variant not in ("llama", "gpt2"):
            raise ValueError(f"unknown variant '{self.variant}'")
        if self.activation not in (None, "silu", "gelu", "gelu_exact",
                                   "relu", "relu2"):
            # "gelu" is the tanh approximation (HF gelu_new — GPT-2/Phi);
            # "gelu_exact" is erf GELU (Falcon's nn.GELU()); "relu2" the
            # squared relu, relu(x)^2 (an ungated MLP's)
            raise ValueError(f"unknown activation '{self.activation}'")
        if self.norm_type not in (None, "rms", "layer"):
            raise ValueError(f"unknown norm_type '{self.norm_type}'")
        if self.shared_ln and not self.parallel_residual:
            raise ValueError("shared_ln requires parallel_residual")
        if not (0.0 < self.rotary_pct <= 1.0):
            raise ValueError("rotary_pct must be in (0, 1]")
        if self.rotary_pct < 1.0 and self.variant == "gpt2":
            raise ValueError("rotary_pct applies to the rotary family")
        if self.lm_head_bias and self.tie_embeddings:
            raise ValueError("lm_head_bias requires an untied lm_head")
        if self.attention_window_pattern is not None:
            p = tuple(self.attention_window_pattern)
            if self.attention_impl != "ulysses":
                raise ValueError(
                    "attention_window_pattern requires "
                    "attention_impl='ulysses'")
            if not p or any(w < 0 for w in p):
                raise ValueError(
                    f"bad attention_window_pattern {p} (non-empty, "
                    "entries >= 0; 0 = global)")
            if self.n_layers % len(p) and not self.n_dense_layers:
                raise ValueError(
                    f"attention_window_pattern length {len(p)} must "
                    f"divide n_layers {self.n_layers}")
            # (behind leading dense layers the pattern is indexed by the
            # MODEL's layer and the stacked ones need not be whole
            # periods: the training scan steps over the whole ones and
            # unrolls the rest, forward_hidden)
            if self.pipeline_stages > 1 or self.random_ltd_layer_range:
                raise NotImplementedError(
                    "attention_window_pattern with pipeline/random-LTD "
                    "layer partitioning")
            # collapse to the MINIMAL period: HF imports arrive expanded
            # to n_layers entries (attention_types repeats sum to
            # num_layers), and the scan body unrolls len(pattern)
            # sublayers — a full-length pattern would unroll EVERY layer
            # (gpt-neo-2.7B: 32 bodies in one scan step). Cyclic
            # equality is preserved: q divides len(p) and p[i]==p[i%q].
            for q_len in range(1, len(p)):
                if len(p) % q_len == 0 and all(
                        p[i] == p[i % q_len] for i in range(len(p))):
                    object.__setattr__(self, "attention_window_pattern",
                                       p[:q_len])
                    break
        if self.alibi and self.attention_impl != "ulysses":
            raise ValueError(
                "alibi requires attention_impl='ulysses' (ring rotates KV "
                "without absolute-position bookkeeping for the bias)"
            )
        if self.alibi and self.rotary_pct < 1.0:
            raise ValueError("alibi replaces rotary embeddings entirely")
        if self.rope_interleaved and not self.use_rope:
            raise ValueError("rope_interleaved applies to the rotary family")

    # -- family-knob resolution (None -> variant preset) ---------------
    @property
    def use_rope(self) -> bool:
        return (self.variant != "gpt2" and not self.alibi
                and self.position_embedding != "none")

    @property
    def use_learned_pos(self) -> bool:
        return (self.variant == "gpt2" and not self.alibi
                and self.position_embedding != "none")

    @property
    def norm_kind(self) -> str:
        return self.norm_type or ("rms" if self.variant == "llama"
                                  else "layer")

    @property
    def norm_has_bias(self) -> bool:
        return self.norm_kind == "layer"

    @property
    def act_name(self) -> str:
        return self.activation or ("silu" if self.variant == "llama"
                                   else "gelu")

    @property
    def is_gated(self) -> bool:
        if self.gated_mlp is not None:
            return self.gated_mlp
        return self.variant == "llama"

    @property
    def has_qkv_bias(self) -> bool:
        if self.qkv_bias is not None:
            return self.qkv_bias
        return self.variant == "gpt2"

    @property
    def has_attn_out_bias(self) -> bool:
        if self.attn_out_bias is not None:
            return self.attn_out_bias
        return self.variant == "gpt2"

    @property
    def has_mlp_bias(self) -> bool:
        if self.mlp_bias is not None:
            return self.mlp_bias
        return self.variant == "gpt2"

    def window_for_layer(self, i: int) -> int:
        """Layer i's sliding window (0 = global attention)."""
        if self.attention_window_pattern is not None:
            return self.attention_window_pattern[
                i % len(self.attention_window_pattern)]
        return self.sliding_window

    @property
    def mixed_windows(self) -> bool:
        """Whether the attention layers see windows of more than one
        length (windowed and full layers mixed): serving then holds the
        windowed layers' K/V in a bounded ring a sequence
        (inference/model.py ring_blocks), the full layers' in pages."""
        return len({self.window_for_layer(li) for li in range(self.depth)
                    if self.layer_kind(li) == "attention"}) > 1

    @property
    def ring_layers(self) -> Tuple[bool, ...]:
        """For each layer that holds K/V, in the cache's order: whether
        serving holds it in rings (a windowed layer of a model of mixed
        windows) and not in pages."""
        return tuple(self.mixed_windows and self.window_for_layer(li) > 0
                     for li in range(self.depth)
                     if self.layer_kind(li) == "attention")

    @property
    def widest_window(self) -> int:
        return max(self.window_for_layer(li) for li in range(self.depth))

    def rope_scaled_at(self, li: int) -> bool:
        """Whether layer li rotates by the SCALED table (rope_inv_freq):
        every layer, or the full-attention layers alone."""
        return not (self.rope_scaling_full_only
                    and self.window_for_layer(li) > 0)

    def rope_at(self, li: int) -> bool:
        """Whether layer li rotates q and k at all: every layer of the
        rotary family, or its windowed layers alone
        (rope_windowed_only: a full layer then has no positions)."""
        return self.use_rope and not (
            self.rope_windowed_only and self.window_for_layer(li) == 0)

    @property
    def is_latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_dim(self) -> int:
        """Values serving caches for one token in one latent layer."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def serving_only(self) -> Tuple[str, ...]:
        """The fields set here that only inference/model.py computes:
        the training forward refuses a configuration that has any.
        (What it computes of the serving families' settings since PR 55,
        on the paths _training_refusal names: sandwich_norm,
        n_shared_experts, n_dense_layers, experts_held, moe_expert_bias,
        moe_scoring "sigmoid", attn_output_gate, embedding_multiplier.)"""
        return tuple(k for k in ("kv_lora_rank", "layer_types",
                                 "output_norm", "gdn_neg_eigval",
                                 "shared_expert_gate", "position_embedding",
                                 "attention_multiplier",
                                 "rope_scaling_full_only", "mixer_only",
                                 "ssm_dt_rank", "differential_attention",
                                 "block_length")
                     if getattr(self, k)) + tuple(
            k for k, plain in (("ssm_groups", 1),
                               ("residual_multiplier", 1.0),
                               ("logits_scaling", 1.0))
            if getattr(self, k) != plain) + (
            ("rope_scaling_type",) if self.rope_scaling_type == "yarn"
            else ())

    def layer_kind(self, li: int) -> str:
        """The operator of layer li of the `depth`: one of LAYER_KINDS."""
        return "attention" if self.layer_types is None else self.layer_types[li]

    def op_index(self, li: int) -> int:
        """Layer li's place among the layers of its own kind: which
        entry of its kind's weight stacks, and which K/V pool of the
        cache, is its."""
        if self.layer_types is None:
            return li
        return self.layer_types[:li].count(self.layer_types[li])

    def state_index(self, li: int) -> int:
        """State layer li's place among the layers that hold state (of
        whatever kind): which entry of the cache's state pools is its."""
        return sum(k in _STATE_LAYERS for k in self.layer_types[:li])

    @property
    def n_kv_layers(self) -> int:
        """Layers that hold K/V (or a latent row) in the paged cache."""
        return (self.depth if self.layer_types is None
                else self.layer_types.count("attention"))

    @property
    def n_state_layers(self) -> int:
        """Layers that hold fixed-size per-sequence state in a slot (a
        layer holds K/V, state or nothing, by its kind: not by depth)."""
        return len(self.state_layer_kinds)

    def _donor(self, reader: str, gives) -> Optional[int]:
        """The last layer that `gives(li)` before the first layer of
        kind `reader`; None where there is no such reader or donor."""
        types = self.layer_types or ()
        if reader not in types:
            return None
        before = [li for li in range(types.index(reader)) if gives(li)]
        return before[-1] if before else None

    @property
    def kv_donor(self) -> Optional[int]:
        """The layer whose K/V the cross_attention layers read: the
        full-attention layer before the first of them."""
        return self._donor("cross_attention", lambda li: (
            self.layer_types[li] == "attention"
            and self.window_for_layer(li) == 0))

    @property
    def memory_donor(self) -> Optional[int]:
        """The layer whose scan output the gated_memory layers gate
        with: the last selective_scan layer before the first of them."""
        return self._donor("gated_memory", lambda li: (
            self.layer_types[li] == "selective_scan"))

    @property
    def n_kv_reader_layers(self) -> int:
        """Layers that walk a K/V pool they do not own."""
        return (self.layer_types or ()).count("cross_attention")

    @property
    def gdn_conv_dim(self) -> int:
        """Channels the linear-attention layer's convolution runs over:
        [q; k; v] of all heads."""
        return (2 * self.gdn_key_heads * self.gdn_key_dim
                + self.gdn_value_heads * self.gdn_value_dim)

    @property
    def ssm_inner(self) -> int:
        """Values all the state-space heads hold a token: x, z and y."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the state-space layer's convolution runs over:
        [x; B; C], one B and one C a group of heads."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state_dim

    @property
    def gdn_state_shape(self) -> Tuple[int, ...]:
        """A sequence's matrices in a linear-attention layer: [Dk
        sublanes, Dv lanes] a value head, and where a head's values are
        no whole lane tiles as many heads side by side as make them
        whole (gdn_pack: two of 192 are 384 lanes, where a head alone
        would lie in 256 and move a third more), as
        ops/pallas/gated_delta.py lays them out."""
        pack = self.gdn_pack
        return (self.gdn_value_heads // pack, self.gdn_key_dim,
                pack * self.gdn_value_dim)

    @property
    def gdn_pack(self) -> int:
        """Value heads of a linear-attention layer that share a lane
        row of their matrices' pool: the fewest whose values together
        are whole 128-lane tiles, where the heads divide into such
        rows; else one."""
        pack = math.lcm(self.gdn_value_dim, 128) // self.gdn_value_dim
        return pack if self.gdn_value_heads % pack == 0 else 1

    @property
    def ssm_state_shape(self) -> Tuple[int, ...]:
        """A sequence's matrices in a state-space layer, as
        ops/pallas/ssm_state.py lays them out: TRANSPOSED, [ssm_state_dim
        sublanes, ssm_head_dim lanes] a head, and where a head is under
        a lane tile as many heads side by side as fill one (ssm_pack:
        two of 64)."""
        pack = self.ssm_pack
        return (self.ssm_heads // pack, self.ssm_state_dim,
                pack * self.ssm_head_dim)

    @property
    def ssm_pack(self) -> int:
        """Heads of a state-space layer that share a 128-lane row of
        their matrices' pool."""
        pack = max(1, 128 // self.ssm_head_dim)
        fills = pack * self.ssm_head_dim == 128 and self.ssm_heads % pack == 0
        return pack if fills else 1

    def conv_channels(self, kind: str) -> int:
        """Channels a state layer's depthwise convolution runs over, by
        its kind (_STATE_LAYERS)."""
        return getattr(self, _STATE_LAYERS[kind][0])

    def state_width(self, kind: str) -> int:
        """Values one sequence carries as the last conv_kernel - 1
        inputs, oldest first, of a state layer's depthwise
        convolution."""
        return (self.conv_kernel - 1) * self.conv_channels(kind)

    def state_shapes(self, kind: str):
        """What one sequence carries in one state layer of `kind`, as
        ((shape, dtype), ...) of its slot in each of the layer's pools
        (dtype None: the cache's): the convolution's carried inputs,
        and before them, for a kind whose heads carry a matrix
        (_STATE_LAYERS: 'linear_attention', 'state_space',
        'selective_scan'), the float32
        matrices. The carried inputs are [conv_kernel - 1, channels]
        with the channels folded into whole lanes where they are some,
        and MORE than a tile's 8 lane rows padded to whole (8, 128)
        tiles (zeros that nothing reads: 8,448 channels are 66 lane
        rows in a slot of 72; up to 8 rows are a block of the whole
        dimension and stay as they are): a slot is then whole
        (sublane, lane) tiles on the chip and one copy moves it
        (ops/pallas/conv_carry.py; Mosaic refuses to slice 66 of 72,
        and `carry_fits` such a pool)."""
        channels = self.conv_channels(kind)
        lanes = 128 if channels % 128 == 0 else channels
        rows = channels // lanes
        if lanes == 128 and rows > 8:
            rows = -(-rows // 8) * 8
        carried = ((self.conv_kernel - 1, rows, lanes), None)
        matrices = _STATE_LAYERS[kind][1]
        if matrices is None:
            return (carried,)
        return ((getattr(self, matrices), jnp.float32), carried)

    @property
    def state_layer_kinds(self) -> Tuple[str, ...]:
        """The kind of each layer that holds state, in their order."""
        return tuple(k for k in (self.layer_types or ())
                     if k in _STATE_LAYERS)

    @property
    def depth(self) -> int:
        """Layers a token passes: leading dense + stacked."""
        return self.n_dense_layers + self.n_layers

    @property
    def carries_census(self) -> bool:
        """Whether each routed layer hands its census out of the training
        forward (aux_width): where the step keeps state that reads it
        (`expert_bias`, moe_expert_bias) or a held share whose pairs a
        counter tells (experts_held)."""
        return self.n_experts > 0 and (
            self.moe_expert_bias or self.experts_held is not None)

    @property
    def n_experts_held(self) -> int:
        return (self.n_experts if self.experts_held is None
                else self.experts_held[1])

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.is_gated:
            d = int(self.d_model * 8 / 3)
            return ((d + 127) // 128) * 128
        return 4 * self.d_model

    def flops_per_token(self, seq_len: Optional[int] = None) -> float:
        """Train-step matmul FLOPs per token for MFU accounting:
        6*N (fwd+bwd over all params) + causal attention term
        6*L*S*E (QK^T and AV each contribute ~S*E fwd flops/token under
        the causal mask; backward doubles it)."""
        S = seq_len or self.max_seq
        n = param_count(self)
        return 6.0 * n + 6.0 * self.n_layers * S * self.d_model


def param_count(cfg: TransformerConfig) -> int:
    shapes = jax.tree.leaves(jax.eval_shape(lambda k: init(cfg, k), jax.random.PRNGKey(0)))
    return sum(int(jnp.prod(jnp.array(s.shape))) for s in shapes)


# ---------------------------------------------------------------------------
# params + logical specs
# ---------------------------------------------------------------------------

def _latent_attention_shapes(cfg: TransformerConfig):
    """The latent-attention leaves of one layer (names after the
    published checkpoints' q_a / q_b / kv_a / kv_b projections): the
    query bottleneck and its norm, the up-projection to every head's
    [nope; rope] query, the down-projection to [latent; shared rotary
    key], the latent's norm, the up-projection to every head's
    [nope key; value], and the output projection."""
    E, H = cfg.d_model, cfg.n_heads
    Rq, Rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "wq_a": ((E, Rq), ("embed", None)),
        "q_a_scale": ((Rq,), (None,)),
        "wq_b": ((Rq, H, Dn + Dr), (None, "heads", "head_dim")),
        "wkv_a": ((E, Rkv + Dr), ("embed", None)),
        "kv_a_scale": ((Rkv,), (None,)),
        "wkv_b": ((Rkv, H, Dn + Dv), (None, "heads", "head_dim")),
        "wo": ((H, Dv, E), ("heads", "head_dim", "embed")),
    }


def _layer_shapes(cfg: TransformerConfig, dense: bool = False
                  ) -> Dict[str, Tuple[Tuple[int, ...], Tuple]]:
    """name -> (shape-without-layer-dim, logical axes-without-layer-dim).
    dense: a LEADING dense layer of a model whose stacked layers are
    routed (cfg.n_dense_layers): the same attention and norms, a dense
    MLP of width cfg.dense_d_ff."""
    E, F = cfg.d_model, cfg.ff_dim
    # (a model whose norms stand on the sublayers' outputs alone,
    # cfg.output_norm, has none before them)
    shapes = {} if cfg.output_norm else {"ln1_scale": ((E,), ("embed",))}
    if cfg.mixer_only:
        # one sublayer a layer: its one norm, and nothing else here
        if cfg.norm_has_bias:
            shapes["ln1_bias"] = ((E,), ("embed",))
        return shapes
    if cfg.layer_types is None:
        # every layer's operator is attention: its leaves are the
        # layer's own (a model of two kinds keeps them in stacks by
        # kind beside `layers`, _operator_shapes)
        shapes.update(_operator_shapes(cfg, "attention"))
    if cfg.sandwich_norm or cfg.output_norm:
        shapes["ln1_post_scale"] = ((E,), ("embed",))
        shapes["ln2_post_scale"] = ((E,), ("embed",))
    if not cfg.shared_ln and not cfg.output_norm:
        shapes["ln2_scale"] = ((E,), ("embed",))
    X = 0 if dense else cfg.n_experts
    if dense:
        F = cfg.dense_d_ff
    if X > 0:
        shapes.update(_routed_shapes(cfg))
    else:
        shapes.update({
            "w_in": ((E, F), ("embed", "mlp")),
            "w_out": ((F, E), ("mlp", "embed")),
        })
        if cfg.is_gated:
            shapes["w_gate"] = ((E, F), ("embed", "mlp"))
    if cfg.norm_has_bias:
        shapes["ln1_bias"] = ((E,), ("embed",))
        if not cfg.shared_ln:
            shapes["ln2_bias"] = ((E,), ("embed",))
    if cfg.has_mlp_bias:
        if cfg.experts_held is not None:
            raise NotImplementedError("mlp biases on a held share of experts")
        shapes["b_in"] = (((X, F) if X > 0 else (F,)),
                          (("expert", "expert_mlp") if X > 0 else ("mlp",)))
        shapes["b_out"] = (((X, E) if X > 0 else (E,)),
                           (("expert", "embed") if X > 0 else ("embed",)))
    return shapes


def _routed_shapes(cfg: TransformerConfig):
    """The leaves of one routed block: the FFN of a routed layer, or
    the operator of an 'experts' layer (cfg.mixer_only). Same form as
    _layer_shapes."""
    E, F, X = cfg.d_model, cfg.ff_dim, cfg.n_experts
    # Expert-stacked FFN weights: leading experts dim shards over the
    # 'expert' mesh axis; the expert-hidden dim may additionally shard
    # over 'model' (ref: moe/experts.py local expert bundle — here one
    # stacked array instead of a ModuleList).
    # the router spans every expert; the stacks hold this chip's
    # share of them (cfg.experts_held; all of them when None)
    Xh = cfg.n_experts_held
    shapes = {
        "w_router": ((E, X), ("embed", None)),
        **({"expert_bias": ((X,), (None,))}
           if cfg.moe_expert_bias else {}),
        "w_in": ((Xh, E, F), ("expert", "embed", "expert_mlp")),
        "w_out": ((Xh, F, E), ("expert", "expert_mlp", "embed")),
    }
    if cfg.is_gated:
        shapes["w_gate"] = ((Xh, E, F), ("expert", "embed", "expert_mlp"))
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        shapes.update({
            **({"ws_gate": ((E, Fs), ("embed", "mlp"))}
               if cfg.is_gated else {}),
            "ws_in": ((E, Fs), ("embed", "mlp")),
            "ws_out": ((Fs, E), ("mlp", "embed")),
            **({"ws_sgate": ((E, 1), ("embed", None))}
               if cfg.shared_expert_gate else {}),
        })
    if cfg.moe_use_residual:
        # PR-MoE: dense residual expert + mixing coefficient
        shapes.update({
            "wr_in": ((E, F), ("embed", "mlp")),
            "wr_out": ((F, E), ("mlp", "embed")),
            "w_coef": ((E, 2), ("embed", None)),
            "b_coef": ((2,), (None,)),
        })
        if cfg.is_gated:
            shapes["wr_gate"] = ((E, F), ("embed", "mlp"))
        if cfg.has_mlp_bias:
            shapes["br_in"] = ((F,), ("mlp",))
            shapes["br_out"] = ((E,), ("embed",))
    return shapes


def _operator_shapes(cfg: TransformerConfig, kind: str):
    """The leaves of one layer's OPERATOR, by its kind: attention
    (plain or latent, with its QK-norm scales and biases), the gated
    short convolution (`conv_in` to [B; C; X], the depthwise `conv_taps`
    [channel, tap], oldest tap first, and `conv_out`), the Gated
    DeltaNet, the state-space mixer, or the routed block of a model
    whose layers are one sublayer each. Same form as _layer_shapes."""
    E, H, KV, D = cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim
    if kind == "experts":
        if cfg.has_mlp_bias:
            raise NotImplementedError("mlp biases in an 'experts' layer")
        return _routed_shapes(cfg)
    if kind == "conv":
        return {
            "conv_in": ((E, 3 * E), ("embed", "mlp")),
            "conv_taps": ((E, cfg.conv_kernel), ("embed", None)),
            "conv_out": ((E, E), ("mlp", "embed")),
        }
    if kind == "linear_attention":
        # `gdn_in` to [q; k; v; z] (q, k of the key heads, v and the
        # output gate z of the value heads), `gdn_ba` to [b; a] (one of
        # each a value head), the depthwise `gdn_taps` [channel, tap]
        # over [q; k; v], oldest tap first, the decay's `gdn_a_log` and
        # `gdn_dt_bias` a value head, the output norm's PLAIN scale of
        # gdn_value_dim, and `gdn_out`
        Hv, Dv = cfg.gdn_value_heads, cfg.gdn_value_dim
        C = cfg.gdn_conv_dim
        return {
            "gdn_in": ((E, C + Hv * Dv), ("embed", "mlp")),
            "gdn_ba": ((E, 2 * Hv), ("embed", None)),
            "gdn_taps": ((C, cfg.conv_kernel), ("mlp", None)),
            "gdn_a_log": ((Hv,), (None,)),
            "gdn_dt_bias": ((Hv,), (None,)),
            "gdn_norm_scale": ((Dv,), (None,)),
            "gdn_out": ((Hv * Dv, E), ("mlp", "embed")),
        }
    if kind == "state_space":
        # `ssm_in` to [z; x; B; C; dt] (the gate z and x of all heads, a B
        # and a C of ssm_state_dim a group, a step dt a head), the depthwise
        # `ssm_taps` [channel, tap] over [x; B; C], oldest tap first, and
        # their `ssm_conv_bias`, the decay's `ssm_a_log` and the step's
        # `ssm_dt_bias` a head, the skip's `ssm_d` a head (the
        # publisher's D; a plain leaf to every recipe here, NOT a
        # `scale`: drawn 1 beside taps of 0.02 the skip D x is a hundred
        # times the state's read S C, and no check sees the state),
        # the gated norm's scale over all heads (its statistic a
        # group's), and `ssm_out`
        Hs, I, C = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim
        return {
            "ssm_in": ((E, I + C + Hs), ("embed", "mlp")),
            "ssm_taps": ((C, cfg.conv_kernel), ("mlp", None)),
            "ssm_conv_bias": ((C,), ("mlp",)),
            "ssm_a_log": ((Hs,), (None,)),
            "ssm_dt_bias": ((Hs,), (None,)),
            "ssm_d": ((Hs,), (None,)),
            "ssm_norm_scale": ((I,), ("mlp",)),
            "ssm_out": ((I, E), ("mlp", "embed")),
        }
    if kind == "selective_scan":
        # `sscan_in` to [x; z] (the scan's input and the gate), the
        # depthwise `sscan_taps` [channel, tap] over x, oldest tap
        # first, and their `sscan_conv_bias`, `sscan_x` to [r; B; C]
        # (the step's bottleneck of ssm_dt_rank, one B and one C of
        # ssm_state_dim a token), `sscan_dt` and `sscan_dt_bias` from r
        # to a step a channel, the decay's `sscan_a_log` a (channel,
        # state) pair, the skip's `sscan_d` a channel (a plain leaf, as
        # `ssm_d`), and `sscan_out`
        I, N, R = cfg.ssm_inner, cfg.ssm_state_dim, cfg.ssm_dt_rank
        return {
            "sscan_in": ((E, 2 * I), ("embed", "mlp")),
            "sscan_taps": ((I, cfg.conv_kernel), ("mlp", None)),
            "sscan_conv_bias": ((I,), ("mlp",)),
            "sscan_x": ((I, R + 2 * N), ("mlp", None)),
            "sscan_dt": ((R, I), (None, "mlp")),
            "sscan_dt_bias": ((I,), ("mlp",)),
            "sscan_a_log": ((I, N), ("mlp", None)),
            "sscan_d": ((I,), ("mlp",)),
            "sscan_out": ((I, E), ("mlp", "embed")),
        }
    if kind == "gated_memory":
        I = cfg.ssm_inner  # the width of what the donor's scan hands on
        return {
            "gmu_in": ((E, I), ("embed", "mlp")),
            "gmu_out": ((I, E), ("mlp", "embed")),
        }
    if cfg.is_latent:
        return _latent_attention_shapes(cfg)
    shapes = {
        "wq": ((E, H, D), ("embed", "heads", "head_dim")),
        "wk": ((E, KV, D), ("embed", "heads", "head_dim")),
        "wv": ((E, KV, D), ("embed", "heads", "head_dim")),
        "wo": ((H, D, E), ("heads", "head_dim", "embed")),
    }
    if kind == "cross_attention":  # the donor's K/V: no key, no value
        del shapes["wk"], shapes["wv"]
    if cfg.differential_attention:
        # the four vectors of the layer's lam and the scale of the norm
        # over a pair's 2 D values
        shapes.update({f"diff_l{n}": ((D,), ("head_dim",))
                       for n in ("q1", "k1", "q2", "k2")})
        shapes["diff_norm_scale"] = ((2 * D,), (None,))
    if cfg.attn_output_gate:
        shapes["wq_gate"] = ((E, H, D), ("embed", "heads", "head_dim"))
    if cfg.qk_norm_per_head:
        shapes["q_norm_scale"] = ((D,), ("head_dim",))
        shapes["k_norm_scale"] = ((D,), ("head_dim",))
    elif cfg.qk_norm:
        # one scale per projected value, kept [heads, head_dim] so the
        # head sharding of wq / wk applies to it as it stands
        shapes["q_norm_scale"] = ((H, D), ("heads", "head_dim"))
        shapes["k_norm_scale"] = ((KV, D), ("heads", "head_dim"))
    if cfg.has_qkv_bias:
        shapes["bq"] = ((H, D), ("heads", "head_dim"))
        if kind != "cross_attention":
            shapes["bk"] = ((KV, D), ("heads", "head_dim"))
            shapes["bv"] = ((KV, D), ("heads", "head_dim"))
    if cfg.has_attn_out_bias:
        shapes["bo"] = ((E,), ("embed",))
    return shapes


# top-level leaves of the leading dense layers (cfg.n_dense_layers)
DENSE_PREFIX = "dense_"
# top-level stacks of the operators' leaves, by kind, of a model whose
# layers are of several kinds (cfg.layer_types); its keys are the kinds
OPERATOR_PREFIX = {"attention": "attn_", "conv": "conv_",
                   "linear_attention": "gdn_", "state_space": "ssm_",
                   "experts": "moe_", "selective_scan": "sscan_",
                   "gated_memory": "gmu_", "cross_attention": "xattn_"}
LAYER_KINDS = tuple(OPERATOR_PREFIX)
# what a layer of each kind that carries state holds a sequence: the
# property that counts its convolution's channels, and the one that
# gives its heads' float32 matrices (None: it has none). An attention
# layer holds K/V; a kind in neither place ('experts', 'gated_memory',
# 'cross_attention') holds nothing
_STATE_LAYERS = {"conv": ("d_model", None),
                 "linear_attention": ("gdn_conv_dim", "gdn_state_shape"),
                 "state_space": ("ssm_conv_dim", "ssm_state_shape"),
                 "selective_scan": ("ssm_inner", "ssm_state_shape")}


def operator_stacks(cfg: TransformerConfig):
    """[(kind, prefix, layers of that kind)] of a model of two kinds;
    empty where every layer's operator is its own."""
    if cfg.layer_types is None:
        return []
    return [(kind, prefix, cfg.layer_types.count(kind))
            for kind, prefix in OPERATOR_PREFIX.items()
            if kind in cfg.layer_types]


def _operator_leaves(cfg: TransformerConfig):
    """(top-level name, kind, leaf name, shape, logical axes) of every
    operator stack's leaves. The conv and gdn leaves carry their prefix
    already (`conv_in` / `conv_out`, clear of the FFN's `w_in` /
    `w_out`)."""
    for kind, prefix, _ in operator_stacks(cfg):
        for name, (shape, logical) in _operator_shapes(cfg, kind).items():
            top = name if name.startswith(prefix) else prefix + name
            yield top, kind, name, shape, logical


def init(cfg: TransformerConfig, rng) -> Dict[str, Any]:
    E, V, L = cfg.d_model, cfg.vocab_size, cfg.n_layers
    keys = jax.random.split(rng, 16)
    std = 0.02

    def norm_init(shape, scale_name):
        return jnp.ones(shape, jnp.float32) if "scale" in scale_name else jnp.zeros(shape, jnp.float32)

    params: Dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (V, E), jnp.float32) * std,
        "ln_f_scale": jnp.ones((E,), jnp.float32),
    }
    if cfg.use_learned_pos:
        params["pos_embed"] = jax.random.normal(keys[1], (cfg.max_seq, E), jnp.float32) * std
    if cfg.embedding_layernorm:
        params["embed_ln_scale"] = jnp.ones((E,), jnp.float32)
        if cfg.norm_has_bias:
            params["embed_ln_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.norm_has_bias:
        params["ln_f_bias"] = jnp.zeros((E,), jnp.float32)
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(keys[2], (E, V), jnp.float32) * std
        if cfg.lm_head_bias:
            params["lm_head_b"] = jnp.zeros((V,), jnp.float32)

    def stack(key, depth: int, shapes):
        out = {}
        lkeys = jax.random.split(key, len(shapes))
        for i, (name, (shape, _)) in enumerate(sorted(shapes.items())):
            full = (depth,) + shape
            if "ln" in name or name.endswith("_scale"):
                out[name] = jnp.broadcast_to(norm_init(shape, name), full).copy()
            elif name.startswith("b") or (
                    name == "expert_bias" and cfg.expert_bias_update_rate):
                # a bias the step itself moves starts at 0, as its
                # published training does (a served tree keeps its
                # seeded one: nothing would move it off 0)
                out[name] = jnp.zeros(full, jnp.float32)
            else:
                scale = std / (2 * cfg.depth) ** 0.5 if name in (
                    "wo", "w_out", "wr_out", "ws_out") else std
                out[name] = jax.random.normal(lkeys[i], full, jnp.float32) * scale
        return out

    params["layers"] = stack(keys[3], L, _layer_shapes(cfg))
    # leading dense layers: top-level `dense_<name>` [n_dense, ...], so
    # `layers` stays one homogeneous stack
    for name, w in stack(keys[4], cfg.n_dense_layers,
                         _layer_shapes(cfg, dense=True)).items() \
            if cfg.n_dense_layers else ():
        params[DENSE_PREFIX + name] = w
    # operators of two kinds: a top-level stack a kind, so that no leaf
    # of `layers` is one only some layers have. The prefixed names keep
    # `conv_in` / `conv_out` clear of the FFN's `w_in` / `w_out`
    for i, (kind, _, n) in enumerate(operator_stacks(cfg)):
        made = stack(keys[5 + i], n, _operator_shapes(cfg, kind))
        params.update({top: made[name] for top, k, name, _, _
                       in _operator_leaves(cfg) if k == kind})
    if cfg.pipeline_stages > 1:
        from ..runtime.pipe import partition_layers

        params["layers"] = partition_layers(
            params["layers"], cfg.pipeline_stages,
            virtual=cfg.pipeline_virtual_stages,
        )
    return params


def logical_specs(cfg: TransformerConfig) -> Dict[str, Any]:
    specs: Dict[str, Any] = {
        "embed": ("vocab", "embed"),
        "ln_f_scale": ("embed",),
    }
    if cfg.use_learned_pos:
        specs["pos_embed"] = (None, "embed")
    if cfg.embedding_layernorm:
        specs["embed_ln_scale"] = ("embed",)
        if cfg.norm_has_bias:
            specs["embed_ln_bias"] = ("embed",)
    if cfg.norm_has_bias:
        specs["ln_f_bias"] = ("embed",)
    if not cfg.tie_embeddings:
        specs["lm_head"] = ("embed", "vocab")
        if cfg.lm_head_bias:
            specs["lm_head_b"] = ("vocab",)
    if cfg.pipeline_stages > 1:
        lead = (("pipe_virtual", "pipe_stage", "layers")
                if cfg.pipeline_virtual_stages > 1
                else ("pipe_stage", "layers"))
    else:
        lead = ("layers",)
    specs["layers"] = {
        name: lead + logical for name, (_, logical) in _layer_shapes(cfg).items()
    }
    if cfg.n_dense_layers:
        for name, (_, logical) in _layer_shapes(cfg, dense=True).items():
            specs[DENSE_PREFIX + name] = ("layers",) + logical
    for top, _, _, _, logical in _operator_leaves(cfg):
        specs[top] = ("layers",) + logical
    return specs


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _norm(x, scale, bias, cfg: TransformerConfig):
    x32 = x.astype(jnp.float32)
    if cfg.norm_kind == "rms":
        rms = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + cfg.norm_eps)
        out = x32 * rms * scale
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        out = (x32 - mean) * jax.lax.rsqrt(var + cfg.norm_eps) * scale + bias
    return out.astype(x.dtype)


def qk_norm(q, k, lp, cfg: TransformerConfig):
    """QK-norm over projected q [..., H, D] and k [..., KV, D]: RMSNorm
    whose statistic runs over ALL heads of a token (the whole projected
    vector, as HF's OlmoeRMSNorm(num_heads * head_dim) sees it), in
    float32, times the learned [heads, head_dim] scale. The one place
    it is written: training's attention and both serving sites call it,
    between the projection and rope. No-op unless cfg.qk_norm.

    Under jit with sharding constraints the mean is over the global
    array, whatever the head sharding. Inside a shard_map region that
    is manual over 'model' (the axis heads shard on) a shard would see
    its own heads only: refused."""
    if not cfg.qk_norm:
        return q, k
    if not cfg.qk_norm_per_head and \
            "model" in jax.sharding.get_abstract_mesh().manual_axes:
        raise NotImplementedError(
            "qk_norm inside a shard_map region over 'model': the RMS "
            "statistic spans all heads of a token, a shard holds only "
            "its own; apply it before entering the manual region")

    # a head at a time (cfg.qk_norm_per_head: one [head_dim] scale for
    # all heads), or over the whole projection
    axes = -1 if cfg.qk_norm_per_head else (-2, -1)

    def norm(x, scale):
        x32 = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x32), axis=axes, keepdims=True)
        return (x32 * jax.lax.rsqrt(ms + cfg.norm_eps)
                * scale.astype(jnp.float32)).astype(x.dtype)

    return norm(q, lp["q_norm_scale"]), norm(k, lp["k_norm_scale"])


def model_alibi_slopes(cfg: TransformerConfig):
    """Per-head ALiBi slopes for this model (the Press et al. ladder
    times the family's scale quirk — see alibi_slope_scale)."""
    from ..ops.attention import alibi_slopes

    return alibi_slopes(cfg.n_heads) * cfg.alibi_slope_scale


def rope_dim(cfg: TransformerConfig) -> int:
    """Rotated dims per head: head_dim, or the partial-rotary slice
    (Phi/NeoX partial_rotary_factor — rope applies to the first
    rotary_pct * head_dim dims, the rest pass through)."""
    if cfg.is_latent:
        return cfg.qk_rope_head_dim
    R = int(cfg.rotary_pct * cfg.head_dim)
    return R - (R % 2)


def yarn_band_range(cfg: TransformerConfig) -> Tuple[int, int]:
    """(low, high) band indices of YaRN's ramp (rope_inv_freq)."""
    D, L = rope_dim(cfg), cfg.rope_original_max_seq

    def band(turns):
        return (D * math.log(L / (2 * math.pi * turns))
                / (2 * math.log(cfg.rope_theta)))

    return (max(math.floor(band(cfg.rope_yarn_beta_fast)), 0),
            min(math.ceil(band(cfg.rope_yarn_beta_slow)), D // 2 - 1))


def rope_inv_freq(cfg: TransformerConfig, scaled: bool = True) -> jnp.ndarray:
    """Per-band rotary frequencies [rope_dim/2], with long-context
    scaling (scaled False: the plain rope_theta table, a windowed
    layer's where cfg.rope_scaling_full_only).

    "linear" divides every frequency by the factor (position
    interpolation); "llama3" is the Llama-3.x NTK-by-parts rule — long
    wavelengths compress by the factor, short ones keep full resolution,
    the middle band interpolates (HF rope_scaling 'llama3' semantics);
    "yarn" is NTK-by-parts over band INDEX: c(n) = D ln(L / (2 pi n)) /
    (2 ln theta) is the band that turns n times over the original
    length L, bands below low = floor(c(beta_fast)) keep their
    frequency, bands above high = ceil(c(beta_slow)) divide it by the
    factor, a linear ramp between (HF 'yarn' with truncate; static in
    the served length)."""
    D = rope_dim(cfg)
    inv = cfg.rope_theta ** (-jnp.arange(0, D // 2, dtype=jnp.float32) / (D // 2))
    if not scaled:
        return inv
    if cfg.rope_scaling_type == "yarn":
        low, high = yarn_band_range(cfg)
        ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        return inv / cfg.rope_scaling_factor * ramp + inv * (1.0 - ramp)
    if cfg.rope_scaling_type == "linear":
        return inv / cfg.rope_scaling_factor
    if cfg.rope_scaling_type == "llama3":
        factor = cfg.rope_scaling_factor
        lo, hi = cfg.rope_low_freq_factor, cfg.rope_high_freq_factor
        old = cfg.rope_original_max_seq
        wavelen = 2.0 * jnp.pi / inv
        scaled = jnp.where(wavelen > old / lo, inv / factor, inv)
        smooth = (old / wavelen - lo) / (hi - lo)
        smoothed = (1.0 - smooth) / factor * inv + smooth * inv
        mid = (wavelen >= old / hi) & (wavelen <= old / lo)
        return jnp.where(mid, smoothed, scaled)
    return inv


def _rope(q, k, cfg: TransformerConfig, offset: int = 0, positions=None):
    """Rotary embeddings (ref kernel: csrc/transformer/inference/csrc/
    apply_rotary_pos_emb.cu — on TPU this is pure VPU code XLA fuses).

    positions: optional [B, S] token positions (random-LTD subsets keep
    their ORIGINAL positions, ref: basic_layer.py position handling)."""
    S = q.shape[1]
    if positions is None:
        pos = jnp.arange(offset, offset + S, dtype=jnp.float32)[None, :]  # [1,S]
    else:
        pos = positions.astype(jnp.float32)  # [B,S]
    freqs = rope_inv_freq(cfg)
    angles = pos[..., None] * freqs[None, None, :]  # [B|1, S, R/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    R = rope_dim(cfg)

    def rot(x):
        xr, xp = x[..., :R], x[..., R:]  # partial rotary passthrough
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
        if cfg.rope_interleaved:
            # GPT-J rotate_every_two: dims (2i, 2i+1) are the pair
            xf = xr.astype(jnp.float32).reshape(*xr.shape[:-1], R // 2, 2)
            x1, x2 = xf[..., 0], xf[..., 1]
            out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s],
                            axis=-1).reshape(xr.shape)
        else:
            x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
            out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
        return jnp.concatenate([out.astype(x.dtype), xp], axis=-1)

    return rot(q), rot(k)


def _shard(x, *spec):
    """Sharding constraint against the ambient mesh (set by the engine via
    jax.sharding.set_mesh). Outside any mesh context — e.g. a plain
    single-device forward — constraints are skipped explicitly; inside a
    mesh context a bad spec raises rather than silently degrading.

    Inside a partial-manual shard_map (the per-worker gradient path for
    1-bit/qgZ compression), axes the caller already mapped over are
    dropped from the spec — constraints may only name Auto axes there."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    manual = set(mesh.manual_axes)
    if manual:
        def strip(entry):
            if entry is None:
                return None
            axes = (entry,) if isinstance(entry, str) else tuple(entry)
            live = tuple(a for a in axes if a not in manual)
            if not live:
                return None
            return live[0] if len(live) == 1 else live

        spec = tuple(strip(e) for e in spec)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _causal_attention(q, k, v, use_flash, alibi=None, **kw):
    """ops.attention.causal_attention, with the flash kernel run PER
    SHARD under a multi-device mesh. Mosaic refuses to partition a
    pallas_call on its own ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map") and accepts one
    only where EVERY mesh axis is manual — so the call is mapped over
    all axes not already manual: batch rows over the DP axes, heads
    over ('model', 'seq') — the layout the caller just constrained
    q/k/v to — and replicated over the rest. Attention needs nothing
    from another device's rows or heads. The jnp reference path
    partitions by itself and is left alone."""
    mesh = jax.sharding.get_abstract_mesh()
    free = [a for a in mesh.axis_names if a not in mesh.manual_axes]
    if mesh.empty or mesh.size == 1 or not free \
            or not uses_flash(q, use_flash):
        return causal_attention(q, k, v, use_flash=use_flash, alibi=alibi,
                                **kw)

    def fit(axes, *dims):
        got, n = [], 1
        for a in axes:
            if a in free and all(d % (n * mesh.shape[a]) == 0 for d in dims):
                got.append(a)
                n *= mesh.shape[a]
        return tuple(got) or None

    heads = fit(("model", "seq"), q.shape[2], k.shape[2])
    spec = P(fit(DP, q.shape[0]), None, heads, None)
    if alibi is None:
        fn = lambda q_, k_, v_: causal_attention(
            q_, k_, v_, use_flash=use_flash, **kw)
        args, specs = (q, k, v), (spec, spec, spec)
    else:  # slopes shard with the heads
        fn = lambda q_, k_, v_, ab_: causal_attention(
            q_, k_, v_, use_flash=use_flash, alibi=ab_, **kw)
        args, specs = (q, k, v, alibi), (spec, spec, spec, P(heads))
    return jax.shard_map(fn, in_specs=specs, out_specs=spec,
                         axis_names=set(free), check_vma=False)(*args)


def _layer_gather(cfg: TransformerConfig):
    """The ZeRO-3 gather of one layer's store slices
    (runtime/overlap.py make_prefetch_gather) when the engine's ambient
    overlap plan carries layer specs (training traces under zero-3
    overlap_comm), else None: eval/generation forwards, pipelined
    stacks and per-period window patterns leave the gathers to the
    partitioner. The layer body applies it to its own slice, inside
    what jax.checkpoint wraps (_make_layer_body)."""
    if cfg.pipeline_stages > 1 or cfg.attention_window_pattern is not None:
        return None
    from ..runtime.overlap import current_plan, make_prefetch_gather

    plan = current_plan()
    if (plan is None or plan.layer_store_specs is None
            or plan.prefetch_depth < 1):
        return None
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes:
        return None  # partial-manual shard_map traces keep per-use gathers
    return make_prefetch_gather(plan.layer_store_specs,
                                plan.layer_tp_specs, plan.mesh)


def _layer_scan(scan, *args):
    """`scan(*args)` over a stack of layers (`jax.lax.scan`, or
    runtime/pipe.py's loop over microbatch slots around a stage's)
    under the device scope `layer_stack`: the loop's own slicing of
    stacked leaves and activations and its control-flow copies,
    forward, recomputation and backward, around the model's layer
    scopes (docs/tracing.md)."""
    with jax.named_scope(LAYER_STACK):
        return scan(*args)


def _act_quant(x, cfg: TransformerConfig):
    """Fake-quantize activations (STE) when activation_quant_bits is set
    (ref: basic_layer.py activation quantization hooks). Applies in train
    AND eval/serving — a QAT model's numerics include the quantizer.

    The scale is PER-TOKEN (absmax over the feature dim): a token's
    quantization grid depends only on that token, so training, prefill
    and decode produce bit-identical quantized activations — a tensor-
    global max would couple tokens across the batch/padding and insert a
    cross-device reduction per layer."""
    bits = cfg.activation_quant_bits
    if bits <= 0:
        return x
    qmax = float(2 ** (bits - 1) - 1)
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    q = (jnp.clip(jnp.round(xf / scale), -qmax, qmax) * scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(q - x)


def _dropout(x, rate: float, rng):
    """Inverted dropout (ref kernel: csrc/transformer/dropout_kernels.cu —
    on TPU this fuses into the surrounding elementwise ops)."""
    if rate <= 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0).astype(x.dtype)


def _attention_delta(h, lp, cfg: TransformerConfig, rng=None, positions=None,
                     window: Optional[int] = None, rope: bool = True):
    """Attention branch over the NORMED input h; returns the residual
    DELTA (the layer body composes sequential vs parallel residuals).

    window: per-layer sliding window override (attention_window_pattern
    layers); None = cfg.sliding_window. rope: whether THIS layer rotates
    (cfg.rope_at: a full layer of a rope_windowed_only model does not)."""
    from jax.ad_checkpoint import checkpoint_name

    if window is None:
        window = cfg.sliding_window
    x = h
    q = jnp.einsum("bse,ehd->bshd", h, lp["wq"].astype(x.dtype))
    k = jnp.einsum("bse,ehd->bshd", h, lp["wk"].astype(x.dtype))
    v = jnp.einsum("bse,ehd->bshd", h, lp["wv"].astype(x.dtype))
    if cfg.has_qkv_bias:
        q = q + lp["bq"].astype(x.dtype)
        k = k + lp["bk"].astype(x.dtype)
        v = v + lp["bv"].astype(x.dtype)
    gate = None
    if cfg.attn_output_gate:
        # named, and in NO recomputation mode's list: save_attn_qkv
        # recomputes this one product of the layer's (recomputed) norm1
        # in the backward, 2 T E H D operations a layer, where keeping
        # it would hold a fourth [B, S, H, D] a layer (PERF.md §6, PR 55)
        gate = checkpoint_name(
            jnp.einsum("bse,ehd->bshd", h, lp["wq_gate"].astype(x.dtype)),
            "attn_gate")
    q, k = qk_norm(q, k, lp, cfg)
    if cfg.use_rope and rope:
        q, k = _rope(q, k, cfg, positions=positions)

    # named for remat="save_attn_qkv": saved q/k/v are exactly the flash
    # custom-vjp residuals, so the attention block's backward needs NO
    # recompute at all (projections included)
    q = checkpoint_name(q, "attn_q")
    k = checkpoint_name(k, "attn_k")
    v = checkpoint_name(v, "attn_v")

    if cfg.attention_impl == "ring":
        from ..parallel.ring_attention import ring_causal_attention

        q = _shard(q, DP, "seq", "model", None)
        k = _shard(k, DP, "seq", None, None)
        v = _shard(v, DP, "seq", None, None)
        out = ring_causal_attention(q, k, v, use_flash=cfg.use_flash)
    else:
        # Ulysses: re-shard seq→heads around attention; XLA emits the
        # all-to-all pair (ref: sequence/layer.py single_all_to_all:15).
        q = _shard(q, DP, None, ("model", "seq"), None)
        k = _shard(k, DP, None, ("model", "seq"), None)
        v = _shard(v, DP, None, ("model", "seq"), None)

        slopes = None
        if cfg.alibi:
            slopes = jnp.asarray(model_alibi_slopes(cfg))
        # a model of mixed windows: device time by the layer's window,
        # around the attention call alone (serving's names; metadata
        # only, and no other model's program carries the scope)
        with (jax.named_scope("attn_window" if window else "attn_full")
              if cfg.mixed_windows else contextlib.nullcontext()):
            out = _causal_attention(q, k, v, cfg.use_flash, alibi=slopes,
                                    window=window,
                                    block_q=cfg.flash_block_q,
                                    block_k=cfg.flash_block_k)  # [B,S,H,D]

    if gate is not None:
        with jax.named_scope("attn_gate"):
            out = out * jax.nn.sigmoid(
                gate.astype(jnp.float32)).astype(out.dtype)
    out = _shard(out, DP, "seq", "model", None)
    out = jnp.einsum("bshd,hde->bse", out, lp["wo"].astype(x.dtype))
    if cfg.has_attn_out_bias:
        out = out + lp["bo"].astype(x.dtype)
    return _dropout(out, cfg.dropout, rng)


# one object a name: a kernel's jit boundary takes the activation as a
# static argument, and a fresh partial a call would be a new signature
_ACT_FNS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_exact": partial(jax.nn.gelu, approximate=False),
            "relu": jax.nn.relu,
            "relu2": lambda x: jnp.square(jax.nn.relu(x))}


def _act_fn(cfg: TransformerConfig):
    return _ACT_FNS[cfg.act_name]


def aux_width(cfg: TransformerConfig) -> int:
    """Values a layer hands out of the scan beside its activations:
    (load-balance l_aux, router z-loss), and behind them, where the
    step's state reads it (cfg.carries_census), the layer's census:
    the tokens that CHOSE each of the n_experts experts, held here or
    not, the held pairs the wire dropped (0) and the chunks of its list
    that ran, as float32 (exact to 2**24 tokens a layer a micro-batch)."""
    return 2 + (cfg.n_experts + 2 if cfg.carries_census else 0)


def _mlp_delta(h, lp, cfg: TransformerConfig, rng=None, dense: bool = False):
    """FFN branch over the NORMED input h; returns (residual delta,
    the layer's aux [aux_width]). dense: a leading dense layer of a
    routed model (cfg.n_dense_layers), whose `lp` is its own leaves."""
    if cfg.n_experts > 0 and not dense:
        return _moe_mlp_delta(h, lp, cfg, rng)
    x = h
    act = _act_fn(cfg)
    if cfg.is_gated:
        from jax.ad_checkpoint import checkpoint_name

        # named for remat="save_attn_mlp": saving the two F-wide products
        # removes the MLP re-forward (the step's largest recompute)
        gate = checkpoint_name(
            jnp.einsum("bse,ef->bsf", h, lp["w_gate"].astype(x.dtype)),
            "mlp_gate")
        up = checkpoint_name(
            jnp.einsum("bse,ef->bsf", h, lp["w_in"].astype(x.dtype)),
            "mlp_up")
        inner = act(gate) * up
    else:
        inner = jnp.einsum("bse,ef->bsf", h, lp["w_in"].astype(x.dtype))
        if cfg.has_mlp_bias:
            inner = inner + lp["b_in"].astype(x.dtype)
        inner = act(inner)
    inner = _shard(inner, DP, "seq", "model")
    out = jnp.einsum("bsf,fe->bse", inner, lp["w_out"].astype(x.dtype))
    if cfg.has_mlp_bias:
        out = out + lp["b_out"].astype(x.dtype)
    return _dropout(out, cfg.dropout, rng), jnp.zeros((aux_width(cfg),),
                                                      jnp.float32)


def _router_settings(cfg: TransformerConfig, lp) -> Dict[str, Any]:
    """What the dropless router of a routed layer is called with."""
    bias = lp.get("expert_bias")
    return dict(
        top_k=cfg.moe_top_k, renormalize=cfg.moe_norm_topk_prob,
        scoring=cfg.moe_scoring,
        # state of the step, never a parameter: the choice has no
        # gradient, and nothing else reads the bias
        choice_bias=None if bias is None else jax.lax.stop_gradient(bias),
        scale=cfg.routed_scaling_factor)


def route_tokens(cfg: TransformerConfig, lp, tokens):
    """(idx [T, K], weights [T, K]) the dropless routed block of layer
    weights `lp` gives tokens [T, E] with no noise: the training step's
    own router (moe/dropless.route under `_router_settings`), alone, so
    that it can be held to a reference on that reference's inputs."""
    from ..moe.dropless import route

    idx, weights, _, _ = route(tokens, lp["w_router"],
                               **_router_settings(cfg, lp))
    return idx, weights


def _moe_mlp_delta(h, lp, cfg: TransformerConfig, rng=None):
    """Expert-parallel MoE FFN over normed h (ref: deepspeed/moe/
    sharded_moe.py MOELayer:421 — dispatch einsum / all-to-all / expert
    FFN / combine). moe_dropless routes through moe/dropless.py
    instead: capacity-free sorted/grouped batching (EP=1) or the
    explicit a2a frame (EP=N, derived from the ambient mesh)."""
    from ..moe.sharded_moe import moe_ffn

    B, S, E = h.shape
    x = h
    act = _act_fn(cfg)
    tokens = h.reshape(B * S, E)

    def expert_fn(xin):  # [X, C, E] expert-major
        if cfg.is_gated:
            gate = jnp.einsum("xce,xef->xcf", xin, lp["w_gate"].astype(x.dtype))
            up = jnp.einsum("xce,xef->xcf", xin, lp["w_in"].astype(x.dtype))
            inner = act(gate) * up
        else:
            inner = jnp.einsum("xce,xef->xcf", xin, lp["w_in"].astype(x.dtype))
            if cfg.has_mlp_bias:
                inner = inner + lp["b_in"][:, None, :].astype(x.dtype)
            inner = act(inner)
        inner = _shard(inner, "expert", None, "model")
        out = jnp.einsum("xcf,xfe->xce", inner, lp["w_out"].astype(x.dtype))
        if cfg.has_mlp_bias:
            out = out + lp["b_out"][:, None, :].astype(x.dtype)
        return out

    def shard(t, *spec):
        return _shard(t, *spec)

    gate_rng = None
    if rng is not None and cfg.moe_noisy_gate_policy is not None:
        rng, gate_rng = jax.random.split(rng)
    if cfg.moe_dropless:
        from ..moe.dropless import dropless_moe_ffn

        ep = int(jax.sharding.get_abstract_mesh().shape.get("expert", 1))
        if cfg.experts_held is not None and ep > 1:
            raise NotImplementedError(
                "experts_held under an 'expert' mesh axis: a held share "
                "is ONE chip's slice of an expert-parallel job, the axis "
                "is the job itself")
        res = dropless_moe_ffn(
            tokens,
            lp["w_router"],
            lp["w_in"],
            lp["w_out"],
            w_gate=lp.get("w_gate"),
            b_in=lp.get("b_in"),
            b_out=lp.get("b_out"),
            act=act,
            rng=gate_rng,
            noisy_gate_policy=cfg.moe_noisy_gate_policy,
            shard=shard,
            ep_size=ep,
            held=cfg.experts_held,
            **_router_settings(cfg, lp),
        )
        out, l_aux, z_loss = res.out, res.l_aux, res.z_loss
    else:
        out, l_aux = moe_ffn(
            tokens,
            lp["w_router"],
            expert_fn,
            top_k=cfg.moe_top_k,
            capacity_factor=cfg.moe_capacity_factor,
            min_capacity=cfg.moe_min_capacity,
            renormalize=cfg.moe_norm_topk_prob,
            rng=gate_rng,
            noisy_gate_policy=cfg.moe_noisy_gate_policy,
            shard=shard,
        )
        z_loss = jnp.float32(0.0)
    out = out.reshape(B, S, E)
    if cfg.n_shared_experts:
        # the shared expert: a dense MLP of n_shared_experts * d_ff every
        # token passes, on every chip alike, unweighted (serving's
        # _moe_shared; its sigmoid gate stays serving's alone)
        with jax.named_scope("moe_shared"):
            up = jnp.einsum("bse,ef->bsf", h, lp["ws_in"].astype(x.dtype))
            inner = (act(jnp.einsum("bse,ef->bsf", h,
                                    lp["ws_gate"].astype(x.dtype))) * up
                     if cfg.is_gated else act(up))
            inner = _shard(inner, DP, "seq", "model")
            out = out + jnp.einsum("bsf,fe->bse", inner,
                                   lp["ws_out"].astype(x.dtype))
    if cfg.moe_use_residual:
        # PR-MoE (ref: moe/layer.py use_residual — moe and a dense
        # residual expert mixed by a learned softmax coefficient)
        if cfg.is_gated:
            inner = act(jnp.einsum("bse,ef->bsf", h,
                                   lp["wr_gate"].astype(x.dtype))) * \
                jnp.einsum("bse,ef->bsf", h, lp["wr_in"].astype(x.dtype))
        else:
            inner = jnp.einsum("bse,ef->bsf", h, lp["wr_in"].astype(x.dtype))
            if cfg.has_mlp_bias:
                inner = inner + lp["br_in"].astype(x.dtype)
            inner = act(inner)
        dense = jnp.einsum("bsf,fe->bse", inner, lp["wr_out"].astype(x.dtype))
        if cfg.has_mlp_bias:
            dense = dense + lp["br_out"].astype(x.dtype)
        coef = jax.nn.softmax(
            (h.astype(jnp.float32) @ lp["w_coef"].astype(jnp.float32)
             + lp["b_coef"].astype(jnp.float32)), axis=-1)
        out = (out * coef[..., 0:1].astype(x.dtype)
               + dense * coef[..., 1:2].astype(x.dtype))
    out = _shard(out, DP, "seq", None)
    aux = jnp.stack([l_aux.astype(jnp.float32),
                     z_loss.astype(jnp.float32)])
    if cfg.carries_census:
        aux = jnp.concatenate([
            aux, res.counts.astype(jnp.float32),
            jnp.asarray(res.dropped, jnp.float32)[None],
            jnp.asarray(res.chunks_run, jnp.float32)[None]])
    return _dropout(out, cfg.dropout, rng), aux


# valid TransformerConfig.remat values; __post_init__ validates so a
# typo cannot silently train with no rematerialization
REMAT_MODES = ("none", "full", "dots", "save_attn", "save_attn_qkv",
               "save_attn_mlp", "save_attn_dots")


def _wants_rng(cfg: TransformerConfig) -> bool:
    """MoE gate noise also wants per-layer rngs, not just dropout."""
    return cfg.dropout > 0.0 or (
        cfg.n_experts > 0 and cfg.moe_noisy_gate_policy is not None
    )


def _make_layer_body(cfg: TransformerConfig, use_rng: bool, positions=None,
                     pld_theta=None, window: Optional[int] = None,
                     gather=None, rope: bool = True, dense: bool = False):
    """One transformer layer as a scan body (shared by the flat
    scan-over-layers path, the pipelined per-stage path, and the
    random-LTD subset segment — which passes the subset's original
    `positions`).

    gather: `_layer_gather`'s ZeRO-3 gather of the layer's own store
    slices (docs/overlap.md). It runs here, inside what the remat modes
    below wrap and with no barrier around it: the scan's xs stay store
    slices, the backward pass gathers again instead of reading a saved
    gathered stack, and the TPU compiler is free to start each gather
    under the matmuls ahead of its consumer.

    pld_theta: traced scalar — Progressive Layer Dropping (ref:
    runtime/progressive_layer_drop.py, arXiv 2010.13369). Each layer is
    skipped with prob (l+1)/L * (1 - theta) (the paper's depth-increasing
    schedule); the skip is a `lax.cond`, so a dropped layer's compute is
    actually skipped at runtime, not masked.

    rope: whether the layer rotates q and k (cfg.rope_at). dense: a
    LEADING dense layer of a routed model: xs holds its `dense_<name>`
    leaves under their plain names, its FFN is dense."""

    def layer_body(carry, xs):
        if pld_theta is not None:
            h0, (lp, layer_rng, idx) = carry, xs
            r1, r2, r_pld = jax.random.split(layer_rng, 3)
        elif use_rng:
            h0, (lp, layer_rng) = carry, xs
            r1, r2 = jax.random.split(layer_rng)
        else:
            h0, lp = carry, xs
            r1 = r2 = None
        if gather is not None:
            lp = gather(lp)

        def post(y, name):
            # sandwich form: a second norm on the sub-layer's OUTPUT,
            # inside its scope, before the residual add
            return _norm(y, lp[name], None, cfg) if cfg.sandwich_norm else y

        def run(h0):
            # named scopes land in every HLO op's metadata op_name, so
            # the xplane/chrome trace attributes MEASURED device time to
            # these modules (profiling/latency.py; ref: profiler.py:282
            # measures the same boundaries with forward hooks)
            with jax.named_scope("norm1"):
                h1 = _act_quant(
                    _norm(h0, lp["ln1_scale"], lp.get("ln1_bias"), cfg), cfg)
            with jax.named_scope("attention"):
                attn = post(_attention_delta(
                    h1, lp, cfg, r1, positions=positions, window=window,
                    rope=rope), "ln1_post_scale")
            if cfg.parallel_residual:
                # Falcon/Phi form: both branches read the SAME residual
                # stream (shared_ln additionally shares the norm)
                with jax.named_scope("norm2"):
                    h2 = h1 if cfg.shared_ln else _act_quant(
                        _norm(h0, lp["ln2_scale"], lp.get("ln2_bias"), cfg),
                        cfg)
                with jax.named_scope("mlp"):
                    mlp, l_aux = _mlp_delta(h2, lp, cfg, r2, dense)
                    mlp = post(mlp, "ln2_post_scale")
                h = h0 + attn + mlp
            else:
                hmid = h0 + attn
                with jax.named_scope("norm2"):
                    h2 = _act_quant(
                        _norm(hmid, lp["ln2_scale"], lp.get("ln2_bias"), cfg),
                        cfg)
                with jax.named_scope("mlp"):
                    mlp, l_aux = _mlp_delta(h2, lp, cfg, r2, dense)
                    mlp = post(mlp, "ln2_post_scale")
                h = hmid + mlp
            h = _shard(h, DP, "seq", None)
            return h, l_aux

        if pld_theta is None:
            return run(h0)
        p_keep = 1.0 - (idx + 1.0) / cfg.n_layers * (1.0 - pld_theta)
        keep = jax.random.bernoulli(r_pld, p_keep)
        return jax.lax.cond(
            keep, run,
            lambda h: (h, jnp.zeros((aux_width(cfg),), jnp.float32)), h0
        )

    if cfg.remat == "full":
        layer_body = jax.checkpoint(layer_body)
    elif cfg.remat == "dots":
        layer_body = jax.checkpoint(
            layer_body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    elif cfg.remat == "save_attn":
        # full remat EXCEPT the flash-attention residuals (o, lse — named
        # in ops/pallas/flash_attention._flash_fwd_rule): the backward
        # then reuses them instead of re-running the fwd kernel, trading
        # 2*S*D f32 per layer of HBM for the whole attention re-forward
        layer_body = jax.checkpoint(
            layer_body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse"
            ),
        )
    elif cfg.remat == "save_attn_qkv":
        # save_attn + the rope-rotated q/k/v (the remaining flash
        # residuals): the attention half of the layer has zero backward
        # recompute; only the MLP re-forwards. ~2.3GB extra at the 350M
        # bench shape
        layer_body = jax.checkpoint(
            layer_body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse", "attn_q", "attn_k", "attn_v"
            ),
        )
    elif cfg.remat == "save_attn_mlp":
        # save_attn + the two F-wide MLP products: the backward's only
        # remaining matmul recompute is the QKV projections (flash
        # residuals). ~4GB extra HBM at the 350M bench shape — the sweet
        # spot between save_attn and the too-fat dots policy
        layer_body = jax.checkpoint(
            layer_body,
            policy=jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse", "mlp_gate", "mlp_up"
            ),
        )
    elif cfg.remat == "save_attn_dots":
        # additionally keep weight-matmul outputs (no-batch-dim dots):
        # backward recomputes only cheap elementwise work — highest HBM
        # footprint short of remat="none"
        layer_body = jax.checkpoint(
            layer_body,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.save_only_these_names(
                    "flash_o", "flash_lse"
                ),
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            ),
        )
    return layer_body


def _training_refusal(cfg: TransformerConfig, ltd: bool = False,
                      pipelined: bool = False) -> None:
    """Refuse, by name, what the training forward does not compute: the
    settings only serving does (cfg.serving_only), and the settings it
    computes on SOME of its paths asked for on another."""
    if cfg.serving_only:
        raise NotImplementedError(
            f"the training forward does not compute {list(cfg.serving_only)}: "
            "this family is served (inference/model.py) and not trained here")
    routed = [k for k, on in (
        ("experts_held", cfg.experts_held is not None),
        ("moe_expert_bias", cfg.moe_expert_bias),
        ("moe_scoring", cfg.moe_scoring != "softmax"),
        ("routed_scaling_factor", cfg.routed_scaling_factor != 1.0)) if on]
    if routed and not cfg.moe_dropless:
        raise NotImplementedError(
            f"{routed} are computed by the dropless wire alone "
            "(moe/dropless.py): set moe_dropless; the capacity-factor "
            "gates (moe/sharded_moe.py) do not know them")
    if routed and cfg.moe_noisy_gate_policy is not None:
        raise NotImplementedError(f"{routed} with a noisy gate")
    stack = [k for k, on in (
        ("n_dense_layers", cfg.n_dense_layers > 0),
        ("embedding_multiplier", cfg.embedding_multiplier != 1.0),
        ("experts_held", cfg.experts_held is not None),
        ("moe_expert_bias", cfg.moe_expert_bias)) if on]
    if stack and (pipelined or cfg.pipeline_stages > 1 or ltd
                  or cfg.random_ltd_layer_range):
        raise NotImplementedError(
            f"{stack} with pipeline stages or random-LTD: the flat "
            "scan-over-layers forward alone computes them")


def forward_hidden(
    params: Dict[str, Any], tokens, cfg: TransformerConfig, rng=None,
    with_aux: bool = False, ltd_idx=None, pld_theta=None,
):
    """tokens [B, S] int32 → final hidden states [B, S, E] (post ln_f).

    with_aux=True additionally returns {"moe_aux_loss": scalar,
    "moe_z_loss": scalar} (sums of per-layer load-balancing / router
    z-losses; 0 for dense models).
    ltd_idx [B, K] (with cfg.random_ltd_layer_range set) routes the LTD
    layer segment over the kept-token subset only.
    pld_theta: traced scalar keep-floor for Progressive Layer Dropping
    (requires rng; eval passes rng=None, which disables PLD like the
    reference's eval forward)."""
    _training_refusal(cfg, ltd=ltd_idx is not None)
    with jax.named_scope("embed"):
        x = params["embed"][tokens]
        if cfg.embedding_multiplier != 1.0:
            x = x * cfg.embedding_multiplier
        x = _shard(x, DP, "seq", None)
        if cfg.use_learned_pos:
            x = x + params["pos_embed"][: tokens.shape[1]].astype(x.dtype)
        if cfg.embedding_layernorm:
            x = _norm(x, params["embed_ln_scale"],
                      params.get("embed_ln_bias"), cfg)

    if rng is None:
        pld_theta = None  # eval: keep every layer
    use_rng = rng is not None and (_wants_rng(cfg) or pld_theta is not None)
    # ZeRO-3 under an overlap plan: each layer gathers its own shards
    # inside its body (runtime/overlap.py, docs/overlap.md)
    gather = _layer_gather(cfg)
    layer_body = _make_layer_body(cfg, use_rng, pld_theta=pld_theta,
                                  gather=gather)

    layers = params["layers"]
    if cfg.pipeline_stages > 1:
        # Params trained pipelined are stored stage-partitioned
        # [P, L/P, ...]; flatten back so the flat forward (generation,
        # eval without a pipe mesh) works on the same tree.
        from ..runtime.pipe import unpartition_layers

        layers = unpartition_layers(layers, virtual=cfg.pipeline_virtual_stages)

    # a key a layer of the model's depth: the leading dense layers take
    # the first, the stacked ones the rest
    nd = cfg.n_dense_layers
    layer_rngs = jax.random.split(rng, cfg.depth) if use_rng else None
    W = aux_width(cfg)
    for d in range(nd):
        # the leading dense layers, before the scanned stack, from their
        # `dense_<name>` leaves (one unrolled body each: they are few)
        lp = {k[len(DENSE_PREFIX):]: v[d] for k, v in params.items()
              if k.startswith(DENSE_PREFIX)}
        body = _make_layer_body(
            cfg, use_rng, window=cfg.window_for_layer(d),
            rope=cfg.rope_at(d), dense=True)
        x, _ = body(x, (lp, layer_rngs[d]) if use_rng else lp)
    if use_rng:
        layer_rngs = layer_rngs[nd:]

    def seg(x_in, lo, hi, body):
        lp = jax.tree.map(lambda t: t[lo:hi], layers)
        if pld_theta is not None:
            xs = (lp, layer_rngs[lo:hi],
                  jnp.arange(lo, hi, dtype=jnp.float32))
        elif use_rng:
            xs = (lp, layer_rngs[lo:hi])
        else:
            xs = lp
        return _layer_scan(jax.lax.scan, body, x_in, xs)

    if cfg.attention_window_pattern is not None:
        # GPT-Neo-class per-layer windows: the window is STATIC in each
        # compiled attention call, so the scan steps over PATTERN
        # PERIODS — the body runs len(pattern) sublayers, each with its
        # own window, and xs leaves carry a [n_periods, p, ...] leading
        # shape (the length-divides check lives in __post_init__)
        # (the pattern is indexed by the MODEL's layer: stacked layer j
        # is layer nd + j, and so is whether it rotates, cfg.rope_at)
        p = len(cfg.attention_window_pattern)
        bodies = [
            _make_layer_body(cfg, use_rng, pld_theta=pld_theta,
                             window=cfg.window_for_layer(nd + j),
                             rope=cfg.rope_at(nd + j))
            for j in range(p)
        ]

        def period_body(carry, xs):
            h, aux = carry, []
            for j in range(p):
                sub = jax.tree.map(lambda t: t[j], xs)
                h, l_aux = bodies[j](h, sub)
                aux.append(l_aux)
            return h, jnp.stack(aux)  # [p, W]: a row a layer

        def seg(x_in, lo, hi, body):  # noqa: F811 — pattern grouping
            assert lo == 0 and hi == cfg.n_layers
            whole = hi // p * p  # the layers of whole periods
            group = lambda t: t[:whole].reshape(whole // p, p, *t.shape[1:])
            idxs = jnp.arange(hi, dtype=jnp.float32)
            if pld_theta is not None:
                xs = (layers, layer_rngs, idxs)
            elif use_rng:
                xs = (layers, layer_rngs)
            else:
                xs = layers
            h, aux = _layer_scan(jax.lax.scan, period_body, x_in,
                                 jax.tree.map(group, xs))
            aux = [jnp.reshape(aux, (-1, W))]
            for j in range(hi - whole):
                # behind leading dense layers the stacked ones need not
                # be whole periods: the rest, unrolled (layer whole + j
                # has body j's window)
                h, l_aux = bodies[j](
                    h, jax.tree.map(lambda t: t[whole + j], xs))
                aux.append(l_aux[None])
            return h, jnp.concatenate(aux)

    if ltd_idx is not None and cfg.random_ltd_layer_range is not None:
        # Random-LTD: layers in [a, b) see only the kept tokens (at their
        # original positions); dropped tokens skip the segment and are
        # re-inserted in order (ref: basic_layer.py fwd gather/scatter,
        # csrc/random_ltd gather_scatter.cu → XLA take/scatter).
        if cfg.pipeline_stages > 1:
            raise NotImplementedError("random-LTD with pipeline_stages > 1")
        a, b = cfg.random_ltd_layer_range
        B = x.shape[0]
        x, aux1 = seg(x, 0, a, layer_body)
        h_sub = jnp.take_along_axis(x, ltd_idx[..., None], axis=1)
        sub_body = _make_layer_body(cfg, use_rng, positions=ltd_idx,
                                    pld_theta=pld_theta, gather=gather)
        h_sub, aux2 = seg(h_sub, a, b, sub_body)
        x = x.at[jnp.arange(B)[:, None], ltd_idx].set(h_sub)
        x, aux3 = seg(x, b, cfg.n_layers, layer_body)
        aux = jnp.concatenate([jnp.reshape(a, (-1, W))
                               for a in (aux1, aux2, aux3)])
    else:
        x, aux = seg(x, 0, cfg.n_layers, layer_body)
    aux = jnp.reshape(aux, (-1, W))  # a row a stacked layer, in order
    aux_sum = jnp.sum(aux[:, :2], axis=0)
    with jax.named_scope("norm_f"):
        out = _norm(x, params["ln_f_scale"], params.get("ln_f_bias"), cfg)
    if with_aux:
        losses = {"moe_aux_loss": aux_sum[0], "moe_z_loss": aux_sum[1]}
        if cfg.carries_census:
            # [n_layers, n_experts]: the tokens that chose each expert
            # in each routed layer (every chosen pair, held here or not)
            losses["moe_census"] = jnp.round(aux[:, 2:-2]).astype(jnp.int32)
            losses["moe_pairs_dropped"], losses["moe_chunks_run"] = (
                jnp.round(jnp.sum(aux[:, -2:], axis=0)).astype(jnp.int32))
        return out, losses
    return out


def forward(params: Dict[str, Any], tokens, cfg: TransformerConfig, rng=None):
    """tokens [B, S] int32 → logits [B, S, V]."""
    x = forward_hidden(params, tokens, cfg, rng)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bse,ev->bsv", x, head.astype(x.dtype))
    if "lm_head_b" in params:
        logits = logits + params["lm_head_b"].astype(logits.dtype)
    return _shard(logits, DP, "seq", "model")


def _chunked_ce(x, head, targets, mask, n_chunks: int, head_b=None):
    """Cross-entropy without materializing [B,S,V] through backward.

    The per-chunk logits+logsumexp are rematerialized in bwd
    (jax.checkpoint), so peak memory is [B, S/n_chunks, V] — the TPU
    analog of the reference's fused softmax-xent kernels
    (ref: csrc/transformer softmax_kernels.cu), achieved with remat
    instead of a handwritten kernel.
    Returns (sum_nll, sum_mask)."""
    B, S, E = x.shape
    C = S // n_chunks

    @jax.checkpoint
    def chunk(x_c, t_c, m_c):
        logits = jnp.einsum("bce,ev->bcv", x_c, head.astype(x_c.dtype))
        if head_b is not None:
            logits = logits + head_b.astype(logits.dtype)
        logits = _shard(logits, DP, None, "model").astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, t_c[..., None], axis=-1)[..., 0]
        nll = (lse - tgt) * m_c
        return jnp.sum(nll), jnp.sum(m_c)

    def body(carry, xs):
        tot, cnt = carry
        x_c, t_c, m_c = xs
        s, c = chunk(x_c, t_c, m_c)
        return (tot + s, cnt + c), None

    xs = (
        x.reshape(B, n_chunks, C, E).swapaxes(0, 1),
        targets.reshape(B, n_chunks, C).swapaxes(0, 1),
        mask.reshape(B, n_chunks, C).swapaxes(0, 1),
    )
    (tot, cnt), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)), xs)
    return tot, cnt


def _lm_head(params: Dict[str, Any], cfg: TransformerConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def _shift_mask(batch, targets):
    """Loss mask aligned with the shifted targets ([..., 1:])."""
    if "mask" in batch:
        return batch["mask"][..., 1:].astype(jnp.float32)
    return jnp.ones(targets.shape, jnp.float32)


def _ce_chunk_count(seq_len: int, loss_chunks: int) -> int:
    return max(loss_chunks if seq_len % max(loss_chunks, 1) == 0 else 1, 1)


def _token_mean_ce(x, head, targets, mask, n_chunks: int, head_b=None):
    """Token-mean CE for one (micro)batch — the single shared loss tail
    for the flat and pipelined paths (identical numerics by
    construction)."""
    tot, cnt = _chunked_ce(x, head, targets, mask, n_chunks, head_b=head_b)
    return tot / jnp.maximum(cnt, 1.0)


def make_loss_fn(cfg: TransformerConfig, loss_chunks: int = 8,
                 has_aux: bool = False):
    """Next-token cross-entropy over batch {"tokens": [B, S(+1)]}.

    loss_chunks: sequence-chunked CE (memory: [B, S/chunks, V] instead of
    [B, S, V]); 1 disables chunking. has_aux: return (loss, aux) for an
    engine built with has_aux, aux the flat dict of what the step reads
    beside the loss: `moe_census` [n_layers, n_experts] int32 where the
    model hands it out (cfg.carries_census; step_state_rule reads it)
    and `moe_pairs_dropped`, the held pairs its wire did not compute,
    beside `moe_chunks_run`, the chunks of its list that ran."""

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        x, aux = forward_hidden(
            params, inputs, cfg, rng, with_aux=True,
            ltd_idx=batch.get("random_ltd"),
            pld_theta=batch.get("pld_theta"),
        )
        n = _ce_chunk_count(inputs.shape[1], loss_chunks)
        with jax.named_scope("lm_head"):
            # the head in its gathered (TP) layout BEFORE the chunk scan:
            # under ZeRO-3 the partitioner otherwise re-gathers it in
            # every chunk, forward and backward (PERF.md §6, PR 24)
            with jax.named_scope(ZERO_GATHER):
                head = _shard(_lm_head(params, cfg), None, "model")
            loss = _token_mean_ce(x, head, targets,
                                  _shift_mask(batch, targets), n,
                                  head_b=params.get("lm_head_b"))
        if cfg.n_experts > 0:
            # Load-balancing aux loss, coefficient per the reference's
            # Megatron-DeepSpeed recipe (ref: sharded_moe.py l_aux
            # usage), plus the ST-MoE router z-loss (dropless routing).
            # A sigmoid router has neither (dropless_moe_ffn hands 0).
            loss = loss + cfg.moe_aux_loss_coef * aux["moe_aux_loss"]
            if cfg.moe_z_loss_coef:
                loss = loss + cfg.moe_z_loss_coef * aux["moe_z_loss"]
        if has_aux:
            return loss, {k: v for k, v in aux.items()
                          if k in ("moe_census", "moe_pairs_dropped",
                                   "moe_chunks_run")}
        return loss

    # what the engine's span `train.init.shapes` says of this model
    loss_fn.shape_ids = flash_census_ids(cfg)
    return loss_fn


def flash_census_ids(cfg: TransformerConfig) -> Dict[str, str]:
    """The tiles the flash kernels visit at cfg.max_seq, a head, by kind
    (ops/pallas/flash_attention.tile_census: the kernels' own predicate,
    no chip): `flash_windows`, the model's distinct windows in its
    layers' order (0: a full layer), and under `flash_tiles_interior` /
    `_edge` / `_general` / `flash_work_over_needed` a number a window in
    that order. {} for a model whose attention is not these kernels'."""
    at_max_seq = jax.ShapeDtypeStruct((1, cfg.max_seq), jnp.bfloat16)
    windows = list(dict.fromkeys(
        cfg.window_for_layer(li) for li in range(cfg.depth)
        if cfg.layer_kind(li) == "attention"))
    if (cfg.attention_impl == "ring" or not windows
            or not uses_flash(at_max_seq, cfg.use_flash)):
        return {}
    from ..ops.pallas.flash_attention import tile_census

    by_window = [tile_census(cfg.max_seq, w, cfg.flash_block_q,
                             cfg.flash_block_k, alibi=cfg.alibi)
                 for w in windows]

    def a_window(key, fmt="{}"):
        return ",".join(fmt.format(c[key]) for c in by_window)

    return {"flash_windows": ",".join(map(str, windows)),
            "flash_tiles_interior": a_window("interior"),
            "flash_tiles_edge": a_window("edge"),
            "flash_tiles_general": a_window("general"),
            "flash_work_over_needed": a_window("work_over_needed", "{:.3f}")}


def step_state_rule(cfg: TransformerConfig):
    """The state of a routed model's train step that is not the
    optimizer's (runtime/engine.py StepStateRule; pass it to
    ds.initialize with has_aux and make_loss_fn(..., has_aux=True)):
    every routed layer's `expert_bias`, moved after the optimizer's
    update by the census of the step (cfg.expert_bias_update_rate), and
    the counters of a step's routing, read back with the loss:
    `moe_pairs_routed` (tokens x k x routed layers), `moe_pairs_held`
    (of them, on the experts held here), `moe_rows_per_expert_max` /
    `_min` (over the held experts of every layer), `moe_pairs_dropped`
    (held pairs the wire did not compute: 0), `moe_chunks_run` (chunks of
    the held wire's list that ran: one a routed layer while its held
    pairs fit the first, each three sums of a trace) and
    `expert_bias_abs_max`.
    None for a model that hands out no census."""
    if not cfg.carries_census:
        return None
    from ..runtime.engine import StepStateRule

    rate = cfg.expert_bias_update_rate
    start, count = cfg.experts_held or (0, cfg.n_experts)

    def update(state, aux):
        c = aux["moe_census"].astype(jnp.float32)  # [n_layers, X]

        def move(b):  # the one state leaf: layers.expert_bias [n_layers, X]
            d = rate * jnp.sign(jnp.mean(c, -1, keepdims=True) - c)
            return b + d - jnp.mean(d, -1, keepdims=True)

        new = jax.tree.map(move, state)
        held = c[:, start:start + count]
        metrics = {
            "moe_pairs_routed": jnp.sum(c),
            "moe_pairs_held": jnp.sum(held),
            "moe_rows_per_expert_max": jnp.max(held),
            "moe_rows_per_expert_min": jnp.min(held),
            "expert_bias_abs_max": jnp.max(jnp.stack(
                [jnp.max(jnp.abs(b)) for b in jax.tree.leaves(new)]
                or [jnp.float32(0.0)])),
        }
        return new, metrics

    return StepStateRule(
        is_state=lambda path: path.endswith("['expert_bias']"),
        update=update, scope=EXPERT_BIAS_UPDATE,
        counters={"moe_pairs_routed": "sum", "moe_pairs_held": "sum",
                  "moe_pairs_dropped": "sum", "moe_chunks_run": "sum",
                  "moe_rows_per_expert_max": "max",
                  "moe_rows_per_expert_min": "min",
                  "expert_bias_abs_max": "last"},
        ids={"experts_held": count, "experts_total": cfg.n_experts,
             # rows of the held wire's list a TOKEN (dropless.
             # held_rows_bound / T): what no skew can pass
             "pair_rows_bound": min(cfg.moe_top_k, count),
             "dense_layers": cfg.n_dense_layers,
             "window_pattern": ",".join(
                 map(str, cfg.attention_window_pattern or ()))})


# ---------------------------------------------------------------------------
# pipeline-parallel forward + loss (runtime/pipe.py integration)
# ---------------------------------------------------------------------------

def make_pipelined_loss_fn(cfg: TransformerConfig, loss_chunks: int = 8):
    """Pipeline-parallel next-token CE over batch {"tokens": [M, mb, S+1]}.

    The engine's gradient-accumulation microbatches ARE the pipeline
    microbatches (ref: runtime/pipe/engine.py train_batch:323 — there the
    1F1B instruction schedule pumps `gradient_accumulation_steps`
    microbatches; here runtime/pipe.pipeline_apply runs them through the
    stage-sharded layer stack in one SPMD program). Use with an engine
    built with pipelined=True so the whole [M, mb, ...] batch reaches
    this loss in one call.

    Numerics match the flat model: microbatch m's rng is fold_in(rng, m)
    and per-layer keys are split over all L layers then stage-sliced, so
    pipe=P reproduces pipe=1 trajectories exactly (dropout included).
    The loss is the mean over microbatches of per-microbatch token-mean
    CE — identical to the flat engine's mean-of-micro-losses.
    """
    from ..runtime.pipe import (
        pipeline_apply,
        pipeline_apply_circular,
        stage_slice_keys,
    )

    _training_refusal(cfg, pipelined=True)
    n_stage = cfg.pipeline_stages
    v = cfg.pipeline_virtual_stages
    if cfg.n_layers % (max(n_stage, 1) * v) != 0:
        raise ValueError(
            f"n_layers {cfg.n_layers} not divisible by pipeline_stages "
            f"{n_stage} x virtual {v}"
        )
    lps = cfg.n_layers // max(n_stage, 1)
    lc = lps // v  # layers per chunk (circular schedule)

    def loss_fn(params, batch, rng):
        tokens = batch["tokens"]
        if tokens.ndim != 3:
            raise ValueError(
                f"pipelined loss expects tokens [M, mb, S+1], got {tokens.shape}"
            )
        M, mb, _ = tokens.shape
        inputs, targets = tokens[:, :, :-1], tokens[:, :, 1:]
        S = inputs.shape[-1]

        # Embedding runs replicated over 'pipe' (cheap gather); the heavy
        # layer stack runs stage-sharded.
        with jax.named_scope("embed"):
            x = params["embed"][inputs]
            if cfg.use_learned_pos:
                x = x + params["pos_embed"][:S].astype(x.dtype)
            if cfg.embedding_layernorm:
                x = _norm(x, params["embed_ln_scale"],
                          params.get("embed_ln_bias"), cfg)
            x = _shard(x, None, DP, "seq", None)

        use_rng = rng is not None and _wants_rng(cfg)
        layer_body = _make_layer_body(cfg, use_rng)

        carry_in = (x, jnp.zeros((M, 2), jnp.float32))
        state_spec = (P("pipe", DP, "seq", None), P("pipe"))
        layers = params["layers"]
        if v > 1:
            # circular (interleaved) schedule: stage_fn applies ONE chunk
            # (lc layers) per chunk-step, selected by the slot's round
            def chunk_fn(lp_stage, carry, mb_key, stage_idx, rnd):
                h, aux = carry
                r = jnp.minimum(rnd, v - 1)  # empty slots clamp (discarded)
                lp = jax.tree.map(
                    lambda l: jax.lax.dynamic_index_in_dim(l, r, 0,
                                                           keepdims=False),
                    lp_stage,
                )
                if use_rng:
                    # chunk (r, p) covers layers [(r*P+p)*lc, ...+lc):
                    # split over ALL layers then slice, as the flat model
                    keys = stage_slice_keys(
                        mb_key, cfg.n_layers, r * n_stage + stage_idx, lc)
                    h, l_aux = _layer_scan(
                        jax.lax.scan, layer_body, h, (lp, keys))
                else:
                    h, l_aux = _layer_scan(jax.lax.scan, layer_body, h, lp)
                return h, aux + jnp.sum(l_aux, axis=0)

            hidden, aux = _layer_scan(
                partial(pipeline_apply_circular,
                        rng=rng if use_rng else None, state_spec=state_spec),
                chunk_fn, layers, carry_in)
        else:
            def stage_fn(lp_stage, carry, mb_key, stage_idx):
                h, aux = carry
                if use_rng:
                    keys = stage_slice_keys(mb_key, cfg.n_layers, stage_idx, lps)
                    h, l_aux = _layer_scan(
                        jax.lax.scan, layer_body, h, (lp_stage, keys))
                else:
                    h, l_aux = _layer_scan(
                        jax.lax.scan, layer_body, h, lp_stage)
                return h, aux + jnp.sum(l_aux, axis=0)

            if n_stage <= 1:
                # degenerate single-stage pipeline: layers stay [L, ...] in
                # storage; add the [1, L, ...] stage dim at trace time
                layers = jax.tree.map(lambda l: l[None], layers)
            hidden, aux = _layer_scan(
                partial(pipeline_apply,
                        rng=rng if use_rng else None, state_spec=state_spec),
                stage_fn, layers, carry_in)

        # Head/loss: shard microbatches over 'pipe' so the CE work (the
        # reference computes loss only on the last stage) splits across
        # stages instead of replicating.
        hidden = _shard(hidden, "pipe", DP, "seq", None)
        with jax.named_scope("norm_f"):
            x_out = _norm(hidden, params["ln_f_scale"],
                          params.get("ln_f_bias"), cfg)
        mask = _shift_mask(batch, targets)
        n = _ce_chunk_count(S, loss_chunks)
        with jax.named_scope("lm_head"):
            head = _lm_head(params, cfg)
            per_micro = jax.vmap(
                lambda xc, tc, mc: _token_mean_ce(
                    xc, head, tc, mc, n, head_b=params.get("lm_head_b"))
            )(x_out, targets, mask)
            loss = jnp.mean(per_micro)
        if cfg.n_experts > 0:
            loss = loss + cfg.moe_aux_loss_coef * jnp.mean(aux[:, 0])
            if cfg.moe_z_loss_coef:
                loss = loss + cfg.moe_z_loss_coef * jnp.mean(aux[:, 1])
        return loss

    return loss_fn
