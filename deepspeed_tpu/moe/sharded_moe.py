"""Mixture-of-Experts with expert parallelism (GShard/Switch-style).

TPU-native redesign of the reference MoE stack
(ref: deepspeed/moe/sharded_moe.py — top1gating:180, top2gating:278,
_AllToAll:95, MOELayer:421; deepspeed/moe/layer.py MoE:17; expert/data
group carving deepspeed/utils/groups.py:113).

Where the reference dispatches tokens with an explicit
torch.distributed all-to-all autograd function between einsums, here
dispatch/combine are einsums against a one-hot dispatch tensor plus a
sharding constraint putting the experts dim on the 'expert' mesh axis —
the XLA SPMD partitioner emits the all-to-all pair in forward and its
transpose in backward. The expert axis is carved out of the
data-parallel world exactly like the reference (batch shards over
data×expert; expert weights shard over 'expert'), so EP size never
changes the global math — only the layout.

All gating math runs in fp32 regardless of compute dtype (the reference
casts gate inputs to fp32 at sharded_moe.py TopKGate.forward).
"""

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def compute_capacity(
    num_tokens: int, num_experts: int, capacity_factor: float, min_capacity: int = 4
) -> int:
    """Static per-expert token capacity
    (ref: sharded_moe.py _capacity — ceil(tokens/experts * factor))."""
    cap = int(math.ceil(num_tokens / num_experts * capacity_factor))
    return max(cap, min_capacity)


def _one_hot(x, n, dtype=jnp.float32):
    return jax.nn.one_hot(x, n, dtype=dtype)


def _load_balance_loss(gates, mask):
    """l_aux = E * Σ_e mean_t(gate_e) · mean_t(assigned_e)  — 1.0 at uniform
    (ref: sharded_moe.py top1gating l_aux)."""
    num_experts = gates.shape[-1]
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask.astype(jnp.float32), axis=0)
    return num_experts * jnp.sum(me * ce)


def _replicated_draw(draw_fn):
    """Run one rng draw pinned REPLICATED under the ambient mesh.

    The gate noise must be a pure function of (seed, step, layer) —
    byte-identical across EP layouts. With jax's default
    non-partitionable threefry, the SPMD partitioner may compute
    DIFFERENT bits for the same key depending on how it shards the
    generation (observed: an {'expert': 2} mesh axis changes the drawn
    noise vs the same key on a pure-DP mesh). Pinning the draw's output
    replicated forces one full layout-independent computation — the
    noise tensor is [T, X]-small, so the cost is nil and EP=1 == EP=N
    stays bitwise."""
    x = draw_fn()
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.manual_axes:
        return x
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, P())


def _apply_noise(logits, rng, policy: Optional[str]):
    """Noisy gating (ref: sharded_moe.py multiplicative_jitter / RSample
    noisy_gate_policy). No-op when rng is None (eval) or policy unset."""
    if rng is None or policy is None:
        return logits
    if policy == "RSample":
        return logits + _replicated_draw(
            lambda: jax.random.normal(rng, logits.shape, logits.dtype))
    if policy == "Jitter":
        eps = 1e-2
        return logits * _replicated_draw(
            lambda: jax.random.uniform(
                rng, logits.shape, logits.dtype, 1.0 - eps, 1.0 + eps))
    raise ValueError(f"unknown noisy_gate_policy {policy!r}")


def topk_gating(
    logits,
    top_k: int,
    capacity_factor: float = 1.0,
    min_capacity: int = 4,
    rng=None,
    noisy_gate_policy: Optional[str] = None,
    renormalize: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Generic capacity-factor top-k gating (Switch at k=1, GShard at
    k=2 — ref: sharded_moe.py top1gating:180 / top2gating:278 — and the
    same queue discipline for any k <= n_experts).

    logits: [T, X] router outputs (any float dtype; math is fp32).
    Capacity C = ceil(T/X * factor * k); choice j's queue starts after
    the tokens the earlier choices actually KEPT per expert — a dropped
    first-choice token never consumes a slot a later choice could have
    used. Tokens beyond capacity are dropped (their combine row is
    zero — the residual around the MoE block carries them).

    Returns (combine [T,X,C] fp32, dispatch [T,X,C] bool, l_aux). k=1
    combines with the raw softmax mass (Switch); k>=2 renormalizes the
    kept choices to sum to 1 (GShard). `renormalize` overrides that rule
    (None keeps it; False = raw softmax mass at any k, HF
    norm_topk_prob=false).
    """
    T, X = logits.shape
    if not 1 <= top_k <= X:
        raise ValueError(
            f"moe top_k must be in [1, {X}] for {X} experts, got {top_k}")
    C = compute_capacity(T, X, capacity_factor * top_k, min_capacity)
    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)

    noisy = _apply_noise(logits, rng, noisy_gate_policy)
    masked = noisy
    kept = jnp.zeros((1, X), jnp.float32)  # KEPT tokens per expert so far
    l_aux = None
    gs, ds = [], []
    for _ in range(top_k):
        mask_j = _one_hot(jnp.argmax(masked, axis=-1), X)  # [T, X]
        masked = jnp.where(mask_j > 0, -jnp.inf, masked)
        if l_aux is None:  # the reference computes l_aux on mask1
            l_aux = _load_balance_loss(gates, mask_j)
        loc_j = jnp.cumsum(mask_j, axis=0) - mask_j + kept
        pos_j = jnp.sum(loc_j * mask_j, axis=-1).astype(jnp.int32)  # [T]
        keep_j = pos_j < C
        kept = kept + jnp.sum(mask_j * keep_j[:, None], axis=0,
                              keepdims=True)
        gs.append(jnp.sum(gates * mask_j, axis=-1) * keep_j)
        ds.append(
            (mask_j[:, :, None] * _one_hot(pos_j, C)[:, None, :])
            * keep_j[:, None, None])
    if top_k > 1 if renormalize is None else renormalize:
        denom = jnp.maximum(sum(gs), jnp.finfo(jnp.float32).eps)
        gs = [g / denom for g in gs]
    combine = sum(d * g[:, None, None] for d, g in zip(ds, gs))
    dispatch = sum(ds) > 0
    return combine, dispatch, l_aux


def top1_gating(logits, **kw):
    """Switch-style top-1 gating (topk_gating at k=1)."""
    return topk_gating(logits, 1, **kw)


def top2_gating(logits, **kw):
    """GShard-style top-2 gating (topk_gating at k=2; capacity is
    2x the top-1 factor and the kept pair renormalizes to sum 1)."""
    return topk_gating(logits, 2, **kw)


def moe_ffn(
    tokens,  # [T, E] flattened tokens, compute dtype
    router_w,  # [E, X]
    expert_fn,  # ([X, C, E] expert-major inputs) -> [X, C, E] outputs
    *,
    top_k: int = 1,
    capacity_factor: float = 1.0,
    min_capacity: int = 4,
    renormalize: Optional[bool] = None,
    rng=None,
    noisy_gate_policy: Optional[str] = None,
    shard=None,  # fn(x, *logical_spec) applying a sharding constraint
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dispatch→expert→combine core (ref: sharded_moe.py MOELayer.forward:421).

    The einsum pair around `expert_fn` contracts the token dim (sharded
    over data×expert) into the experts dim (sharded over 'expert') and
    back — under SPMD that IS the reference's all-to-all pair
    (ref: _AllToAll:95), chosen by the XLA partitioner instead of issued
    by hand. Returns (output [T, E], l_aux).
    """
    dtype = tokens.dtype
    logits = tokens.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [T, X]
    combine, dispatch, l_aux = topk_gating(
        logits,
        top_k,
        capacity_factor=capacity_factor,
        min_capacity=min_capacity,
        rng=rng,
        noisy_gate_policy=noisy_gate_policy,
        renormalize=renormalize,
    )
    x = jnp.einsum("txc,te->xce", dispatch.astype(dtype), tokens)
    if shard is not None:
        x = shard(x, "expert", None, None)
    y = expert_fn(x)  # [X, C, E]
    if shard is not None:
        y = shard(y, "expert", None, None)
    out = jnp.einsum("txc,xce->te", combine.astype(dtype), y)
    return out, l_aux
