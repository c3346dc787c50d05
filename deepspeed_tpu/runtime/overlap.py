"""Comm/compute overlap on the training hot paths (docs/overlap.md).

TPU-native redesign of the reference's overlap machinery: the
partitioned-parameter prefetch coordinator
(ref: runtime/zero/partitioned_param_coordinator.py:261
fetch_sub_module — all-gather the NEXT submodule's shards while the
current one computes) and the overlap_comm bucketed gradient reduction
(ref: runtime/zero/stage_1_and_2.py:923 IPG buckets launched during
backward). On TPU both collapse into *where the collective sits on the
XLA schedule* relative to its first consumer:

  prefetch   — the scanned layer stack carries a gathered-weights
               double buffer: iteration i issues the all-gather for
               layer i+prefetch_depth's zero-sharded shards, pinned
               (optimization_barrier) to the slot UNDER layer i's
               compute (scan_with_prefetch). The gather's first real
               consumer is one scan iteration away, so the latency-
               hiding scheduler spans it with the whole layer body.
  bucketing  — gradient reduce-scatters launch in bucket_mb-sized
               groups, software-pipelined: bucket j+1's scatters are
               barrier-pinned to issue before bucket j's accumulate/
               scale compute (bucketed_apply), instead of one
               serialized constraint wall at the accumulation
               boundary.
  permute    — runtime/pipe.py issues the 1F1B boundary
               collective-permute right after the stage compute and
               pins it ahead of the exit-collection bookkeeping, so
               the hop rides under the next microbatch's work.

All three are LAYOUT/SCHEDULE rewrites only — the gathered values,
grads, and stage hand-offs are the same arrays, so the canonical fp32
loss trajectory is bitwise identical overlap-on vs overlap-off
(tests/test_overlap.py pins this). The measured effect is the S007/
S009 exposure drop that scripts/ds_schedule.py commits as regression
pins (`overlap` keys in SCHEDULE.json).

The engine activates the layer by entering `overlap_scope` around the
loss trace (`zero_optimization.overlap_comm`, knobs `prefetch_depth` /
`bucket_mb`); models and the pipeline runtime read the ambient plan at
trace time — the same ambient-context discipline as
jax.sharding.set_mesh.
"""

import contextlib
import contextvars
import dataclasses
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils.profiler import GRAD_REDUCE, ZERO_GATHER

__all__ = [
    "OverlapPlan",
    "overlap_scope",
    "current_plan",
    "scoped_loss",
    "make_prefetch_gather",
    "scan_with_prefetch",
    "bucket_partition",
    "bucketed_apply",
    "overlap_stats",
]


@dataclasses.dataclass(frozen=True)
class OverlapPlan:
    """The ambient overlap configuration for one traced step.

    layer_store_specs / layer_tp_specs are the `layers` subtrees of the
    engine's storage and TP spec trees (None when the model has no
    scanned stack, the program is pipelined, or prefetch is off) —
    forward_hidden slices them per layer to build the prefetch gather.
    """

    mesh: Any
    prefetch_depth: int = 1
    bucket_mb: float = 32.0
    layer_store_specs: Any = None
    layer_tp_specs: Any = None


_PLAN: contextvars.ContextVar = contextvars.ContextVar(
    "ds_overlap_plan", default=None)


def current_plan() -> Optional[OverlapPlan]:
    """The ambient OverlapPlan (None outside an engine overlap scope —
    e.g. a plain eval/generation forward, or overlap_comm: false)."""
    return _PLAN.get()


@contextlib.contextmanager
def overlap_scope(plan: Optional[OverlapPlan]):
    """Install `plan` as the ambient overlap context for the enclosed
    trace (trace-time only: jax tracing is synchronous Python, so the
    contextvar is live exactly while the wrapped loss builds jaxprs)."""
    token = _PLAN.set(plan)
    try:
        yield plan
    finally:
        _PLAN.reset(token)


def scoped_loss(loss_fn: Callable, plan: Optional[OverlapPlan]) -> Callable:
    """Wrap a loss so its trace runs under `overlap_scope(plan)`."""
    if plan is None:
        return loss_fn

    def wrapped(*args, **kwargs):
        with overlap_scope(plan):
            return loss_fn(*args, **kwargs)

    return wrapped


# ----------------------------------------------------------------------
# differentiable issue-slot barrier
# ----------------------------------------------------------------------

@jax.custom_vjp
def barrier(xs):
    """jax.lax.optimization_barrier with a VJP (the primitive has no
    differentiation rule): backward barriers the cotangents at the
    mirrored program point, so a forward issue-slot pin (gather before
    layer compute) transposes to a backward ordering tie (scatter
    cotangent joined with the activation cotangent). Values pass
    through untouched in both directions — the pin is schedule-only."""
    return jax.lax.optimization_barrier(xs)


def _barrier_fwd(xs):
    return barrier(xs), None


def _barrier_bwd(_, ct):
    leaves, treedef = jax.tree.flatten(ct)
    live = [i for i, l in enumerate(leaves)
            if getattr(l, "dtype", None) != jax.dtypes.float0]
    if live:
        pinned = jax.lax.optimization_barrier([leaves[i] for i in live])
        for i, p in zip(live, pinned):
            leaves[i] = p
    return (treedef.unflatten(leaves),)


barrier.defvjp(_barrier_fwd, _barrier_bwd)


# ----------------------------------------------------------------------
# ZeRO-3 parameter prefetch (scan-carried gathered-weights buffer)
# ----------------------------------------------------------------------

def _drop_lead(spec: P, n: int) -> P:
    """The per-layer slice of a stacked leaf's PartitionSpec: drop the
    first n (stacking) dims' entries (parallel.sharding's spec
    surgery, imported lazily to keep this module import-light)."""
    from ..parallel.sharding import drop_leading_dims

    return drop_leading_dims(spec, n)


def make_prefetch_gather(store_specs, tp_specs, mesh, n_lead: int = 1):
    """Per-leaf prefetch gather for a scanned layer stack.

    For every zero-sharded stacked leaf (per-layer store slice differs
    from its TP/gathered slice), returns a custom-vjp function whose
    forward constrains the slice store→gathered — XLA emits the
    all-gather at the constraint, which scan_with_prefetch pins one
    iteration ahead of the consumer — and whose backward constrains the
    cotangent straight back to the store slice, so the grad
    reduce-scatter runs per layer INSIDE the backward scan instead of
    at the accumulation boundary (the make_qwz_gather discipline,
    runtime/zero.py, minus quantization). Leaves whose store slice
    already equals the gathered slice (persistence-threshold params) or
    whose stacking dim itself carries mesh axes pass through identity.
    """

    def leaf_fn(store_spec, tp_spec):
        lead = list(store_spec)[:n_lead]
        if any(e is not None for e in lead):
            return lambda w: w  # stacking dim sharded: slice inexpressible
        s = _drop_lead(store_spec, n_lead)
        g = _drop_lead(tp_spec, n_lead)
        if s == g:
            return lambda w: w  # persistent / not zero-sharded

        @jax.custom_vjp
        def gather(w):
            with jax.named_scope(ZERO_GATHER):
                w = jax.lax.with_sharding_constraint(
                    w, NamedSharding(mesh, s))
                return jax.lax.with_sharding_constraint(
                    w, NamedSharding(mesh, g))

        def fwd(w):
            return gather(w), None

        def bwd(_, ct):
            with jax.named_scope(GRAD_REDUCE):
                return (jax.lax.with_sharding_constraint(
                    ct, NamedSharding(mesh, s)),)

        gather.defvjp(fwd, bwd)
        return gather

    def pin_leaf_fn(store_spec, tp_spec):
        lead = list(store_spec)[:n_lead]
        if any(e is not None for e in lead):
            return lambda w: w
        s = _drop_lead(store_spec, n_lead)
        g = _drop_lead(tp_spec, n_lead)
        if s == g:
            return lambda w: w

        def pin_gathered(w):
            with jax.named_scope(ZERO_GATHER):
                return jax.lax.with_sharding_constraint(
                    w, NamedSharding(mesh, g))

        return pin_gathered

    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    fns = jax.tree.map(leaf_fn, store_specs, tp_specs, is_leaf=is_spec)
    pin_fns = jax.tree.map(pin_leaf_fn, store_specs, tp_specs,
                           is_leaf=is_spec)

    def apply(w_slice):
        return jax.tree.map(lambda fn, w: fn(w), fns, w_slice)

    def pin(w_gathered):
        """Re-assert the gathered layout on a buffer crossing a scan
        carry boundary. Without this the SPMD partitioner is free to
        resolve the while-loop carry as the store slice — resharding
        the gathered value down at the backedge and re-gathering at the
        consumer, which silently undoes the prefetch (and doubles the
        collective count)."""
        return jax.tree.map(lambda fn, w: fn(w), pin_fns, w_gathered)

    apply.pin = pin
    return apply


def scan_with_prefetch(body, init, w_stack, rest, pack, gather, depth: int):
    """jax.lax.scan over a layer stack with a gathered-weights
    double buffer carried `depth` iterations ahead.

    body(carry, xs) -> (carry, out) is the unmodified layer body;
    `pack(w, rest_i)` rebuilds its xs from a gathered weight slice and
    the non-weight xs slice (rngs / layer indices). Iteration i
    consumes the gathered buffer for layer i from the carry and issues
    `gather` on layer (i+depth) mod L's store slice; the
    optimization_barrier ties that issue to the slot BEFORE layer i's
    compute, so the all-gather sits a full layer body away from its
    first real consumer — the slack window analysis/schedule.py
    credits. The wrapped tail re-gathers the head layers into the
    final carry unconsumed: one wasted gather per segment, the price
    of a branch-free scan body (XLA dead-values them out of the
    backward).
    """
    leaves = jax.tree.leaves(w_stack)
    if not leaves:
        raise ValueError("scan_with_prefetch needs a non-empty stack")
    L = int(leaves[0].shape[0])
    depth = max(1, min(int(depth), L))

    def fetch(i):
        return jax.tree.map(
            lambda t: jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False),
            w_stack)

    pin = getattr(gather, "pin", lambda t: t)
    bufs = tuple(gather(fetch(i)) for i in range(depth))

    def body2(carry, xs):
        x, bufs = carry
        # every carry crossing re-asserts the gathered layout — see
        # make_prefetch_gather.pin
        bufs = tuple(pin(b) for b in bufs)
        i, rest_i = xs
        g_next = gather(fetch((i + depth) % L))
        # issue-slot pin: the layer input now depends on the gather
        # having been ISSUED (not consumed), so the scheduler cannot
        # sink the collective down to its consumer next iteration
        g_next, x = barrier((g_next, x))
        y, out = body(x, pack(bufs[0], rest_i))
        return (y, tuple(pin(b) for b in bufs[1:]) + (g_next,)), out

    idxs = jnp.arange(L, dtype=jnp.int32)
    (x_fin, _), outs = jax.lax.scan(body2, (init, bufs), (idxs, rest))
    return x_fin, outs


# ----------------------------------------------------------------------
# bucketed gradient reduce-scatter (software-pipelined launches)
# ----------------------------------------------------------------------

def bucket_partition(nbytes: Sequence[int], bucket_mb: float,
                     ) -> List[List[int]]:
    """Deterministic contiguous bucketing of leaf indices by size:
    flatten order (the engine's grad-tree order), each bucket closed
    once it holds >= bucket_mb MiB (a leaf larger than the bucket gets
    its own). The per-bucket ledger monitor.training_events emits uses
    the same partition."""
    cap = max(1.0, float(bucket_mb) * 2.0 ** 20)
    buckets: List[List[int]] = []
    cur: List[int] = []
    filled = 0.0
    for j, nb in enumerate(nbytes):
        cur.append(j)
        filled += float(nb)
        if filled >= cap:
            buckets.append(cur)
            cur, filled = [], 0.0
    if cur:
        buckets.append(cur)
    return buckets


def bucketed_apply(grads, grad_specs, mesh, bucket_mb: float,
                   consume: Callable[[int, Any], Any]):
    """Constrain a grad tree to its sharded layout in bucket_mb-sized
    launch groups, software-pipelined against `consume`.

    Bucket j+1's reduce-scatters (the constraint to the ZeRO grad
    layout, ref: stage_1_and_2.py:923 IPG buckets) are barrier-pinned
    to issue BEFORE bucket j's consume compute (the accumulate add /
    loss-scale multiply), so each launch group's wire time hides under
    the previous group's arithmetic instead of serializing at the
    accumulation boundary. consume(leaf_index, scattered_grad) maps
    each scattered leaf to its output (flatten order preserved).
    """
    from ..parallel import sharding as shd

    leaves, treedef = jax.tree.flatten(grads)
    specs = jax.tree.leaves(grad_specs, is_leaf=lambda x: isinstance(x, P))
    if len(specs) != len(leaves) or not leaves:
        # structure mismatch (custom grad trees): serialized fallback
        flat = [shd.constraint(g, s, mesh) for g, s in zip(leaves, specs)]
        return treedef.unflatten(
            [consume(j, g) for j, g in enumerate(flat)])
    buckets = bucket_partition([g.size * g.dtype.itemsize for g in leaves],
                               bucket_mb)

    def launch(idx_group):
        return [shd.constraint(leaves[j], specs[j], mesh)
                for j in idx_group]

    out: List[Any] = [None] * len(leaves)
    cur = launch(buckets[0])
    for b, group in enumerate(buckets):
        nxt = launch(buckets[b + 1]) if b + 1 < len(buckets) else None
        if nxt is not None:
            # pin: the next bucket's scatters are issued before this
            # bucket's consume compute runs (the barrier makes the
            # consumed values depend on the issue, not the payloads)
            nxt, cur = barrier((nxt, cur))
            nxt, cur = list(nxt), list(cur)
        for j, g in zip(group, cur):
            out[j] = consume(j, g)
        cur = nxt
    return treedef.unflatten(out)


# ----------------------------------------------------------------------
# per-step overlap accounting (monitor.training_events feed)
# ----------------------------------------------------------------------

def overlap_stats(schedule) -> Optional[dict]:
    """Flatten a ScheduleAnalysis into the monitor's overlap feed:
    headline exposure numbers plus the per-bucket reduce-scatter
    launch/complete ledger (schedule position of each scatter's issue
    slot and first real consumer, with its wire/exposed time). Returns
    None without a schedule artifact."""
    if schedule is None:
        return None
    ledger = []
    for c in schedule.collectives:
        if c.op != "reduce-scatter":
            continue
        ledger.append({
            "name": c.name,
            "computation": c.computation,
            "payload_bytes": int(c.payload_bytes),
            # window origin is the issue slot: the wire completes at
            # +wire_us, the first real consumer lands at +consumer_us —
            # exposed is the gap when the wire outlives the window
            "launch_us": 0.0,
            "complete_us": round(c.t_comm_s * 1e6, 3),
            "consumer_us": round(max(c.overlap_s, c.slack_s) * 1e6, 3),
            "exposed_us": round(c.exposed_s * 1e6, 3),
        })
    comm_us = schedule.t_comm_s * 1e6
    return {
        "exposed_comm_us": round(schedule.exposed_s * 1e6, 3),
        "hideable_slack_us": round(schedule.slack_s * 1e6, 3),
        "achieved_overlap_frac": round(
            1.0 - schedule.exposed_comm_fraction, 6) if comm_us else 1.0,
        "n_hidden_sync": schedule.n_hidden_sync,
        "buckets": ledger,
    }
