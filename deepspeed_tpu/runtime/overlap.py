"""Comm/compute overlap on the training hot paths (docs/overlap.md).

TPU-native redesign of the reference's overlap machinery: the
partitioned-parameter prefetch coordinator
(ref: runtime/zero/partitioned_param_coordinator.py:261
fetch_sub_module — all-gather the NEXT submodule's shards while the
current one computes) and the overlap_comm bucketed gradient reduction
(ref: runtime/zero/stage_1_and_2.py:923 IPG buckets launched during
backward). On TPU both collapse into *where the collective sits on the
XLA schedule* relative to its first consumer:

  gather     — under ZeRO-3 the scanned layer stack scans over the
               STORE slices and gathers layer i's shards INSIDE layer
               i's body, inside the function jax.checkpoint wraps
               (make_prefetch_gather, applied by models/transformer's
               layer body). The scan carries activations only; the
               backward pass re-gathers (ZeRO-3's own semantics), so no
               gathered leaf is a scan residual. Nothing pins the
               gather: the TPU compiler's async collective fusion
               starts each gather of a body under the matmuls that
               precede its consumer, all but the first of a pass.
  bucketing  — gradient reduce-scatters launch in bucket_mb-sized
               groups: bucket j+1's scatters are ordered (barrier)
               before bucket j's accumulate/scale compute
               (bucketed_apply), instead of one constraint wall at the
               accumulation boundary. The barrier makes bucket j's
               compute wait for bucket j+1's scatters to be DONE (see
               below); no benchmark cell accumulates, so the chip has
               not priced it (ROADMAP Q4).
  permute    — runtime/pipe.py issues the 1F1B boundary
               collective-permute right after the stage compute and
               orders it (barrier) ahead of the exit-collection
               bookkeeping.

All three are LAYOUT/SCHEDULE rewrites only — the gathered values,
grads, and stage hand-offs are the same arrays, so the canonical fp32
loss trajectory is bitwise identical overlap-on vs overlap-off
(tests/test_overlap.py pins this). scripts/ds_schedule.py commits the
S007/S009 PROJECTION of the CPU-compiled step as regression pins
(`overlap` keys in SCHEDULE.json); what the chip pays is read off the
compiled step itself (`<kind>_async_n` of the collective manifest,
profiling/hlo.py) and off a device trace (PERF.md).

What `optimization_barrier` binds (the `barrier` below): its outputs
exist once ALL its inputs exist. `a, b = barrier((a, b))` therefore
makes every consumer of `b` wait until `a` is DONE, not until `a` has
been issued — a collective on one side and the compute that should
hide it on the other are serialised, and the TPU compiler turns the
collective's start/done pair back into a synchronous instruction.
Until PR 39 the layer gather was carried one iteration ahead through
the scan and pinned this way; on the chip 35.86 of 36.17 ms of
collectives a step ran with nothing beside them (PERF.md §6). No
barrier may stand between a layer gather and that layer's compute.

The engine activates the layer by entering `overlap_scope` around the
loss trace (`zero_optimization.overlap_comm`, knob `bucket_mb`;
`prefetch_depth: 0` leaves the layer gathers to the partitioner, any
other value means the in-body gather); models and the pipeline runtime
read the ambient plan at trace time — the same ambient-context
discipline as jax.sharding.set_mesh.
"""

import contextlib
import contextvars
import dataclasses
from typing import Any, Callable, List, Optional, Sequence

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils.profiler import GRAD_REDUCE, ZERO_GATHER

__all__ = [
    "OverlapPlan",
    "overlap_scope",
    "current_plan",
    "scoped_loss",
    "make_prefetch_gather",
    "bucket_partition",
    "bucketed_apply",
    "overlap_stats",
]


@dataclasses.dataclass(frozen=True)
class OverlapPlan:
    """The ambient overlap configuration for one traced step.

    layer_store_specs / layer_tp_specs are the `layers` subtrees of the
    engine's storage and TP spec trees (None when the model has no
    scanned stack, the program is pipelined, or prefetch_depth is 0) —
    forward_hidden builds the layer body's gather from them.
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
# differentiable ordering barrier
# ----------------------------------------------------------------------

@jax.custom_vjp
def barrier(xs):
    """jax.lax.optimization_barrier with a VJP (the primitive has no
    differentiation rule): backward barriers the cotangents at the
    mirrored program point. Every output waits for EVERY input (module
    docstring): it orders whole values, it cannot say "issued". Its two
    users are bucketed_apply and runtime/pipe.py's boundary permute.
    Values pass through untouched in both directions."""
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
# ZeRO-3 layer gather (inside the layer body)
# ----------------------------------------------------------------------

def _drop_lead(spec: P, n: int) -> P:
    """The per-layer slice of a stacked leaf's PartitionSpec: drop the
    first n (stacking) dims' entries (parallel.sharding's spec
    surgery, imported lazily to keep this module import-light)."""
    from ..parallel.sharding import drop_leading_dims

    return drop_leading_dims(spec, n)


def make_prefetch_gather(store_specs, tp_specs, mesh, n_lead: int = 1):
    """Per-leaf ZeRO-3 gather for one layer's slice of a scanned stack.

    For every zero-sharded stacked leaf (per-layer store slice differs
    from its TP/gathered slice), returns a custom-vjp function whose
    forward constrains the slice store→gathered — XLA emits the
    all-gather at the constraint — and whose backward constrains the
    cotangent straight back to the store slice, so the grad
    reduce-scatter runs per layer INSIDE the backward scan instead of
    at the accumulation boundary (the make_qwz_gather discipline,
    runtime/zero.py, minus quantization). Leaves whose store slice
    already equals the gathered slice (persistence-threshold params, a
    one-chip mesh) or whose stacking dim itself carries mesh axes pass
    through identity.

    The caller applies it to the layer's own slice at the top of the
    layer body, INSIDE whatever jax.checkpoint wraps: the scan's xs
    stay store slices, the backward pass gathers again, and a gathered
    leaf is a scan residual only under remat "none".
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

    fns = jax.tree.map(leaf_fn, store_specs, tp_specs,
                       is_leaf=lambda x: isinstance(x, P))

    def apply(w_slice):
        return jax.tree.map(lambda fn, w: fn(w), fns, w_slice)

    return apply


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
    layout, ref: stage_1_and_2.py:923 IPG buckets) are ordered by a
    barrier BEFORE bucket j's consume compute (the accumulate add /
    loss-scale multiply), meant to hide each launch group's wire time
    under the previous group's arithmetic instead of serializing at the
    accumulation boundary (what the barrier really binds: module
    docstring). consume(leaf_index, scattered_grad) maps
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
            # the next bucket's scatters are ordered before this
            # bucket's consume compute. The barrier makes the consumed
            # values wait for those scatters' PAYLOADS, not their issue
            # (module docstring); left as it is until a cell that
            # accumulates prices it (ROADMAP Q4)
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
