"""ZeRO stages as sharding derivation.

TPU-native redesign of the reference ZeRO machinery
(ref: runtime/zero/stage_1_and_2.py DeepSpeedZeroOptimizer:97,
runtime/zero/stage3.py DeepSpeedZeroOptimizer_Stage3:75,
runtime/zero/partition_parameters.py zero.Init:780). Per SURVEY §7, the
~6k LoC of hook/bucket/coordinator machinery collapses on TPU into
*where each array lives on the mesh*:

  stage 1 — optimizer state (fp32 master + moments) carries an extra
            'data'-axis sharding; params stay replicated over 'data'.
            XLA emits the reduce-scatter/all-gather pair around the
            sharded update that the reference does by hand
            (stage_1_and_2.py:1811 step / all_gather_into_tensor).
  stage 2 — gradients are additionally *constrained* to the sharded
            layout at the accumulation boundary, so XLA reduce-scatters
            grads instead of all-reducing them
            (ref: stage_1_and_2.py:923 IPG bucketing → one annotation).
  stage 3 — parameters themselves are *stored* sharded over 'data';
            XLA's SPMD partitioner inserts the per-use all-gathers that
            the reference's prefetch coordinator
            (partitioned_param_coordinator.py:261 fetch_sub_module)
            schedules manually. Small params stay replicated below
            `param_persistence_threshold`
            (ref: parameter_offload.py:242 persistent params).

MiCS / ZeRO++ hpZ sub-grouping (ref: zero/mics.py:64, config.py:264) is
the 'zero' mesh sub-axis: when the data dimension is factored data×zero
(engine does this from zero_hpz_partition_size, or the user sets
mesh.zero directly — the MiCS_Init analog), ZeRO state shards over
'zero' ONLY and replicates across 'data' groups. XLA then emits
intra-group all-gathers for params plus a cross-group grad all-reduce —
the MiCS hierarchical comm pattern (mics.py allgather within shard
group, allreduce across replica groups) derived from layout. Offload
tiering and quantized collectives live in their own modules.
"""

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..config.config import ZeroConfig
from ..utils.profiler import GRAD_REDUCE, ZERO_GATHER


def zero_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The mesh axes ZeRO state shards over: the 'zero' sub-group when
    factored in (MiCS/hpZ), else the whole 'data' axis. The expert axis
    already shards expert params; MoE expert leaves get these added on
    top of their 'expert' dim."""
    if mesh.shape.get("zero", 1) > 1:
        return ("zero",)
    return ("data",)


def _spec_dims(spec: P, rank: int):
    dims = list(spec) + [None] * (rank - len(spec))
    return dims[:rank]


def _axes_of(entry):
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


# The TPU's memory tile over the two minor-most dims of an array (JAX
# docs, "Pallas TPU": min tile (8, 128) for 32-bit, (16, 128) for bf16):
# 128 lanes on the minor dim; on the second-minor dim 8 sublanes of 32
# bits, so a bf16 tile packs 16 rows. A ZeRO leaf is held as the bf16
# compute copy AND the fp32 master/moments under one dim, so the sublane
# constant is bf16's 16: a multiple of it is whole tiles in both. A
# shard boundary inside a tile makes the leaf's all-gather a relayout
# (gather and transpose in one instruction) and pads its gradient's
# reduction: 6% of one head gather on four v5e chips (PERF.md §6, PR 24).
TILE_LANES = 128
TILE_SUBLANES = 16


def _keeps_tile(dim: int, rank: int, shard: int) -> bool:
    """Whether a shard of `shard` elements along `dim` is whole TPU
    tiles. Dims outside the two minor-most are never tiled."""
    if dim == rank - 1:
        return shard % TILE_LANES == 0
    if dim == rank - 2:
        return shard % TILE_SUBLANES == 0
    return True


def _live_axes(mesh: Mesh, axes: Optional[Tuple[str, ...]]):
    if axes is None:
        axes = zero_axes(mesh)
    live = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
    return live, int(np.prod([mesh.shape[a] for a in live])) if live else 1


def _shard_candidates(dims, shape, mesh: Mesh, axis_n: int):
    """[(dim, shard extent)] of the dims the ZeRO axes could take: the
    local extent (after the spec's existing sharding) divides by them."""
    out = []
    for i, d in enumerate(shape):
        existing = int(np.prod([mesh.shape[a] for a in _axes_of(dims[i])])) if dims[i] else 1
        local = d // existing
        if local % axis_n == 0:
            out.append((i, local // axis_n))
    return out


def _largest(cands):
    """The first of the largest candidates (None when there is none)."""
    return max(cands, key=lambda c: c[1], default=None)


def _choose(cands, rank: int) -> Optional[int]:
    """The dim of the largest candidate whose shard keeps the TPU tile;
    of the largest of all when none does, so no leaf that could be
    sharded stays replicated; None without candidates."""
    kept = [c for c in cands if _keeps_tile(c[0], rank, c[1])]
    best = _largest(kept or cands)
    return None if best is None else best[0]


def zero_shard_spec(
    spec: P,
    shape,
    mesh: Mesh,
    min_size: int = 0,
    axes: Optional[Tuple[str, ...]] = None,
) -> P:
    """Add the ZeRO axes to the best dimension of one leaf's PartitionSpec.

    Candidates are the dims that (a) are not already sharded over the
    ZeRO axes, (b) are divisible by the axes' total size after
    accounting for existing sharding. Of those, the largest whose shard
    is whole TPU tiles (`_keeps_tile`: decided from the shape and the
    spec alone); when no candidate keeps the tile, the largest. Leaves
    smaller than `min_size` elements stay untouched (the
    persistence-threshold analog). Returns the original spec when no dim
    qualifies — those leaves stay replicated over the data axes, which is
    exactly the reference's persistent-param behavior.
    """
    live, axis_n = _live_axes(mesh, axes)
    if not live:
        return spec
    size = int(np.prod(shape)) if len(shape) else 1
    if size < max(min_size, axis_n) or len(shape) == 0:
        return spec
    dims = _spec_dims(spec, len(shape))
    if any(set(live) & set(_axes_of(d)) for d in dims):
        return spec  # already zero-sharded
    best = _choose(_shard_candidates(dims, shape, mesh, axis_n), len(shape))
    if best is None:
        return spec
    cur = _axes_of(dims[best])
    dims[best] = cur + live
    if len(dims[best]) == 1:
        dims[best] = dims[best][0]
    while dims and dims[-1] is None:
        dims.pop()
    return P(*dims)


def derive_param_storage_specs(param_specs, shapes, mesh: Mesh, zero_config: ZeroConfig):
    """Specs for how parameters are *stored* between steps.

    stage < 3: TP spec as-is (replicated over 'data').
    stage 3:   + 'data' sharding on leaves above the persistence threshold.
    """
    if zero_config.stage < 3:
        return param_specs
    return jax.tree.map(
        lambda spec, shp: zero_shard_spec(
            spec, shp, mesh, min_size=zero_config.param_persistence_threshold
        ),
        param_specs,
        shapes,
        is_leaf=lambda x: isinstance(x, P),
    )


def derive_optimizer_specs(param_specs, shapes, mesh: Mesh, zero_config: ZeroConfig):
    """Specs for optimizer state (fp32 master + moments).

    stage >= 1: sharded over 'data' (the ZeRO-1 partition,
    ref: stage_1_and_2.py flattened param-group partitioning). No
    persistence threshold — the reference partitions *all* optimizer
    state; tiny leaves that don't divide simply stay replicated.
    """
    if zero_config.stage < 1:
        return param_specs
    return jax.tree.map(
        lambda spec, shp: zero_shard_spec(spec, shp, mesh, min_size=0),
        param_specs,
        shapes,
        is_leaf=lambda x: isinstance(x, P),
    )


def derive_grad_specs(param_specs, opt_specs, zero_config: ZeroConfig):
    """Specs gradients are constrained to at the accumulation boundary.

    stage >= 2: the sharded (optimizer) layout → XLA reduce-scatters
    (ref: stage_1_and_2.py average_tensor:1033 reduce-scatter path).
    stage < 2:  the param layout → plain all-reduce semantics.
    """
    return opt_specs if zero_config.stage >= 2 else param_specs


def zero_layout_report(gathered_specs, zero_specs, shapes, mesh: Mesh,
                       itemsize: int):
    """What the tile rule did to this layout, for the engine's log and
    the `train.init.shapes` span: `zero_leaves_moved` counts the
    zero-sharded leaves whose dim is not the largest candidate (the
    choice before the rule), `zero_leaves_off_tile` those whose shard
    still breaks a tile because no candidate kept it; `*_bytes` sums
    each group's leaves at `itemsize` bytes an element."""
    out = {"zero_leaves_moved": 0, "zero_bytes_moved": 0,
           "zero_leaves_off_tile": 0, "zero_bytes_off_tile": 0}
    _, axis_n = _live_axes(mesh, None)

    def leaf(gathered, sharded, shape):
        k = _zero_sharded_dim(sharded, gathered, len(shape), mesh)
        if k is None:
            return
        cands = _shard_candidates(
            _spec_dims(gathered, len(shape)), shape, mesh, axis_n)
        shard = dict(cands)[k]
        nbytes = int(np.prod(shape)) * itemsize
        if k != _largest(cands)[0]:
            out["zero_leaves_moved"] += 1
            out["zero_bytes_moved"] += nbytes
        if not _keeps_tile(k, len(shape), shard):
            out["zero_leaves_off_tile"] += 1
            out["zero_bytes_off_tile"] += nbytes

    jax.tree.map(leaf, gathered_specs, zero_specs, shapes,
                 is_leaf=lambda x: isinstance(x, P))
    return out


def _zero_sharded_dim(store_spec: P, gathered_spec: P, rank: int, mesh: Mesh):
    """The dim whose spec gains ZeRO axes in storage (None if the leaf is
    not zero-sharded)."""
    s_dims = _spec_dims(store_spec, rank)
    g_dims = _spec_dims(gathered_spec, rank)
    zaxes = set(zero_axes(mesh))
    for i in range(rank):
        if (set(_axes_of(s_dims[i])) - set(_axes_of(g_dims[i]))) & zaxes:
            return i
    return None


def zero_sharded_dims(store_specs, gathered_specs, shapes, mesh: Mesh):
    """Pytree of per-leaf ZeRO-sharded dim indices (-1 = the leaf is
    replicated over the zero axes; -1 rather than None because None is
    an empty subtree to jax pytrees). The shard-slicing contract of the
    peer-redundancy layer (resilience/redundancy.py): rank r of a world
    of W owns elements [r*d/W, (r+1)*d/W) along this dim."""

    def dim_of(s, g, shp):
        d = _zero_sharded_dim(s, g, len(shp), mesh)
        return -1 if d is None else d

    return jax.tree.map(
        dim_of, store_specs, gathered_specs, shapes,
        is_leaf=lambda x: isinstance(x, P),
    )


def axis_sharded_dims(specs, shapes, mesh: Mesh, axis: str = "pipe"):
    """Pytree of per-leaf dim indices whose spec entry is LED by `axis`
    (-1 = the leaf is not sharded over it). The stage-slicing contract
    of the pipeline peer-redundancy path (resilience/redundancy.py):
    stage s of a pipe world of P owns [s*d/P, (s+1)*d/P) along this dim
    — exactly the XLA shard geometry of a leading-'pipe' PartitionSpec
    entry ([P, L/P, ...] plain stacks: dim 0; [v, P, lc, ...] circular
    stacks: dim 1). Dims where `axis` is a trailing co-axis (e.g. vocab
    over ('model', 'pipe')) are NOT stage-sliced: the slice order would
    interleave with the major axis, so those leaves stay whole in every
    payload — conservative, always reassemblable."""
    if mesh.shape.get(axis, 1) <= 1:
        return jax.tree.map(
            lambda s, shp: -1, specs, shapes,
            is_leaf=lambda x: isinstance(x, P))

    def dim_of(spec, shp):
        dims = _spec_dims(spec, len(shp))
        for i, d in enumerate(dims):
            ax = _axes_of(d)
            if ax and ax[0] == axis:
                return i
        return -1

    return jax.tree.map(
        dim_of, specs, shapes, is_leaf=lambda x: isinstance(x, P))


def make_qwz_gather(store_specs, gathered_specs, shapes, mesh: Mesh):
    """ZeRO++ qwZ: int8-quantized weight all-gather.

    (ref: runtime/zero/partition_parameters.py:725 CUDAQuantizer +
    all_gather_coalesced quantized path; docs/_tutorials/zeropp.md qwZ —
    halves all-gather volume vs fp16/bf16.)

    Returns f(params_tree) that, for every zero-sharded leaf, quantizes
    the local shard to int8 with one scale per slice of the sharded dim
    (shard-local by construction), constrains codes+scales to the
    GATHERED layout — so XLA's all-gather moves int8, not bf16 — and
    dequantizes locally. Backward passes gradients straight through to
    the sharded layout (the reduce-scatter stays full precision; qgZ
    handles gradient compression separately).
    """
    from ..ops.quantization import dequantize_per_axis, quantize_per_axis

    def leaf_fn(store_spec, gathered_spec, shape):
        k = _zero_sharded_dim(store_spec, gathered_spec, len(shape), mesh)
        if k is None:
            return lambda w: w  # not zero-sharded: plain (already-local) use
        g_dims = _spec_dims(gathered_spec, len(shape))
        scale_spec = P(g_dims[k]) if g_dims[k] is not None else P()

        @jax.custom_vjp
        def gather(w):
            w = jax.lax.with_sharding_constraint(
                w, jax.sharding.NamedSharding(mesh, store_spec)
            )
            q, s = quantize_per_axis(w, k)
            with jax.named_scope(ZERO_GATHER):
                q = jax.lax.with_sharding_constraint(
                    q, jax.sharding.NamedSharding(mesh, gathered_spec)
                )
                s = jax.lax.with_sharding_constraint(
                    s, jax.sharding.NamedSharding(mesh, scale_spec)
                )
            return dequantize_per_axis(q, s, k, w.dtype)

        def fwd(w):
            return gather(w), None

        def bwd(_, g):
            with jax.named_scope(GRAD_REDUCE):
                return (
                    jax.lax.with_sharding_constraint(
                        g, jax.sharding.NamedSharding(mesh, store_spec)
                    ),
                )

        gather.defvjp(fwd, bwd)
        return gather

    fns = jax.tree.map(
        leaf_fn, store_specs, gathered_specs, shapes,
        is_leaf=lambda x: isinstance(x, P),
    )

    def apply(params):
        return jax.tree.map(lambda fn, p: fn(p), fns, params)

    return apply


def validate_no_conflicts(specs) -> None:
    """Debug-mode check: no spec uses one mesh axis twice (the sharding
    analog of the reference's safe_mode re-derivation,
    ref: stage3.py:1249 __reduce_and_partition_ipg_grads(safe_mode))."""

    def check(spec):
        seen = []
        for entry in spec:
            for ax in _axes_of(entry):
                if ax in seen:
                    raise ValueError(f"mesh axis {ax} used twice in {spec}")
                seen.append(ax)
        return spec

    jax.tree.map(check, specs, is_leaf=lambda x: isinstance(x, P))
