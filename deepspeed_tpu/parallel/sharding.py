"""Logical-axis sharding rules — the AutoTP analog.

The reference shards HF models by graph-walking Linear layers and slicing
rows/cols (ref: deepspeed/module_inject/auto_tp.py:188 AutoTP,
ReplaceWithTensorSlicing:30) or by per-model policy classes. TPU-first,
the same capability is a *rules table*: model parameters carry logical
axis names ("embed", "heads", "mlp", "vocab", ...) and one table maps
logical names → mesh axes. Changing the parallelism layout = changing
the table, no model surgery.
"""

from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


MeshAxes = Union[None, str, Tuple[str, ...]]

# Default rules table. Megatron-style TP: attention heads and the MLP
# hidden dim are sharded over 'model' (column-parallel first matmul /
# row-parallel second is what XLA derives from these specs); the vocab /
# embedding table is sharded over 'model' like the reference's
# VocabParallelEmbedding contract; batch rides the data axes; sequence
# rides 'seq' (Ulysses).
DEFAULT_LOGICAL_RULES: List[Tuple[str, MeshAxes]] = [
    ("batch", ("data", "zero", "expert")),
    ("seq", "seq"),
    ("embed", None),
    ("heads", "model"),
    ("head_dim", None),
    ("mlp", "model"),
    # vocab shards over TP and, under pipeline parallelism, ALSO over
    # 'pipe': each stage holds V/(model*pipe) embedding/head rows — the
    # TPU answer to the reference's stage-placing of tied embedding/head
    # (ref: runtime/pipe/module.py TiedLayerSpec — there stage 0 and P-1
    # hold the full table and all-reduce its grad; here no stage holds
    # more than a slice and XLA inserts the gather/psum)
    ("vocab", ("model", "pipe")),
    ("expert", "expert"),
    ("expert_mlp", "model"),
    ("kv_length", None),
    ("layers", None),  # stacked-layer leading dim (scan-over-layers)
    ("pipe_stage", "pipe"),  # pipeline-stage leading dim (runtime/pipe.py)
    ("pipe_virtual", None),  # interleave round dim (circular schedule)
]


def make_rules(overrides: Optional[Dict[str, MeshAxes]] = None) -> Dict[str, MeshAxes]:
    rules = dict(DEFAULT_LOGICAL_RULES)
    if overrides:
        rules.update(overrides)
    return rules


def logical_to_mesh_spec(
    logical_spec: Sequence[Optional[str]],
    rules: Dict[str, MeshAxes],
    mesh: Mesh,
    shape: Optional[Sequence[int]] = None,
) -> P:
    """Map one logical PartitionSpec to a mesh PartitionSpec.

    A logical axis maps to None if the rules say so, if its mesh axis has
    size 1, or (when `shape` is given) if the dim isn't divisible by the
    mesh-axis size — e.g. 2 GQA kv-heads under model=4 fall back to
    replicated instead of failing at jit time.
    """
    out = []
    used = set()
    for i, name in enumerate(logical_spec):
        if name is None:
            out.append(None)
            continue
        mapped = rules.get(name, None)
        if mapped is None:
            out.append(None)
            continue
        if isinstance(mapped, str):
            mapped = (mapped,)
        live = tuple(ax for ax in mapped if mesh.shape.get(ax, 1) > 1 and ax not in used)
        if shape is not None and live:
            # keep every axis whose CUMULATIVE product still divides the
            # dim (a non-dividing axis is skipped, later ones are still
            # tried) — one bad axis must not strip the sharding the
            # others provide (e.g. vocab 32000 under model=2 x pipe=3
            # keeps the 2-way model shard)
            kept = []
            total = 1
            for ax in live:
                if shape[i] % (total * mesh.shape[ax]) == 0:
                    kept.append(ax)
                    total *= mesh.shape[ax]
            live = tuple(kept)
        used.update(live)
        if not live:
            out.append(None)
        elif len(live) == 1:
            out.append(live[0])
        else:
            out.append(live)
    # Trim trailing Nones for canonical form.
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def tree_logical_to_mesh(
    logical_specs,  # pytree of tuple-of-logical-names (or PartitionSpec of names)
    rules: Dict[str, MeshAxes],
    mesh: Mesh,
    shapes=None,  # matching pytree of shape tuples (enables divisibility guard)
):
    """Map a whole pytree of logical specs to mesh PartitionSpecs."""
    is_spec = lambda x: isinstance(x, (tuple, P)) and all(
        s is None or isinstance(s, str) for s in x
    )
    if shapes is None:
        return jax.tree.map(
            lambda spec: logical_to_mesh_spec(tuple(spec), rules, mesh),
            logical_specs,
            is_leaf=is_spec,
        )
    return jax.tree.map(
        lambda spec, shp: logical_to_mesh_spec(tuple(spec), rules, mesh, shape=shp),
        logical_specs,
        shapes,
        is_leaf=is_spec,
    )


def pipe3d_specs(param_logical_specs, shapes, mesh: Mesh, zero_config,
                 rules: Optional[Dict[str, MeshAxes]] = None):
    """One-call 3D (pipeline x ZeRO x TP) spec derivation — the
    combined-layout authority the interleaved pipeline composes with
    (docs/pipeline.md).

    Layer 1 — the rules table places logical names on mesh axes:
    'pipe_stage' rides 'pipe' (the stage dim of a [P, L/P, ...] or
    [v, P, lc, ...] stack), TP names ('heads', 'mlp', ...) ride
    'model', 'pipe_virtual' stays replicated (every stage holds all v
    of its own chunks). Layer 2 — runtime/zero.py adds ZeRO sharding
    on top: storage specs (stage-3 param sharding over the data axes),
    optimizer-state specs (stage >= 1), and the gradient-constraint
    specs. One mesh, three orthogonal axis families; XLA derives the
    stage collective-permute, the TP psums, and the ZeRO
    gather/reduce-scatter pair from these specs alone.

    Returns {"tp": ..., "storage": ..., "opt": ..., "grads": ...}
    (pytrees of PartitionSpec matching `shapes`)."""
    from ..runtime import zero

    tp = tree_logical_to_mesh(
        param_logical_specs, make_rules(rules), mesh, shapes=shapes)
    storage = zero.derive_param_storage_specs(tp, shapes, mesh, zero_config)
    opt = zero.derive_optimizer_specs(tp, shapes, mesh, zero_config)
    grads = zero.derive_grad_specs(storage, opt, zero_config)
    return {"tp": tp, "storage": storage, "opt": opt, "grads": grads}


def tree_shardings(specs, mesh: Mesh):
    """PartitionSpec pytree → NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )


def constraint(x, spec: P, mesh: Mesh):
    """with_sharding_constraint under an explicit mesh."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def drop_leading_dims(spec: P, n: int) -> P:
    """The spec of one slice of a stacked array: drop the first n
    (stacking) dims' entries and strip trailing Nones. The prefetch
    gather (runtime/overlap.py) uses this to derive per-layer store/TP
    slice specs from the engine's stacked `layers` spec trees."""
    entries = list(spec)[n:]
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def batch_spec(batch_leaf_ndim: int, *, leading_accum_dim: bool = False) -> P:
    """Canonical spec for an input-batch leaf: [(gas,) batch, seq, ...].

    Batch dim shards over data+expert; sequence dim over 'seq'.
    """
    dims: List[MeshAxes] = []
    if leading_accum_dim:
        dims.append(None)
    dims.append(("data", "zero", "expert"))
    if batch_leaf_ndim > len(dims):
        dims.append("seq")
    while len(dims) < batch_leaf_ndim:
        dims.append(None)
    return P(*dims[:batch_leaf_ndim])
