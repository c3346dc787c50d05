"""Pipeline parallelism as a single SPMD collective-permute program.

TPU-native redesign of the reference pipeline engine
(ref: runtime/pipe/engine.py PipelineEngine:55, schedule.py
TrainSchedule:189 (1F1B), module.py LayerSpec:30 / _partition_layers:370,
p2p.py). The reference runs one process per stage and executes an
instruction schedule (LoadMicroBatch / SendActivation / RecvActivation /
ForwardPass / ...) with eager p2p between stage processes. On TPU the
whole pipeline is ONE jitted SPMD program:

- The stacked layer pytree [L, ...] is reshaped to [P, L/P, ...]
  (`partition_layers` — the LayerSpec/_partition_layers analog) with the
  stage dim sharded over the 'pipe' mesh axis.
- A stage-major shift register [P, mb, ...] (dim 0 sharded over 'pipe')
  holds one in-flight microbatch per stage. Each loop iteration applies
  every stage's local layers in parallel (`jax.vmap` over the stage dim
  with spmd_axis_name='pipe') and rotates the register one slot
  (`jnp.roll` on the sharded dim → XLA collective-permute over ICI —
  the p2p.py send/recv analog, but compiler-scheduled).
- M microbatches drain in M+P-1 iterations: the same bubble fraction
  (P-1)/(M+P-1) as the reference's 1F1B schedule. 1F1B's memory
  advantage over GPipe is recovered by jax.checkpoint on the stage body
  (activations rematerialize in backward) instead of schedule
  interleaving; `jax.grad` through the loop automatically runs the
  reversed pipeline (the transpose of a collective-permute is the
  reverse permute), giving backward the same overlap structure.

Warmup/drain slots compute on garbage that never reaches an output —
bubbles cost wasted FLOPs here instead of idle time, identical wall-clock.

Activations may be arbitrary pytrees (e.g. hidden states plus an
accumulating MoE aux-loss channel); every leaf travels the register with
a leading microbatch dim.
"""

from typing import Any, Callable, Optional

import jax
from .overlap import barrier as _overlap_barrier, current_plan
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _is_spec(x):
    return isinstance(x, P)


def _constraint_auto_only(t, spec):
    """with_sharding_constraint with MANUAL mesh axes stripped from the
    spec — inside the per-worker gradient shard_map (1-bit/0-1/qgZ x
    pipeline), the data axes are already mapped over and constraints may
    only name Auto axes (same rule as models/transformer._shard)."""
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    if manual:
        def strip(entry):
            if entry is None:
                return None
            axes = (entry,) if isinstance(entry, str) else tuple(entry)
            live = tuple(a for a in axes if a not in manual)
            if not live:
                return None
            return live[0] if len(live) == 1 else live

        spec = P(*(strip(e) for e in tuple(spec)))
    return jax.lax.with_sharding_constraint(t, spec)


def num_stages(stage_params) -> int:
    return jax.tree.leaves(stage_params)[0].shape[0]


def partition_layers(stacked_params, n_stages: int, method: str = "uniform",
                     virtual: int = 1, interleave: Optional[int] = None):
    """[L, ...] layer-stacked pytree → stage-partitioned.

    The LayerSpec partitioner analog (ref: runtime/pipe/module.py
    _partition_layers:370). The reference offers uniform/parameters/
    regex/profile strategies over heterogeneous nn.Module lists; a
    scanned stack is homogeneous by construction, so 'uniform' is exact
    load balance and the only strategy that changes anything.

    virtual=1: [P, L/P, ...] (contiguous blocks).
    virtual=v>1: [v, P, L/(v*P), ...] — CYCLIC chunk assignment for the
    circular (interleaved/virtual-stage) schedule: chunk c = r*P + p runs
    on physical stage p at round r, the Megatron interleaved placement
    (ref: runtime/pipe/module.py interleave docs; bubble shrinks ~v, see
    pipeline_apply_circular).

    `interleave` is the documented name for the virtual-stage degree
    (docs/pipeline.md); it is an alias of `virtual` and the two may not
    disagree.
    """
    if interleave is not None:
        if virtual not in (1, int(interleave)):
            raise ValueError(
                f"interleave={interleave} conflicts with virtual={virtual}"
            )
        virtual = int(interleave)
    if method != "uniform":
        raise NotImplementedError(
            f"partition method '{method}' — scanned layer stacks are "
            "homogeneous; only 'uniform' applies"
        )

    def reshape(leaf):
        L = leaf.shape[0]
        if L % (n_stages * virtual) != 0:
            raise ValueError(
                f"layer count {L} not divisible by pipeline stages "
                f"{n_stages} x virtual {virtual}"
            )
        if virtual > 1:
            return leaf.reshape(
                (virtual, n_stages, L // (n_stages * virtual)) + leaf.shape[1:]
            )
        return leaf.reshape((n_stages, L // n_stages) + leaf.shape[1:])

    return jax.tree.map(reshape, stacked_params)


def unpartition_layers(stage_params, virtual: int = 1):
    """[P, L/P, ...] (virtual=1) or [v, P, lc, ...] (virtual>1) →
    [L, ...] for export / checkpoint consolidation. The circular
    layout's row-major (round, stage, slot) order IS layer order, so one
    reshape inverts both."""
    lead = 3 if virtual > 1 else 2

    def flat(leaf):
        n = 1
        for s in leaf.shape[:lead]:
            n *= s
        return leaf.reshape((n,) + leaf.shape[lead:])

    return jax.tree.map(flat, stage_params)


def pipeline_apply(
    stage_fn: Callable,
    stage_params: Any,
    x: Any,
    rng: Optional[jax.Array] = None,
    state_spec: Any = None,
):
    """Run M microbatches through a P-stage pipeline.

    stage_fn(stage_local_params, carry, mb_rng, stage_idx) -> carry'
    applies one stage's local layers to one microbatch's activation
    pytree. It is vmapped over the stage dim with spmd_axis_name='pipe',
    so sharding constraints inside it compose with the stage sharding.

    x: activation pytree, every leaf [M, ...] (microbatch-major).
    rng: per-call key; microbatch m travels with fold_in(rng, m), the
         same per-microbatch key derivation the flat engine uses.
    state_spec: optional PartitionSpec pytree for the [P, ...] shift
         register leaves (e.g. P('pipe', ('data','expert'), 'seq')).

    Returns the same pytree with leaves [M, ...]: microbatch m's output
    of the final stage.
    """
    n_stage = num_stages(stage_params)
    M = jax.tree.leaves(x)[0].shape[0]
    T = M + n_stage - 1

    # Inject garbage for the drain iterations — those slots' outputs fall
    # beyond the ys slice and are never observed (the scheduler-bubble
    # analog: compute runs, result is discarded).
    def pad_leaf(leaf):
        pad = jnp.zeros((n_stage - 1,) + leaf.shape[1:], leaf.dtype)
        return jnp.concatenate([leaf, pad], axis=0)

    xs_in = jax.tree.map(pad_leaf, x)

    if rng is not None:
        mb_keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(T))
    else:
        mb_keys = jnp.zeros((T, 2), jnp.uint32)

    state = jax.tree.map(
        lambda leaf: jnp.zeros((n_stage,) + leaf.shape[1:], leaf.dtype), x
    )
    key_state = jnp.zeros((n_stage,) + mb_keys.shape[1:], mb_keys.dtype)
    stage_ids = jnp.arange(n_stage)

    # Outside a pipe>1 mesh (pure-function tests, pipe folded away) run as
    # a plain vmap with no sharding annotations.
    mesh = jax.sharding.get_abstract_mesh()
    has_pipe = not mesh.empty and mesh.shape.get("pipe", 1) > 1
    vstage = jax.vmap(
        stage_fn,
        in_axes=(0, 0, 0, 0),
        spmd_axis_name="pipe" if has_pipe else None,
    )

    def constrain(tree):
        if state_spec is None or not has_pipe:
            return tree
        return jax.tree.map(
            lambda t, s: _constraint_auto_only(t, s) if s is not None else t,
            tree,
            state_spec,
            is_leaf=lambda v: v is None or _is_spec(v),
        )

    overlap_hop = current_plan() is not None

    def body(carry, xs_t):
        h_state, k_state = carry
        x_t, k_t = xs_t
        # LoadMicroBatch: stage-0 slot takes the next microbatch
        # (ref: pipe/engine.py _exec_load_micro_batch:810).
        h_state = jax.tree.map(lambda s, v: s.at[0].set(v), h_state, x_t)
        k_state = k_state.at[0].set(k_t)
        h_state = constrain(h_state)
        # ForwardPass on every stage in parallel
        # (ref: pipe/engine.py _exec_forward_pass:653).
        new_state = vstage(stage_params, h_state, k_state, stage_ids)
        # Send/RecvActivation: rotate the register one stage
        # (ref: pipe/p2p.py — here one collective-permute over ICI).
        rolled = constrain(jax.tree.map(lambda s: jnp.roll(s, 1, axis=0), new_state))
        k_state = jnp.roll(k_state, 1, axis=0)
        if overlap_hop:
            # permute overlap: the boundary hop is ISSUED before the
            # exit-row collection below, so the wire rides under the
            # next iteration's stage compute instead of serializing at
            # the scan boundary (docs/overlap.md)
            rolled, new_state = _overlap_barrier((rolled, new_state))
        y = jax.tree.map(lambda s: s[-1], new_state)
        return (rolled, k_state), y

    (_, _), ys = jax.lax.scan(body, (state, key_state), (xs_in, mb_keys))
    # Microbatch m surfaces at the last stage on iteration m + P - 1.
    return jax.tree.map(lambda l: l[n_stage - 1 :], ys)


def circular_schedule_len(M: int, n_stage: int, virtual: int) -> int:
    """Scan steps the circular schedule runs: microbatches enter the
    P-slot ring in waves of P, each occupying its slot for v*P
    chunk-steps; a microbatch's LAST chunk runs at slot P-1, where its
    output is collected post-compute — no wraparound rotate, so the
    scan runs T = v*P*ceil(M/P) + P - 1 steps, every one of them
    computing.

    Bubble math (the point of the interleave, ref: Megatron interleaved
    schedule / runtime/pipe/module.py docs): one chunk-step costs
    tau/v (a stage's per-microbatch work tau split over v rounds), so
    wall-clock at M = k*P is (v*M + P - 1) * tau/v = M*tau +
    (P-1)*tau/v — the (P-1)*tau warmup/drain bubble of the plain
    schedule divided by v, i.e. bubble fraction (P-1)/(v*M + P-1).
    The SPMD dual of that wall-clock win is wasted-FLOP reduction:
    idle-slot garbage compute drops from (P-1)·L layer-applications
    per wave (plain) to (P-1)·L/v (interleaved)."""
    return virtual * n_stage * -(-M // n_stage) + n_stage - 1


def bubble_fraction(M: int, n_stage: int, virtual: int = 1) -> float:
    """Closed-form pipeline bubble fraction: the idle share of every
    stage's timeline. Plain (v=1): (P-1)/(M+P-1); interleaved:
    (P-1)/(v*M+P-1) at M = k*P — the Megatron interleaved-1F1B bound
    the ds_pipe gate pins the measured schedule against."""
    return (n_stage - 1) / (virtual * M + n_stage - 1)


def simulate_schedule(M: int, n_stage: int, virtual: int = 1):
    """MEASURED schedule accounting from iteration counts: replay the
    exact entry/exit calendar the compiled scan runs (the same rotation
    arithmetic, host-side) and count live vs total slot-steps. Returns
    {total_steps, slot_steps, live_slot_steps, bubble_fraction,
    wall_tau} where bubble_fraction = 1 - live/total slot-steps (each
    live chunk-step is useful work; everything else is warmup/drain
    garbage whose output is discarded) and wall_tau is the wall-clock
    in units of one stage's full per-microbatch work tau
    (total_steps / v). Equals the closed form at M = k*P; strictly
    worse when the last wave is padded."""
    P, v = int(n_stage), int(virtual)
    if v <= 1:
        T = M + P - 1
        live = M * P
        total = T * P
        return {
            "total_steps": T, "slot_steps": total,
            "live_slot_steps": live,
            "bubble_fraction": (total - live) / total,
            "wall_tau": float(T),
        }
    T = circular_schedule_len(M, P, v)
    # occupancy replay: slot s is live at step t iff some microbatch m
    # entered it at e = v*P*(m//P) + m%P and t - e in [0, v*P)
    live = 0
    for m in range(M):
        e = v * P * (m // P) + (m % P)
        live += min(v * P, T - e)
    total = T * P
    return {
        "total_steps": T, "slot_steps": total,
        "live_slot_steps": live,
        "bubble_fraction": (total - live) / total,
        "wall_tau": T / v,
    }


def pipeline_apply_circular(
    stage_fn: Callable,
    stage_params: Any,
    x: Any,
    rng: Optional[jax.Array] = None,
    state_spec: Any = None,
):
    """Interleaved (virtual-stage) pipeline: the circular schedule.

    stage_params: pytree of [v, P, lc, ...] leaves (partition_layers
    virtual=v — chunk r*P+p lives on physical stage p, round r). Each
    microbatch rides the P-slot ring v times; per chunk-step every stage
    applies ONE chunk (L/(v*P) layers), so the warmup/drain bubble is a
    (P-1)-chunk-step affair instead of (P-1) full-stage steps — the
    Megatron interleaved-1F1B bubble reduction expressed as SPMD
    (ref: runtime/pipe/schedule.py TrainSchedule + Megatron interleaving;
    here the schedule is the rotation arithmetic, not an instruction
    list). A microbatch's output is collected at slot P-1 the moment its
    LAST chunk computes (no wraparound rotate back to slot 0), so the
    scan runs exactly circular_schedule_len = v*P*ceil(M/P) + P - 1
    steps and the bubble fraction is (P-1)/(v*M + P-1) at M = k*P.

    stage_fn(stage_chunks, carry, mb_key, stage_idx, round) -> carry':
    applies chunk `round` of this stage's [v, lc, ...] local stack.
    Rounds >= v mark empty slots (their compute is discarded).

    Returns microbatch-major outputs [M, ...].
    """
    leaves = jax.tree.leaves(stage_params)
    v, n_stage = leaves[0].shape[0], leaves[0].shape[1]
    M = jax.tree.leaves(x)[0].shape[0]
    Mp = -(-M // n_stage) * n_stage  # pad entries to full waves
    T = circular_schedule_len(M, n_stage, v)

    # Static entry/exit calendar: microbatch m enters stage 0 at
    # t = v*n_stage*(m//n_stage) + m%n_stage; its LAST chunk runs at
    # slot n_stage-1 exactly v*n_stage - 1 steps later, where the
    # output is read post-compute (pre-rotate) — the final wraparound
    # rotate of the old calendar was a whole wasted stage-step.
    import numpy as np

    entry_step = np.full((T,), Mp, np.int32)   # Mp = "no entry" sentinel
    exit_step = np.full((T,), -1, np.int32)
    for m in range(Mp):
        e = v * n_stage * (m // n_stage) + (m % n_stage)
        if e < T:
            entry_step[e] = m
        xe = e + v * n_stage - 1
        if xe < T and m < M:
            exit_step[xe] = m
    entry_idx = jnp.asarray(entry_step)
    exit_idx = jnp.asarray(exit_step)

    def pad_leaf(leaf):
        pad = jnp.zeros((Mp - M,) + leaf.shape[1:], leaf.dtype)
        return jnp.concatenate([leaf, pad], axis=0) if Mp > M else leaf

    xs_in = jax.tree.map(pad_leaf, x)

    if rng is not None:
        mb_keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(jnp.arange(Mp))
    else:
        mb_keys = jnp.zeros((Mp, 2), jnp.uint32)

    state = jax.tree.map(
        lambda leaf: jnp.zeros((n_stage,) + leaf.shape[1:], leaf.dtype), x
    )
    out_acc = jax.tree.map(
        lambda leaf: jnp.zeros((Mp,) + leaf.shape[1:], leaf.dtype), x
    )
    rounds0 = jnp.full((n_stage,), v, jnp.int32)  # all slots empty
    key_state = jnp.zeros((n_stage,) + mb_keys.shape[1:], mb_keys.dtype)
    stage_ids = jnp.arange(n_stage)

    mesh = jax.sharding.get_abstract_mesh()
    has_pipe = not mesh.empty and mesh.shape.get("pipe", 1) > 1
    vstage = jax.vmap(
        stage_fn,
        in_axes=(1, 0, 0, 0, 0),  # params [v, P, ...] batch over dim 1
        spmd_axis_name="pipe" if has_pipe else None,
    )

    def constrain(tree):
        if state_spec is None or not has_pipe:
            return tree
        return jax.tree.map(
            lambda t, s: _constraint_auto_only(t, s) if s is not None else t,
            tree,
            state_spec,
            is_leaf=lambda n: n is None or _is_spec(n),
        )

    overlap_hop = current_plan() is not None

    def body(carry, t_idx):
        h_state, k_state, rounds, out_acc = carry
        ent, ext = entry_idx[t_idx], exit_idx[t_idx]
        done = rounds[0] >= v
        # LoadMicroBatch into the freed slot (ent == Mp means no entry
        # this step; the slot stays marked empty).
        fresh = jax.tree.map(
            lambda xs: jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(ent, Mp - 1), 0, keepdims=False),
            xs_in,
        )
        load = done & (ent < Mp)
        h_state = jax.tree.map(
            lambda s, f: s.at[0].set(jnp.where(load, f, s[0])), h_state, fresh
        )
        k_state = k_state.at[0].set(
            jnp.where(load, mb_keys[jnp.minimum(ent, Mp - 1)], k_state[0])
        )
        rounds = rounds.at[0].set(jnp.where(load, 0, jnp.minimum(rounds[0], v)))
        h_state = constrain(h_state)
        # One chunk on every stage in parallel.
        new_state = vstage(stage_params, h_state, k_state, stage_ids, rounds)
        # keep empty slots inert (their compute is garbage)
        live = (rounds < v)
        new_state = jax.tree.map(
            lambda n, o: jnp.where(
                live.reshape((n_stage,) + (1,) * (n.ndim - 1)), n, o
            ),
            new_state, h_state,
        )
        # Rotate one stage — issued BEFORE the exit collection under an
        # overlap plan, so the boundary hop rides under the collection
        # and the next chunk's compute (docs/overlap.md).
        rolled = constrain(jax.tree.map(
            lambda s: jnp.roll(s, 1, axis=0), new_state))
        if overlap_hop:
            rolled, new_state = _overlap_barrier((rolled, new_state))
        # Exit: the slot at stage P-1 on its LAST round just computed a
        # finished microbatch — collect it post-compute, pre-rotate
        # (predicated no-op write when ext < 0), saving the wraparound
        # rotate and the whole stage-step it used to cost.
        take = (ext >= 0) & (rounds[n_stage - 1] == v - 1)
        out_acc = jax.tree.map(
            lambda acc, s: jax.lax.dynamic_update_index_in_dim(
                acc,
                jnp.where(
                    take,
                    s[n_stage - 1],
                    jax.lax.dynamic_index_in_dim(acc, jnp.maximum(ext, 0), 0,
                                                 keepdims=False),
                ),
                jnp.maximum(ext, 0), 0,
            ),
            out_acc, new_state,
        )
        # The slot wrapping P-1 -> 0 advances a round.
        k_state = jnp.roll(k_state, 1, axis=0)
        rounds = jnp.roll(rounds, 1, axis=0).at[0].add(1)
        return (rolled, k_state, rounds, out_acc), ()

    (h_state, k_state, rounds, out_acc), _ = jax.lax.scan(
        body, (state, key_state, rounds0, out_acc), jnp.arange(T)
    )
    return jax.tree.map(lambda l: l[:M], out_acc)


def stage_slice_keys(mb_key, n_layers: int, stage_idx, layers_per_stage: int):
    """Per-layer dropout keys for one stage, matching the flat model's
    `jax.random.split(rng, n_layers)` exactly: split over ALL layers,
    then slice this stage's span — so pipe=P reproduces pipe=1 numerics."""
    all_keys = jax.random.split(mb_key, n_layers)
    return jax.lax.dynamic_slice_in_dim(
        all_keys, stage_idx * layers_per_stage, layers_per_stage, axis=0
    )
