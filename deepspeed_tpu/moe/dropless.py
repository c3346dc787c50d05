"""Dropless (capacity-factor-free) expert-parallel MoE routing.

MegaBlocks-style routing (Gale et al., arXiv 2211.15841) rebuilt for a
static-shape SPMD world: instead of the GShard/Switch fixed [X, C]
per-expert buffers of `sharded_moe.py` — which either drop tokens past
capacity or pad capacity to waste — tokens are *sorted by expert id*
and the expert FFN runs as a grouped (ragged) GEMM over the sorted
assignment buffer. No token is ever dropped and no expert slot is ever
padded, regardless of routing skew.

Two dispatch wires share one gating authority:

- ragged (EP=1, and the serving ragged batch): stable-sort the T*K
  (token, expert) assignments by expert id, run the expert MLP with
  `jax.lax.ragged_dot` (grouped GEMM over contiguous expert segments;
  a masked-scan oracle covers backends without it), and combine with a
  weighted `segment_sum` back to token order.
- a2a (EP=N training): tokens regroup as [G, T/G] over the 'expert'
  mesh axis, dispatch group-locally into a [G, X, C, E] frame with the
  per-group dropless bound C = T/G (each local token contributes at
  most one assignment per expert, so nothing can overflow — dropless
  by construction, not by tuning), and two explicit single-axis
  reshard constraints move the frame group-sharded -> expert-sharded
  and back: the XLA partitioner emits exactly the reference's
  dispatch/combine all-to-all pair (ref: deepspeed/moe/sharded_moe.py
  _AllToAll:95) with 'expert'-axis replica groups, which the schedule
  analyzer (S005/S007) attributes per step.

A chip that holds a SHARE of the experts under expert parallelism
(`held=(start, count)`, TransformerConfig.experts_held) routes over the
full width and takes the ragged wire over its own pairs alone
(_held_wire): the pairs that landed on a held expert are sorted to the
front of a static list of T x min(k, count) rows, the trivial bound no
skew can pass, so no held pair is ever dropped, walked in chunks of
2 T rows: the first always, a later one only where the held pairs
reach it; pairs routed elsewhere add nothing (their chips add it). On a
v5e the grouped product's time follows the rows INSIDE its groups, with
a smaller charge a buffer row, and it leaves the rows OUTSIDE them
unwritten, forward and backward (PERF.md §6, PR 55: 5.4 ms forward +
backward for 16,384 live rows of E 2048 x F 1024 in a buffer of as
many, 10.8 ms in one of 131,072, 37.7 ms with all of those live), and
its weights' gradient does not read them (PR 61: the same bits with
zeros, data or 100s there). Inside a chunk no row is moved by a
scatter: rows_of and sum_to_tokens, each the other's transpose.

Gate math runs in fp32 regardless of compute dtype (the reference
casts at TopKGate.forward) and generalizes to any top_k <= n_experts:
selection by `lax.top_k` over the (optionally noised) logits, combine
weights renormalized for k > 1 (the GShard top-2 convention) and raw
softmax mass for k = 1 (the Switch convention) — bit-matching the
capacity-factor paths wherever those would not drop; `renormalize`
(TransformerConfig.moe_norm_topk_prob, HF norm_topk_prob) overrides
the rule, False keeping the raw mass at any k (OLMoE). The router
z-loss (ST-MoE, arXiv 2202.08906) and the load-balance aux loss ride
the return value so the training loss can thread both.
"""

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .sharded_moe import _apply_noise, _load_balance_loss, _one_hot


@dataclasses.dataclass(frozen=True)
class DroplessOut:
    """Result of one dropless MoE FFN application."""

    out: Any      # [T, E] combined expert outputs, compute dtype
    l_aux: Any    # scalar fp32 load-balance loss (1.0 at uniform)
    z_loss: Any   # scalar fp32 router z-loss (ST-MoE logsumexp^2)
    counts: Any   # [X] int32 tokens routed per expert (the census)
    # held pairs the wire did NOT compute (scalar int32): 0 by the
    # buffer's bound, counted all the same (the held wire alone)
    dropped: Any = 0
    # chunks of the held wire's list that ran (scalar int32): 1 while
    # the held pairs fit the first, which runs at any load
    chunks_run: Any = 0


def router_z_loss(logits) -> jnp.ndarray:
    """ST-MoE router z-loss: mean over tokens of logsumexp(logits)^2 —
    keeps router logits small so the fp32 gate softmax stays sharp
    without saturating (arXiv 2202.08906 eq. 5)."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    return jnp.mean(jnp.square(lse))


def chosen_scores(scores, idx) -> jnp.ndarray:
    """scores [T, X] at idx [T, K] -> [T, K]: `take_along_axis` by a
    comparison against an iota. One term of each sum is nonzero, so the
    value is the chosen score bit for bit, and its transpose is a select
    and a sum where a gather's is a scatter-add: an element-indexed
    gather or scatter costs a v5e ~8.7 ns an element whatever it moves
    (1.04 ms for the 131,072 pairs of a routed training layer, beside a
    row gather of 134 MB in 0.21: PERF.md §5). The tokens stand LAST in
    the comparison, so the sum over the experts adds whole registers:
    0.016 ms at those pairs where the sum along the lanes of a
    [T, K, X] comparison took 0.417 (PERF.md §6, PR 59)."""
    hit = idx.T[:, None, :] == jnp.arange(
        scores.shape[-1], dtype=idx.dtype)[:, None]  # [K, X, T]
    return jnp.where(hit, scores.T, 0).sum(1).T


def sigmoid_topk_gating(logits, top_k: int, bias=None,
                        renormalize: Optional[bool] = None,
                        scale: float = 1.0):
    """Sigmoid-scored top-k (DeepSeek-V3 class routers): each expert's
    score is the sigmoid of its own logit, in float32; the k largest
    are chosen (ties to the lowest index), their scores divided by
    their sum when `renormalize`, then multiplied by `scale`. `bias`
    [X] (a layer's `expert_bias`) is added to the scores for the CHOICE
    alone: the weights are the chosen experts' unbiased scores. No
    groups. The one place it is written: serving
    (inference/model.py _sigmoid_topk_gating) and the training gate
    below call it. logits [T, X] f32 -> (idx [T, k] int32, weights
    [T, k] f32)."""
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(
        scores if bias is None else scores + bias.astype(jnp.float32), top_k)
    # (not top_k's own values where there is no bias: the same floats,
    # but their gradient is a scatter-add over the pairs)
    wts = chosen_scores(scores, idx)
    if renormalize:
        wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-20)
    return idx, wts * scale


def dropless_topk_gating(
    logits,
    top_k: int,
    rng=None,
    noisy_gate_policy: Optional[str] = None,
    renormalize: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Capacity-free top-k gate (generic k; math fp32).

    logits: [T, X] router outputs. Selection runs on the noised logits
    (train-time exploration), combine weights come from the CLEAN
    softmax — exactly the capacity paths' split, so where those would
    keep every token the two agree bitwise.

    renormalize: None = (top_k > 1), matching top1_gating (raw softmax
    mass) and top2_gating (pair renormalized to sum 1).

    Returns (expert_idx [T, K] int32, weights [T, K] fp32, l_aux,
    z_loss). No capacity, no keep-mask: every row routes.
    """
    T, X = logits.shape
    if not 1 <= top_k <= X:
        raise ValueError(
            f"moe top_k must be in [1, {X}] for {X} experts, got {top_k}")
    if renormalize is None:
        renormalize = top_k > 1
    logits = logits.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    z_loss = router_z_loss(logits)

    noisy = _apply_noise(logits, rng, noisy_gate_policy)
    _, idx = jax.lax.top_k(noisy, top_k)  # [T, K], ties -> lowest index
    weights = chosen_scores(gates, idx)  # [T, K] fp32
    if renormalize:
        weights = weights / jnp.maximum(
            jnp.sum(weights, axis=-1, keepdims=True),
            jnp.finfo(jnp.float32).eps)

    # load-balance loss over the FIRST choice — the formula both
    # capacity paths use (top1gating/top2gating compute l_aux on mask1)
    l_aux = _load_balance_loss(gates, _one_hot(idx[:, 0], X))
    return idx, weights, l_aux, z_loss


def expert_counts(expert_idx, n_experts: int) -> jnp.ndarray:
    """[X] int32 assignment census from [T, K] (or flat) expert ids, by
    a comparison (chosen_scores says why not a scatter-add of ones)."""
    flat = expert_idx.reshape(-1)
    return (flat[:, None] == jnp.arange(n_experts, dtype=flat.dtype)).sum(
        0, dtype=jnp.int32)


@jax.custom_vjp
def sort_pairs(key, vals):
    """ONE stable sort of the flat pairs by `key` [A] int32 that carries
    each pair's slot and its value `vals` [A] along: returns (sorted
    key, order, sorted vals), order the stable argsort of key bit for
    bit and the other two `key[order]`, `vals[order]`, with no gather.
    The file's one way to permute per-pair values: what jax derives for
    a sort's payload is a gather forward and a scatter backward over
    the A elements (chosen_scores has their price), so the cotangent of
    `vals` goes back by a sort too, on `order`."""
    return jax.lax.sort(
        (key, jax.lax.iota(jnp.int32, key.shape[0]), vals),
        num_keys=1, is_stable=True)


def _sort_pairs_fwd(key, vals):
    out = sort_pairs(key, vals)
    return out, out[1]


def _sort_pairs_bwd(order, cts):
    # the inverse of a permutation is the sort of its indices
    _, d_vals = jax.lax.sort((order, cts[2]), num_keys=1)
    return None, d_vals


sort_pairs.defvjp(_sort_pairs_fwd, _sort_pairs_bwd)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TokenOrder:
    """The rows of one chunk of the held wire's list, and the same rows
    in TOKEN order: what rows_of and sum_to_tokens move rows by."""

    src: Any    # [C] int32: the token of each row
    live: Any   # [C] bool: the row is a held pair's
    ids: Any    # [C] int32: src ascending, the dead rows (n_tokens) last
    perm: Any   # [C] int32: the row each of ids came from
    n_tokens: int = dataclasses.field(metadata=dict(static=True))


def token_order(src, live, n_tokens: int) -> TokenOrder:
    """ONE stable sort of a chunk's token ids with the row's position as
    payload, a dead row keyed n_tokens so that it sorts behind every
    token (sort_pairs' `lax.sort`; 0.03 ms for 32,768 ids)."""
    ids, perm = jax.lax.sort(
        (jnp.where(live, src, n_tokens),
         jax.lax.iota(jnp.int32, src.shape[0])), num_keys=1, is_stable=True)
    return TokenOrder(src, live, ids, perm, n_tokens)


@jax.custom_vjp
def rows_of(tokens, order: TokenOrder):
    """tokens [T, E] -> the chunk's rows [C, E], a row gather: a live
    row is its token's; a dead row is nobody's (it lies in no group of
    the products, which neither read it nor write its cotangent), so it
    holds whatever the gather put there, as a constant. The transpose
    is therefore sum_to_tokens, which leaves the dead rows out, where
    jax would derive a row scatter-add of every row (twelve times the
    gather's time on a v5e, PERF.md section 6, PR 59) behind a select
    of the dead rows at either end."""
    return tokens[order.src]


@jax.custom_vjp
def sum_to_tokens(rows, order: TokenOrder):
    """rows [C, E] -> [T, E]: a token's live rows summed, a dead row
    left out whatever it holds (an Inf there must reach no token);
    `segment_sum(where(live, rows, 0), src, T)` with no scatter: the
    rows are gathered into token order, where a tile of tokens owns one
    contiguous range of them, and summed by a banded one-hot product
    (ops/pallas/token_sum.py), in float32 with one rounding at the end
    (the bits a v5e's own bf16 scatter-add gives: PERF.md section 6,
    PR 61). Its transpose is rows_of with the dead rows zero."""
    from ..ops.pallas.token_sum import token_tile_sum

    return token_tile_sum(rows[order.perm], order.ids, order.n_tokens)


rows_of.defvjp(lambda tokens, order: (rows_of(tokens, order), order),
               lambda order, ct: (sum_to_tokens(ct, order), None))
sum_to_tokens.defvjp(
    lambda rows, order: (sum_to_tokens(rows, order), order),
    lambda order, ct: (jnp.where(order.live[:, None], rows_of(ct, order), 0),
                       None))


def sort_by_expert(expert_idx) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stable-sort the flat assignment list by expert id.

    expert_idx: [T, K]. Returns (order [A], src [A], sorted_experts [A])
    with A = T*K: `order` permutes flat assignment slots into expert-
    contiguous runs, `src` is the source TOKEN of each sorted slot.
    Stability makes the permutation a pure function of the routing
    decision — identical across EP layouts, so the grouped GEMM sees
    the same row order no matter how the mesh is carved.
    """
    K = expert_idx.shape[1]
    flat = expert_idx.reshape(-1)
    sorted_experts, order = jax.lax.sort(
        (flat, jax.lax.iota(jnp.int32, flat.shape[0])),
        num_keys=1, is_stable=True)
    return order, order // K, sorted_experts


def grouped_mm(xs, w, counts, impl: str = "ragged"):
    """Grouped (ragged) GEMM: rows of `xs` [A, E] are expert-contiguous
    segments sized by `counts` [X]; each segment contracts with its own
    expert weight from `w` [X, E, F] -> [A, F].

    impl: 'ragged' = lax.ragged_dot; 'dense' = the X-pass masked scan
    (the correctness oracle)."""
    if impl == "ragged":
        return jax.lax.ragged_dot(xs, w.astype(xs.dtype),
                                  counts.astype(jnp.int32))
    if impl != "dense":
        raise ValueError(f"unknown grouped_mm impl {impl!r}")
    offsets = jnp.cumsum(counts) - counts  # [X]
    pos = jnp.arange(xs.shape[0], dtype=jnp.int32)

    def body(acc, ws):
        w_e, off, n = ws
        seg = ((pos >= off) & (pos < off + n))[:, None]
        return acc + jnp.where(seg, xs @ w_e.astype(xs.dtype), 0), None

    acc0 = jnp.zeros((xs.shape[0], w.shape[-1]), xs.dtype)
    out, _ = jax.lax.scan(
        body, acc0, (w, offsets.astype(jnp.int32), counts.astype(jnp.int32)))
    return out


def _expert_mlp_sorted(xs, sorted_experts, counts, w_in, w_out, w_gate,
                       b_in, b_out, act, impl):
    """The expert MLP over the expert-sorted assignment buffer."""
    if w_gate is not None:
        inner = act(grouped_mm(xs, w_gate, counts, impl)) \
            * grouped_mm(xs, w_in, counts, impl)
    else:
        inner = grouped_mm(xs, w_in, counts, impl)
        if b_in is not None:
            inner = inner + b_in[sorted_experts].astype(xs.dtype)
        inner = act(inner)
    ys = grouped_mm(inner, w_out, counts, impl)
    if b_out is not None:
        ys = ys + b_out[sorted_experts].astype(xs.dtype)
    return ys


def _ragged_wire(tokens, idx, weights, counts, w_in, w_out, w_gate,
                 b_in, b_out, act, impl):
    """EP=1 / serving wire: sort -> grouped GEMM -> segment-sum."""
    T, K = idx.shape
    # the scopes a trace tells the wire's three parts by (serving reads
    # them; under training they nest inside `mlp`)
    with jax.named_scope("moe_route"):
        sorted_experts, order, wf = sort_pairs(
            idx.reshape(-1), weights.reshape(-1))
        src = order // K
        xs = tokens[src]  # [A, E] expert-contiguous
    with jax.named_scope("moe_experts"):
        ys = _expert_mlp_sorted(xs, sorted_experts, counts, w_in, w_out,
                                w_gate, b_in, b_out, act, impl)
    with jax.named_scope("moe_combine"):
        return jax.ops.segment_sum(
            ys * wf.astype(tokens.dtype)[:, None], src, num_segments=T)


def held_rows_bound(n_tokens: int, top_k: int, held_count: int) -> int:
    """Rows of the held wire's buffer: a token's k choices are distinct
    experts, so at most min(k, count) of them are held. No skew passes
    it: nothing is ever dropped."""
    return n_tokens * min(top_k, held_count)


def held_chunk_rows(n_tokens: int, top_k: int, held_count: int) -> int:
    """Rows of one chunk of the held wire's list: 2 T, or T where the
    bound is an odd multiple of T."""
    return (2 if min(top_k, held_count) % 2 == 0 else 1) * n_tokens


def _held_wire(tokens, idx, weights, counts, held, w_in, w_out, w_gate,
               act, impl):
    """The ragged wire of a chip that holds experts [start, start +
    count) of a full-width router: the pairs that landed on a held
    expert sort to the front of a list of held_rows_bound rows by held
    expert (stable: a pure function of the routing); the list is walked
    in CHUNKS of 2 T rows (T where min(k, count) is odd), each one
    gather, one grouped product a projection over the part of every
    expert's run that lies in the chunk, one weighted sum back to the
    tokens in token order (sum_to_tokens). The FIRST chunk always runs;
    a later one that starts past the last held pair is skipped at run
    time (`lax.cond`): an
    even router's T pairs a layer, and up to twice as many, take ONE
    chunk, the worst skew all of them, and none is ever dropped. A
    chunk costs its rows whether they are live or not, ~9.5 ms a layer
    of a 517 ms step (PERF.md §5, PR 61), and moves no row by a scatter:
    the rows come by a row gather (rows_of) and go back, forward,
    recomputed and as that gather's transpose, by sum_to_tokens: a sort
    of the chunk's ids by token 0.02 ms, a row gather into that order
    1.1 (its 128 MiB operand is out of the compiler's fast memory) and
    a banded one-hot product 0.12-0.45 by the live rows, where the row
    scatter-add it replaced took 2.6-2.9 and the selects of dead rows
    around it 0.4 each (scripts/wire_bench.py); the row gathers are 5
    of the 9.5, the products' elementwise and the weighted sum ~3. So a
    layer whose load crosses 2 T pays a second chunk (with chunks of T
    rows, the even load itself, a step took one chunk or two a layer by
    the batch's luck), and a layer that holds NO pair pays the first all
    the same: a chip's step takes the time of its shape at any load up
    to 2 T a layer, as the steps of the job's other chips do. A job
    that trains has no such layer; a run of one chip's cut alone has,
    and a first chunk skipped there turns three stray tokens into 15 ms
    a layer, on or off by the seed (PERF.md §6, PR 55, third round).
    Finer chunks would smooth the second chunk's step
    and were not taken: 32 chunks of T / 4 rows compiled to 13.7 GB of
    temporaries for a described v5e where these take 5.5 (PERF.md §6,
    PR 55). Each chunk is recomputed in the backward
    (`jax.checkpoint`), so the wire holds one chunk's rows at a time
    whatever the bound. idx, weights [T, K] over all X experts; counts
    [X] the full census. Outside the chunks nothing is indexed by pair:
    a pair's slot and weight ride the sort (sort_pairs), its held flag is
    the sorted key under `count`. Returns (out [T, E], held pairs NOT
    computed, by the groups of the products that ran: 0; the chunks
    that ran)."""
    start, count = held
    T, K = idx.shape
    bound = held_rows_bound(T, K, count)
    C = held_chunk_rows(T, K, count)
    n_chunks = bound // C
    with jax.named_scope("moe_route"):
        local = idx.reshape(-1) - start
        is_held = (local >= 0) & (local < count)
        # held pairs first, by held expert; the rest behind them (the
        # list is the sorted arrays' prefix: where count < K it is
        # shorter than the T K pairs)
        key, order, wf = sort_pairs(
            jnp.where(is_held, local, count), weights.reshape(-1))
        src = (order[:bound] // K).reshape(n_chunks, C)
        live = key[:bound] < count
        wf = wf[:bound].astype(tokens.dtype).reshape(n_chunks, C)
        held_counts = jax.lax.dynamic_slice_in_dim(
            counts.astype(jnp.int32), start, count)
        ends = jnp.cumsum(held_counts)
        n_held = ends[-1]

    @jax.checkpoint
    def chunk(c, src_c, wf_c, live_c):
        """What the pairs of rows [c C, (c + 1) C) of the list add to
        the tokens (its inputs are all the backward keeps of it). A row
        past the last held pair lies in no group: what a grouped product
        leaves there, forward or backward, is whatever the buffer held
        (`ragged_dot` skips it: that is why its time follows the live
        rows), so such a row is LEFT OUT where rows go back to tokens,
        forward (the combine) and backward (the gather's transpose):
        sum_to_tokens selects it to zero, since a weight of 0 would turn
        an Inf there into a NaN of every token's."""
        lo = c * C

        def run():
            with jax.named_scope("moe_route"):
                # the part of each held expert's run inside [lo, lo + C)
                part = jnp.clip(jnp.minimum(ends, lo + C)
                                - jnp.maximum(ends - held_counts, lo), 0, C)
                order = token_order(src_c, live_c, T)
                rows = rows_of(tokens, order)
            with jax.named_scope("moe_experts"):
                ys = _expert_mlp_sorted(rows, None, part, w_in, w_out,
                                        w_gate, None, None, act, impl)
            with jax.named_scope("moe_combine"):
                return (sum_to_tokens(ys * wf_c[:, None], order),
                        jnp.sum(part), jnp.int32(1))

        return jax.lax.cond(
            (c == 0) | (lo < n_held), run,
            lambda: (jnp.zeros_like(tokens), jnp.int32(0), jnp.int32(0)))

    # (the sum rides AROUND the checkpointed chunk: inside it, every
    # chunk's incoming sum would be kept for the backward)
    def add(carry, xs):
        return jax.tree.map(jnp.add, carry, chunk(*xs)), None

    (out, computed, chunks_run), _ = jax.lax.scan(
        add, (jnp.zeros_like(tokens), jnp.int32(0), jnp.int32(0)),
        (jnp.arange(n_chunks, dtype=jnp.int32), src, wf,
         live.reshape(n_chunks, C)))
    # held pairs no chunk multiplied: a wrong gradient if ever above 0,
    # so it is counted from the groups of the products that RAN (a
    # chunk skipped wrongly, or a run cut at a chunk's edge, shows here)
    return out, n_held - computed, chunks_run


def _a2a_wire(tokens, idx, weights, ep_size, w_in, w_out, w_gate,
              b_in, b_out, act, shard):
    """EP=N wire: group-local dispatch into the [G, X, C, E] frame with
    the per-group dropless bound C = T/G, then two single-axis reshards
    (group-sharded <-> expert-sharded) that the partitioner lowers to
    the dispatch/combine all-to-all pair over the 'expert' groups."""
    T, E = tokens.shape
    X = w_in.shape[0]
    G = ep_size
    Tl = T // G
    C = Tl  # dropless bound: <=1 assignment per (local token, expert)
    dtype = tokens.dtype

    tg = tokens.reshape(G, Tl, E)
    idxg = idx.reshape(G, Tl, -1)
    wg = weights.reshape(G, Tl, -1)
    if shard is not None:
        tg = shard(tg, "expert", None, None)

    onehot = _one_hot(idxg, X)                      # [G, Tl, K, X] fp32
    mask = jnp.sum(onehot, axis=2)                  # [G, Tl, X] 0/1
    pos = jnp.cumsum(mask, axis=1) - mask           # [G, Tl, X]
    d = mask[..., None] * _one_hot(pos.astype(jnp.int32), C)  # [G,Tl,X,C]

    z = jnp.einsum("gtxc,gte->gxce", d.astype(dtype), tg)
    if shard is not None:
        z = shard(z, None, "expert", None, None)    # dispatch all-to-all
    if w_gate is not None:
        inner = act(jnp.einsum("gxce,xef->gxcf", z, w_gate.astype(dtype))) \
            * jnp.einsum("gxce,xef->gxcf", z, w_in.astype(dtype))
    else:
        inner = jnp.einsum("gxce,xef->gxcf", z, w_in.astype(dtype))
        if b_in is not None:
            inner = inner + b_in[None, :, None, :].astype(dtype)
        inner = act(inner)
    y = jnp.einsum("gxcf,xfe->gxce", inner, w_out.astype(dtype))
    if b_out is not None:
        # padding slots pick up the bias too; the combine one-hot below
        # zeroes them before any token sees the frame
        y = y + b_out[None, :, None, :].astype(dtype)
    if shard is not None:
        y = shard(y, "expert", None, None, None)    # combine all-to-all
    gatew = jnp.sum(onehot * wg[..., None], axis=2)  # [G, Tl, X]
    comb = (d * gatew[..., None]).astype(dtype)
    out = jnp.einsum("gtxc,gxce->gte", comb, y)
    if shard is not None:
        out = shard(out, "expert", None, None)
    return out.reshape(T, E)


def dropless_apply(
    tokens, expert_idx, weights, counts, w_in, w_out, w_gate=None,
    b_in=None, b_out=None, *, act, impl: str = "ragged",
):
    """The ragged wire on PRE-COMPUTED routing decisions — the serving
    entry point (inference/model.py _mlp): the scheduler's mixed
    prefill/decode rows arrive as one flat [T, E] batch and leave as
    per-expert contiguous grouped-GEMM segments in the same compiled
    program. expert_idx [T, K], weights [T, K], counts [X]."""
    return _ragged_wire(tokens, expert_idx, weights, counts, w_in,
                        w_out, w_gate, b_in, b_out, act, impl)


def route(tokens, router_w, *, top_k: int = 1,
          renormalize: Optional[bool] = None, rng=None,
          noisy_gate_policy: Optional[str] = None,
          scoring: str = "softmax", choice_bias=None, scale: float = 1.0):
    """The router of dropless_moe_ffn, alone: float32 logits of the
    compute-dtype tokens [T, E] over ALL of router_w's [E, X] experts,
    then the gate `scoring` names. The one place the training router's
    precision is written, so what holds it to a reference holds the
    step's (benchmarks/runners/train_routed.py feeds it the reference's
    own layer inputs). Returns (idx [T, K] int32, weights [T, K] f32,
    l_aux, z_loss)."""
    logits = tokens.astype(jnp.float32) @ router_w.astype(jnp.float32)
    if scoring == "sigmoid":
        idx, weights = sigmoid_topk_gating(
            logits, top_k, choice_bias, renormalize, scale)
        return idx, weights, jnp.float32(0.0), jnp.float32(0.0)
    if choice_bias is not None or scale != 1.0:
        raise NotImplementedError(
            "a choice bias or a scale on a softmax router")
    return dropless_topk_gating(
        logits, top_k, rng=rng, noisy_gate_policy=noisy_gate_policy,
        renormalize=renormalize)


def dropless_moe_ffn(
    tokens,          # [T, E] flattened tokens, compute dtype
    router_w,        # [E, X]
    w_in,            # [X, E, F]
    w_out,           # [X, F, E]
    w_gate=None,     # [X, E, F] (gated MLP)
    b_in=None,       # [X, F]
    b_out=None,      # [X, E]
    *,
    act,
    top_k: int = 1,
    renormalize: Optional[bool] = None,  # None = (top_k > 1)
    rng=None,
    noisy_gate_policy: Optional[str] = None,
    shard=None,      # fn(x, *mesh axis names) sharding constraint
    ep_size: int = 1,
    impl: str = "ragged",
    scoring: str = "softmax",
    choice_bias=None,  # [X]: added to the scores for the CHOICE alone
    scale: float = 1.0,
    held: Optional[Tuple[int, int]] = None,
) -> DroplessOut:
    """Dropless dispatch -> grouped expert MLP -> combine.

    ep_size > 1 (and T divisible by it) selects the a2a wire — the
    expert-parallel frame whose dispatch/combine pair the schedule
    analyzer attributes; otherwise the sorted ragged wire runs (zero
    padding — the serving path and the EP=1 training path). Both wires
    share the gating authority, so the routed math is identical and
    EP=1 == EP=N up to float reassociation (test-pinned).

    scoring "sigmoid" (with `choice_bias` and `scale`): the
    sigmoid_topk_gating router, which has neither auxiliary loss (both
    are handed back 0). held (start, count): router_w spans all X
    experts, the stacks hold `count`; the held wire computes this
    chip's pairs alone. `counts` is the FULL census either way.
    """
    with jax.named_scope("moe_route"):
        idx, weights, l_aux, z_loss = route(
            tokens, router_w, top_k=top_k, renormalize=renormalize, rng=rng,
            noisy_gate_policy=noisy_gate_policy, scoring=scoring,
            choice_bias=choice_bias, scale=scale)
        counts = expert_counts(idx, router_w.shape[-1])
    if held is not None:
        if b_in is not None or b_out is not None or ep_size > 1:
            raise NotImplementedError(
                "a held share of experts with biases or an expert axis")
        out, dropped, chunks_run = _held_wire(
            tokens, idx, weights, counts, held, w_in, w_out, w_gate, act,
            impl)
        return DroplessOut(out=out, l_aux=l_aux, z_loss=z_loss,
                           counts=counts, dropped=dropped,
                           chunks_run=chunks_run)
    elif ep_size > 1 and tokens.shape[0] % ep_size == 0:
        out = _a2a_wire(tokens, idx, weights, ep_size, w_in, w_out,
                        w_gate, b_in, b_out, act, shard)
    else:
        out = _ragged_wire(tokens, idx, weights, counts, w_in, w_out,
                           w_gate, b_in, b_out, act, impl)
    return DroplessOut(out=out, l_aux=l_aux, z_loss=z_loss, counts=counts)
