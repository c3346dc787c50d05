"""Ring attention: context parallelism by rotating KV around the seq axis.

The long-context alternative to Ulysses (SURVEY §5: "ring/blockwise
attention via shard_map collective-permute — noted as extension"; absent
from the reference snapshot, which only ships Ulysses
deepspeed/sequence/layer.py). Design follows the blockwise/ring
attention recipe: queries stay resident on their sequence shard; K/V
shards rotate around the 'seq' ring with `jax.lax.ppermute`, and each
hop's partial attention folds into a numerically-stable online softmax
(the flash-attention accumulator (m, l, acc) — so the full [S, S] score
matrix never materializes and per-device memory is O(S/n · S/n) per
hop).

Causality by ring position: a KV shard strictly ahead of the query
shard contributes nothing (its hop is masked entirely), the diagonal
hop applies the exact in-shard causal mask, earlier shards attend
densely. Ulysses moves activations twice per layer (all-to-all) but
runs LOCAL attention; the ring moves K/V n-1 times but never reshards
heads — preferable when heads < seq-parallel degree or for very long
sequences where all-to-all volume dominates.
"""

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas import kernels_runnable


def _merge_partials(out, lse, o_hop, lse_hop):
    """Fold one hop's NORMALIZED partial attention (o, logsumexp) into
    the running result: o_c = Σ o_i·exp(lse_i − lse_c),
    lse_c = logaddexp(lse_i). Exact — the same identity the flash
    kernels use internally, applied across hops.
    out [B,Sl,H,D] f32; lse/lse_hop [B,H,Sl] f32."""
    lse_new = jnp.logaddexp(lse, lse_hop)
    w_old = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
    w_hop = jnp.exp(lse_hop - lse_new).transpose(0, 2, 1)[..., None]
    return out * w_old + o_hop.astype(jnp.float32) * w_hop, lse_new


def ring_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, axis_name: str = "seq",
    use_flash: bool = False, block_q: int = 512, block_k: int = 1024,
    with_lse: bool = False,
):
    """Causal attention over sequence-sharded q/k/v INSIDE a shard_map
    whose manual axes include `axis_name`.

    q: [B, S_local, H, D]; k/v: [B, S_local, KV, D] (GQA consumed
    in place — never repeated through the ring's ICI hops).
    Returns [B, S_local, H, D].

    use_flash=True runs each hop through the Pallas flash kernels
    (flash_attention_with_lse) and merges hop partials by logsumexp —
    per-hop memory drops from the dense [B, H, Sl, Sl] f32 logits to
    the kernels' VMEM tiles, which is what makes 16k+ tokens per shard
    feasible. The diagonal hop runs the causal kernel; strictly-behind
    hops run dense (non-causal); hops strictly AHEAD of this shard are
    skipped entirely under lax.cond (no kernel launch — the old path
    computed full logits and discarded them)."""
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Sl, H, D = q.shape
    KV = k.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]

    if use_flash:
        from ..ops.pallas.flash_attention import flash_attention_with_lse

        hop_fn = partial(flash_attention_with_lse,
                         block_q=block_q, block_k=block_k)
    else:
        hop_fn = partial(_dense_hop, n_rep=H // KV)

    # diagonal hop (this shard's own KV): exact causal
    out, lse = hop_fn(q, k, v, causal=True)
    out = out.astype(jnp.float32)

    def hop(carry, t):
        out, lse, k_cur, v_cur = carry
        # rotate FIRST: after t rotations we hold shard (my - t) % n
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        # live iff the source shard is strictly behind this one:
        # src = my - t (mod n) < my  ⇔  t <= my for t in 1..n-1
        live = t <= my

        def attend(args):
            out, lse, k_cur, v_cur = args
            o_hop, lse_hop = hop_fn(q, k_cur, v_cur, causal=False)
            return _merge_partials(out, lse, o_hop, lse_hop)

        out, lse = jax.lax.cond(
            live, attend, lambda a: (a[0], a[1]), (out, lse, k_cur, v_cur))
        return (out, lse, k_cur, v_cur), None

    (out, lse, _, _), _ = jax.lax.scan(
        hop, (out, lse, k, v), jnp.arange(1, n))
    if with_lse:
        return out.astype(q.dtype), lse
    return out.astype(q.dtype)


def _dense_hop(q, k, v, causal: bool, n_rep: int = 1):
    """jnp hop for CPU/testing: returns (normalized o, lse) like the
    flash kernel (GQA repeat materialized — oracle path only)."""
    B, Sl, H, D = q.shape
    scale = 1.0 / np.sqrt(D)
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sl, k.shape[1]), bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    m = logits.max(axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p / l[..., None], v.astype(jnp.float32))
    return o.astype(q.dtype), m + jnp.log(l)


def _ring_bwd(q, k, v, out, lse, do, axis_name: str,
              use_flash: bool, block_q: int, block_k: int):
    """The ring-attention BACKWARD, itself a ring (inside shard_map).

    Per live hop the flash backward kernels run against the GLOBAL
    (out, lse): p = exp(s − lse_global) and delta = Σ do·out_global are
    then exactly the merged softmax's probabilities and row dots, so
    each hop's (dq, dk, dv) contributions are the true global-softmax
    gradients. dq accumulates locally; the (dk, dv) accumulators RIDE
    the KV rotation — after the full circle they arrive back at their
    home shard. This keeps every hop's memory at kernel-tile scale in
    the backward too (a plain autodiff transpose would rematerialize
    dense per-hop logits)."""
    n = jax.lax.axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    B, Sl, H, D = q.shape
    KV = k.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]

    # (the dense ring path differentiates through plain autodiff of its
    # shard_mapped forward; only the flash route needs this hand ring)
    assert use_flash, "_ring_bwd backs the flash route only"
    from ..ops.pallas.flash_attention import _flash_bwd

    bq = min(block_q, Sl)
    bk = min(block_k, Sl)

    def to_bh(x):
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(B * h, Sl, D)

    q_bh, do_bh, o_bh = to_bh(q), to_bh(do), to_bh(out)
    lse_bh = lse.reshape(B * H, Sl)

    def hop_bwd(k_cur, v_cur, causal):
        dq_h, dk_h, dv_h = _flash_bwd(
            q_bh, to_bh(k_cur), to_bh(v_cur), None, o_bh, lse_bh,
            do_bh, causal, bq, bk, H, KV)
        back = lambda x, h: x.reshape(B, h, Sl, D).transpose(0, 2, 1, 3)
        return back(dq_h, H), back(dk_h, KV), back(dv_h, KV)

    dq0, dk0, dv0 = hop_bwd(k, v, causal=True)
    dq = dq0.astype(jnp.float32)

    def hop(carry, t):
        dq, dk_acc, dv_acc, k_cur, v_cur = carry
        # rotate KV AND its gradient accumulators together: after the
        # full circle each (dk, dv) lands back on its home shard
        k_cur, v_cur, dk_acc, dv_acc = (
            jax.lax.ppermute(x, axis_name, perm)
            for x in (k_cur, v_cur, dk_acc, dv_acc))
        live = t <= my

        def attend(args):
            dq, dk_acc, dv_acc, k_cur, v_cur = args
            dq_h, dk_h, dv_h = hop_bwd(k_cur, v_cur, causal=False)
            return (dq + dq_h.astype(jnp.float32),
                    dk_acc + dk_h.astype(jnp.float32),
                    dv_acc + dv_h.astype(jnp.float32))

        dq, dk_acc, dv_acc = jax.lax.cond(
            live, attend, lambda a: (a[0], a[1], a[2]),
            (dq, dk_acc, dv_acc, k_cur, v_cur))
        return (dq, dk_acc, dv_acc, k_cur, v_cur), None

    (dq, dk_acc, dv_acc, _, _), _ = jax.lax.scan(
        hop, (dq, dk0.astype(jnp.float32), dv0.astype(jnp.float32), k, v),
        jnp.arange(1, n))
    # n-1 rotations so far: one more completes the circle home
    dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
    dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
    return dq.astype(q.dtype), dk_acc.astype(k.dtype), dv_acc.astype(v.dtype)


def _ring_smap(impl, mesh, in_specs, out_specs):
    return jax.shard_map(impl, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={"seq"},
                         check_vma=False)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ring_flash_global(q, k, v, mesh, block_q, block_k):
    return _ring_flash_global_fwd(q, k, v, mesh, block_q, block_k)[0]


def _ring_flash_global_fwd(q, k, v, mesh, block_q, block_k):
    """custom_vjp at the GLOBAL level: both passes are their own
    explicit shard_maps, so the flash kernels' custom_vjp residuals
    never cross a partial-auto shard_map boundary (jax cannot infer
    specs for those — the residual out_specs land on auto axes)."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, "seq", None, None)
    lspec = P(None, None, "seq")
    out, lse = _ring_smap(
        partial(ring_attention, axis_name="seq", use_flash=True,
                block_q=block_q, block_k=block_k, with_lse=True),
        mesh, (spec, spec, spec), (spec, lspec))(q, k, v)
    return out, (q, k, v, out, lse)


def _ring_flash_global_bwd(mesh, block_q, block_k, res, do):
    from jax.sharding import PartitionSpec as P

    q, k, v, out, lse = res
    spec = P(None, "seq", None, None)
    lspec = P(None, None, "seq")
    return _ring_smap(
        partial(_ring_bwd, axis_name="seq", use_flash=True,
                block_q=block_q, block_k=block_k),
        mesh, (spec, spec, spec, spec, lspec, spec),
        (spec, spec, spec))(q, k, v, out, lse, do)


_ring_flash_global.defvjp(lambda q, k, v, mesh, bq, bk:
                          _ring_flash_global_fwd(q, k, v, mesh, bq, bk),
                          _ring_flash_global_bwd)


def ring_causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, mesh=None,
    use_flash: bool = False, block_q: int = 512, block_k: int = 1024,
) -> jax.Array:
    """SPMD entry: q/k/v [B, S, H|KV, D] sequence-sharded over 'seq';
    runs ring_attention under shard_map with every other axis auto.
    use_flash routes BOTH passes through the Pallas kernels: the
    forward's hop partials merge by logsumexp, and the backward is its
    own ring (_ring_bwd) wired through a global-level custom_vjp.

    The kernel route engages wherever kernels run (the same
    ops/pallas.kernels_runnable gate causal_attention applies: a TPU,
    or an explicit interpret request — the interpreter runs every hop
    orders of magnitude slower, and the custom_vjp route needs jit)."""
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get("seq", 1) <= 1:
        # no ring: plain causal attention (honoring the flash setting)
        from ..ops.attention import causal_attention

        return causal_attention(q, k, v, use_flash=use_flash)
    if use_flash and kernels_runnable():
        return _ring_flash_global(q, k, v, mesh, block_q, block_k)
    from jax.sharding import PartitionSpec as P

    spec = P(None, "seq", None, None)
    fn = _ring_smap(
        partial(ring_attention, axis_name="seq", use_flash=False,
                block_q=block_q, block_k=block_k),
        mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)
