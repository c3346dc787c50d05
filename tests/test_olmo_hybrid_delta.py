"""The delta rule of an Olmo-Hybrid model (tests/test_olmo_hybrid.py
holds the model): heads of 96 x 192, two side by side in a lane row of
the state pool (ops/pallas/gated_delta.py pack_heads), a write strength
drawn up to 2. The chunked form against the recurrence where
near-parallel keys make its triangular system as bad as it gets, the
step over ragged rows in XLA and (interpreted) the `gdn_state` kernel
against the recurrence (runs of one and of several, a pad row, a first
token), the shared walk's row patterns on paired heads, what the kernel
takes, the walk at one query head a KV head, and the two kernels
compiled for a described v5e at the cell's shapes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import _family as F
import _state_walk as W
import pytest
from _family import one_chip  # noqa: F401 - the described v5e chip

from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import gated_delta as GD
from deepspeed_tpu.ops.pallas import paged_attention as PA


def _delta_inputs(rng, *lead, H=2, Dk=96, Dv=192, parallel=0.0):
    """q, k, v, g, beta of a head of Dk x Dv with beta drawn in (0, 2).
    `parallel` > 0: every key of a head is one direction plus that much
    noise (cosines near 1), beta in (1.6, 2) and a slow decay: the
    worst case of the chunked form's triangular system."""
    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    k = normal(*lead, H, Dk)
    if parallel:
        k = normal(H, Dk) + parallel * k
    beta = 2 * jax.nn.sigmoid(normal(*lead, H))
    g = -jnp.exp(normal(*lead, H)) * 0.3
    if parallel:
        beta = 1.6 + 0.4 * jax.nn.sigmoid(normal(*lead, H))
        g = g * 0.1
    return (unit(normal(*lead, H, Dk)) * Dk ** -0.5, unit(k),
            normal(*lead, H, Dv), g, beta)


@pytest.mark.parametrize("tokens,chunk,parallel", [
    (1, 64, 0.0), (70, 64, 0.0), (131, 64, 0.0), (37, 16, 0.0),
    (64, 64, 0.05), (150, 64, 0.05), (150, 64, 0.01), (23, 7, 0.05)])
def test_the_chunked_form_is_the_recurrence_up_to_beta_two(rng, tokens, chunk,
                                                           parallel):
    """Heads of 96 x 192 from a state that is not zero, beta up to 2;
    with near-parallel keys (cosines of 0.99 and more) the powers of
    the chunk's strictly lower matrix reach 1e20 and more: the solve
    by substitution holds where their product gave NaN."""
    args = _delta_inputs(rng, 2, tokens, parallel=parallel)
    state = jnp.asarray(rng.normal(size=(2, 2, 96, 192)), jnp.float32)
    # (each form ONE program: op by op the chunked form is some forty
    # small compiles a case)
    o1, s1 = jax.jit(GD.gated_delta_recurrent)(*args, state)
    o2, s2 = jax.jit(functools.partial(GD.gated_delta_chunked, chunk=chunk))(
        *args, state)
    assert np.isfinite(o2).all() and np.isfinite(s2).all()
    tol = 2e-4 if parallel else 2e-5
    np.testing.assert_allclose(o2, o1, atol=tol)
    np.testing.assert_allclose(s2, s1, atol=tol)


def test_a_write_strength_of_two_reflects_the_state_along_the_key(rng):
    """beta = 2, no decay: S <- (I - 2 k k^T) S + 2 k v^T, an eigenvalue
    of -1 along k. Written twice with the same key and value the state
    is back where it was plus nothing: the second write undoes the
    first's reflection of the old state and keeps k v^T's own."""
    q, k, v, _, _ = _delta_inputs(rng, 1, 1)
    two = lambda a: jnp.concatenate([a, a], axis=1)
    S0 = jnp.asarray(rng.normal(size=(1, 2, 96, 192)), jnp.float32)
    zero, beta = jnp.zeros((1, 2, 2)), jnp.full((1, 2, 2), 2.0)
    for form in (GD.gated_delta_recurrent, GD.gated_delta_chunked):
        _, S1 = form(q, k, v, zero[:, :1], beta[:, :1], S0)
        _, S2 = form(two(q), two(k), two(v), zero, beta, S0)
        kk = jnp.einsum("bhk,bhj->bhkj", k[:, 0], k[:, 0])
        reflected = S0 - 2 * jnp.einsum("bhkj,bhjv->bhkv", kk, S0)
        np.testing.assert_allclose(
            S1, reflected + 2 * k[:, 0, :, :, None] * v[:, 0, :, None, :],
            atol=2e-5)
        np.testing.assert_allclose(S2, S0, atol=2e-5)


def test_a_padded_prompt_leaves_the_state_in_the_pools_layout(rng):
    """_recur_prompts: prompts of 9 and 30 tokens padded to 32, a pad
    prompt beside them; each slot gets the state after its prompt's own
    last token, two heads side by side, the others are not touched."""
    mcfg = T.TransformerConfig(
        n_layers=1, layer_types=("linear_attention",), conv_kernel=4,
        gdn_key_heads=4, gdn_value_heads=4, gdn_key_dim=96, gdn_value_dim=192)
    q, k, v, g, beta = _delta_inputs(rng, 3, 32, H=4)
    pool = jnp.full((5, 2, 96, 384), 7.0)
    n_real = jnp.asarray([9, 30, 0], jnp.int32)
    slots = jnp.asarray([2, 0, -1], jnp.int32)
    o, new = M._recur_prompts("linear_attention", (q, k, v, g, beta), pool,
                              slots, n_real, mcfg)
    for i, (n, slot) in enumerate([(9, 2), (30, 0)]):
        want_o, want_s = GD.gated_delta_recurrent(
            q[i:i + 1, :n], k[i:i + 1, :n], v[i:i + 1, :n], g[i:i + 1, :n],
            beta[i:i + 1, :n])
        np.testing.assert_allclose(o[i, :n], want_o[0], atol=2e-5)
        np.testing.assert_allclose(GD.unpack_heads(new[slot], 2), want_s[0],
                                   atol=2e-5)
    assert (np.asarray(new[1]) == 7).all() and (np.asarray(new[3]) == 7).all()


def _check_step(step, rng, H, pack, parallel=0.0):
    pool, slots, pos, runs = W.ragged(rng, (6, H // pack, 96, pack * 192))
    q, k, v, g, beta = _delta_inputs(rng, 11, H=H, parallel=parallel)
    o, new = jax.jit(step)(q, k, v, g, beta, pool, slots, pos)
    assert o.shape == v.shape and new.shape == pool.shape
    for rows, slot, start in runs:
        want_o, want_s = jax.jit(GD.gated_delta_recurrent)(
            q[None, rows], k[None, rows], v[None, rows], g[None, rows],
            beta[None, rows],
            None if start is None else GD.unpack_heads(start, pack)[None])
        np.testing.assert_allclose(o[rows], want_o[0], atol=2e-5)
        np.testing.assert_allclose(GD.unpack_heads(new[slot], pack),
                                   want_s[0], atol=2e-5)
    # the slots of no row of this step are as they were
    np.testing.assert_array_equal(new[2], pool[2])
    np.testing.assert_array_equal(new[4], pool[4])


@pytest.mark.parametrize("H,pack", [(2, 2), (3, 1), (4, 2)])
@pytest.mark.parametrize("parallel", [0.0, 0.05], ids=["spread", "parallel"])
def test_the_step_over_runs_is_a_segmented_recurrence(rng, H, pack, parallel):
    """The loop over rows in XLA at heads of 96 x 192: in pairs, and
    (three heads: no pairs) one a row."""
    _check_step(GD.gated_delta_step_xla, rng, H, pack, parallel)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("H", [2, 4])
@pytest.mark.parametrize("parallel", [0.0, 0.05], ids=["spread", "parallel"])
def test_the_step_kernel_matches_the_recurrence_at_96_by_192(rng, H, parallel):
    """Runs of one and of several, a pad row, a first token, beta up to
    2 (and near-parallel keys): two heads side by side in a lane row,
    each head's column, decay and strength over its own 192 lanes."""
    pool = jax.ShapeDtypeStruct((6, H // 2, 96, 384), jnp.float32)
    assert GD.step_fits(11, pool)
    _check_step(GD.gated_delta_step, rng, H, 2, parallel)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("pattern,walk", W.CASES)
def test_the_walk_over_a_steps_rows_with_heads_in_pairs(rng, monkeypatch,
                                                        pattern, walk):
    """The kernel's own copies (tests/_state_walk.py: the rows'
    patterns, the walk's batches) against the loop over rows in XLA, on
    a pool of three lane rows of two heads of 8 x 192."""
    shape = (W.SLOTS + 1, 3, 8, 384)
    W.set_walk(monkeypatch, walk, shape)
    W.check_walk(GD.gated_delta_step, GD.gated_delta_step_xla,
                 lambda rng, n: _delta_inputs(rng, n, H=6, Dk=8), shape,
                 pattern, rng)


@pytest.mark.parametrize("what,n_rows,shape,dtype,fits", [
    ("the cell's", 128, (129, 15, 96, 384), jnp.float32, True),
    ("the other DeltaNet cell's", 256, (257, 32, 128, 128), jnp.float32, True),
    ("a head of 192 alone: one and a half lane tiles", 128,
     (129, 30, 96, 192), jnp.float32, False),
    ("keys that fill no sublane tile", 8, (5, 2, 92, 384), jnp.float32,
     False),
    ("bfloat16 state", 8, (5, 2, 96, 384), jnp.bfloat16, False),
])
def test_step_fits(what, n_rows, shape, dtype, fits):
    assert GD.step_fits(n_rows, jax.ShapeDtypeStruct(shape, dtype)) is fits
    if fits:
        assert GD.walk_shape(shape) == (8, 4)


@pytest.mark.usefixtures("pallas_interpret")
def test_the_walk_with_one_query_head_a_kv_head_matches_the_oracle(rng):
    """30 query and 30 KV heads of 128, contexts that end inside a
    block, at a block's edge and nowhere (a pad row)."""
    F.walk_matches_the_oracle(rng, H=30, KV=30, D=128)


@pytest.mark.usefixtures("pallas_interpret")
def test_the_walk_of_a_packed_pool_matches_the_oracle(rng):
    """30 query and 30 KV heads of 128 over the pool kv_pack lays them
    out in, 2 heads of 1,920, blocks of 16: rows of their own whose
    contexts end inside a block, at a block's edge and nowhere (a pad
    row), beside a chunk of 32 rows on one table. A head's 15 queries
    are laid out in 16 rows (_packed_group: whole sublane tiles), so
    the chunk walks as two groups of 16 that each read its blocks once
    (at 15 rows a head every row would walk alone)."""
    S, H, D, bs, NB = 40, 30, 128, 16, 4
    pack = PA.kv_pack(30, D, 2)
    assert pack == 15 and PA._packed_group(pack) == 16
    ctx = np.zeros(S, np.int32)
    ctx[:5], ctx[5:37], ctx[37:] = [1, 17, 40, 64, 0], 20 + np.arange(32), 33
    tbl = rng.permutation(S * NB).reshape(S, NB)
    tbl[5:37] = tbl[5]
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = normal(S, H, D)
    kc, vc = (normal(S * NB + 1, bs, 30 // pack, pack * D) for _ in "kv")
    qg = jax.eval_shape(lambda q: PA._group_queries(
        PA._pack_queries(q, pack, 1), 2)[0], q)
    assert qg.shape == (S, 2, 16, pack * D)
    assert PA._walks_live_blocks(qg, kc) and PA._group_rows(16, S) == 16
    # by the walk's own grouping: each half of the chunk reads the blocks
    # of its longest row (contexts 35 and 51), 30 rows ride
    assert PA.walk_reads(tbl, ctx, bs, 1, pack) == (
        int(np.sum(-(-np.delete(ctx, np.s_[5:37]) // bs))) + 3 + 4, 30)
    assert PA.walk_reads(tbl, ctx, bs, 15)[1] == 0  # 15 rows a head: alone
    # and of the groups' 3 + 4 visits, those past a group's shortest context
    assert PA.walk_masks(tbl, ctx, bs, 1, pack=pack) == (4, 7)
    got, want = (jax.jit(fn)(q, kc, vc, jnp.asarray(tbl, jnp.int32),
                             jnp.asarray(ctx)) for fn in (
        PA.paged_decode_attention, PA.paged_decode_attention_xla))
    live = ctx > 0
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    # the same values a head a head, the pool the model's own shape
    plain = jax.jit(PA.paged_decode_attention_xla)(
        q, kc.reshape(-1, bs, 30, D), vc.reshape(-1, bs, 30, D),
        jnp.asarray(tbl, jnp.int32), jnp.asarray(ctx))
    np.testing.assert_allclose(got[live], plain[live], atol=2e-5)


@pytest.mark.parametrize("kv,d,itemsize,pack", [
    (30, 128, 2, 15), (12, 128, 2, 3), (30, 256, 2, 15), (30, 128, 1, 1),
    (16, 128, 2, 1), (32, 128, 2, 1), (8, 128, 2, 1), (30, 128, 4, 1),
    (30, 64, 2, 2), (6, 128, 2, 1), (1, 128, 2, 1), (30, 384, 4, 15),
    (10, 128, 2, 5), (2, 640, 2, 1)])
def test_a_pool_lays_heads_side_by_side_where_the_layout_would_pad(
        kv, d, itemsize, pack):
    """The packing rule's table. More than a tile of KV heads of whole
    lanes that are no whole tiles lie side by side, the fewest to a
    head that leave whole tiles with nothing padded (30 of 128 in 16
    bits: 2 heads of 1,920; ten: 2 of 640, what ten PAIRS of 64 fold
    to, kv_pair_fold); heads of 64 two a lane row; whole tiles, counts
    up to the tile, a 32-bit pool of exactly 128 lanes and a count no
    fold makes whole tiles of (30 heads in 8 bits) stay as they are."""
    assert PA.kv_pack(kv, d, itemsize) == pack
    assert pack == 1 or d == 64 or (
        not PA._whole_tiles(kv, d, itemsize)
        and PA._whole_tiles(kv // pack, d * pack, itemsize)
        and pack == PA.kv_pair_fold(kv, d, itemsize))


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("G", [1, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("fused", [False, True], ids=["write_walk", "fused"])
def test_packing_changes_nothing_the_model_sees(rng, G, fused):
    """_write_pools + _decode_attention on a pool of 12 heads of 128
    laid out as 4 of 384 (what kv_pack answers for 12) for rows of 12
    heads and queries of 12 G: the same attention as on a pool of 12,
    and the same row-major bytes in the pools."""
    S, KV, D, bs, NB = 4, 12, 128, 16, 3
    H, pack = KV * G, PA.kv_pack(KV, D, 2)
    assert pack == 3
    ctx = jnp.asarray([5, 17, 33, 0], jnp.int32)
    tbl = jnp.asarray(rng.permutation(S * NB).reshape(S, NB), jnp.int32)
    flat = jnp.where(ctx > 0, jnp.take_along_axis(
        tbl, ((ctx - 1) // bs)[:, None], 1)[:, 0] * bs + (ctx - 1) % bs, -1)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, kn, vn = normal(S, H, D), normal(S, KV, D), normal(S, KV, D)
    own = (normal(S * NB + 1, bs, KV, D), normal(S * NB + 1, bs, KV, D))
    packed = tuple(p.reshape(-1, bs, KV // pack, pack * D) for p in own)
    outs = []
    for pools in (own, packed):
        if fused:
            att, *pools = M._decode_attention(
                q, pools, tbl, ctx, True, k_new=kn, v_new=vn, slots=flat)
        else:
            pools = M._write_pools(pools, kn, vn, flat)
            att = M._decode_attention(q, pools, tbl, ctx, True)
        outs.append((att, pools))
    (a0, p0), (a1, p1) = outs
    assert a1.shape == (S, H, D)
    np.testing.assert_allclose(a1[:3], a0[:3], atol=2e-5)
    for x, y in zip(p0, p1):
        np.testing.assert_array_equal(y.reshape(x.shape), x)


def test_the_step_kernel_compiles_for_v5e_at_the_cells_shapes(one_chip):
    """128 rows of 30 heads of 96 x 192 over a pool of 129 slots of 15
    lane rows of two heads, aliased in and out (no second 285 MB pool
    among the temporaries): Mosaic takes the pairs where it would
    refuse a row of 192 lanes."""
    sds = F.on_chip(one_chip, jnp.float32)
    rows, pool = 128, sds((129, 15, 96, 384))
    assert GD.step_fits(rows, pool)
    F.compiles_one_aliased_kernel(GD.gated_delta_step, (
        sds((rows, 30, 96)), sds((rows, 30, 96)), sds((rows, 30, 192)),
        sds((rows, 30)), sds((rows, 30)), pool, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32)), 5, "gdn_state")


def _walk_args(one_chip, pool_heads):
    sds = F.on_chip(one_chip, jnp.bfloat16)
    rows, pool = 128, sds((705, 128, *pool_heads))
    q = new = sds((rows, 30, 128))
    table, ints = sds((rows, 32), jnp.int32), sds((rows,), jnp.int32)
    return q, pool, pool, new, new, table, ints, ints


def test_the_walk_and_both_writes_compile_for_v5e_at_30_heads_in_2(one_chip):
    """30 query / 30 KV heads of 128 over pools [705, 128, 2, 1920]
    (kv_pack: 15 heads side by side), a table of 32 slots a row, 128
    rows, through the serving model's own two calls: the row write and
    the live-block walk of the shared-table step, and the fused walk of
    the single-token step, at the packed shape."""
    def shared(q, kc, vc, kn, vn, table, ctx, slots):
        pools = M._write_pools((kc, vc), kn, vn, slots)
        return M._decode_attention(q, pools, table, ctx, True), pools

    def single(q, kc, vc, kn, vn, table, ctx, slots):
        return M._decode_attention(q, (kc, vc), table, ctx, True, k_new=kn,
                                   v_new=vn, slots=slots)

    pack = PA.kv_pack(30, 128, 2)
    args = _walk_args(one_chip, (30 // pack, 128 * pack))
    assert args[1].shape == (705, 128, 2, 1920)
    text = jax.jit(shared, donate_argnums=(1, 2)).lower(
        *args).compile().as_text()
    for name in ("paged_decode_grid", "paged_kv_write"):
        assert any(name in line for line in F.kernels(text)), name
    # the walk is the live-block one at 2 heads of 16 query rows
    assert any("paged_decode_grid" in line and "bf16[128,2,16,1920]" in line
               for line in F.kernels(text))
    assert PA.kv_write_path(args[1].shape, jnp.bfloat16) == "rows"
    text = jax.jit(single, donate_argnums=(1, 2)).lower(
        *args).compile().as_text()
    assert any("paged_decode_fused" in line for line in F.kernels(text))


@pytest.mark.parametrize("heads", [30, 6, 1])
def test_heads_that_are_no_whole_tiles_stay_on_the_grid_and_are_not_refused(
        one_chip, heads):
    """What the packing is for: [705, 128, 30, 128] in 16 bits is no
    whole tiles, its walk is the (S, NB) grid's and its write a whole
    block's; the fused walk is not tried on it (Mosaic refuses its
    row's DMA: "Slice shape along dimension 2 must be aligned to tiling
    (8), but is 30"). A pool under a mesh, which is not packed, takes
    this path, and so do the counts up to the tile that nothing packs
    (6 heads, 1: the fused walk was tried on them until PR 63, and
    refused)."""
    sds = F.on_chip(one_chip, jnp.bfloat16)
    pool = sds((705, 128, heads, 128))
    q = new = sds((128, heads, 128))
    table, ints = sds((128, 32), jnp.int32), sds((128,), jnp.int32)
    assert not PA._whole_tiles(heads, 128, 2)
    assert PA.kv_write_path(pool.shape, jnp.bfloat16) == "blocks"
    qg = jax.eval_shape(lambda q: PA._group_queries(q, heads, None)[0], q)
    assert not PA._walks_live_blocks(qg, pool)
    if heads == 30:
        packed = jax.eval_shape(lambda q: PA._group_queries(
            PA._pack_queries(q, 15, 1), 2, None)[0], q)
        assert packed.shape == (128, 2, 16, 1920)
        assert PA._walks_live_blocks(packed, sds((705, 128, 2, 1920)))

    def single(q, kc, vc, kn, vn, table, ctx, slots):
        return PA.paged_decode_attention(q, kc, vc, table, ctx, k_new=kn,
                                         v_new=vn, slots=slots)

    text = jax.jit(single, donate_argnums=(1, 2)).lower(
        q, pool, pool, new, new, table, ints, ints).compile().as_text()
    assert not any("paged_decode_fused" in line for line in F.kernels(text))
    assert any("paged_decode_grid" in line for line in F.kernels(text))
