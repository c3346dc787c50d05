"""The selective scan (Mamba-1, ops/pallas/selective_scan.py) and the
two layouts that came with Phi-4-mini-flash: the step kernel
(interpreted) and its XLA form against the `lax.scan` oracle over
ragged rows with runs of one and several, a first token, a pad row; the
chunked scan against it across a chunk boundary and with padding; and
differential attention's pairs as zero-padded heads, folded side by
side in a pool's head or not, against dense differential attention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import _family as F
import _state_walk as W
import pytest
from _family import one_chip  # noqa: F401 - a described v5e

from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import paged_attention as PA
from deepspeed_tpu.ops.pallas import selective_scan as SS


def scan_inputs(rng, *lead, I=256, N=16):
    """x, dt, A, B, C of a Mamba-1 step over `lead` rows: I channels, a
    state of N, rates a (channel, state) pair; decays exp(dt A) from
    ~0.05 to ~0.99 a token."""
    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return (normal(*lead, I), jax.nn.softplus(normal(*lead, I) - 1.0),
            -jnp.exp(normal(I, N) * 0.7), normal(*lead, N), normal(*lead, N))


def test_the_two_views_of_a_state_are_inverse(rng):
    h = jnp.asarray(rng.normal(size=(3, 256, 16)), jnp.float32)
    packed = SS.pool_view(h, 128)
    assert packed.shape == (3, 2, 16, 128)
    # channel c of lane row c // 128 on lane c % 128, the state on sublanes
    assert float(packed[1, 1, 5, 7]) == float(h[1, 128 + 7, 5])
    np.testing.assert_array_equal(SS.state_view(packed), h)


@pytest.mark.parametrize("step", ["xla", "kernel"])
def test_a_step_over_ragged_rows_matches_the_recurrence(rng, step, request):
    """A run of five from a slot's state, a decode row, a pad row, a
    run of three from position 0 (the slot's NaN must not be read),
    another pad row; slots in no row stay bit for bit."""
    if step == "kernel":
        request.getfixturevalue("pallas_interpret")
    fn = {"xla": SS.sscan_step_xla, "kernel": SS.sscan_step}[step]
    shape = (6, 2, 16, 128)
    pool, slots, pos, runs = W.ragged(rng, shape)
    x, dt, A, Bm, Cm = scan_inputs(rng, slots.shape[0])
    y, new = jax.jit(fn)(x, dt, A, Bm, Cm, pool, slots, pos)
    oracle = jax.jit(SS.sscan_recurrent)
    for rows, slot, start in runs:
        state = None if start is None else SS.state_view(start)[None]
        want, last = oracle(x[rows][None], dt[rows][None], A, Bm[rows][None],
                            Cm[rows][None], state)
        np.testing.assert_allclose(y[rows], want[0], atol=2e-5)
        np.testing.assert_allclose(SS.state_view(new[slot]), last[0],
                                   atol=2e-5)
    for untouched in (2, 4):
        np.testing.assert_array_equal(new[untouched], pool[untouched])


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("pattern,walk", [
    c for c in W.CASES if c.values[1] != "batches_of_three_one_aside"
    or c.values[0] in ("a_chunk_first", "two_chunks_adjacent", "a_pad_row",
                       "a_first_token_beside_old_state",
                       "a_slot_read_after_its_write")])
def test_the_walk_over_a_steps_rows(rng, monkeypatch, pattern, walk):
    """The shared walk with THIS body (a tile of rates a lane row kept
    in VMEM: `consts`) against the loop over rows in XLA: three lane
    rows of 128 channels, a state of 8."""
    shape = (W.SLOTS + 1, 3, 8, 128)
    W.set_walk(monkeypatch, walk, shape)
    W.check_walk(SS.sscan_step, SS.sscan_step_xla,
                 lambda rng, n: scan_inputs(rng, n, I=384, N=8), shape,
                 pattern, rng)


@pytest.mark.parametrize("T_,chunk", [(37, 8), (16, 8), (5, 64), (40, 16)])
def test_the_chunked_scan_matches_the_recurrence(rng, T_, chunk):
    """Across chunk boundaries, a tail that fills no chunk, a prompt
    shorter than a chunk; from a state that is not zero."""
    x, dt, A, Bm, Cm = scan_inputs(rng, 2, T_, I=128)
    start = jnp.asarray(rng.normal(size=(2, 128, 16)), jnp.float32)
    want, last = jax.jit(SS.sscan_recurrent)(x, dt, A, Bm, Cm, start)
    got, state = jax.jit(lambda *a: SS.sscan_chunked(*a, chunk=chunk))(
        x, dt, A, Bm, Cm, start)
    np.testing.assert_allclose(got, want, atol=5e-5)
    np.testing.assert_allclose(state, last, atol=5e-5)


def test_padding_leaves_the_state_as_it_is(rng):
    """A pad token has dt = 0: the state after a padded prompt is the
    state after its real tokens."""
    x, dt, A, Bm, Cm = scan_inputs(rng, 1, 24, I=128)
    real = (jnp.arange(24) < 17)[None, :, None]
    _, padded = jax.jit(lambda *a: SS.sscan_chunked(*a, chunk=8))(
        x, jnp.where(real, dt, 0.0), A, Bm, Cm)
    _, want = jax.jit(SS.sscan_recurrent)(
        x[:, :17], dt[:, :17], A, Bm[:, :17], Cm[:, :17])
    np.testing.assert_allclose(padded, want, atol=2e-5)


@pytest.mark.parametrize("what,shape,fits", [
    ("the cell's", (129, 40, 16, 128), True),
    ("channels that fill no lanes", (7, 1, 16, 64), False),
    ("a state of no whole sublanes", (7, 2, 12, 128), False),
])
def test_sscan_step_fits(what, shape, fits):
    assert SS.sscan_step_fits(
        128, jax.ShapeDtypeStruct(shape, jnp.float32)) is fits
    assert not SS.sscan_step_fits(
        128, jax.ShapeDtypeStruct(shape, jnp.bfloat16))


def test_the_step_kernel_compiles_for_v5e_at_the_cells_shapes(one_chip):
    """128 rows of 5,120 channels over a pool of 129 slots of 320 KiB,
    aliased in and out."""
    sds = F.on_chip(one_chip, jnp.float32)
    rows, pool = 128, sds((129, 40, 16, 128))
    F.compiles_one_aliased_kernel(SS.sscan_step, (
        sds((rows, 5120)), sds((rows, 5120)), sds((5120, 16)),
        sds((rows, 16)), sds((rows, 16)), pool, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32)), 5, "sscan_state")


def test_the_walk_compiles_for_v5e_at_two_heads_of_640(one_chip):
    """What the fold is for: 10 pairs of 128 are no whole tiles of a
    16-bit pool (it would hold 16), 2 heads of 640 are; the row write
    and the live-block walk take the pool at 40 query heads (the rule's
    table: tests/test_olmo_hybrid_delta.py)."""
    assert not PA._whole_tiles(10, 128, 2) and PA._whole_tiles(2, 640, 2)
    assert PA.kv_pack(2, 640, 2) == 1
    F.walk_and_write_compile(one_chip, 128, 40, 2, 640, (257, 128, 2, 640))


# -- differential attention as zero-padded heads -----------------------------

def dense_differential(q, k, v, lam, window=0):
    """q [S, H, D], k, v [S, KV, D] -> [S, H / 2, 2D]: each pair's two
    dense causal maps computed apart over V = [v1; v2], a1 - lam a2."""
    S, H, D = q.shape
    per = (H // 2) // (k.shape[1] // 2)
    rows, keys = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    live = keys <= rows
    if window:
        live &= rows - keys < window

    def one(qh, kh, vg):
        s = jnp.where(live, qh @ kh.T * D ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, -1) @ vg

    out = []
    for p in range(H // 2):
        g = p // per
        vg = jnp.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], -1)
        out.append(one(q[:, 2 * p], k[:, 2 * g], vg)
                   - lam * one(q[:, 2 * p + 1], k[:, 2 * g + 1], vg))
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("H,KV,fold", [(8, 4, 1), (12, 6, 1), (12, 12, 3),
                                        (40, 20, 5)])
def test_paired_heads_give_the_two_maps_of_each_pair(rng, H, KV, fold):
    """The identity the model serves by: a pair of K heads IS one K head
    of twice the width, a query zero outside its own half scores
    against its own key alone; and `fold` pairs side by side in one
    head of a pool, a query zero outside its own pair's lanes. Through
    causal_attention (a whole prompt) and through the paged walk's
    oracle over a pool the rows were written to."""
    S, D, bs = 24, 64, 8
    cfg = T.TransformerConfig(
        vocab_size=64, n_layers=2, n_heads=H, n_kv_heads=KV, d_model=64,
        head_dim_override=D, position_embedding="none",
        differential_attention=True)
    assert PA.kv_pair_fold(KV // 2, 2 * D) == fold
    pool_heads, pool_dim = M.kv_pool_shape(cfg)
    assert (pool_heads * fold, pool_dim) == (KV // 2, 2 * D * fold)
    normal = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    q, k, v = normal(S, H, D), normal(S, KV, D), normal(S, KV, D)
    lam = 0.37
    want = dense_differential(q, k, v, lam)

    def combine(att):  # a1 - lam a2 of the paired maps [S, H, 2D]
        return att[:, 0::2] - lam * att[:, 1::2]

    @jax.jit
    def whole_prompt(q, k, v):
        qp, kp, vp = M._paired_heads(q, k, v, cfg)
        return combine(M.causal_attention(qp[None], kp[None], vp[None],
                                          use_flash=False)[0])

    np.testing.assert_allclose(whole_prompt(q, k, v), want, atol=2e-5)

    @jax.jit
    def through_a_pool(q, k, v):
        qp, kp, vp = M._paired_heads(q, k, v, cfg)
        pool = jnp.zeros((S // bs + 1, bs, pool_heads, pool_dim),
                         jnp.float32)
        ck, cv = M._write_kv_xla(pool, pool, M._pool_rows(kp, cfg),
                                 M._pool_rows(vp, cfg), jnp.arange(S))
        # every token a row of one sequence: contexts 1..S of one table
        table = jnp.broadcast_to(jnp.arange(S // bs), (S, S // bs))
        wide, unfold = M._fold_pairs(qp, ck)
        return combine(unfold(PA.paged_decode_attention_xla(
            wide, ck, cv, table, jnp.arange(1, S + 1))))

    np.testing.assert_allclose(through_a_pool(q, k, v), want, atol=2e-5)


def test_the_combine_is_the_papers(rng):
    """_diff_combine against the formula written out: lam from the four
    vectors and the layer's index, the RMS norm over the pair's 2D
    values, the factor 1 - lam0."""
    import math

    H, D, li = 4, 8, 5
    cfg = T.TransformerConfig(vocab_size=64, n_layers=2, n_heads=H,
                              n_kv_heads=2, d_model=32,
                              position_embedding="none",
                              differential_attention=True)
    normal = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    lp = {f"diff_l{n}": normal(D) * 0.5 for n in ("q1", "k1", "q2", "k2")}
    lp["diff_norm_scale"] = 1 + 0.3 * normal(2 * D)
    att = normal(3, H, 2 * D)
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * li)
    lam = (math.exp(float(lp["diff_lq1"] @ lp["diff_lk1"]))
           - math.exp(float(lp["diff_lq2"] @ lp["diff_lk2"])) + lam0)
    o = att[:, 0::2] - lam * att[:, 1::2]
    want = (o / np.sqrt(np.mean(np.square(o), -1, keepdims=True) + cfg.norm_eps)
            * lp["diff_norm_scale"] * (1 - lam0))
    got = jax.jit(lambda a: M._diff_combine(a, lp, li, cfg))(att)
    assert got.shape == (3, H, D)
    np.testing.assert_allclose(got.reshape(3, H // 2, 2 * D), want, atol=1e-5)
