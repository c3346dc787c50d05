"""Flash attention numerics vs the jnp oracle (ref model: tests/unit/ops
kernel-vs-torch-reference checks). Off-TPU the Pallas kernels run through
the interpreter (flash_attention._interpret), so the CPU lane tests the
real kernel math — fwd, the Pallas dq and dk/dv backward kernels, GQA
index maps, and the padding path. The same tests compile to Mosaic when
run on TPU hardware."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.ops.attention import _xla_attention, causal_attention
# Mosaic requires the lse tile (1, block_q) to satisfy the (8,128)
# tiling rule, so real-TPU runs use 128-sized blocks; the interpreter
# lane keeps 64 for speed. Same kernels either way.
BLK = 128 if jax.default_backend() == "tpu" else 64

from deepspeed_tpu.ops.pallas import flash_attention as FA
from deepspeed_tpu.ops.pallas import interpret_kernels
from deepspeed_tpu.ops.pallas.flash_attention import (

    _flash_bwd,
    _flash_fwd,
    flash_attention,
    tile_census,
)

pytestmark = pytest.mark.usefixtures("pallas_interpret_module")


def make_qkv(rng, B=2, S=128, H=2, KV=None, D=64, dtype=jnp.float32):
    KV = KV or H
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KV, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KV, D)), dtype)
    return q, k, v


def oracle(q, k, v, causal=True):
    """[B,S,H,D] oracle attention with GQA repeat."""
    n_rep = q.shape[2] // k.shape[2]
    if n_rep > 1:
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    return _xla_attention(q, k, v, causal=causal)


class TestForwardKernel:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("S", [128, 96])  # 96: padding path
    def test_fwd_matches_oracle(self, rng, causal, S):
        BH, D = 3, 64
        q = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        with jax.default_matmul_precision("highest"):
            o, lse = _flash_fwd(q, k, v, None, causal, BLK, BLK, H=1, KV=1)
            ref = oracle(q[:, :, None], k[:, :, None], v[:, :, None], causal)[:, :, 0]
            # reference lse
            scale = 1.0 / (D**0.5)
            s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
            if causal:
                s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -1e30)
            lse_ref = jax.scipy.special.logsumexp(s, axis=-1)
        np.testing.assert_allclose(o, ref, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(lse, lse_ref, rtol=2e-3, atol=2e-3)


class TestBackwardKernels:
    """The Pallas dq / dkdv kernels must match autodiff of the oracle."""

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("S", [128, 96])  # 96: padding path
    def test_grads_match_oracle(self, rng, causal, S):
        with jax.default_matmul_precision("highest"):
            self._run(rng, causal, S)

    def _run(self, rng, causal, S):
        BH, D = 3, 64
        q = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)
        do = jnp.asarray(rng.normal(size=(BH, S, D)), jnp.float32)

        def f(q, k, v):
            out = oracle(q[:, :, None], k[:, :, None], v[:, :, None], causal)[:, :, 0]
            return jnp.sum(out * do)

        # (the oracle's gradient ONE program: op by op it is dozens of
        # small compiles a case, here and below)
        dq_ref, dk_ref, dv_ref = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)

        o, lse = _flash_fwd(q, k, v, None, causal, BLK, BLK, H=1, KV=1)
        dq, dk, dv = _flash_bwd(q, k, v, None, o, lse, do, causal, BLK, BLK,
                                H=1, KV=1)
        np.testing.assert_allclose(dq, dq_ref, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(dk, dk_ref, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(dv, dv_ref, rtol=2e-3, atol=2e-3)


class TestFlashGQA:
    @pytest.mark.parametrize("KV", [1, 2, 4])
    def test_fwd_and_grad_match_oracle(self, rng, KV):
        B, S, H, D = 2, 128, 4, 32
        q, k, v = make_qkv(rng, B, S, H, KV, D)
        do = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)

        def f_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, block_q=BLK, block_k=BLK) * do)

        def f_ref(q, k, v):
            return jnp.sum(oracle(q, k, v, causal=True) * do)

        with jax.default_matmul_precision("highest"):
            out = flash_attention(q, k, v, block_q=BLK, block_k=BLK)
            ref = jax.jit(oracle)(q, k, v)
            g1 = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(q, k, v)
            g2 = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)
        for a, b, name in zip(g1, g2, "qkv"):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3, err_msg=f"d{name}")


class TestBF16:
    def test_full_layer_grad_bf16(self, rng):
        B, S, H, D = 2, 256, 2, 64
        q, k, v = make_qkv(rng, B, S, H, None, D, jnp.bfloat16)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v).astype(jnp.float32) ** 2)

        def loss_ref(q, k, v):
            return jnp.sum(oracle(q, k, v).astype(jnp.float32) ** 2)

        g1 = jax.jit(jax.grad(loss_flash))(q, k, v)
        g2 = jax.jit(jax.grad(loss_ref))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(g1, np.float32), np.asarray(g2, np.float32), rtol=5e-2, atol=5e-2
        )


class TestWrapper:
    def test_gqa_repeat_matches_full(self, rng):
        B, S, H, D = 2, 64, 4, 32
        q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, 2, D)), jnp.float32)
        out = causal_attention(q, k, v, use_flash=False)
        k_full = jnp.repeat(k, 2, axis=2)
        v_full = jnp.repeat(v, 2, axis=2)
        ref = causal_attention(q, k_full, v_full, use_flash=False)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_xla_attention_is_causal(self, rng):
        B, S, H, D = 1, 16, 1, 8
        q, k, v = make_qkv(rng, B, S, H, None, D)
        with jax.default_matmul_precision("highest"):
            out = _xla_attention(q, k, v, causal=True)
        # first token attends only to itself
        np.testing.assert_allclose(out[0, 0, 0], v[0, 0, 0], rtol=1e-4, atol=1e-4)


class TestSlidingWindowKernel:
    """window > 0: the kernels must match the windowed XLA oracle in fwd
    AND both backward kernels, across block-boundary window sizes, GQA,
    and the padding path."""

    @pytest.mark.parametrize("window", [16, 64, 100])
    @pytest.mark.parametrize("S", [128, 96])
    def test_fwd_and_grads_match_oracle(self, rng, window, S):
        q, k, v = make_qkv(rng, B=2, S=S, H=2, D=64)

        def win_oracle(q, k, v):
            return _xla_attention(q, k, v, causal=True, window=window)

        def flash_fn(q, k, v):
            return flash_attention(q, k, v, causal=True, block_q=BLK,
                                   block_k=BLK, window=window)

        with jax.default_matmul_precision("highest"):
            o = flash_fn(q, k, v)
            ref = jax.jit(win_oracle)(q, k, v)
            np.testing.assert_allclose(o, ref, rtol=2e-3, atol=2e-3)

            cot = jnp.asarray(rng.normal(size=o.shape), o.dtype)
            g = jax.jit(jax.grad(lambda *a: jnp.vdot(flash_fn(*a), cot),
                                 argnums=(0, 1, 2)))(q, k, v)
            gr = jax.jit(jax.grad(lambda *a: jnp.vdot(win_oracle(*a), cot),
                                  argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(g, gr):
            np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3)

    def test_gqa_window(self, rng):
        q, k, v = make_qkv(rng, B=2, S=128, H=4, KV=2, D=64)
        with jax.default_matmul_precision("highest"):
            o = flash_attention(q, k, v, causal=True, block_q=BLK,
                                block_k=BLK, window=32)
            n_rep = 2
            ref = _xla_attention(q, jnp.repeat(k, n_rep, axis=2),
                                 jnp.repeat(v, n_rep, axis=2),
                                 causal=True, window=32)
        np.testing.assert_allclose(o, ref, rtol=2e-3, atol=2e-3)


class TestAlibi:
    """ALiBi-biased flash kernels vs the XLA oracle (Bloom-class models;
    ref: the CUDA softmax alibi path in csrc/transformer/inference)."""

    def _slopes(self, H):
        from deepspeed_tpu.ops.attention import alibi_slopes

        return jnp.asarray(alibi_slopes(H))

    @pytest.mark.parametrize("KV", [2, 4])
    def test_fwd_and_grads_match_oracle(self, rng, KV):
        H = 4
        q, k, v = make_qkv(rng, B=2, S=2 * BLK, H=H, KV=KV, D=64)
        ab = self._slopes(H)

        def orc(q, k, v):
            n_rep = H // KV
            return _xla_attention(jnp.repeat(q, 1, axis=2),
                                  jnp.repeat(k, n_rep, axis=2),
                                  jnp.repeat(v, n_rep, axis=2),
                                  causal=True, alibi=ab)

        with jax.default_matmul_precision("highest"):
            out = flash_attention(q, k, v, causal=True, block_q=BLK,
                                  block_k=BLK, alibi=ab)
            ref = jax.jit(orc)(q, k, v)
            np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

            do = jnp.asarray(rng.normal(size=out.shape), out.dtype)
            gk = jax.jit(jax.grad(lambda *a: jnp.sum(flash_attention(
                *a, causal=True, block_q=BLK, block_k=BLK, alibi=ab) * do),
                argnums=(0, 1, 2)))(q, k, v)
            go = jax.jit(jax.grad(lambda *a: jnp.sum(orc(*a) * do),
                                  argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(gk, go):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)

    def test_alibi_with_window(self, rng):
        """ALiBi composes with the sliding-window mask."""
        H = 4
        q, k, v = make_qkv(rng, B=1, S=2 * BLK, H=H, D=64)
        ab = self._slopes(H)
        with jax.default_matmul_precision("highest"):
            out = flash_attention(q, k, v, causal=True, block_q=BLK,
                                  block_k=BLK, window=40, alibi=ab)
            ref = _xla_attention(q, k, v, causal=True, window=40, alibi=ab)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


# --- a tile does the work its kind holds (_tile_kinds) -----------------

# S 1,024 in tiles of 512 with slabs of 256 in all three kernels (128
# in `slabs_128`): an edge tile is walked in 2 (4) slabs, so the static
# slices and the masked sub-tile are real. window 0: interior + diagonal; 512: diagonal + window_edge,
# no interior tile; 1,024 = S: a window that binds on no tile.
KIND_CASES = {
    "causal": dict(window=0),
    "window_512": dict(window=512),
    "window_is_S": dict(window=1024),
    "slabs_128": dict(window=512, slab=128),
    "gqa_8_2": dict(window=512, H=8, KV=2),
    # ... and calls that must take today's body for every tile
    "window_100": dict(window=100, general=True),
    "window_256_of_512": dict(window=256, general=True),
    "padded_S": dict(window=512, S=1000, general=True),
    "alibi": dict(window=0, alibi=True, general=True),
    "unequal_tiles": dict(window=512, block_q=256, general=True),
}


class TestTileKinds:
    @pytest.mark.parametrize("case", KIND_CASES)
    def test_fwd_and_grads_match_oracle(self, rng, case, monkeypatch):
        c = dict(dict(S=1024, H=2, KV=None, block_q=512, alibi=False,
                      slab=256, general=False), **KIND_CASES[case])
        S, H, w = c["S"], c["H"], c["window"]
        KV = c["KV"] or H
        monkeypatch.setattr(FA, "SLAB_FWD", c["slab"])
        monkeypatch.setattr(FA, "SLAB_BWD", c["slab"])
        census = tile_census(S, w, c["block_q"], 512, alibi=c["alibi"])
        assert (census["general"] > 0) == c["general"], census
        assert (census["interior"] + census["edge"] > 0) != c["general"]

        q, k, v = make_qkv(rng, B=2, S=S, H=H, KV=KV, D=64)
        ab = (jnp.asarray(TestAlibi()._slopes(H)) if c["alibi"] else None)

        def flash_fn(q, k, v):
            return flash_attention(q, k, v, causal=True, window=w, alibi=ab,
                                   block_q=c["block_q"], block_k=512)

        def ref_fn(q, k, v):
            n_rep = H // KV
            return _xla_attention(q, jnp.repeat(k, n_rep, axis=2),
                                  jnp.repeat(v, n_rep, axis=2), causal=True,
                                  window=w, alibi=ab)

        with jax.default_matmul_precision("highest"):
            o = flash_fn(q, k, v)
            np.testing.assert_allclose(o, jax.jit(ref_fn)(q, k, v), rtol=2e-3,
                                       atol=2e-3)
            cot = jnp.asarray(rng.normal(size=o.shape), o.dtype)
            g = jax.jit(jax.grad(lambda *a: jnp.vdot(flash_fn(*a), cot),
                                 argnums=(0, 1, 2)))(q, k, v)
            gr = jax.jit(jax.grad(lambda *a: jnp.vdot(ref_fn(*a), cot),
                                  argnums=(0, 1, 2)))(q, k, v)
        for a, b, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-3,
                                       err_msg=f"d{name}")

    @pytest.mark.parametrize("S,window,bq,bk,causal,alibi", [
        (1024, 0, 256, 256, True, False), (1024, 512, 256, 256, True, False),
        (1024, 1024, 256, 256, True, False), (768, 256, 256, 256, True, False),
        (1024, 0, 256, 256, False, False), (1024, 100, 256, 256, True, False),
        (1024, 384, 256, 256, True, False), (1000, 512, 256, 256, True, False),
        (1024, 512, 128, 256, True, False), (1024, 0, 256, 256, True, True),
        (96, 0, 96, 96, True, False),
    ])
    def test_the_predicate_is_exact_tile_by_tile(self, S, window, bq, bk,
                                                 causal, alibi):
        """Every tile of the padded square against the mask itself, and
        the census against the count of what the predicate said."""
        rows, cols = np.arange(-(-S // bq) * bq), np.arange(-(-S // bk) * bk)
        mask = np.broadcast_to(cols[None] < S, (len(rows), len(cols)))
        if causal:
            mask = mask & (cols[None] <= rows[:, None])
        if window:
            mask = mask & (cols[None] > rows[:, None] - window)
        seen = dict(interior=0, edge=0, general=0)
        tri = np.tril(np.ones((bq, bk), bool))
        for qs in rows[::bq]:
            for ks in cols[::bk]:
                tile = mask[qs:qs + bq, ks:ks + bk]
                kinds = FA._tile_kinds(int(qs), int(ks), bq, bk, S, causal,
                                       window, alibi)
                if not tile.any():
                    # the kernels never build a tile the mask empties
                    assert not kinds.live or kinds.general
                    continue
                assert kinds.live
                assert sum(map(bool, kinds[1:])) == 1, kinds
                if kinds.interior:
                    assert tile.all()
                if kinds.diagonal:
                    assert (tile == tri).all()
                if kinds.window_edge:
                    assert (tile == ~tri).all()
                if alibi:
                    assert kinds.general
                if qs < S and ks < S:
                    seen["general" if kinds.general else "interior"
                         if kinds.interior else "edge"] += 1
        census = tile_census(S, window, bq, bk, causal, alibi)
        assert {k: census[k] for k in seen} == seen
        assert census["needed"] == pytest.approx(
            mask[:S, :S].sum() / (bq * bk))

    @pytest.mark.parametrize("cell,S,window,tiles", [
        # (interior, edge, general): ISSUE 56's table
        ("trinity_windowed", 8192, 2048, (7, 14, 0)),
        ("trinity_full", 8192, 0, (28, 8, 0)),
        ("mistral", 4096, 4096, (6, 4, 0)),
    ])
    def test_the_census_of_the_training_cells(self, cell, S, window, tiles):
        c = tile_census(S, window, 1024, 1024)
        assert (c["interior"], c["edge"], c["general"]) == tiles
        # an edge tile of 1,024: 12/16 in the forward's slabs of 512 (2
        # products a tile), 10/16 in the backward's of 256 (3 + 4)
        edge = (2 * 12 / 16 + 7 * 10 / 16) / 9
        assert c["work"] == pytest.approx(tiles[0] + tiles[1] * edge)
        assert c["work_over_needed"] == pytest.approx(
            c["work"] / (sum(tiles) - tiles[1] / 2), rel=1e-3)
        # today's body (one unit a live tile) ran 1.5 / 1.125 / 1.25 x
        assert sum(tiles) / c["needed"] == pytest.approx(
            {"trinity_windowed": 1.5, "trinity_full": 1.125,
             "mistral": 1.25}[cell], rel=1e-3)


def test_a_models_loss_function_carries_the_census():
    """`flash_*` ids, a number a distinct window in the layers' order
    (tests/test_train_step_scopes.py holds the engine to putting a loss
    function's `shape_ids` on `train.init.shapes`); none where the
    model runs no flash kernel."""
    from deepspeed_tpu.models import transformer as T

    mcfg = T.TransformerConfig(
        vocab_size=256, n_layers=4, n_heads=4, d_model=64, max_seq=512,
        variant="llama", flash_block_q=128, flash_block_k=128,
        attention_window_pattern=(256, 0))
    assert T.make_loss_fn(mcfg).shape_ids == {
        "flash_windows": "256,0", "flash_tiles_interior": "3,6",
        "flash_tiles_edge": "6,4", "flash_tiles_general": "0,0",
        "flash_work_over_needed": "1.498,1.248"}
    with interpret_kernels(False):  # the CPU: the jnp reference runs
        assert T.flash_census_ids(mcfg) == {}
    assert T.flash_census_ids(dataclasses.replace(mcfg, use_flash=False)) == {}


# --- the cells' shapes, compiled for a described v5e (no chip) ---------

@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("B,H,KV,S,window", [
    (2, 32, 4, 8192, 2048), (2, 32, 4, 8192, 0), (4, 32, 8, 4096, 4096)])
def test_a_cells_three_kernels_compile_for_v5e(one_chip, B, H, KV, S, window):
    """Mosaic takes the slabs' static slices at the cells' tiles (the
    interpreter takes any)."""
    static = (True, 1024, 1024, H, KV, window, False)
    qs, kvs = ((B * h, S, 128) for h in (H, KV))
    sd = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(
        shape, dt, sharding=one_chip)

    def step(q, k, v, do):
        o, lse = _flash_fwd(q, k, v, None, *static)
        return _flash_bwd(q, k, v, None, o, lse, do, *static)

    with interpret_kernels(False):
        text = jax.jit(step).lower(sd(qs), sd(kvs), sd(kvs),
                                   sd(qs)).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert any(name in line for line in calls), name
