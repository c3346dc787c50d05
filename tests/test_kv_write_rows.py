"""paged_kv_write moves the rows it writes (PR 52): against the jnp
scatter, bit for bit and over the WHOLE pool, so that a block the call
did not name is seen untouched. Both of kv_write_path's cases run here
("rows": a DMA a row; "blocks": the read-modify-write that pool shapes
Mosaic refuses a row copy of keep), interpreted on the CPU lane; the
last tests compile the cells' shapes for a described v5e, which is where
the tile rule the choice rests on (_whole_tiles) can be checked with no
chip.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

import deepspeed_tpu.ops.pallas.paged_attention as PA
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.utils import profiler

BS = 16  # tokens a block here; the cells' 128 in the compiles below

# (KV, D) of a pool as a cell holds it, and what else takes each case
CELL_POOLS = {
    "dense_granite_8x128": (8, 128),
    "olmoe_16x128": (16, 128),
    "four_heads_4x128": (4, 128),  # Mellum 2's and LFM2's until PR 64
    "qwen3next_mellum2_lfm2_2x256": (2, 256),
    "nemotron_2x128": (2, 128),
}
# shapes whose rows Mosaic refuses as a DMA: they keep the block path
BLOCK_POOLS = {
    "unpacked_8x64": (8, 64, jnp.bfloat16),
    "odd_heads_3x128": (3, 128, jnp.bfloat16),
    "int8_2x128": (2, 128, jnp.int8),
    "phi_4x80": (4, 80, jnp.float32),
}


def _pools(rng, n_blocks, kv, d, dtype, bs=BS):
    draw = lambda *shape: jnp.asarray(
        rng.integers(-100, 100, shape), dtype)
    return draw(n_blocks, bs, kv, d), draw(n_blocks, bs, kv, d)


def _rows(rng, t, kv, d, dtype):
    return (jnp.asarray(rng.integers(-100, 100, (t, kv, d)), dtype),
            jnp.asarray(rng.integers(-100, 100, (t, kv, d)), dtype))


def _same_as_scatter(kc, vc, kn, vn, slots):
    slots = jnp.asarray(slots, jnp.int32)
    want_k, want_v = M._write_kv_xla(kc, vc, kn, vn, slots)
    got_k, got_v = PA.paged_kv_write(kc, vc, kn, vn, slots)
    assert got_k.dtype == kc.dtype and got_k.shape == kc.shape
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    return np.asarray(got_k)


def _scattered_slots(rng, n_slots, t):
    """t distinct slots, every fifth row a -1 pad among the live ones."""
    slots = rng.permutation(n_slots)[:t].astype(np.int32)
    slots[::5] = -1
    return slots


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("pool", CELL_POOLS)
def test_a_cells_pool_takes_its_rows_by_dma(rng, pool, dtype):
    kv, d = CELL_POOLS[pool]
    kc, vc = _pools(rng, 5, kv, d, dtype)
    assert PA.kv_write_path(kc.shape, kc.dtype) == "rows"
    kn, vn = _rows(rng, 40, kv, d, dtype)
    _same_as_scatter(kc, vc, kn, vn, _scattered_slots(rng, 5 * BS, 40))


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("kv", [4, 8, 16])
def test_an_int8_pool_of_whole_tiles_takes_rows(rng, kv):
    kc, vc = _pools(rng, 5, kv, 128, jnp.int8)
    assert PA.kv_write_path(kc.shape, kc.dtype) == "rows"
    kn, vn = _rows(rng, 24, kv, 128, jnp.int8)
    _same_as_scatter(kc, vc, kn, vn, _scattered_slots(rng, 5 * BS, 24))


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("pool", BLOCK_POOLS)
def test_a_pool_mosaic_refuses_a_row_of_keeps_the_block_path(rng, pool):
    kv, d, dtype = BLOCK_POOLS[pool]
    kc, vc = _pools(rng, 5, kv, d, dtype)
    assert PA.kv_write_path(kc.shape, kc.dtype) == "blocks"
    kn, vn = _rows(rng, 24, kv, d, dtype)
    _same_as_scatter(kc, vc, kn, vn, _scattered_slots(rng, 5 * BS, 24))


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("kv,d", [(8, 128), (3, 128)],
                         ids=["rows", "blocks"])
def test_an_all_pad_call_writes_nothing(rng, kv, d):
    kc, vc = _pools(rng, 3, kv, d, jnp.bfloat16)
    kn, vn = _rows(rng, 8, kv, d, jnp.bfloat16)
    got = _same_as_scatter(kc, vc, kn, vn, np.full(8, -1))
    np.testing.assert_array_equal(got, np.asarray(kc))


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("offset", range(8))
def test_a_chunk_that_straddles_a_block_boundary(rng, offset):
    """32 rows of one sequence from position 8 + offset: blocks 3 then 1
    of the pool, the boundary at every offset mod 8, pad rows behind."""
    kc, vc = _pools(rng, 5, 4, 128, jnp.bfloat16)
    table = np.asarray([3, 1, 4])
    pos = 8 + offset + np.arange(32)
    slots = np.concatenate([table[pos // BS] * BS + pos % BS, [-1] * 4])
    kn, vn = _rows(rng, 36, 4, 128, jnp.bfloat16)
    got = _same_as_scatter(kc, vc, kn, vn, slots)
    for block in (0, 2):  # named by no row
        np.testing.assert_array_equal(got[block], np.asarray(kc)[block])
    np.testing.assert_array_equal(got[3, 8 + offset], np.asarray(kn)[0])


@pytest.mark.usefixtures("pallas_interpret")
def test_a_ring_block_that_holds_another_turns_rows_is_overwritten(rng):
    """A windowed layer's ring of 2 blocks: the sequence's third block
    of tokens lands where its first lay. The rows of the turn before
    stay wherever this call names no slot."""
    kc, vc = _pools(rng, 5, 4, 128, jnp.bfloat16)  # ring 1: blocks 2, 3
    ring = 2 + (np.arange(48) // BS) % 2
    slots = ring * BS + np.arange(48) % BS
    kn, vn = _rows(rng, 48, 4, 128, jnp.bfloat16)
    first = _same_as_scatter(kc, vc, kn[:32], vn[:32], slots[:32])
    np.testing.assert_array_equal(first[2], np.asarray(kn)[:BS])
    # positions 32..41 come round to block 2, ten of its sixteen slots
    turn = _same_as_scatter(jnp.asarray(first), vc, kn[32:42], vn[32:42],
                            slots[32:42])
    np.testing.assert_array_equal(turn[2, :10], np.asarray(kn)[32:42])
    np.testing.assert_array_equal(turn[2, 10:], np.asarray(kn)[10:BS])
    np.testing.assert_array_equal(turn[3], first[3])


@pytest.mark.usefixtures("pallas_interpret")
def test_512_rows_in_one_call(rng):
    """The LFM2 cell's step: a packed pool takes [T, 8, 64] rows as its
    own [T, 2, 256]."""
    assert PA.kv_pack(8, 64, 2) == 4
    kc, vc = _pools(rng, 40, 2, 256, jnp.bfloat16)
    kn, vn = _rows(rng, 512, 8, 64, jnp.bfloat16)
    slots = rng.permutation(40 * BS)[:512].astype(np.int32)
    slots[-7:] = -1
    _same_as_scatter(kc, vc, kn, vn, slots)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("kv,d", [(4, 128), (8, 64)], ids=["4x128", "8x64"])
@pytest.mark.parametrize("what", ["decode_rows", "chunk_of_32",
                                  "ring_two_turns"])
def test_two_wide_heads_take_the_models_rows(rng, kv, d, what):
    """A whole-tile pool of fewer than 8 KV heads is [.., 2, 256]
    (kv_pack, PR 64): rows [T, kv, d] of the model land, by the row
    DMA, where a scatter into the UNPACKED float32 pool puts them:
    decode rows in slots of their own, a chunk of 32 over a block's
    edge, and a ring of 10 blocks written two turns deep."""
    pack = PA.kv_pack(kv, d, 2)
    assert (kv // pack, d * pack) == (2, 256)
    plain = _pools(rng, 21, kv, d, jnp.float32)
    kc, vc = (p.reshape(21, BS, 2, 256) for p in plain)
    assert PA.kv_write_path(kc.shape, kc.dtype) == "rows"
    if what == "decode_rows":
        slots = _scattered_slots(rng, 21 * BS, 40)
    else:  # positions 8.., or 2 x 10 x BS + 8.. round the ring 1 .. 10
        pos = 8 + np.arange(32) + (20 * BS if what == "ring_two_turns" else 0)
        slots = np.concatenate([
            (1 + (pos // BS) % 10) * BS + pos % BS, [-1] * 4])
    kn, vn = _rows(rng, len(slots), kv, d, jnp.float32)
    got = _same_as_scatter(kc, vc, kn, vn, slots)
    want, _ = M._write_kv_xla(*plain, kn, vn, jnp.asarray(slots, jnp.int32))
    np.testing.assert_array_equal(got.reshape(want.shape), np.asarray(want))
    live = np.asarray(slots) >= 0
    assert (got != np.asarray(kc)).any(axis=(2, 3)).sum() <= live.sum()


@pytest.mark.usefixtures("pallas_interpret")
def test_a_slot_past_the_arena_stays_inside_it(rng):
    """_arena_block's containment: wrong, but in the last block."""
    kc, vc = _pools(rng, 3, 8, 128, jnp.bfloat16)
    kn, vn = _rows(rng, 2, 8, 128, jnp.bfloat16)
    got_k, _ = PA.paged_kv_write(kc, vc, kn, vn,
                                 jnp.asarray([5, 7 * BS + 3], jnp.int32))
    np.testing.assert_array_equal(np.asarray(got_k)[0, 5], np.asarray(kn)[0])
    np.testing.assert_array_equal(np.asarray(got_k)[2, 3], np.asarray(kn)[1])


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("kv", [2, 8])
def test_the_scale_view_keeps_the_block_path(rng, kv):
    """A slot's KV scales are part of one lane tile: no row to copy."""
    assert PA.kv_write_path((5, BS, 1, kv), jnp.float32) == "blocks"
    ks, vs = (jnp.asarray(rng.normal(size=(5, BS, kv)), jnp.float32)
              for _ in range(2))
    kn, vn = (jnp.asarray(rng.normal(size=(24, kv)), jnp.float32)
              for _ in range(2))
    slots = jnp.asarray(_scattered_slots(rng, 5 * BS, 24))
    got = PA.paged_scale_write(ks, vs, kn, vn, slots)
    want = M._write_scales_xla(ks, vs, kn, vn, slots)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("tp,kv,how", [(2, 8, "rows"), (4, 8, "rows"),
                                       (2, 2, "blocks"), (4, 6, "xla")])
def test_under_a_model_mesh_each_device_writes_its_heads(rng, tp, kv, how):
    """_write_kv rides _shard_map_kernel with the KV dim sharded: the
    choice is made from the shard a device holds (8 heads over 4 devices
    leave 2, whole bf16 tiles; 2 over 2 leave 1, which is not)."""
    mesh = Mesh(np.asarray(jax.devices()[:tp]), ("model",))
    kc, vc = _pools(rng, 5, kv, 128, jnp.bfloat16)
    assert M.kv_write_how(kc, mesh) == how
    kn, vn = _rows(rng, 24, kv, 128, jnp.bfloat16)
    slots = jnp.asarray(_scattered_slots(rng, 5 * BS, 24))
    want = M._write_kv_xla(kc, vc, kn, vn, slots)
    got = jax.jit(lambda *a: M._write_kv(*a, mesh=mesh))(kc, vc, kn, vn, slots)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_decode_impl_xla_is_reported_as_the_scatter(rng):
    kc, _ = _pools(rng, 2, 8, 128, jnp.bfloat16)
    assert M.kv_write_how(kc, None, use_kernel=False) == "xla"


# what Mosaic answered for a row copy, v5e, libtpu 0.0.34 (PR 52): the
# predicate's table of answers, so a change to it is a change here
TILE_RULE = [
    (8, 128, 2, True), (16, 128, 2, True), (4, 128, 2, True),
    (2, 256, 2, True), (2, 128, 2, True), (24, 128, 2, True),
    (1, 128, 2, False), (3, 128, 2, False), (6, 128, 2, False),
    (12, 128, 2, False), (20, 384, 2, False), (8, 64, 2, False),
    (8, 80, 2, False), (4, 128, 1, True), (8, 128, 1, True),
    (40, 128, 1, True), (1, 128, 1, False), (2, 128, 1, False),
    (12, 128, 1, False), (1, 128, 4, True), (3, 128, 4, True),
    (12, 128, 4, True), (2, 384, 4, True), (3, 384, 4, False),
    (12, 384, 4, False), (24, 384, 4, True), (8, 64, 4, False),
]


@pytest.mark.parametrize("kv,d,itemsize,whole", TILE_RULE)
def test_the_tile_rule(kv, d, itemsize, whole):
    assert PA._whole_tiles(kv, d, itemsize) is whole


def _pool_span(cfg, dtype=jnp.float32, **kw):
    from deepspeed_tpu.inference import init_inference

    profiler.spans(clear=True)
    init_inference(T.init(cfg, jax.random.PRNGKey(0)), cfg, dict(
        max_seq_len=64, kv_block_size=8, num_kv_blocks=24, max_batch_size=4,
        max_tracked_sequences=4, **kw), dtype=dtype)
    return next(s for s in profiler.spans(clear=True)
                if s.name == "init.pool")


@pytest.mark.usefixtures("pallas_interpret")
def test_init_pool_says_how_each_pool_is_written():
    paged = T.TransformerConfig(
        vocab_size=64, n_layers=2, n_heads=4, n_kv_heads=2, d_model=512,
        max_seq=64, variant="llama", use_flash=False)
    assert paged.head_dim == 128
    ids = _pool_span(paged, decode_impl="pallas").ids
    assert ids["kv_write"] == "rows"
    assert "ring_kv_write" not in ids and "scale_kv_write" not in ids
    ids = _pool_span(paged, decode_impl="pallas", kv_cache_dtype="int8").ids
    # int8 codes of 2 heads are half a tile; scales never a row
    assert (ids["kv_write"], ids["scale_kv_write"]) == ("blocks", "blocks")
    assert _pool_span(paged, decode_impl="xla").ids["kv_write"] == "xla"
    narrow = T.TransformerConfig(
        vocab_size=64, n_layers=2, n_heads=4, n_kv_heads=1, d_model=512,
        max_seq=64, variant="llama", use_flash=False)
    assert _pool_span(narrow, decode_impl="pallas").ids["kv_write"] == "rows"
    # float32 pools of 128 lanes take any count of heads; bf16 of 1 not
    assert PA.kv_write_path((25, 8, 1, 128), jnp.bfloat16) == "blocks"


@pytest.mark.parametrize("kv,d,itemsize,pack", [
    # fewer than a tile of whole-tile heads: two wide heads (PR 64),
    # for the served 16 bits whatever the pool's dtype
    (4, 128, 2, 2), (8, 64, 2, 4), (4, 128, 4, 2), (8, 64, 4, 4),
    (4, 256, 2, 2),
    # what must NOT move: a tile of heads or more, pools of 2 heads
    # already, the pools PR 63 packed, heads of 64 that pair to 2 or to
    # no whole tiles, 8 bits
    (8, 128, 2, 1), (16, 128, 2, 1), (2, 256, 2, 1), (2, 128, 2, 1),
    (2, 640, 2, 1), (30, 128, 2, 15), (3, 64, 2, 1), (4, 64, 2, 2),
    (2, 64, 2, 2), (12, 64, 2, 2), (6, 128, 2, 1), (4, 128, 1, 1),
])
def test_who_lies_in_two_wide_heads(kv, d, itemsize, pack):
    assert PA.kv_pack(kv, d, itemsize) == pack
    if (kv, d) in ((4, 128), (8, 64), (4, 256)) and itemsize > 1:
        # the MOST heads side by side that leave whole tiles (one head
        # of all the lanes is none in 16 bits); the pairs' rule
        # (kv_pair_fold) answers the FEWEST, 1 for 4 heads of 128
        assert PA._whole_tiles(kv // pack, d * pack, 2)
        assert not PA._whole_tiles(1, kv * d, 2)
        assert PA.kv_pair_fold(4, 128) == 1


def test_a_meshed_or_quantised_pool_of_4_heads_is_not_packed():
    cfg = T.TransformerConfig(
        vocab_size=64, n_layers=2, n_heads=8, n_kv_heads=4, d_model=1024,
        max_seq=64, variant="llama", use_flash=False)
    assert (cfg.kv_heads, cfg.head_dim) == (4, 128)
    shapes = lambda **kw: {
        x.shape for x in jax.eval_shape(
            lambda: M.init_cache(cfg, 5, 8, jnp.bfloat16, **kw)).k}
    assert shapes() == {(5, 8, 2, 256)}
    assert shapes(kv_quant=True) == {(5, 8, 4, 128)}
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    assert {x.shape for x in M.init_cache(
        cfg, 5, 8, jnp.bfloat16, mesh=mesh).k} == {(5, 8, 4, 128)}


@pytest.mark.parametrize("heads,kv,d_model,dtype,kw,pack", [
    (4, 2, 512, jnp.float32, {}, 1),       # 2 heads of 128: whole tiles
    (8, 4, 512, jnp.float32, {}, 2),       # heads of 64: two a lane row
    (12, 12, 1536, jnp.bfloat16, {}, 3),   # 12 of 128 in 16 bits: 4 of 384
    (12, 12, 1536, jnp.float32, {}, 1),    # in 32 bits any count is whole
    (12, 12, 1536, jnp.bfloat16, dict(kv_cache_dtype="int8"), 1),
    (8, 4, 1024, jnp.float32, {}, 2),      # 4 of 128: 2 of 256 (PR 64)
    (8, 8, 512, jnp.bfloat16, {}, 4),      # 8 of 64: 2 of 256
    (8, 4, 1024, jnp.bfloat16, dict(kv_cache_dtype="int8"), 1),
    (8, 8, 1024, jnp.bfloat16, {}, 1),     # a tile of heads stays
], ids=["whole_tiles", "head_dim_64", "no_whole_tiles", "float32", "int8",
        "four_heads", "eight_of_64", "four_heads_int8", "eight_heads"])
def test_init_pool_says_what_a_pool_head_holds(heads, kv, d_model, dtype, kw,
                                               pack):
    """`kv_pack`, the KV heads a pool's head holds side by side, and
    `kv_heads_padded`, heads held beyond the model's (0: no pool pads
    its heads since PR 63), off the allocated pool's own shape."""
    cfg = T.TransformerConfig(
        vocab_size=64, n_layers=2, n_heads=heads, n_kv_heads=kv,
        d_model=d_model, max_seq=64, variant="llama", use_flash=False)
    span = _pool_span(cfg, dtype, decode_impl="xla", **kw)
    assert (span.ids["kv_pack"], span.ids["kv_heads_padded"]) == (pack, 0)


@pytest.mark.usefixtures("pallas_interpret")
def test_init_pool_names_the_rings_of_a_model_of_mixed_windows():
    from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

    hf = {"model_type": "mellum", "attention_bias": False, "head_dim": 128,
          "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
          "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
          "mlp_layer_types": ["sparse"] * 4, "max_position_embeddings": 4096,
          "max_window_layers": 0, "moe_intermediate_size": 32,
          "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 8,
          "num_experts_per_tok": 2, "num_hidden_layers": 4,
          "num_key_value_heads": 2, "rms_norm_eps": 1e-6,
          "rope_parameters": {
              "full_attention": {"rope_type": "default", "rope_theta": 1e4},
              "sliding_attention": {"rope_type": "default",
                                    "rope_theta": 1e4}},
          "sliding_window": 16, "tie_word_embeddings": False,
          "vocab_size": 128, "use_sliding_window": True}
    ringed = config_from_hf(hf, max_seq=512, use_flash=False)
    assert ringed.mixed_windows
    ids = _pool_span(ringed, decode_impl="pallas", num_kv_rings=4).ids
    assert (ids["kv_write"], ids["ring_kv_write"]) == ("rows", "rows")
    assert ids["window_layers"] == 3


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


def _compiles(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            and "paged_kv_write" in line]


# (rows of a step, pool blocks, KV, D) as each serving cell writes
CELL_WRITES = {
    "dense": (128, 705, 8, 128), "olmoe": (128, 705, 16, 128),
    "mellum2_pages": (256, 3073, 2, 256), "mellum2_rings": (256, 961, 2, 256),
    "lfm2_packed": (512, 2049, 2, 256), "qwen3next": (256, 1025, 2, 256),
    "granite": (128, 1025, 8, 128), "nemotron": (256, 1025, 2, 128),
    # what Mellum 2's pools were until PR 64, and a meshed pool's still
    "four_heads": (256, 3073, 4, 128),
}


@pytest.mark.parametrize("cell", CELL_WRITES)
def test_a_cells_write_compiles_for_v5e_as_row_copies(one_chip, cell):
    rows, blocks, kv, d = CELL_WRITES[cell]
    pool = ((blocks, 128, kv, d), jnp.bfloat16)
    new = ((rows, kv, d), jnp.bfloat16)
    assert PA.kv_write_path(*pool) == "rows"
    calls = _compiles(one_chip, PA.paged_kv_write, pool, pool, new, new,
                      ((rows,), jnp.int32))
    assert len(calls) == 1


@pytest.mark.parametrize("kv,d,dtype", [
    (8, 128, jnp.int8), (4, 128, jnp.int8), (2, 128, jnp.int8),
    (8, 128, jnp.float32), (3, 128, jnp.float32), (3, 128, jnp.bfloat16),
    (8, 64, jnp.bfloat16), (12, 128, jnp.bfloat16), (4, 80, jnp.bfloat16)],
    ids=lambda v: getattr(v, "__name__", str(v)))
def test_every_other_pool_compiles_on_the_path_the_rule_picks(
        one_chip, kv, d, dtype):
    pool = ((65, 128, kv, d), dtype)
    new = ((128, kv, d), dtype)
    assert _compiles(one_chip, PA.paged_kv_write, pool, pool, new, new,
                     ((128,), jnp.int32))


def test_the_scale_write_compiles_for_v5e(one_chip):
    pool = ((65, 128, 8), jnp.float32)
    new = ((128, 8), jnp.float32)
    assert _compiles(one_chip, PA.paged_scale_write, pool, pool, new, new,
                     ((128,), jnp.int32))


@pytest.mark.parametrize("kv,d,itemsize,whole", [
    c for c in TILE_RULE if not c[3] and c[1] % 128 == 0])
def test_mosaic_refuses_the_rows_the_rule_sends_to_blocks(
        one_chip, kv, d, itemsize, whole, monkeypatch):
    """The rule is not merely safe: where it says "blocks" at whole
    lanes, a row copy IS refused (a shape it wrongly held back would
    pay a block for a row)."""
    dtype = {1: jnp.int8, 2: jnp.bfloat16, 4: jnp.float32}[itemsize]
    monkeypatch.setattr(PA, "_whole_tiles", lambda *_: True)
    PA._kv_write.clear_cache()
    pool = ((9, 128, kv, d), dtype)
    new = ((128, kv, d), dtype)
    try:
        with pytest.raises(Exception, match="aligned to tiling"):
            _compiles(one_chip, PA.paged_kv_write, pool, pool, new, new,
                      ((128,), jnp.int32))
    finally:
        PA._kv_write.clear_cache()
