"""The one-pass kernel of a state layer's short convolution over a
step's ragged rows (ops/pallas/conv_carry.py), in interpret mode on the
CPU, against the XLA path it replaces (inference/model.py `_carry_rows`
+ `_depthwise`): the float32 sum and the pool, EXACTLY, over row
layouts that each break something if a run boundary, a slot or a tile
is wrong; `carry_fits`, the one chooser; the kernel at both state
cells' shapes compiled for a described v5e; and a model without state
counting nothing."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from deepspeed_tpu.inference import model as M
from deepspeed_tpu.ops.pallas import conv_carry as CC

SHAPES = [(2, 256), (3, 512)]  # (K - 1, E)
N_SLOTS = 8

# (slots, positions) of a step's rows; -1: a pad row. Every live run
# continues a sequence (position > 0) unless the case says otherwise.
LAYOUTS = {
    "all_decode_rows": ([3, 0, 7, 1, 5, 2], [9, 4, 1, 2, 3, 17]),
    "a_chunk_longer_than_the_taps": ([4] * 7, [5, 6, 7, 8, 9, 10, 11]),
    "chunks_of_one_and_of_two_rows": ([6, 2, 2, 5], [3, 7, 8, 1]),
    "a_prompt_starts_in_a_slot_of_garbage": (
        [1, 1, 1, 1, 1, 0], [0, 1, 2, 3, 4, 0]),
    "a_run_short_of_the_taps_after_one_token": ([3, 3, 0, 0], [1, 2, 2, 3]),
    "pad_rows_at_the_end_and_between_runs": (
        [2, 2, -1, 5, -1, 4, 4, 4, -1, -1], [6, 7, 0, 2, 0, 1, 2, 3, 0, 0]),
    "the_last_row_ends_a_run": ([0, 6, 6, 6, 6], [8, 2, 3, 4, 5]),
    "decode_rows_and_chunks_mixed": (
        [7, 3, 3, 3, 3, 3, 0, 5, 5, 1, 2, 2], [11, 0, 1, 2, 3, 4, 6, 3, 4, 1,
                                               64, 65]),
}


def _inputs(k1, E, dtype, n_rows, seed=0, garbage=()):
    rng = np.random.default_rng(seed)
    lanes = CC.LANES if E % CC.LANES == 0 else E
    u = jnp.asarray(rng.standard_normal((n_rows, E)), dtype)
    taps = jnp.asarray(rng.standard_normal((E, k1 + 1)), dtype)
    pool = rng.standard_normal((N_SLOTS, k1, E // lanes, lanes))
    pool[list(garbage)] = np.nan
    return u, taps, jnp.asarray(pool, dtype)


def _xla(u, taps, pool, slots, positions):
    past, pool = M._carry_rows(u, pool, slots, positions)
    return M._depthwise(past, u, taps), pool


def _both(u, taps, pool, slots, positions):
    slots = jnp.asarray(slots, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    want = jax.jit(_xla)(u, taps, pool, slots, positions)
    got = jax.jit(CC.conv_carry)(u, taps, pool, slots, positions)
    return got, want, np.asarray(slots) >= 0


def _same(got, want, live):
    """The sum of every live row and the whole pool, bit for bit (NaN
    where both hold NaN: a slot nobody wrote keeps its garbage)."""
    np.testing.assert_array_equal(np.asarray(got[0])[live],
                                  np.asarray(want[0])[live])
    np.testing.assert_array_equal(np.asarray(got[1], np.float32),
                                  np.asarray(want[1], np.float32))


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("k1,E", SHAPES)
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_kernel_is_the_xla_path_exactly(layout, k1, E, dtype):
    slots, positions = LAYOUTS[layout]
    # a prompt's slot holds NaN: at position 0 nothing of it is read
    garbage = [s for s, p in zip(slots, positions) if p == 0 and s >= 0]
    u, taps, pool = _inputs(k1, E, dtype, len(slots), garbage=garbage)
    assert CC.carry_fits(len(slots), dtype, pool)
    got, want, live = _both(u, taps, pool, slots, positions)
    _same(got, want, live)
    assert np.isfinite(np.asarray(got[0], np.float32)[live]).all()
    assert got[0].dtype == jnp.float32 and got[1].dtype == pool.dtype
    # pad rows and sequences with no row in the step leave their slots
    idle = sorted(set(range(N_SLOTS)) - set(slots))
    np.testing.assert_array_equal(np.asarray(got[1], np.float32)[idle],
                                  np.asarray(pool, np.float32)[idle])


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("k1,E", SHAPES)
def test_a_run_continues_from_the_slot_an_earlier_step_wrote(k1, E):
    """Three steps chained through the pool: a prompt's first chunk, its
    second beside another sequence's decode row, then single rows; the
    sums equal one causal convolution over the whole sequence."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((9, E)), jnp.float32)
    _, taps, pool = _inputs(k1, E, jnp.float32, 1, garbage=range(N_SLOTS))
    other = jnp.asarray(rng.standard_normal((1, E)), jnp.float32)
    steps = [(x[0:4], [5] * 4, [0, 1, 2, 3]),
             (jnp.concatenate([other, x[4:7]]), [2, 5, 5, 5], [0, 4, 5, 6]),
             (x[7:8], [5], [7]), (x[8:9], [5], [8])]
    sums = []
    for u, slots, positions in steps:
        got, want, live = _both(u, taps, pool, slots, positions)
        _same(got, want, live)
        pool = got[1]
        sums.append(np.asarray(got[0])[np.asarray(slots) == 5])
    padded = jnp.pad(x, ((k1, 0), (0, 0)))
    whole = M._depthwise([padded[j:j + 9] for j in range(k1)], x, taps)
    # (another program: XLA contracts other multiply-adds on the CPU)
    np.testing.assert_allclose(np.concatenate(sums), np.asarray(whole),
                               rtol=0, atol=2e-6)
    np.testing.assert_array_equal(
        np.asarray(pool[5]).reshape(k1, E), np.asarray(x[9 - k1:]))


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("rows", [1, 2, 3, 4, 6])
def test_a_run_crosses_a_row_tile_boundary(monkeypatch, rows):
    """12 rows in tiles of `rows`: a chunk's predecessors lie in the
    tile before, its slot is fetched in one tile and written in
    another, and the last tile waits for two tiles' writes."""
    k1, E = 3, 512
    slots, positions = LAYOUTS["decode_rows_and_chunks_mixed"]
    u, taps, pool = _inputs(k1, E, jnp.float32, len(slots), garbage=[3])
    monkeypatch.setattr(CC, "_TILE_BYTES", rows * CC._row_bytes(E, k1, 4))
    assert CC._tile_rows(len(slots), E, k1, 4) == rows
    _same(*_both(u, taps, pool, slots, positions))


def test_the_tile_follows_the_rows_bytes():
    """48 KB a slot (Qwen3-Next) against 8 KB (LFM2): 32 and 128 rows a
    grid step of the cells' 256 and 512; a width no power of two
    divides still tiles."""
    assert CC._tile_rows(256, 8192, 3, 2) == 32
    assert CC._tile_rows(512, 2048, 2, 2) == 128
    assert CC._tile_rows(8, 1280, 3, 4) == 8
    assert CC._tile_rows(7 * 9, 8192, 3, 2) == 21


def _pool(k1, E, dtype=jnp.bfloat16, n_slots=256):
    lanes = CC.LANES if E % CC.LANES == 0 else E
    return jax.ShapeDtypeStruct((n_slots, k1, E // lanes, lanes), dtype)


@pytest.mark.parametrize("what,n_rows,dtype,pool,fits", [
    ("qwen3_next_cell", 256, jnp.bfloat16, _pool(3, 8192), True),
    ("lfm2_cell", 512, jnp.bfloat16, _pool(2, 2048, n_slots=1024), True),
    ("granite4h_cell", 128, jnp.bfloat16, _pool(3, 9216), True),
    # its 1,280 channels in the slot of 2,048 that state_shapes gives it
    ("a_tiny_float32_model", 8, jnp.float32, _pool(3, 2048, jnp.float32),
     True),
    ("lane_rows_under_one_tile", 8, jnp.float32, _pool(2, 256, jnp.float32),
     True),
    ("lane_rows_that_are_not_whole_tiles", 8, jnp.float32,
     _pool(3, 1280, jnp.float32), False),
    ("channels_that_are_not_whole_lanes", 8, jnp.float32,
     _pool(2, 192, jnp.float32), False),
    ("a_pool_in_another_dtype_than_the_inputs", 256, jnp.bfloat16,
     _pool(3, 8192, jnp.float32), False),
    ("inputs_beyond_the_kernels_vmem", 4096, jnp.bfloat16, _pool(3, 8192),
     False),
    ("facts_beyond_scalar_memory", 1 << 15, jnp.bfloat16, _pool(2, 128),
     False),
])
def test_carry_fits(what, n_rows, dtype, pool, fits):
    assert CC.carry_fits(n_rows, dtype, pool) is fits


def _step_kernels(cfg_kw, dtype, use_kernel, pool_dtype=None):
    """The kernels in the jaxpr of one decode_step of a two-layer model
    of a conv and an attention layer."""
    from deepspeed_tpu.models import transformer as T

    cfg = T.TransformerConfig(
        vocab_size=64, n_layers=2, n_heads=2, max_seq=64, variant="llama",
        use_flash=False, layer_types=("conv", "attention"), conv_kernel=3,
        **cfg_kw)
    params = jax.tree.map(lambda a: a.astype(dtype),
                          T.init(cfg, jax.random.PRNGKey(0)))
    cache = M.init_cache(cfg, 5, 16, pool_dtype or dtype, state_slots=4)
    ints = lambda *s: jnp.zeros(s, jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, c: M.decode_step(
        p, c, ints(8), ints(8, 4), ints(8) + 1, cfg, use_kernel=use_kernel,
        slots=ints(8)))(params, cache)
    return str(jaxpr)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("what,cfg_kw,kw,kernel", [
    ("whole_lanes", dict(d_model=128), {}, True),
    ("decode_impl_xla", dict(d_model=128), dict(use_kernel=False), False),
    ("channels_off_the_lanes", dict(d_model=64), {}, False),
    ("a_float32_pool_under_bfloat16_inputs", dict(d_model=128),
     dict(dtype=jnp.bfloat16, pool_dtype=jnp.float32), False),
])
def test_what_does_not_fit_takes_the_xla_path(what, cfg_kw, kw, kernel):
    kw = dict(dict(dtype=jnp.float32, use_kernel=True), **kw)
    assert ("conv_carry" in _step_kernels(cfg_kw, **kw)) is kernel


@pytest.mark.usefixtures("pallas_interpret")
def test_a_model_without_state_counts_nothing():
    from _serving_models import engine_for, small_model
    from deepspeed_tpu.inference import (ServingScheduler,
                                         ServingSchedulerConfig)

    eng = engine_for(*small_model())
    assert eng.resolved_impl == "pallas" and not eng.carry_kernel(8)
    assert not eng.step_kernel(8)
    s = ServingScheduler(eng, ServingSchedulerConfig(
        max_num_batched_tokens=16, prefill_chunk=8, warmup=False))
    s.submit(list(range(11)), max_new_tokens=3)
    s.run()
    assert s.counters["steps"] > 0
    assert s.counters["state_carry_kernel_steps"] == 0
    assert s.counters["state_step_kernel_steps"] == 0


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


@pytest.mark.parametrize("rows,E,k1,n_slots", [
    (256, 8192, 3, 256), (512, 2048, 2, 1024), (128, 8192, 3, 256)])
def test_the_kernel_compiles_for_v5e_at_the_cells_shapes(one_chip, rows, E,
                                                         k1, n_slots):
    """Both state cells' programs and the 128-row bucket: one Mosaic
    kernel, the pool aliased in and out (no second pool among the
    temporaries)."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((n_slots, k1, E // 128, 128))
    assert CC.carry_fits(rows, jnp.bfloat16, pool)
    compiled = jax.jit(CC.conv_carry, donate_argnums=(2,)).lower(
        sds((rows, E)), sds((E, k1 + 1)), pool, sds((rows,), jnp.int32),
        sds((rows,), jnp.int32)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "conv_carry" in calls[0]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= n_slots * k1 * E * 2
    assert mem.temp_size_in_bytes < 32 << 20
