"""AOT compiles of the latent cell's two kernels for a DESCRIBED
v5e:2x2 topology, at the shapes `serve-pangu-longchat-saturated` runs
them (PR 33): no chip, no chip time. As test_aot_kernels.py: a refusal
here (tiling, VMEM) is what the chip's compiler would raise; a compile
that passes is not a chip run."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# openPangu-Ultra-MoE's latent row (576 values in 640 lanes), the
# cell's 128 rows of 128 heads, tables of 72 blocks, a pool of 5,633
ROWS, H, LANES, V_DIM, BLOCK, BLOCKS_PER_SEQ, POOL = 128, 128, 640, 512, 128, 72, 5633


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


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [line for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def test_the_latent_walk_at_the_cells_shapes(one_chip):
    from deepspeed_tpu.ops.pallas import paged_attention as PA

    pool = _sds((POOL, BLOCK, LANES), jnp.bfloat16, one_chip)
    assert PA.latent_walk_fits(BLOCKS_PER_SEQ, pool)
    calls = _kernels(
        lambda q, p, t, c: PA.paged_latent_attention(q, p, t, c, V_DIM),
        _sds((ROWS, H, LANES), jnp.bfloat16, one_chip), pool,
        _sds((ROWS, BLOCKS_PER_SEQ), jnp.int32, one_chip),
        _sds((ROWS,), jnp.int32, one_chip))
    assert len(calls) == 1 and "paged_decode_grid" in calls[0]


def test_the_latent_write_at_the_cells_shapes(one_chip):
    from deepspeed_tpu.ops.pallas import paged_attention as PA

    calls = _kernels(
        PA.paged_latent_write,
        _sds((POOL, BLOCK, LANES), jnp.bfloat16, one_chip),
        _sds((ROWS, LANES), jnp.bfloat16, one_chip),
        _sds((ROWS,), jnp.int32, one_chip))
    assert len(calls) == 1 and "paged_latent_write" in calls[0]


def test_a_pool_of_the_rows_own_width_is_refused_by_the_compiler(one_chip):
    """Why the pool pads 576 to 640: Mosaic takes a manual DMA of a
    block only if its minor dim fills whole lane tiles."""
    from deepspeed_tpu.ops.pallas import paged_attention as PA

    with pytest.raises(Exception, match="aligned to tiling"):
        _kernels(
            lambda q, p, t, c: PA.paged_latent_attention(q, p, t, c, V_DIM),
            _sds((ROWS, H, 576), jnp.bfloat16, one_chip),
            _sds((POOL, BLOCK, 576), jnp.bfloat16, one_chip),
            _sds((ROWS, BLOCKS_PER_SEQ), jnp.int32, one_chip),
            _sds((ROWS,), jnp.int32, one_chip))
