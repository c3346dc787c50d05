"""AOT compiles of the cells' six kernels for a DESCRIBED v5e:2x2
topology: no chip, no chip time, about two seconds each. They guard
the cells' shapes on every later PR: a refusal here (tiling, VMEM,
partitioning) is what the chip's compiler would raise.

A compile that passes is not a chip run and is never reported as one.
The topology is described inside a module-scoped fixture (never while
a module is imported), and every compile runs in this process: only
one process at a time may load the TPU's library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# Mistral-7B attention widths, as the cells run them
H, KV, D = 32, 8, 128
SEQ = 4096            # train-seq4k
ROWS, BLOCK, BLOCKS_PER_SEQ, POOL = 128, 128, 32, 704   # serve cells


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described compile is written to the persistent cache but cannot
    # be read back without a chip: keep these out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _has_kernel(text, name):
    # under autodiff the instruction is e.g. %transpose_jvp_flash_bwd_dq__.1
    return any('custom_call_target="tpu_custom_call"' in line and name in line
               for line in text.splitlines())


def test_flash_forward_and_backward_at_4096(one_chip):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    q = _sds((1, SEQ, H, D), jnp.bfloat16, one_chip)
    kv = _sds((1, SEQ, KV, D), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=1024,
                            block_k=1024, window=4096)
        return jnp.sum(o.astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert _has_kernel(text, name), name


def _paged_args(sharding, rows_q=ROWS):
    cache = _sds((POOL + 1, BLOCK, KV, D), jnp.bfloat16, sharding)
    q = _sds((rows_q, H, D), jnp.bfloat16, sharding)
    new = _sds((rows_q, KV, D), jnp.bfloat16, sharding)
    table = _sds((rows_q, BLOCKS_PER_SEQ), jnp.int32, sharding)
    ints = _sds((rows_q,), jnp.int32, sharding)
    return cache, q, new, table, ints


def test_paged_decode_fused_at_128_rows(one_chip):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_fused

    cache, q, new, table, ints = _paged_args(one_chip)

    def fn(q, kc, vc, table, ctx, kn, vn, slots):
        return paged_decode_fused(q, kc, vc, table, ctx, kn, vn, slots,
                                  window=4096)

    text = _compile(fn, q, cache, cache, table, ints, new, new, ints)
    assert _has_kernel(text, "paged_decode_fused")


def test_paged_decode_grid_at_128_rows(one_chip):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention

    cache, q, _, table, ints = _paged_args(one_chip)

    def fn(q, kc, vc, table, ctx):
        return paged_decode_attention(q, kc, vc, table, ctx, window=4096)

    text = _compile(fn, q, cache, cache, table, ints)
    assert _has_kernel(text, "paged_decode_grid")


def test_paged_kv_write_at_128_rows(one_chip):
    from deepspeed_tpu.ops.pallas.paged_attention import paged_kv_write

    cache, _, new, _, ints = _paged_args(one_chip)
    text = _compile(paged_kv_write, cache, cache, new, new, ints)
    assert _has_kernel(text, "paged_kv_write")
