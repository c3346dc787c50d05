"""The shared-table decode attention (`paged_decode_attention`, unfused
and unquantised: trace name `paged_decode_grid`) walks, for each row,
the LIVE blocks of that row's table and nothing else.

- the kernel against the gather oracle, interpreted, as cases of one
  test: a prefill chunk's rows on one table among distinct rows, the
  context edges around a block boundary, both serving cells' head
  layouts, a window shorter than the context, ALiBi, head dim 64
  (which the (S, NB) grid keeps);
- dead table slots are not read, not merely masked;
- which case takes which kernel, read from the traced program;
- AOT compiles for a DESCRIBED v5e at both serving cells' shapes (no
  chip; the topology is described inside a module-scoped fixture and
  every compile runs in this process, as benchmarks/tests/
  test_aot_kernels.py does);
- the scheduler's `kv_live_blocks` counter and its reader.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import deepspeed_tpu.models.transformer as T
from deepspeed_tpu.inference import (
    ServingScheduler, ServingSchedulerConfig, init_inference)
from deepspeed_tpu.ops.attention import alibi_slopes
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, paged_decode_attention_xla)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _case(H=4, KV=2, D=128, bs=16, NB=4, ctx=(5, 33, 64), chunk=None,
          window=0, alibi=False, dtype=jnp.float32):
    return dict(H=H, KV=KV, D=D, bs=bs, NB=NB, ctx=ctx, chunk=chunk,
                window=window, alibi=alibi, dtype=dtype)


CASES = {
    # a 5-token chunk (rows 2-6: one table, ctx rising by one across a
    # block boundary) between distinct rows
    "chunk_among_distinct_rows": _case(
        ctx=(9, 40, 14, 15, 16, 17, 18, 64), chunk=(2, 5)),
    # pad row, first token, and the edges of a 128-token block
    "ctx_edges_block_128": _case(
        bs=128, NB=2, ctx=(0, 1, 127, 128, 129, 256, 0, 200)),
    "gqa_32q_8kv_bf16": _case(
        H=32, KV=8, ctx=(1, 16, 17, 49, 64), dtype=jnp.bfloat16),
    "mha_16q_16kv_group_padded": _case(
        H=16, KV=16, ctx=(3, 31, 32, 64)),
    "window_shorter_than_context": _case(
        ctx=(5, 33, 50, 64, 64), chunk=(3, 2), window=20),
    "window_not_a_block_multiple": _case(ctx=(7, 41, 64), window=37),
    "alibi": _case(H=8, KV=2, ctx=(2, 30, 64), alibi=True),
    # Mosaic takes no manual DMA of a 64-wide block: the grid keeps it
    "head_dim_64": _case(D=64, ctx=(0, 5, 33, 64)),
    "head_dim_64_window": _case(D=64, ctx=(5, 33, 64), window=20),
}


def _inputs(rng, c):
    S, NBLK = len(c["ctx"]), len(c["ctx"]) * c["NB"] + 1
    shape = (NBLK, c["bs"], c["KV"], c["D"])
    q = jnp.asarray(rng.normal(size=(S, c["H"], c["D"])), c["dtype"])
    kc = jnp.asarray(rng.normal(size=shape), c["dtype"])
    vc = jnp.asarray(rng.normal(size=shape), c["dtype"])
    tbl = rng.permutation(NBLK - 1)[: S * c["NB"]].reshape(S, c["NB"])
    tbl = tbl.astype(np.int32)
    if c["chunk"]:
        first, n = c["chunk"]
        tbl[first:first + n] = tbl[first]
    return q, kc, vc, tbl, np.asarray(c["ctx"], np.int32)


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("name", sorted(CASES))
def test_shared_table_attention_matches_oracle(rng, name):
    c = CASES[name]
    q, kc, vc, tbl, ctx = _inputs(rng, c)
    kw = {}
    if c["alibi"]:
        kw["alibi_slopes"] = jnp.asarray(alibi_slopes(c["H"]), jnp.float32)
    with jax.default_matmul_precision("highest"):
        out = paged_decode_attention(q, kc, vc, jnp.asarray(tbl),
                                     jnp.asarray(ctx), window=c["window"],
                                     **kw)
        ref = paged_decode_attention_xla(q, kc, vc, jnp.asarray(tbl),
                                         jnp.asarray(ctx),
                                         window=c["window"], **kw)
    tol = 3e-2 if c["dtype"] == jnp.bfloat16 else 2e-3
    real = ctx > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                               np.asarray(ref, np.float32)[real],
                               rtol=tol, atol=tol)
    if c["D"] % 128 == 0:  # the walk: a pad row stores zeros
        assert not np.asarray(out, np.float32)[~real].any()


@pytest.mark.usefixtures("pallas_interpret")
def test_dead_slots_are_not_read(rng):
    """Table entries beyond a row's live blocks point at a block full of
    NaN: masking alone would still let 0 x NaN into the accumulator."""
    c = _case(NB=4, ctx=(0, 1, 16, 17, 40, 41, 42, 64), chunk=(4, 3))
    q, kc, vc, tbl, ctx = _inputs(rng, c)
    poison = kc.shape[0] - 1  # the one block no table names
    kc = kc.at[poison].set(jnp.nan)
    vc = vc.at[poison].set(jnp.nan)
    clean = tbl.copy()
    for s in range(len(ctx)):
        tbl[s, -(-int(ctx[s]) // c["bs"]):] = poison
    assert (tbl == poison).sum() >= 12
    with jax.default_matmul_precision("highest"):
        out = paged_decode_attention(q, kc, vc, jnp.asarray(tbl),
                                     jnp.asarray(ctx))
        want = paged_decode_attention(q, kc, vc, jnp.asarray(clean),
                                      jnp.asarray(ctx))
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def _kernel_grids(fn, *args):
    """Grid of every pallas_call named paged_decode_grid in fn's trace."""
    grids = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                assert "paged_decode_grid" in str(eqn.params["name"])
                grids.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return grids


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("what,KV,D,dtype,quant,rows_only", [
    ("bf16_d128", 8, 128, jnp.bfloat16, False, True),
    ("bf16_mha_d128", 16, 128, jnp.bfloat16, False, True),
    ("f32_d128_3kv", 3, 128, jnp.float32, False, True),
    ("d64", 8, 64, jnp.bfloat16, False, False),
    ("bf16_12kv", 12, 128, jnp.bfloat16, False, False),
    ("bf16_1kv", 1, 128, jnp.bfloat16, False, False),
    ("int8_kv", 8, 128, jnp.bfloat16, True, False),
])
def test_which_case_walks_and_which_keeps_the_grid(what, KV, D, dtype,
                                                   quant, rows_only):
    """Adapting on dtype and shape, nothing else: the per-row walk has a
    grid of (rows,), what it cannot take keeps (rows, slots); both are
    named paged_decode_grid."""
    S, NB, bs, NBLK = 8, 4, 16, 9
    q = jnp.zeros((S, KV, D), dtype)
    cache = jnp.zeros((NBLK, bs, KV, D), jnp.int8 if quant else dtype)
    kw = {}
    if quant:
        kw = dict(k_scale=jnp.ones((NBLK, bs, KV), jnp.float32),
                  v_scale=jnp.ones((NBLK, bs, KV), jnp.float32))
    grids = _kernel_grids(
        lambda q, k, v, t, c: paged_decode_attention(q, k, v, t, c, **kw),
        q, cache, cache, jnp.zeros((S, NB), jnp.int32),
        jnp.ones((S,), jnp.int32))
    assert grids == [(S,) if rows_only else (S, NB)]


# ---------------------------------------------------------------------------
# AOT compiles for a described v5e at the serving cells' shapes
# ---------------------------------------------------------------------------

ROWS, BLOCK, BLOCKS_PER_SEQ, POOL, D = 128, 128, 32, 704, 128


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


@pytest.mark.parametrize("cell,H,KV", [
    ("serve-chat-saturated", 32, 8),
    ("serve-olmoe-chat-saturated", 16, 16),
])
@pytest.mark.parametrize("window", [4096, 0])
def test_shared_table_attention_compiles_for_v5e(one_chip, cell, H, KV,
                                                 window):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cache = sds((POOL + 1, BLOCK, KV, D), jnp.bfloat16)
    args = (sds((ROWS, H, D), jnp.bfloat16), cache, cache,
            sds((ROWS, BLOCKS_PER_SEQ), jnp.int32), sds((ROWS,), jnp.int32))

    def fn(q, kc, vc, table, ctx):
        return paged_decode_attention(q, kc, vc, table, ctx, window=window)

    assert _kernel_grids(fn, *args) == [(ROWS,)]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert any('custom_call_target="tpu_custom_call"' in line
               and "paged_decode_grid" in line for line in text.splitlines())


# ---------------------------------------------------------------------------
# the counter the kernel's time should follow, and its reader
# ---------------------------------------------------------------------------

def test_the_scheduler_counts_the_live_blocks_it_dispatches(rng):
    cfg = T.TransformerConfig(vocab_size=128, n_layers=2, n_heads=4,
                              d_model=64, max_seq=128, variant="llama",
                              use_flash=False)
    eng = init_inference(
        T.init(cfg, jax.random.PRNGKey(0)), cfg,
        dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
             min_prefill_bucket=8, max_batch_size=8), dtype=jnp.float32)
    sched = ServingScheduler(
        eng, ServingSchedulerConfig(max_num_batched_tokens=16,
                                    prefill_chunk=4, warmup=False), seed=0)
    seen = []
    count = sched._count_tokens

    def spy(n, width, ctx=None, steps=1):
        before = sched.counters["kv_live_blocks"]
        count(n, width, ctx, steps)
        if ctx is not None:
            seen.append((np.array(ctx), steps,
                         sched.counters["kv_live_blocks"] - before))

    sched._count_tokens = spy
    for n in (11, 5):
        sched.submit(rng.integers(0, 128, n).astype(np.int32),
                     max_new_tokens=6)
    sched.run()
    assert seen, "no dispatcher handed over its ctx array"
    for ctx, steps, added in seen:
        assert added == sum(-(-(int(c) + k) // 8)
                            for c in ctx[ctx > 0] for k in range(steps))
    assert sched.counters["kv_live_blocks"] == sum(a for _, _, a in seen)
    # the first chunks' rows sit inside their first block: one each;
    # later rows (contexts of 9 to 17 tokens) read two or three
    ctx, _, added = seen[0]
    assert ctx.max() <= 8 and added == (ctx > 0).sum() > 0
    ctx, _, added = seen[-1]
    assert ctx.max() > 8 and added > (ctx > 0).sum()


def test_the_live_block_reader():
    spec = importlib.util.spec_from_file_location(
        "paged_live_blocks_per_step",
        os.path.join(REPO, "benchmarks", "metrics",
                     "paged_live_blocks_per_step.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    obs = {"counters_delta": {"steps": 1290, "kv_live_blocks": 554700}}
    assert mod.read(obs) == pytest.approx(430.0)
    # a program without the counter (the parent): nothing, no raise
    assert mod.read({"counters_delta": {"steps": 1290}}) is None
    assert mod.read({}) is None
