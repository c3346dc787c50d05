"""LFM2-MoE (`lfm2_moe`) on the normal serving path at a tiny size on the
CPU, against the plain float32 reference of
benchmarks/reference/lfm2_moe.py: layers of two kinds (gated short
convolutions whose sequences carry their last two inputs in a state
slot, attention layers that alone hold K/V, in pools packed two heads
of 64 a row), a leading dense layer, a sigmoid router with an expert
bias, through whole-prompt prefill, chunks and single steps, through
the scheduler with slots reused and under preemption, the mutants that
must fail, every refusal, the packed D = 64 kernels against their
oracle, and the cut's file.

Everything is float32 with seeded weights: the published layer pattern,
1 dense + two periods of (attention, conv, conv, conv), d 256, 4 query
/ 2 KV heads of 64, 8 experts of 128 top-2.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import _family as F
import pytest
from _family import (  # noqa: F401 - the contract's fixtures and cases, collected here
    engines,
    family,
    model,
    one_chip,
    pytest_generate_tests,
    served,
    test_a_chunk_boundary_at_every_offset,
    test_a_wrong_model_fails_the_written_tolerance,
    test_prefill_chunks_and_single_steps_match_the_reference,
    test_preemption_recomputes_to_identical_tokens,
    test_the_cuts_file_keeps_the_published_widths,
    test_the_engine_refuses_at_build,
    test_the_training_forward_refuses_the_family,
    test_whole_prompt_waves_and_fused_decode_carry_the_state,
)

from benchmarks.reference import lfm2_moe as ref
from deepspeed_tpu.inference import ServingScheduler, ServingSchedulerConfig
from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import paged_attention as PA
from deepspeed_tpu.utils import profiler
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

BENCH = F.BENCH
CUT = BENCH / "configs/lfm2-8b-a1b-serve-l13.json"
HF = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 256,
      "intermediate_size": 384,
      "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                      "full_attention", "conv", "conv", "conv"],
      "max_position_embeddings": 512, "model_type": "lfm2_moe",
      "moe_intermediate_size": 128, "norm_eps": 1e-05,
      "norm_topk_prob": True, "num_attention_heads": 4,
      "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
      "num_hidden_layers": 9, "num_key_value_heads": 2,
      "rope_theta": 1000000, "routed_scaling_factor": 1,
      "use_expert_bias": True, "vocab_size": 256}

# float32 on both sides, logits up to 5.0. The system reassociates (the
# fused QKV matmul, the expert paths' running sums, the taps' sum in
# another order, the oracle's softmax), which moves a logit by ~1e-5
# (measured here: 1.3e-5 over prefill, chunk and single steps, at most
# 1.5e-5 over the chunk offsets); a router tie flipped by that noise
# would move one by ~0.05, and none is at these seeds. The mutants
# differ by 0.24 (the bias used as a weight), 0.53 (a cache rounded to
# float8), 0.69 (no QK-norm), 1.2 (one expert fewer), 1.9 (weights
# rounded to float8), 6.3 (no state carried) and 7.1 (taps reversed):
# all at least 1,200 x the limit, which is 13 x the noise.
LOGITS_ATOL = 2e-4
ENGINE = dict(max_seq_len=256, kv_block_size=32, num_kv_blocks=48,
              max_batch_size=32, max_tracked_sequences=6,
              min_prefill_bucket=32)


def _jig(k, v, key):
    """Every norm scale matters (T.init gives ones); the taps and the
    expert bias have a size at which leaving them out shows."""
    if "scale" in k:
        return 1 + 0.3 * jax.random.normal(key, v.shape)
    if k == "conv_taps":
        return 0.6 * jax.random.normal(key, v.shape)
    if k == "expert_bias":
        return 0.2 * jax.random.normal(key, v.shape)
    return v


def test_what_only_this_cut_states():
    hf = json.loads(CUT.read_text())
    published = json.loads(
        (BENCH / "configs/published/lfm2-8b-a1b.json").read_text())
    assert hf["layer_types"] == published["layer_types"][1:14]


# the first chunk starts 1..6 tokens before the prompt's end: every
# residue mod the kernel's 3 taps, one and two tokens among them (a row
# whose two inputs before it are BOTH in the slot, one in the slot and
# one a neighbour row, both neighbour rows)
FAMILY = F.Family(
    hf=HF, ref=ref, atol=LOGITS_ATOL, engine=ENGINE, jig=_jig,
    spread=2.5, chunks=(1, 2, 3, 4, 5, 6), cut=CUT,
    reduced=("layer_types", "num_dense_layers", "num_hidden_layers"),
    assumed=("split_order", "no_conv_activation", "qk_norm", "expert_bias",
             "tie_word_embeddings", "weights", "kv_pool", "state_slots",
             "max_seq_len"))


# -- the configuration ---------------------------------------------------

def test_the_cut_builds_at_published_widths():
    hf, cfg = F.cut_of(FAMILY)
    assert (cfg.n_dense_layers, cfg.n_layers, cfg.depth) == (1, 12, 13)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == \
        (2048, 32, 8, 64)
    assert (cfg.ff_dim, cfg.dense_d_ff, cfg.conv_kernel) == (1792, 7168, 3)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.experts_held) == (32, 4, None)
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_expert_bias
    assert cfg.qk_norm and cfg.qk_norm_per_head and cfg.tie_embeddings
    assert cfg.layer_types == ("conv",) + ("attention", "conv", "conv",
                                           "conv") * 3
    assert (cfg.n_kv_layers, cfg.n_state_layers, cfg.state_width("conv")) == \
        (3, 10, 4096)
    assert cfg.vocab_size == 65536 and cfg.rope_theta == 1e6
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert shapes["layers"]["w_in"].shape == (12, 32, 2048, 1792)
    assert shapes["layers"]["expert_bias"].shape == (12, 32)
    assert shapes["dense_w_in"].shape == (1, 2048, 7168)
    assert shapes["conv_in"].shape == (10, 2048, 6144)
    assert shapes["conv_taps"].shape == (10, 2048, 3)
    assert shapes["attn_wk"].shape == (3, 2048, 8, 64)
    assert shapes["attn_q_norm_scale"].shape == (3, 64)
    flat = F.one_stack(cfg, shapes)
    # the file's own count, every leaf
    assert sum(int(np.prod(s.shape)) for s in flat.values()) == 4_606_249_728
    # the cache: K/V for the attention layers alone, packed; a slot a
    # tracked sequence a conv layer
    cache = jax.eval_shape(lambda: M.init_cache(
        cfg, 2049, 128, jnp.bfloat16, state_slots=1024))
    assert [a.shape for a in cache.k] == [(2049, 128, 2, 256)] * 3
    assert [a.shape for (a,) in cache.state] == [(1024, 2, 16, 128)] * 10
    assert sum(a.size * 2 for a in cache.k + cache.v) / 2049 / 128 == 6144


def test_the_published_shapes_group_their_pairs_past_the_ridge():
    """32 experts of 2048 x 1792, top-4: the cell's 512-row program
    multiplies an expert by its own rows; 128 rows, under the chip's
    ridge, every token by all 32; the logits check's whole-prompt
    prefill (two prompts of 512 rows: 128 rows an expert) keeps the
    ragged wire."""
    hf, cfg = F.cut_of(FAMILY)
    lp = F.expert_stacks(32, 2048, 1792)
    widths = (8, 16, 128, 256, 257, 512, 768, 1024, 2048)
    assert [M.expert_path(t, cfg, lp, True) for t in widths] == [
        "ragged", "stream", "stream", "stream", "grouped", "grouped",
        "grouped", "ragged", "ragged"]
    # kernels off (decode_impl "xla"), or a mesh: as before the entry
    assert [M.expert_path(t, cfg, lp, False) for t in widths] == [
        "ragged", "ragged", "scan", "scan", "scan", "scan", "scan",
        "ragged", "ragged"]


@pytest.mark.parametrize("what,hf", [
    ("a latent key lfm2_moe does not read", dict(HF, kv_lora_rank=32)),
    ("a shared expert lfm2_moe does not read", dict(HF, n_shared_experts=1)),
    ("conv layers under another architecture",
     dict(F.MISTRAL, layer_types=["conv", "full_attention"])),
    ("an expert bias under another architecture",
     dict(F.MISTRAL, use_expert_bias=True)),
])
def test_a_block_key_the_mapping_does_not_read_stays_an_error(what, hf):
    with pytest.raises(ValueError, match="does not read"):
        config_from_hf(hf)


def test_full_attention_layer_types_ask_nothing_of_another_architecture():
    cfg = config_from_hf(dict(F.MISTRAL, layer_types=["full_attention"] * 2))
    assert cfg.layer_types is None and cfg.n_state_layers == 0


# -- the engine against the reference -------------------------------------

def test_a_host_tree_the_device_cannot_hold_twice_is_laid_out_on_the_host(
        model, engines, monkeypatch):
    """9.2 GB of weights come to init_inference as host arrays (the
    benchmark's runner); the compiled transform would hold them twice.
    The engine reads the device's own limit: over half of it, the
    serving layout is made of host views and sent once, the same tree."""
    mcfg, params = model
    host = jax.device_get(params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(host))
    # its own two: the build reads the patched device's limit
    compiled = engines.fresh(model=(mcfg, host))

    class Small:
        def memory_stats(self):
            return {"bytes_limit": int(1.5 * nbytes), "bytes_in_use": 0}

    monkeypatch.setattr(jax, "local_devices", lambda: [Small()])
    assert compiled._host_tree_too_large_twice(host)
    assert not compiled._host_tree_too_large_twice(params)  # device arrays
    on_host = engines.fresh(model=(mcfg, host))
    monkeypatch.undo()
    assert not compiled._host_tree_too_large_twice(host)  # the CPU: no limit
    a, ta = jax.tree.flatten(compiled.params)
    b, tb = jax.tree.flatten(on_host.params)
    assert ta == tb
    assert all(x.dtype == y.dtype and np.array_equal(x, y)
               for x, y in zip(a, b))


def test_the_bias_moves_the_choice_and_not_the_weights():
    cfg = config_from_hf(HF)
    logits = jnp.asarray([[0.0, 0.1, 0.2, 0.3, -1, -1, -1, -1.0]])
    plain_idx, plain_w = M._sigmoid_topk_gating(logits, cfg)
    bias = jnp.zeros((8,)).at[0].set(1.0)
    idx, w = M._sigmoid_topk_gating(logits, cfg, bias)
    assert sorted(np.asarray(plain_idx[0])) == [2, 3]
    assert sorted(np.asarray(idx[0])) == [0, 3]
    s = jax.nn.sigmoid(logits[0])
    want = np.asarray([s[0], s[3]]) / float(s[0] + s[3])
    np.testing.assert_allclose(sorted(np.asarray(w[0])), sorted(want),
                               rtol=1e-6)
    np.testing.assert_allclose(float(plain_w.sum()), 1.0, rtol=1e-6)


# -- through the scheduler: slots taken, reused, reset --------------------

def test_the_scheduler_serves_unequal_sequences_through_reused_slots(
        model, engines):
    """12 requests of unequal lengths through 6 slots (a row budget of
    6 sequences at a time): every slot is handed on to a later
    sequence, and what the last one left in it (here: NaN, put there
    before the first admission too) never reaches the next."""
    eng = engines.sched()
    d, requests = F.through_reused_slots(FAMILY, model, eng)
    assert d["state_prefix_credits_refused"] == 0


def test_step_and_run_serve_the_same_tokens(engines):
    requests = F.requests(FAMILY, 8, seed=9)
    _, ahead = F.serve(engines.sched(), requests)
    s = ServingScheduler(engines.sched(), ServingSchedulerConfig(
        max_num_batched_tokens=48, prefill_chunk=8, prefill_mode="chunked",
        decode_chunk=1, warmup=False))
    rids = [s.submit(p, max_new_tokens=n) for p, n in requests]
    while s.has_work:
        s.step()
    assert [s.finished[r].output for r in rids] == ahead


# -- what cannot be right yet is refused where it is built ----------------

def test_pool_kinds_and_what_each_cannot_do(model):
    mcfg, _ = model
    assert E.pool_kinds(mcfg) == ("kv", "state")
    assert E.pool_kinds(config_from_hf(F.MISTRAL)) == ("kv",)
    with pytest.raises(NotImplementedError, match=r"kv \+ state.*the state"):
        E.refuse_for_pools(mcfg, "speculation")


def test_pages_do_not_travel_without_their_slot(engines):
    eng = engines()
    eng.put([1], [np.arange(40, dtype=np.int32)])
    with pytest.raises(NotImplementedError, match="page_transfer"):
        eng.export_kv(1)
    with pytest.raises(NotImplementedError, match="page_transfer"):
        eng.import_kv(2, {})
    with pytest.raises(NotImplementedError, match="page_transfer"):
        eng.warmup_kv_transfer()
    eng.flush(1)


def test_the_scheduler_refuses_spill_handoff_and_speculation(engines):
    eng = engines()
    with pytest.raises(NotImplementedError, match="speculation"):
        ServingScheduler(eng, ServingSchedulerConfig(warmup=False),
                         speculative={"ngram": 2, "draft_len": 3})
    with pytest.raises(NotImplementedError, match="page_transfer"):
        ServingScheduler(eng, ServingSchedulerConfig(
            warmup=False, pressure={"enabled": True, "spill_enabled": True}))
    s = ServingScheduler(eng, ServingSchedulerConfig(warmup=False))
    with pytest.raises(NotImplementedError, match="page_transfer"):
        s.submit([1, 2, 3], handoff=True)
    with pytest.raises(NotImplementedError, match="speculation"):
        eng.generate_speculative([[1, 2, 3, 1, 2, 3, 1, 2]], max_new_tokens=4)


def test_a_prefix_credit_is_declined_and_counted(model, engines):
    """The index fills and is walked, and no admission is credited: the
    credited tokens' state is in no slot."""
    eng = engines(prefix_cache={"enabled": True})
    prompt = np.random.default_rng(2).integers(0, 256, 70).tolist()
    requests = [(prompt, 4), (prompt + [7, 8, 9], 4)]
    s = ServingScheduler(eng, ServingSchedulerConfig(
        max_num_batched_tokens=48, prefill_chunk=8, prefill_mode="chunked",
        decode_chunk=1, warmup=False))
    outputs = []
    for p, n in requests:  # the second arrives when the first is indexed
        rid = s.submit(p, max_new_tokens=n)
        s.run()
        outputs.append(s.finished[rid].output)
        assert s.finished[rid].n_cached == 0
    assert eng.state.indexed_blocks > 0
    assert s.counters["state_prefix_credits_refused"] == 1
    assert eng.state.stats["cached_tokens"] == 0
    F.greedy_by_the_reference(FAMILY, model, requests, outputs)


def test_the_set_up_spans_name_the_layers_by_kind(engines):
    profiler.enable()
    try:
        profiler.spans(clear=True)
        eng = engines.fresh()  # its own: the spans of a build
        eng.warmup(widths=[8], footprint=False)
        spans = profiler.spans(clear=True)
    finally:
        profiler.disable()
    pool = next(s for s in spans if s.name == "init.pool")
    assert pool.ids["kv_layers"] == 2 and pool.ids["state_layers"] == 7
    assert pool.ids["state_slots"] == 6
    assert pool.ids["state_bytes"] == 7 * 6 * 2 * 256 * 4
    init = next(s for s in spans if s.name == "init.inference")
    assert (init.ids["kv_layers"], init.ids["state_layers"]) == (2, 7)
    programs = [s for s in spans if s.name == "warmup.program"
                and s.ids["kind"] == "decode"]
    assert programs and all(s.ids["state_layers"] == 7 for s in programs)


def test_the_scopes_of_the_operator_are_in_the_program(engines):
    text = F.step_text(engines())
    for scope in ("short_conv/conv_project", "short_conv/conv_state",
                  "short_conv/conv_out", "attention"):
        assert scope in text, scope


@pytest.mark.usefixtures("pallas_interpret")
def test_the_convolution_kernel_serves_what_the_xla_path_serves(model,
                                                                engines):
    """The step program with its convolutions as the one-pass kernel
    (ops/pallas/conv_carry.py, under `conv_state`) against decode_impl
    'xla' (_carry_rows + _depthwise): the same logits over a prefill, a
    chunk and single steps, the same served tokens through reused
    slots, and every step of the schedule counted where the kernel ran
    and none where it did not. (One width of program throughout: the
    interpreter's kernels are slow to trace.)"""
    eng, xla = engines.sched(), engines.sched(decode_impl="xla")
    assert eng.resolved_impl == "pallas" and eng.carry_kernel(8)
    assert xla.resolved_impl == "xla" and not xla.carry_kernel(8)
    # (no head carries a matrix here: the step kernels' counter stays 0)
    assert not eng.step_kernel(8) and not xla.step_kernel(8)
    assert "short_conv/conv_state/jit(_conv_carry)" in F.step_text(eng)
    assert "jit(_conv_carry)" not in F.step_text(xla)
    got, want, _, _ = F.feeds(FAMILY, model, eng, [21], [5], 2, seed=6)
    oracle, _, _, _ = F.feeds(FAMILY, model, xla, [21], [5], 2, seed=6)
    assert np.abs(got - oracle).max() < LOGITS_ATOL
    assert np.abs(got - want).max() < LOGITS_ATOL
    requests = [(p[:12], n) for p, n in F.requests(FAMILY, 8, seed=8)]
    s, served = F.serve(eng, requests, max_num_batched_tokens=8)
    sx, served_xla = F.serve(xla, requests, max_num_batched_tokens=8)
    assert served == served_xla
    assert s.counters["state_slot_resets"] == 8  # through 6 slots
    assert s.counters["state_carry_kernel_steps"] == s.counters["steps"] > 0
    assert sx.counters["state_carry_kernel_steps"] == 0 < sx.counters["steps"]
    assert s.counters["state_step_kernel_steps"] == 0
    assert sx.counters["state_step_kernel_steps"] == 0


# -- head dim 64: packed pools through the unchanged kernels --------------

def _packed_case(rng, ctx, H=8, KV=4, D=64, bs=16, NB=4, dtype=jnp.float32,
                 chunk=None):
    S, NBLK = len(ctx), len(ctx) * NB + 1
    q = jnp.asarray(rng.normal(size=(S, H, D)), dtype)
    kc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), dtype)
    vc = jnp.asarray(rng.normal(size=(NBLK, bs, KV, D)), dtype)
    tbl = rng.permutation(NBLK - 1)[:S * NB].reshape(S, NB).astype(np.int32)
    if chunk:
        tbl[chunk[0]:chunk[0] + chunk[1]] = tbl[chunk[0]]
    packed = lambda c: c.reshape(NBLK, bs, KV // 2, 2 * D)
    return q, kc, vc, packed(kc), packed(vc), jnp.asarray(tbl), \
        jnp.asarray(np.asarray(ctx, np.int32))


def test_who_packs():
    # two a lane row, and of LFM2's 4 rows of 128 two a pool head (PR 64)
    assert PA.kv_pack(8, 64, 2) == 4 and PA.kv_pack(2, 64, 4) == 2
    assert PA.kv_pack(4, 64, 2) == 2 and PA.kv_pack(12, 64, 2) == 2
    assert PA.kv_pack(8, 128, 2) == 1 and PA.kv_pack(8, 32, 2) == 1
    assert PA.kv_pack(3, 64, 2) == 1  # an odd count of heads fills no row


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("what,kw", [
    ("groups_of_2", {}),
    ("one_query_a_head", dict(H=4, KV=4)),
    ("groups_of_4_bf16", dict(H=16, KV=4, dtype=jnp.bfloat16)),
    ("a_chunk_shares_a_table", dict(chunk=(2, 3))),
])
def test_the_packed_walk_matches_the_oracle(rng, what, kw):
    """Packed pools through the live-block walk, against the oracle on
    the UNPACKED pools (so the packing itself is checked, not only the
    kernel against its own view)."""
    ctx = (0, 1, 16, 17, 40, 41, 42, 64)
    q, kc, vc, pk, pv, tbl, ctx = _packed_case(rng, ctx, **kw)
    with jax.default_matmul_precision("highest"):
        # (each ONE program: op by op the oracle is thirty small compiles)
        out = jax.jit(PA.paged_decode_attention)(q, pk, pv, tbl, ctx)
        also = jax.jit(PA.paged_decode_attention_xla)(q, pk, pv, tbl, ctx)
        want = jax.jit(PA.paged_decode_attention_xla)(q, kc, vc, tbl, ctx)
    tol = 3e-2 if q.dtype == jnp.bfloat16 else 2e-3
    real = np.asarray(ctx) > 0
    for got in (out, also):
        np.testing.assert_allclose(np.asarray(got, np.float32)[real],
                                   np.asarray(want, np.float32)[real],
                                   rtol=tol, atol=tol)
    assert not np.asarray(out, np.float32)[~real].any()
    # it IS the walk: one grid step a row, whatever the table's width
    qg = PA._group_queries(PA._pack_queries(q, 2, q.shape[1] // 4), 2)[0]
    assert PA._walks_live_blocks(qg, pk)


@pytest.mark.usefixtures("pallas_interpret")
def test_packed_rows_are_written_and_the_fused_walk_reads_them(rng):
    ctx = (1, 16, 17, 40, 0, 64)
    q, kc, vc, pk, pv, tbl, ctx = _packed_case(rng, ctx)
    S, KV, D = len(ctx), 4, 64
    kn = jnp.asarray(rng.normal(size=(S, KV, D)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(S, KV, D)), jnp.float32)
    pos = np.maximum(np.asarray(ctx) - 1, 0)
    slots = np.where(np.asarray(ctx) > 0,
                     np.asarray(tbl)[np.arange(S), pos // 16] * 16 + pos % 16,
                     -1).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        wk, wv = PA.paged_kv_write(pk, pv, kn, vn, jnp.asarray(slots))
        xk, xv = M._write_kv_xla(pk, pv, kn, vn, jnp.asarray(slots))
        want = PA.paged_decode_attention_xla(q, xk, xv, tbl, ctx)
        fused, fk, fv = PA.paged_decode_attention(
            q, pk, pv, tbl, ctx, k_new=kn, v_new=vn, slots=jnp.asarray(slots))
    np.testing.assert_array_equal(np.asarray(wk), np.asarray(xk))
    np.testing.assert_array_equal(np.asarray(wv), np.asarray(xv))
    # the unpacked view holds the row where an unpacked write puts it
    uk, _ = M._write_kv_xla(kc, vc, kn, vn, jnp.asarray(slots))
    np.testing.assert_array_equal(np.asarray(xk).reshape(uk.shape),
                                  np.asarray(uk))
    real = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(fused)[real], np.asarray(want)[real],
                               rtol=2e-3, atol=2e-3)
    live = np.asarray(tbl)[real].ravel()
    np.testing.assert_array_equal(np.asarray(fk)[live], np.asarray(xk)[live])


@pytest.mark.usefixtures("pallas_interpret")
def test_eight_kv_heads_of_64_are_served_from_two_wide_heads():
    """The published 8 KV heads of 64 (under 8 query heads of a model
    512 wide): the pool holds them four a head, [.., 2, 256] (kv_pack,
    PR 64), the set-up's span says so, and the interpreted write and
    walk serve what the reference computes from the heads one by one:
    whole prompts, a chunk of 2 x 4 rows and single steps."""
    hf = dict(HF, hidden_size=512, num_attention_heads=8,
              num_key_value_heads=8)
    model = F.model_of(FAMILY, hf)
    assert (model[0].kv_heads, model[0].head_dim) == (8, 64)
    profiler.spans(clear=True)
    eng = F.Engines(model, ENGINE).fresh()
    pool = next(s for s in profiler.spans(clear=True)
                if s.name == "init.pool")
    assert (pool.ids["kv_pack"], pool.ids["kv_heads_padded"],
            pool.ids["kv_write"]) == (4, 0, "rows")
    assert {k.shape[2:] for k in eng.cache.k} == {(2, 256)}
    assert eng.resolved_impl == "pallas"
    got, want, _, _ = F.feeds(FAMILY, model, eng, [32, 36], [4], 3, seed=4,
                              hf=hf)
    assert np.abs(want).max() > FAMILY.spread
    assert np.abs(got - want).max() < LOGITS_ATOL, np.abs(got - want).max(-1)


def test_a_wide_step_writes_first_and_attends_after():
    assert PA.fused_write_fits(128) and PA.fused_write_fits(248)
    assert not PA.fused_write_fits(256) and not PA.fused_write_fits(512)


@pytest.mark.parametrize("rows", [512, 128])
def test_the_packed_walk_and_write_compile_for_v5e(one_chip, rows):
    """The cell's shapes: 32 query / 8 KV heads of 64 over pools packed
    to [2049, 128, 2, 256], a table of 32 slots a row."""
    pack = PA.kv_pack(8, 64, 2)
    F.walk_and_write_compile(one_chip, rows, 32, 8, 64,
                             (2049, 128, 8 // pack, 64 * pack))
