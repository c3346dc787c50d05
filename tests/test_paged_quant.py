"""Int8 per-block KV quantization + fused Pallas flash-decode tests.

Covers the docs/paged_attention.md contract end to end, all under
Pallas INTERPRET mode on the CPU mesh (decode_impl='pallas' — the same
kernel code path the TPU runs, minus Mosaic):

- kernel vs reference lax-path logit equivalence: full-precision pools
  within the f32 reassociation tolerance, int8 pools within the PINNED
  int8 tolerance, across GQA/window/fused write+attend/pad rows;
- the fused kernel's in-kernel quantizer writes codes + per-block scale
  tiles BIT-IDENTICAL to quantize_kv_rows (token identity across the
  fused, chunked and prefill write paths depends on it);
- engine lanes: chunked prefill, fused decode_multi, COW'd
  shared-prefix tails, spill->resume round trips — int8 Pallas vs the
  int8 lax oracle, token-identical;
- handoff payloads ship codes + scales under the digest envelope (a
  tampered scale byte is rejected before any allocation), mixed-dtype
  fleets are rejected with the typed KvCacheDtypeError, and
  kv_payload_nbytes accounts the scale tensors;
- capacity: kv_bytes_per_token ratio bf16/int8 >= 1.8x at real head
  dims, and the gather-materialization probe (profiling/hlo.py
  max_gather_bytes) separates the fused program from the oracle.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (
    KvCacheDtypeError,
    ServingRouter,
    ServingScheduler,
    ServingSchedulerConfig,
    init_inference,
)
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_xla,
    paged_scale_write,
    quantize_kv_rows,
)
from deepspeed_tpu.resilience.integrity import HandoffIntegrityError

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every engine here names decode_impl explicitly; the Pallas ones run
# interpreted on the CPU lane, Mosaic-compiled on the hardware lane
pytestmark = pytest.mark.usefixtures("pallas_interpret_module")

# PINNED tolerances (docs/paged_attention.md): kernel-vs-oracle on the
# SAME int8 pool differs only by f32 reassociation; int8-vs-full-
# precision differs by the quantization error itself (per-(slot, head)
# absmax/127 scales, unit-normal activations).
KERNEL_VS_ORACLE_ATOL = 5e-5
INT8_VS_FP_ATOL = 0.08


@pytest.fixture(scope="module")
def model():
    cfg = T.TransformerConfig(
        vocab_size=128, n_layers=2, n_heads=4, d_model=64, max_seq=128,
        variant="llama", use_flash=False)
    params = T.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def engine_for(model, **over):
    # max_seq_len 32 keeps the interpret-mode grid unroll small (4
    # table slots) — the fast lane budget pays per traced grid step
    cfg, params = model
    kw = dict(max_seq_len=32, kv_block_size=8, num_kv_blocks=32,
              min_prefill_bucket=8, max_batch_size=8, decode_impl="xla")
    kw.update(over)
    return init_inference(params, cfg, kw, dtype=jnp.float32)


@pytest.fixture(scope="module")
def int8_pair(model):
    """One shared (Pallas-kernel, lax-oracle) int8 engine pair for the
    read-mostly equivalence lanes — engines are seconds-expensive under
    the interpreter, and generate()/scheduler runs flush their
    sequences so the pair stays clean between tests."""
    return (engine_for(model, kv_cache_dtype="int8",
                       decode_impl="pallas"),
            engine_for(model, kv_cache_dtype="int8", decode_impl="xla"))


@pytest.fixture(scope="module")
def fp_engine(model):
    return engine_for(model)


def _quant_pool(rng, NBLK, bs, KV, D):
    """Full-precision rows -> (codes pools, scale pools, fp pools)."""
    kf = rng.normal(size=(NBLK * bs, KV, D)).astype(np.float32)
    vf = rng.normal(size=(NBLK * bs, KV, D)).astype(np.float32)
    qk, ks, qv, vs = (np.asarray(x) for x in
                      quantize_kv_rows(jnp.asarray(kf), jnp.asarray(vf)))
    return (qk.reshape(NBLK, bs, KV, D), qv.reshape(NBLK, bs, KV, D),
            ks.reshape(NBLK, bs, KV), vs.reshape(NBLK, bs, KV),
            kf.reshape(NBLK, bs, KV, D), vf.reshape(NBLK, bs, KV, D))


class TestQuantKernel:
    """paged_decode_attention with k_scale/v_scale vs the lax oracle."""

    def test_nonfused_matches_oracle_and_fp_within_pins(self, rng):
        S, H, KV, D, bs, NB, NBLK = 4, 8, 4, 16, 8, 3, 16
        q = rng.normal(size=(S, H, D)).astype(np.float32)
        kc, vc, ksc, vsc, kcf, vcf = _quant_pool(rng, NBLK, bs, KV, D)
        tbl = rng.permutation(NBLK)[:S * NB].reshape(S, NB).astype(np.int32)
        for ctx in ([5, bs * NB, 1, 17], [2, 3, bs, bs + 1]):
            ctx = np.asarray(ctx, np.int32)
            out = paged_decode_attention(q, kc, vc, tbl, ctx,
                                         k_scale=ksc, v_scale=vsc)
            ref = paged_decode_attention_xla(q, kc, vc, tbl, ctx,
                                             k_scale=ksc, v_scale=vsc)
            np.testing.assert_allclose(out, ref,
                                       atol=KERNEL_VS_ORACLE_ATOL, rtol=0)
            fp = paged_decode_attention_xla(q, kcf, vcf, tbl, ctx)
            np.testing.assert_allclose(out, fp, atol=INT8_VS_FP_ATOL,
                                       rtol=0)

    def test_window_quant_matches_oracle(self, rng):
        S, H, KV, D, bs, NB, NBLK = 3, 4, 4, 16, 8, 4, 16
        q = rng.normal(size=(S, H, D)).astype(np.float32)
        kc, vc, ksc, vsc, _, _ = _quant_pool(rng, NBLK, bs, KV, D)
        tbl = rng.permutation(NBLK)[:S * NB].reshape(S, NB).astype(np.int32)
        ctx = np.asarray([30, 12, 7], np.int32)
        out = paged_decode_attention(q, kc, vc, tbl, ctx, window=10,
                                     k_scale=ksc, v_scale=vsc)
        ref = paged_decode_attention_xla(q, kc, vc, tbl, ctx, window=10,
                                         k_scale=ksc, v_scale=vsc)
        np.testing.assert_allclose(out, ref, atol=KERNEL_VS_ORACLE_ATOL,
                                   rtol=0)

    def test_fused_write_attend_codes_and_scales_bit_identical(self, rng):
        """The in-kernel quantizer must reproduce quantize_kv_rows
        exactly, and attention must see the round-tripped new row (so
        this step's logits equal every later read of the codes)."""
        S, H, KV, D, bs, NB, NBLK = 4, 8, 4, 16, 8, 3, 16
        q = rng.normal(size=(S, H, D)).astype(np.float32)
        kc, vc, ksc, vsc, _, _ = _quant_pool(rng, NBLK, bs, KV, D)
        tbl = rng.permutation(NBLK)[:S * NB].reshape(S, NB).astype(np.int32)
        ctx = np.asarray([5, bs * NB, 0, 17], np.int32)  # row 2 = pad
        kn = rng.normal(size=(S, KV, D)).astype(np.float32)
        vn = rng.normal(size=(S, KV, D)).astype(np.float32)
        slots = np.asarray(
            [tbl[s, (ctx[s] - 1) // bs] * bs + (ctx[s] - 1) % bs
             if ctx[s] > 0 else -1 for s in range(S)], np.int32)
        out, ck, cv, cks, cvs = paged_decode_attention(
            q, kc.copy(), vc.copy(), tbl, ctx,
            k_new=jnp.asarray(kn), v_new=jnp.asarray(vn),
            slots=jnp.asarray(slots),
            k_scale=ksc.copy(), v_scale=vsc.copy())
        # reference: quantize via the authority, write rows, run oracle
        qkn, skn, qvn, svn = (np.asarray(x) for x in
                              quantize_kv_rows(jnp.asarray(kn),
                                               jnp.asarray(vn)))
        kc2, vc2 = kc.copy(), vc.copy()
        ks2, vs2 = ksc.copy(), vsc.copy()
        for s in range(S):
            if slots[s] < 0:
                continue
            b, o = slots[s] // bs, slots[s] % bs
            kc2[b, o], vc2[b, o] = qkn[s], qvn[s]
            ks2[b, o], vs2[b, o] = skn[s], svn[s]
        assert np.array_equal(np.asarray(ck), kc2)
        assert np.array_equal(np.asarray(cv), vc2)
        assert np.array_equal(np.asarray(cks), ks2)
        assert np.array_equal(np.asarray(cvs), vs2)
        ref = paged_decode_attention_xla(q, kc2, vc2, tbl, ctx,
                                         k_scale=ks2, v_scale=vs2)
        live = ctx > 0
        np.testing.assert_allclose(np.asarray(out)[live],
                                   np.asarray(ref)[live],
                                   atol=KERNEL_VS_ORACLE_ATOL, rtol=0)

    def test_scale_write_matches_xla_scatter(self, rng):
        from deepspeed_tpu.inference.model import _write_scales_xla

        NBLK, bs, KV, TT = 6, 8, 4, 5
        ks = np.abs(rng.normal(size=(NBLK, bs, KV))).astype(np.float32)
        vs = np.abs(rng.normal(size=(NBLK, bs, KV))).astype(np.float32)
        ksn = np.abs(rng.normal(size=(TT, KV))).astype(np.float32)
        vsn = np.abs(rng.normal(size=(TT, KV))).astype(np.float32)
        slots = np.asarray([3, -1, 17, 40, 0], np.int32)
        a = paged_scale_write(jnp.asarray(ks), jnp.asarray(vs),
                              jnp.asarray(ksn), jnp.asarray(vsn),
                              jnp.asarray(slots))
        b = _write_scales_xla(jnp.asarray(ks), jnp.asarray(vs),
                              jnp.asarray(ksn), jnp.asarray(vsn),
                              jnp.asarray(slots))
        np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
        np.testing.assert_array_equal(np.asarray(a[1]), np.asarray(b[1]))


class TestEngineLanes:
    """int8 Pallas engine vs the int8 lax-oracle engine — the serving
    lanes the issue pins: chunked prefill, fused decode_multi, COW'd
    shared-prefix tails — all token-identical."""

    def test_generate_kernel_vs_oracle_token_identical(self, int8_pair,
                                                       rng):
        kern, orac = int8_pair
        prompts = [list(rng.integers(0, 128, n)) for n in (6, 9, 4)]
        assert kern.generate(prompts, max_new_tokens=10, chunk=2) == \
            orac.generate(prompts, max_new_tokens=10, chunk=2)

    def test_put_logits_kernel_vs_oracle_within_pin(self, int8_pair,
                                                    rng):
        kern, orac = int8_pair
        toks = np.asarray(rng.integers(0, 128, 7), np.int32)
        lk = kern.put([901], [toks])
        lo = orac.put([901], [toks])
        kern.flush(901)
        orac.flush(901)
        np.testing.assert_allclose(lk, lo, atol=KERNEL_VS_ORACLE_ATOL,
                                   rtol=0)

    def test_int8_lane_tracks_fp_lane_within_pin(self, int8_pair,
                                                 fp_engine, rng):
        """The acceptance pin: the int8-KV serving lane's greedy tokens
        match the full-precision lane and its logits stay within the
        committed tolerance."""
        q8, fp = int8_pair[0], fp_engine
        prompts = [list(rng.integers(0, 128, n)) for n in (6, 9, 4)]
        assert q8.generate(prompts, max_new_tokens=10, chunk=2) == \
            fp.generate(prompts, max_new_tokens=10, chunk=2)
        toks = np.asarray(rng.integers(0, 128, 7), np.int32)
        lq, lf = q8.put([902], [toks]), fp.put([902], [toks])
        q8.flush(902)
        fp.flush(902)
        np.testing.assert_allclose(lq, lf, atol=INT8_VS_FP_ATOL, rtol=0)

    def test_chunked_prefill_kernel_vs_oracle(self, int8_pair, rng):
        prompts = [list(rng.integers(0, 128, n)) for n in (11, 7, 14)]
        outs = []
        for eng in int8_pair:
            sched = ServingScheduler(
                eng, ServingSchedulerConfig(
                    prefill_chunk=4, max_num_batched_tokens=8,
                    warmup=False), seed=0)
            rids = [sched.submit(p, 8) for p in prompts]
            sched.run()
            outs.append([sched.finished[r].output for r in rids])
        assert outs[0] == outs[1]

    def test_fused_decode_multi_matches_stepwise(self, int8_pair, rng):
        """decode_multi (the fused multi-step program, write+attend
        kernel inside lax.scan) produces the same tokens as
        step-by-step decode on the same int8 pool."""
        eng = int8_pair[0]
        prompts = [list(rng.integers(0, 128, n)) for n in (6, 9)]
        fused = eng.generate(prompts, max_new_tokens=8, chunk=2)
        step = eng.generate(prompts, max_new_tokens=8, chunk=1)
        assert fused == step

    def test_cow_shared_prefix_tail_kernel_vs_oracle(self, model, rng):
        """A second prompt sharing the first's full prefix triggers the
        COW'd tail (page + scale-tile clone) — kernel and oracle lanes
        stay token-identical and both take the cache hit."""
        shared = list(rng.integers(0, 128, 16))
        outs = []
        for impl in ("pallas", "xla"):
            eng = engine_for(model, kv_cache_dtype="int8",
                             decode_impl=impl,
                             prefix_cache={"enabled": True})
            a = eng.generate([shared], max_new_tokens=6)
            b = eng.generate([list(shared)], max_new_tokens=6)
            stats = eng.prefix_cache_stats()
            assert stats["lookup_hits"] >= 1
            assert stats["cow_copies"] >= 1
            outs.append((a, b))
        # kernel and oracle lanes agree run-for-run. (Unlike bf16, a
        # cache-HIT continuation is not bit-identical to its cache-miss
        # run: the hit's first logits read quantized prefix KV where
        # the wave prefill attended full precision — the documented
        # int8 approximation, bounded by INT8_VS_FP_ATOL.)
        assert outs[0] == outs[1]

    @pytest.mark.slow
    def test_tp_int8_matches_single_device(self, model, rng):
        """TP serving with a quantized pool: code pools and scale
        tiles shard on the KV-head dim, row writes quantize in XLA
        before the sharded code/scale writes — tokens match the
        single-device int8 engine."""
        if len(jax.devices()) < 2:
            pytest.skip("needs the multi-device CPU mesh")
        prompts = [list(rng.integers(0, 128, n)) for n in (6, 9)]
        ref = engine_for(model, kv_cache_dtype="int8").generate(
            prompts, max_new_tokens=8)
        tp = engine_for(model, kv_cache_dtype="int8", tp_size=2)
        assert tp.cache.k[0].dtype == jnp.int8
        assert tp.generate(prompts, max_new_tokens=8) == ref

    def test_spill_resume_roundtrip_int8(self, model, int8_pair, rng):
        """Preempt-to-host under RED with a quantized pool: the spilled
        payload carries codes + scale tiles, resume is token-identical
        to the unpressured int8 run, and nothing strands in the tier."""
        from deepspeed_tpu.inference import RED

        prompts = [list(rng.integers(0, 128, n)) for n in (6, 9, 4)]
        want = int8_pair[0].generate(prompts, max_new_tokens=10)
        eng = engine_for(model, kv_cache_dtype="int8",
                         decode_impl="pallas", num_kv_blocks=6)
        sched = ServingScheduler(
            eng, ServingSchedulerConfig(
                prefill_chunk=3, max_num_batched_tokens=8, warmup=False,
                pressure={"enabled": True, "yellow": 0.5, "red": 0.8,
                          "brownout": 0.99}), seed=0)
        rids = [sched.submit(p, 10) for p in prompts]
        sched.run()
        assert [sched.finished[r].output for r in rids] == want
        assert sched.counters["spills"] >= 1
        assert sched.counters["spill_resumes"] >= 1
        assert sched.governor.max_level >= RED
        assert sched.spill_store.used_bytes == 0


class TestQuantHandoff:
    """export_kv/import_kv with quantized pools: scales ride the
    payload under the digest; dtype mismatches are typed-rejected.
    Source/destination engines are module-shared (uids are disjoint
    per test; rejected imports touch no state by contract)."""

    @pytest.fixture(scope="class")
    def src(self, model):
        return engine_for(model, kv_cache_dtype="int8")

    @pytest.fixture(scope="class")
    def dst(self, model):
        return engine_for(model, kv_cache_dtype="int8")

    def _exported(self, src, rng, uid):
        toks = np.asarray(rng.integers(0, 128, 11), np.int32)
        src.put([uid], [toks])
        return toks, src.export_kv(uid)

    def test_payload_ships_scales_and_roundtrips(self, model, src, dst,
                                                 rng):
        _, p = self._exported(src, rng, 5)
        assert p["kv_dtype"] == "int8"
        assert p["k"].dtype == np.int8
        assert p["k_scale"].dtype == np.float32
        assert p["k_scale"].shape == p["k"].shape[:4]  # [L, nb, bs, KV]
        dst.import_kv(5, p)
        nxt = np.asarray([99], np.int32)
        np.testing.assert_array_equal(src.put([5], [nxt]),
                                      dst.put([5], [nxt]))

    def test_digest_covers_scale_tensors(self, model, src, dst, rng):
        _, p = self._exported(src, rng, 15)
        p["k_scale"] = p["k_scale"].copy()
        flat = p["k_scale"].reshape(-1)
        flat[0] = flat[0] * 1.0000001 + 1e-6  # one flipped scale
        before = dst.state.free_blocks
        with pytest.raises(HandoffIntegrityError):
            dst.import_kv(15, p)
        # rejected BEFORE any allocation
        assert dst.state.get(15) is None
        assert dst.state.free_blocks == before

    def test_scaleless_int8_payload_rejected_typed(self, model, src,
                                                   dst, rng):
        _, p = self._exported(src, rng, 25)
        p2 = {k: v for k, v in p.items()
              if k not in ("k_scale", "v_scale", "digest")}
        with pytest.raises(KvCacheDtypeError):
            dst.import_kv(25, p2)
        assert dst.state.get(25) is None

    def test_mixed_dtype_import_rejected_typed(self, model, src,
                                               fp_engine, rng):
        _, p = self._exported(src, rng, 35)
        with pytest.raises(KvCacheDtypeError):
            fp_engine.import_kv(35, p)
        assert fp_engine.state.get(35) is None  # before any allocation
        # and the reverse direction
        fp_engine.put([36], [np.asarray([1, 2, 3], np.int32)])
        p36 = fp_engine.export_kv(36)
        fp_engine.flush(36)
        with pytest.raises(KvCacheDtypeError):
            src.import_kv(36, p36)

    def test_mixed_dtype_fleet_rejected_at_construction(self, model, src,
                                                        fp_engine):
        with pytest.raises(KvCacheDtypeError):
            ServingRouter([src, fp_engine],
                          {"replicas": 2, "scheduler": {"warmup": False}})

    def test_kv_payload_nbytes_accounts_scales(self, model, src,
                                               fp_engine, rng):
        _, p = self._exported(src, rng, 45)
        seq = src.state.get(45)
        want = sum(p[k].nbytes for k in ("k", "v", "k_scale", "v_scale"))
        assert src.kv_payload_nbytes(len(seq.blocks)) == want
        # and the quantized payload is materially smaller than the
        # full-precision pool's would be
        assert fp_engine.kv_payload_nbytes(len(seq.blocks)) >= 1.8 * want


class TestCapacityAndCounters:
    def test_bytes_per_token_ratio_f32(self, int8_pair, fp_engine):
        ratio = (fp_engine.kv_bytes_per_token()
                 / int8_pair[0].kv_bytes_per_token())
        assert ratio >= 1.8

    def test_bytes_per_token_ratio_bf16_real_head_dim(self):
        """At real head dims (>= 64) the bf16/int8 ratio clears the
        committed 1.8x floor (the canonical toy D=16 geometry needs the
        f32 reference — the ds_budget gate pins that one)."""
        cfg = T.TransformerConfig(
            vocab_size=64, n_layers=1, n_heads=2, d_model=128,
            max_seq=64, variant="llama", use_flash=False)
        params = T.init(cfg, jax.random.PRNGKey(0))
        kw = dict(max_seq_len=32, kv_block_size=8, num_kv_blocks=8,
                  min_prefill_bucket=8, max_batch_size=8)
        fp = init_inference(params, cfg, dict(kw), dtype=jnp.bfloat16)
        q8 = init_inference(params, cfg,
                            dict(kw, kv_cache_dtype="int8"),
                            dtype=jnp.bfloat16)
        assert fp.kv_bytes_per_token() / q8.kv_bytes_per_token() >= 1.8

    def test_stats_and_metrics_expose_residency(self, int8_pair,
                                                fp_engine):
        q8 = int8_pair[1]
        st = q8.prefix_cache_stats()
        assert st["kv_quantized"] == 1.0
        assert st["kv_bytes_per_token"] == q8.kv_bytes_per_token()
        assert st["kv_pool_bytes"] > 0
        sched = ServingScheduler(
            q8, ServingSchedulerConfig(warmup=False), seed=0)
        m = sched.metrics()
        assert m["kv_pool_quantized"] == 1.0
        assert m["kv_bytes_per_token"] == float(q8.kv_bytes_per_token())
        assert fp_engine.prefix_cache_stats()["kv_quantized"] == 0.0

    def test_config_validation(self, model):
        with pytest.raises(ValueError):
            engine_for(model, kv_cache_dtype="int4")
        with pytest.raises(ValueError):
            engine_for(model, decode_impl="cuda")


class TestGatherProbe:
    """profiling/hlo.max_gather_bytes — the ds_schedule regression
    probe: the fused program's largest gather stays lookup-sized while
    the oracle materializes the whole block-table context."""

    def test_fused_program_is_gather_free_oracle_is_not(self, int8_pair):
        import warnings

        from deepspeed_tpu.profiling.hlo import max_gather_bytes

        progs = {}
        for impl, eng in zip(("pallas", "xla"), int8_pair):
            toks = np.zeros((8,), np.int32)
            ctx = np.zeros((8,), np.int32)
            tables = np.full((8, eng.config.blocks_per_seq),
                             eng.pad_block, np.int32)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                compiled = eng._decode_fn(8, True).lower(
                    eng.params, eng.cache, eng._dev(toks),
                    eng._dev(tables), eng._dev(ctx)).compile()
            progs[impl] = max_gather_bytes(compiled.as_text())
        # the oracle's gather materializes [S, NB*bs, KV, D] codes per
        # layer; the fused kernel's biggest gather is the embedding row
        # lookup
        assert progs["xla"] >= 8 * eng.config.blocks_per_seq * \
            eng.config.kv_block_size * 4  # >= S*NB*bs*KV(min bytes)
        assert progs["pallas"] < progs["xla"]
        assert progs["pallas"] <= 4096

    def test_max_gather_bytes_ignores_all_gather(self):
        from deepspeed_tpu.profiling.hlo import max_gather_bytes

        hlo = (
            "ENTRY %e {\n"
            "  %ag = f32[1024,8]{1,0} all-gather(f32[128,8]{1,0} %p), "
            "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n"
            "  %g = f32[4,8]{1,0} gather(f32[16,8]{1,0} %t, s32[4]{0} "
            "%i), offset_dims={1}\n"
            "}\n")
        assert max_gather_bytes(hlo) == 4 * 8 * 4
