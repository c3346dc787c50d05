"""ServingScheduler tests: token-identity against one-shot generate()
(staggered arrivals, chunked prefill, forced preemption), immediate
block reclamation, admission policies, AOT-warmup zero-recompile
steady state (S003), the look-ahead's pure-decode case, and monitor
counters (the look-ahead's own cases: tests/test_lookahead.py).

Fast lane: tiny model, f32, CPU — the control plane is host-side and
the compiled programs are seconds-cheap at this size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (
    ServingScheduler,
    ServingSchedulerConfig,
    init_inference,
)
from deepspeed_tpu.models import transformer as T


@pytest.fixture(scope="module")
def model():
    cfg = T.TransformerConfig(
        vocab_size=128, n_layers=2, n_heads=4, d_model=64, max_seq=64,
        variant="llama", use_flash=False)
    params = T.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


def engine_for(model, **over):
    cfg, params = model
    kw = dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
              min_prefill_bucket=8, max_batch_size=8)
    kw.update(over)
    return init_inference(params, cfg, kw, dtype=jnp.float32)


def _prompts(rng, lens=(6, 9, 4)):
    return [list(rng.integers(0, 128, n)) for n in lens]


def _drain(sched, rids):
    sched.run()
    return [sched.finished[r].output for r in rids]


class TestEquivalence:
    """Fixed seed => the scheduler's outputs are token-identical to a
    one-shot generate() run, per request, regardless of chunking,
    arrival staggering, and preemption — draws are keyed by
    (seed, stream, position), not by batch composition."""

    def test_chunked_prefill_matches_generate(self, model, rng):
        prompts = _prompts(rng)
        want = engine_for(model).generate(prompts, max_new_tokens=5)
        sched = ServingScheduler(
            engine_for(model),
            ServingSchedulerConfig(prefill_chunk=3,
                                   max_num_batched_tokens=8,
                                   warmup=False))
        rids = [sched.submit(p, 5) for p in prompts]
        got = _drain(sched, rids)
        assert got == want

    def test_staggered_arrivals_match(self, model, rng):
        """Requests join MID-FLIGHT (the continuous-batching point) and
        still reproduce the one-shot run token for token."""
        prompts = _prompts(rng, (6, 9, 4, 7))
        want = engine_for(model).generate(prompts, max_new_tokens=6)
        sched = ServingScheduler(
            engine_for(model),
            ServingSchedulerConfig(prefill_chunk=4,
                                   max_num_batched_tokens=8,
                                   warmup=False))
        rids = [sched.submit(prompts[0], 6, stream=0)]
        pending = list(enumerate(prompts))[1:]

        def tick(s):
            # one new arrival every other iteration, mid-generation
            if pending and s.counters["steps"] % 2 == 0:
                i, p = pending.pop(0)
                rids.append(s.submit(p, 6, stream=i))

        sched.run(tick=tick)
        while pending:  # arrivals that missed the drain
            i, p = pending.pop(0)
            rids.append(sched.submit(p, 6, stream=i))
            sched.run(tick=tick)
        got = [sched.finished[r].output for r in rids]
        assert got == want
        assert sched.counters["admitted"] == len(prompts)

    def test_preemption_token_identical(self, model, rng):
        """A block pool too small for the full batch forces preemption
        (flush + re-queue + recompute) — outputs must not change."""
        prompts = _prompts(rng)
        want = engine_for(model).generate(prompts, max_new_tokens=10)
        eng = engine_for(model, num_kv_blocks=6)
        sched = ServingScheduler(
            eng,
            ServingSchedulerConfig(prefill_chunk=3,
                                   max_num_batched_tokens=8,
                                   warmup=False))
        rids = [sched.submit(p, 10) for p in prompts]
        got = _drain(sched, rids)
        assert got == want
        assert sched.counters["preemptions"] > 0
        assert all(sched.finished[r].finish_reason == "length"
                   for r in rids)

    def test_sampled_matches_generate(self, model, rng):
        prompts = _prompts(rng)
        kw = dict(do_sample=True, temperature=0.9, top_k=12)
        want = engine_for(model).generate(
            prompts, max_new_tokens=7, seed=7, **kw)
        sched = ServingScheduler(
            engine_for(model),
            ServingSchedulerConfig(prefill_chunk=4,
                                   max_num_batched_tokens=16,
                                   warmup=False),
            sampling=kw, seed=7)
        rids = [sched.submit(p, 7) for p in prompts]
        got = _drain(sched, rids)
        assert got == want

    def test_eos_retires_immediately(self, model, rng):
        prompts = _prompts(rng, (6,))
        probe = engine_for(model).generate(prompts, max_new_tokens=8)
        eos = probe[0][2]
        want = engine_for(model).generate(prompts, max_new_tokens=8,
                                          eos_token_id=eos)
        sched = ServingScheduler(
            engine_for(model),
            ServingSchedulerConfig(prefill_chunk=3,
                                   max_num_batched_tokens=8,
                                   warmup=False))
        rids = [sched.submit(p, 8, eos_token_id=eos) for p in prompts]
        got = _drain(sched, rids)
        assert got == want
        assert got[0][-1] == eos
        assert sched.finished[rids[0]].finish_reason == "eos"


class TestImmediateRetirement:
    def test_blocks_reclaimed_at_finish_iteration(self, model, rng):
        """A short request's KV blocks rejoin the pool the iteration it
        finishes, while the long request is still decoding — the
        satellite generate() fix, observed through the scheduler."""
        eng = engine_for(model, prefix_cache={"enabled": False})
        sched = ServingScheduler(
            eng,
            ServingSchedulerConfig(prefill_chunk=8,
                                   max_num_batched_tokens=32,
                                   warmup=False))
        short = sched.submit(list(rng.integers(0, 128, 6)), 2)
        long = sched.submit(list(rng.integers(0, 128, 6)), 16)
        seen = []
        while sched.has_work:
            sched.step()
            seen.append((sched.finished.get(short) is not None,
                         sched.finished.get(long) is not None,
                         eng.state.free_blocks))
        # some iteration had short finished, long still running, and
        # short's block back in the pool (only long's single block out)
        assert any(s and not l and free == eng.config.num_kv_blocks - 1
                   for s, l, free in seen), seen

    def test_generate_flushes_eos_sequences_mid_batch(self, model, rng):
        """generate() itself (rebased on the scheduler) frees finished
        sequences' blocks before the batch drains: with one sequence
        stopping early via EOS, every block is back by the end AND the
        long sequence still matches its solo run."""
        eng = engine_for(model)
        prompts = _prompts(rng, (6, 9))
        probe = engine_for(model).generate(prompts, max_new_tokens=12)
        eos = probe[0][1]  # stops sequence 0 at its 2nd token
        want_long = engine_for(model).generate(
            [prompts[1]], max_new_tokens=12, eos_token_id=eos)
        outs = eng.generate(prompts, max_new_tokens=12, eos_token_id=eos)
        assert outs[0] == probe[0][:probe[0].index(eos) + 1]
        assert outs[1] == want_long[0]
        assert eng.state.free_blocks == eng.config.num_kv_blocks


class TestAdmission:
    def test_queue_deeper_than_batch(self, model, rng):
        """More requests than max_batch_size queue and all finish (the
        old generate() raised RuntimeError here)."""
        eng = engine_for(model, max_batch_size=4, num_kv_blocks=16)
        prompts = [list(rng.integers(0, 128, 5)) for _ in range(9)]
        want = engine_for(model).generate(prompts, max_new_tokens=4)
        sched = ServingScheduler(
            eng, ServingSchedulerConfig(prefill_chunk=8,
                                        max_num_batched_tokens=16,
                                        warmup=False))
        rids = [sched.submit(p, 4, stream=i)
                for i, p in enumerate(prompts)]
        got = _drain(sched, rids)
        assert got == want
        assert sched.counters["finished"] == 9

    def test_skip_policy_admits_past_misfit(self, model, rng):
        """'skip' admission scans past a waiting request that does not
        fit yet; 'fcfs' blocks behind it."""
        def build(policy):
            eng = engine_for(model, num_kv_blocks=7,
                             prefix_cache={"enabled": False})
            sched = ServingScheduler(
                eng, ServingSchedulerConfig(admission=policy,
                                            prefill_chunk=8,
                                            max_num_batched_tokens=64,
                                            warmup=False))
            # big holds 5 blocks; huge (5 blocks) cannot join; tiny can
            sched.submit(list(rng.integers(0, 128, 33)), 6)   # big
            sched.step()
            huge = sched.submit(list(rng.integers(0, 128, 33)), 2)
            tiny = sched.submit(list(rng.integers(0, 128, 4)), 2)
            sched.step()
            return sched, huge, tiny

        sched, huge, tiny = build("skip")
        assert sched.finished.get(tiny) is None  # still running is fine
        tiny_active = any(r.rid == tiny for r in sched.active)
        assert tiny_active  # admitted past the misfit
        sched.run()
        assert len(sched.finished) == 3

        sched, huge, tiny = build("fcfs")
        assert not any(r.rid == tiny for r in sched.active)
        sched.run()
        assert len(sched.finished) == 3

    def test_oversized_prompt_rejected(self, model):
        sched = ServingScheduler(
            engine_for(model),
            ServingSchedulerConfig(warmup=False))
        with pytest.raises(ValueError, match="max_seq_len"):
            sched.submit(list(range(65)), 4)

    def test_prompt_bigger_than_pool_capacity_finishes(self, model, rng):
        """A prompt that can never fit the KV pool finishes with
        reason='capacity' instead of wedging the queue."""
        eng = engine_for(model, num_kv_blocks=2,
                         prefix_cache={"enabled": False})
        sched = ServingScheduler(
            eng, ServingSchedulerConfig(warmup=False))
        rid = sched.submit(list(rng.integers(0, 128, 30)), 4)
        ok = sched.submit(list(rng.integers(0, 128, 5)), 2)
        sched.run()
        assert sched.finished[rid].finish_reason == "capacity"
        assert sched.finished[rid].output == []
        assert len(sched.finished[ok].output) == 2


class TestWarmupZeroRecompile:
    def test_steady_state_serving_compiles_nothing(self, model, rng):
        """engine.warmup() precompiles the (width x chunk) grid; a
        staggered serving workload afterwards adds NO compiled decode
        programs and the S003 RecompileTracker reports zero findings."""
        eng = engine_for(model)
        info = eng.warmup()
        assert info["programs"] > 0 and info["widths"] == [8]
        n_decode = len(eng._decode_fns)
        n_sample = len(eng._sample_fns)
        sigs_before = {n: eng.recompile_tracker.n_signatures(n)
                       for n in list(eng.recompile_tracker._sigs)}
        sched = ServingScheduler(
            eng, ServingSchedulerConfig(prefill_chunk=3,
                                        max_num_batched_tokens=8,
                                        warmup=False))
        prompts = _prompts(rng, (6, 9, 4, 7))
        pending = list(prompts)

        def tick(s):
            if pending and s.counters["steps"] % 2 == 0:
                s.submit(pending.pop(0), 6)

        sched.submit(pending.pop(0), 6)
        sched.run(tick=tick)
        while pending:
            sched.submit(pending.pop(0), 6)
            sched.run(tick=tick)
        assert sched.counters["finished"] == 4
        # zero S003 findings (no signature churn on any warmed program)
        assert eng.recompile_tracker.findings == []
        # and no NEW compiled decode/sample programs at all
        assert len(eng._decode_fns) == n_decode
        assert len(eng._sample_fns) == n_sample
        for name, n in sigs_before.items():
            assert eng.recompile_tracker.n_signatures(name) == n, name

    def test_tracker_flags_seeded_drift(self, model):
        """The wiring actually fires: a same-name signature with a
        different shape is classified as an S003 miss."""
        eng = engine_for(model)
        eng.recompile_tracker.record(
            "serving_decode[w8,u1]", (np.zeros((8,), np.int32),))
        assert eng.recompile_tracker.record(
            "serving_decode[w8,u1]", (np.zeros((8,), np.int32),))
        eng.recompile_tracker.record(
            "serving_decode[w8,u1]", (np.zeros((16,), np.int32),))
        assert any(f.rule == "S003"
                   for f in eng.recompile_tracker.findings)


class TestDoubleBuffering:
    def test_chained_steps_fire_and_match(self, model, rng):
        """run() launches each iteration on the previous one's
        device-resident token array (its readback lands after the next
        launch): the pure-decode stretch that used to chain is the
        look-ahead's case "every row carried, source map = identity",
        and so are the chunked-prefill iterations before it; tokens
        equal the step() drive, which always reads back first."""
        prompts = _prompts(rng, (6, 9))
        cfg = ServingSchedulerConfig(prefill_chunk=8,
                                     max_num_batched_tokens=16,
                                     decode_chunk=1, warmup=False)
        a = ServingScheduler(engine_for(model), cfg)
        ra = [a.submit(p, 10) for p in prompts]
        got = _drain(a, ra)
        assert a.counters["lookahead_steps"] == a.counters["steps"] - 1
        assert a.counters["lookahead_fallbacks"] == 0

        b = ServingScheduler(engine_for(model), cfg)
        rb = [b.submit(p, 10) for p in prompts]
        while b.has_work:
            b.step()
        assert b.counters["lookahead_steps"] == 0
        assert got == [b.finished[r].output for r in rb]

    def test_fused_steady_state(self, model, rng):
        """decode_chunk > 1: the steady state dispatches fused
        multi-step programs (tokens device-resident across the chunk)
        and still matches stepwise."""
        prompts = _prompts(rng, (6, 4))
        cfg1 = ServingSchedulerConfig(prefill_chunk=8,
                                      max_num_batched_tokens=16,
                                      decode_chunk=4, warmup=False)
        a = ServingScheduler(engine_for(model), cfg1)
        ra = [a.submit(p, 9) for p in prompts]
        got = _drain(a, ra)
        assert a.counters["fused_steps"] > 0
        want = engine_for(model).generate(prompts, max_new_tokens=9)
        assert got == want


class TestSpeculativeControlPlane:
    def test_scheduler_drives_speculation(self, model, rng):
        base = list(rng.integers(0, 128, 6))
        prompt = (base * 4)[:22]
        want = engine_for(model).generate([prompt], max_new_tokens=10)
        sched = ServingScheduler(
            engine_for(model),
            ServingSchedulerConfig(prefill_mode="wave", warmup=False),
            speculative={"ngram": 2, "draft_len": 4})
        rid = sched.submit(prompt, 10)
        got = _drain(sched, [rid])
        assert got == want
        assert sched.spec_stats["draft_tokens"] > 0
        # multi-token runs were accepted: fewer verify steps than the
        # tokens they committed
        assert (sched.spec_stats["accepted_tokens"]
                > sched.spec_stats["verified_chunks"])


class TestObservability:
    def test_metrics_and_monitor_events(self, model, rng):
        from deepspeed_tpu.monitor import serving_events

        sched = ServingScheduler(
            engine_for(model),
            ServingSchedulerConfig(prefill_chunk=4,
                                   max_num_batched_tokens=8,
                                   warmup=False))
        rids = [sched.submit(p, 4) for p in _prompts(rng)]
        _drain(sched, rids)
        m = sched.metrics()
        for key in ("ttft_p50_ms", "tpot_p50_ms", "queue_depth",
                    "preemptions", "batched_tokens_per_step",
                    "recompiles", "finished"):
            assert key in m, key
        assert m["finished"] == 3
        assert m["ttft_p50_ms"] > 0
        events = serving_events(sched, step=7)
        assert all(name.startswith("inference/serving/")
                   for name, _, _ in events)
        assert all(s == 7 for _, _, s in events)
        assert {n.rsplit("/", 1)[1] for n, _, _ in events} == set(m)

    def test_config_validation(self):
        with pytest.raises(ValueError, match="admission"):
            ServingSchedulerConfig(admission="lifo")
        with pytest.raises(ValueError, match="prefill_mode"):
            ServingSchedulerConfig(prefill_mode="eager")
