"""ServingRouter tests: prefix-aware routing, session affinity with
load-based eviction, prefill/decode disaggregation (KV block-table
transfer, token-identical vs colocated), replica failover without
token loss, degenerate fleets, fleet metrics/monitor events, the
per-replica speculative mode flag, and the bench device-probe
retry-with-backoff satellite.

Fast lane: tiny model, f32, CPU, warmup off — the routing and handoff
control planes are host-side; only the handoff gather/scatter pair and
the tiny decode programs compile."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import (
    ServingRouter,
    ServingRouterConfig,
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


NO_WARM = {"scheduler": {"warmup": False}}


def router_for(model, n, rng=None, sampling=None, seed=0, **cfg):
    c = dict(NO_WARM)
    c.update(cfg)
    c["replicas"] = n
    return ServingRouter([engine_for(model) for _ in range(n)], c,
                         sampling=sampling, seed=seed)


def reference_outputs(model, prompts, max_new, sampling=None, seed=0,
                      eos=None):
    """Single-scheduler outputs with streams 0..n-1 — what any router
    topology must reproduce token for token (router gids are its
    streams)."""
    sched = ServingScheduler(
        engine_for(model), ServingSchedulerConfig(warmup=False),
        sampling=sampling, seed=seed)
    rids = [sched.submit(p, max_new, eos_token_id=eos, stream=i)
            for i, p in enumerate(prompts)]
    sched.run()
    return [sched.finished[r].output for r in rids]


class TestRouting:
    def test_prefix_aware_routes_to_cached_replica(self, model, rng):
        """Request 2 of a shared-prefix pair must land on the replica
        that served request 1 — the hash-chain index is the routing
        signal."""
        router = router_for(model, 3)
        prefix = list(rng.integers(0, 128, 24))  # 3 full blocks
        g0 = router.submit(prefix + [1, 2], 3)
        router.serve()
        first = router._where[g0]
        g1 = router.submit(prefix + [9, 8, 7], 3)
        assert router._where[g1] == first
        assert router.counters["cache_hit_routes"] == 1
        router.serve()
        assert router.result(g1).done

    def test_round_robin_cycles(self, model, rng):
        router = router_for(model, 3, policy="round_robin",
                            session_affinity=False)
        prompt = list(rng.integers(0, 128, 6))
        where = [router._where[router.submit(prompt, 2)]
                 for _ in range(6)]
        assert where == [0, 1, 2, 0, 1, 2]
        router.serve()

    def test_least_loaded_wins_without_cache_signal(self, model, rng):
        """No prefix anywhere: the scored path degrades to least-
        loaded (queue-normalized)."""
        router = router_for(model, 2)
        # load replica 0 directly (bypassing the router's balancing)
        for _ in range(4):
            router.schedulers[0].submit(list(rng.integers(0, 128, 6)), 2)
        g = router.submit(list(rng.integers(0, 128, 6)), 2)
        assert router._where[g] == 1
        router.serve()

    def test_session_affinity_pins_and_evicts(self, model, rng):
        router = router_for(model, 2, affinity_evict_margin=2)
        p = list(rng.integers(0, 128, 6))
        g0 = router.submit(p, 2, session="s")
        pinned = router._where[g0]
        g1 = router.submit(list(rng.integers(0, 128, 6)), 2, session="s")
        assert router._where[g1] == pinned
        assert router.counters["affinity_hits"] == 1
        # skew the pinned replica's backlog past the margin
        for _ in range(5):
            router.schedulers[pinned].submit(
                list(rng.integers(0, 128, 6)), 2)
        g2 = router.submit(list(rng.integers(0, 128, 6)), 2, session="s")
        assert router._where[g2] != pinned
        assert router.counters["affinity_evictions"] == 1
        # the session re-pinned to the new replica
        assert router._sessions["s"] == router._where[g2]
        router.serve()


class TestDegenerate:
    def test_zero_replicas_raises(self):
        with pytest.raises(ValueError, match="at least one"):
            ServingRouter([])

    def test_one_replica_serves(self, model, rng):
        prompts = [list(rng.integers(0, 128, n)) for n in (6, 9)]
        want = reference_outputs(model, prompts, 4)
        router = router_for(model, 1)
        gids = [router.submit(p, 4) for p in prompts]
        router.serve()
        assert [router.result(g).output for g in gids] == want

    def test_disaggregated_falls_back_when_fleet_small(self, model, rng):
        router = router_for(model, 1, mode="disaggregated")
        assert router.mode == "colocated"
        g = router.submit(list(rng.integers(0, 128, 6)), 3)
        router.serve()
        assert router.result(g).done
        assert router.counters["handoffs"] == 0

    def test_replica_count_mismatch_raises(self, model):
        with pytest.raises(ValueError, match="engines were provided"):
            ServingRouter([engine_for(model)],
                          {"replicas": 2, **NO_WARM})

    def test_heterogeneous_fleet_raises(self, model):
        with pytest.raises(ValueError, match="geometry"):
            ServingRouter([engine_for(model),
                           engine_for(model, kv_block_size=16)], NO_WARM)


class TestDisaggregation:
    def test_token_identical_vs_colocated(self, model, rng):
        """Acceptance: paged KV blocks hand off prefill -> decode with
        token-identical output vs the colocated control plane, sampled
        decoding included."""
        sampling = dict(do_sample=True, temperature=0.9, top_k=20)
        prompts = [list(rng.integers(0, 128, n)) for n in (6, 19, 9, 14)]
        want = reference_outputs(model, prompts, 6, sampling=sampling)
        router = router_for(model, 2, sampling=sampling,
                            mode="disaggregated")
        assert router.describe()["replica_mode"] == ["prefill", "decode"]
        gids = [router.submit(p, 6) for p in prompts]
        router.serve()
        assert [router.result(g).output for g in gids] == want
        assert router.counters["handoffs"] == len(prompts)
        assert router.metrics()["fleet/handoff_p50_ms"] > 0.0

    def test_transferred_prefix_registers_on_decode_replica(self, model,
                                                            rng):
        """import_kv feeds the decode replica's hash-chain index: the
        moved prefix becomes a routable cache asset there."""
        router = router_for(model, 2, mode="disaggregated")
        prompt = list(rng.integers(0, 128, 17))  # 2 full blocks
        router.submit(prompt, 3)
        router.serve()
        dec = router.schedulers[1].engine
        assert dec.state.lookup_prefix(prompt) >= 16

    def test_handoff_capacity_fallback_requeues(self, model, rng):
        """A decode replica that cannot take the transfer (batch full)
        falls back to requeue-for-recompute — outputs unchanged."""
        prompts = [list(rng.integers(0, 128, 8)) for _ in range(3)]
        want = reference_outputs(model, prompts, 6)
        engines = [engine_for(model),
                   engine_for(model, max_batch_size=1)]
        router = ServingRouter(
            engines, {"replicas": 2, "mode": "disaggregated", **NO_WARM})
        gids = [router.submit(p, 6) for p in prompts]
        router.serve()
        assert [router.result(g).output for g in gids] == want
        assert router.counters["handoff_fallbacks"] >= 1

    def test_eos_on_prefill_replica_skips_transfer(self, model, rng):
        """A request whose budget is one token finishes at the prefill
        replica — no transfer for a sequence that never decodes."""
        router = router_for(model, 2, mode="disaggregated")
        g = router.submit(list(rng.integers(0, 128, 6)), 1)
        router.serve()
        assert router.result(g).done
        assert router.result(g).finish_reason == "length"
        assert router.counters["handoffs"] == 0


class TestFailover:
    def test_replica_death_mid_decode_no_token_loss(self, model, rng):
        sampling = dict(do_sample=True, temperature=0.9, top_k=20)
        prompts = [list(rng.integers(0, 128, n)) for n in (12, 19, 9, 14)]
        want = reference_outputs(model, prompts, 8, sampling=sampling)
        router = router_for(model, 2, sampling=sampling)
        gids = [router.submit(p, 8) for p in prompts]
        for _ in range(3):
            router.step()
        mid = [list(router.result(g).output) for g in gids]
        assert any(mid)  # some tokens were already produced
        victim = max(range(2), key=lambda i: len(router.schedulers[i].active)
                     + len(router.schedulers[i].waiting))
        moved = router.fail_replica(victim)
        assert moved > 0
        assert router.counters["requeued_on_death"] == moved
        router.serve()
        got = [router.result(g).output for g in gids]
        assert got == want
        # already-delivered tokens were preserved verbatim
        assert all(got[i][:len(mid[i])] == mid[i] for i in range(len(gids)))

    def test_decode_replica_death_in_disaggregated_fleet(self, model, rng):
        prompts = [list(rng.integers(0, 128, n)) for n in (9, 14, 11)]
        want = reference_outputs(model, prompts, 6)
        router = router_for(model, 3, mode="disaggregated")
        gids = [router.submit(p, 6) for p in prompts]
        # run until at least one sequence decodes on a decode replica
        for _ in range(6):
            router.step()
        router.fail_replica(2)
        router.serve()
        assert [router.result(g).output for g in gids] == want

    def test_dead_session_pins_move_off_the_dead_replica(self, model,
                                                         rng):
        router = router_for(model, 2)
        g = router.submit(list(rng.integers(0, 128, 6)), 2, session="s")
        pinned = router._where[g]
        router.fail_replica(pinned)
        # the failover requeue re-routed the session: its pin (if any)
        # now points at a live replica, never the dead one
        assert router._sessions.get("s") != pinned
        router.serve()
        assert router.result(g).done


class TestObservability:
    def test_metrics_and_monitor_events(self, model, rng):
        from deepspeed_tpu.monitor.monitor import serving_events

        router = router_for(model, 2)
        gids = [router.submit(list(rng.integers(0, 128, 6)), 3)
                for _ in range(4)]
        router.serve()
        m = router.metrics()
        for key in ("fleet/replicas", "fleet/live_replicas",
                    "fleet/ttft_p50_ms", "fleet/cache_hit_route_rate",
                    "fleet/routed", "fleet/finished",
                    "replica0/queue_depth", "replica1/ttft_p50_ms"):
            assert key in m, key
        assert m["fleet/replicas"] == 2.0
        assert m["fleet/finished"] == float(len(gids))
        events = serving_events(router, step=7)
        assert all(s == 7 for _, _, s in events)
        names = {n for n, _, _ in events}
        assert "inference/serving/fleet/ttft_p50_ms" in names
        assert "inference/serving/replica0/steps" in names

    def test_speculative_replica_mode_reports_through_router(self, model,
                                                             rng):
        """The per-replica speculative flag: outputs stay exact-greedy
        and the router surfaces acceptance stats per replica and
        fleet-aggregate."""
        # repetitive prompts so the n-gram draft actually lands
        prompts = [([7, 8, 9, 10] * 5)[:14] for _ in range(2)]
        want = reference_outputs(model, prompts, 8)
        router = router_for(model, 2, policy="round_robin",
                            session_affinity=False,
                            speculative_replicas=1)
        assert router.replica_mode == ["mixed", "speculative"]
        gids = [router.submit(p, 8) for p in prompts]
        router.serve()
        assert [router.result(g).output for g in gids] == want
        m = router.metrics()
        assert "replica1/spec_draft_acceptance_rate" in m
        assert "fleet/spec_draft_acceptance_rate" in m
        assert 0.0 <= m["fleet/spec_draft_acceptance_rate"] <= 1.0


class TestSpecStatsPlumbing:
    def test_generate_speculative_reports_acceptance_rate(self, model):
        eng = engine_for(model)
        prompt = ([3, 4, 5, 6] * 6)[:20]
        outs, stats = eng.generate_speculative(
            [prompt], max_new_tokens=10, ngram=3, draft_len=3,
            return_stats=True)
        assert len(outs[0]) == 10
        assert "draft_acceptance_rate" in stats
        assert 0.0 <= stats["draft_acceptance_rate"] <= 1.0
        assert stats["draft_tokens"] > 0
        # the rate is the DRAFT acceptance (guaranteed pending token
        # excluded), consistent with the raw counters
        assert stats["draft_acceptance_rate"] == pytest.approx(
            (stats["accepted_tokens"] - stats["verified_chunks"])
            / stats["draft_tokens"])

    def test_collapsed_steps_never_exceed_steps(self, model, rng):
        """The collapse counter ticks per DISPATCHED step, so the
        stats contract draft_collapsed_steps <= steps holds even when
        an iteration produces no verifiable chunk."""
        eng = engine_for(model, max_batch_size=2)
        prompts = [list(rng.integers(0, 128, 8)) for _ in range(2)]
        _, stats = eng.generate_speculative(
            prompts, max_new_tokens=6, draft_len=4, return_stats=True)
        assert stats["draft_collapsed_steps"] == stats["steps"] > 0
