"""Overload-resilience tests: the pressure governor (tiered
watermarks, YELLOW parked-trim, S004 watermark scaling), the bounded
pinned-host KV spill tier (preempt-to-host under RED + import-resume
token identity, with recompute fallback on faults/corruption/budget),
SLO-aware admission (deadline rejection before any block allocation),
the preemption-starvation bound, BlockedAllocator exhaustion edges,
and the router's pressure-aware routing / handoff backpressure /
brownout shed (docs/fault_tolerance.md pressure section).

Fast lane: tiny model, f32, CPU — the control plane is host-side and
the compiled programs are seconds-cheap at this size."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.config.config import PressureConfig
from deepspeed_tpu.inference import (
    BROWNOUT,
    GREEN,
    RED,
    YELLOW,
    BlockedAllocator,
    KVCacheExhaustedError,
    PressureGovernor,
    ServingRouter,
    ServingScheduler,
    ServingSchedulerConfig,
    StateManager,
    init_inference,
)
from deepspeed_tpu.inference.offload_store import HostKvSpillStore
from deepspeed_tpu.inference.pressure import (
    C_DISPATCH,
    estimate_ttft,
)
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.resilience import armed

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def model():
    cfg = T.TransformerConfig(
        vocab_size=128, n_layers=2, n_heads=4, d_model=64, max_seq=128,
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


def _fake_engine(num_blocks=10, block_size=8, footprints=None,
                 prefix=False):
    sm = StateManager(num_blocks, block_size,
                      enable_prefix_cache=prefix)
    return types.SimpleNamespace(state=sm,
                                 warmup_footprints=footprints or {})


# the spill scenarios want admissions to land BEFORE the RED gate
# engages (growth overshoot, not admission, must trigger preemption)
PRESSURE = {"enabled": True, "yellow": 0.5, "red": 0.8,
            "brownout": 0.99}


class TestPressureGovernor:
    def test_levels_rise_immediately_and_relax_with_hysteresis(self):
        eng = _fake_engine(num_blocks=10)
        gov = PressureGovernor(
            PressureConfig(enabled=True, yellow=0.5, red=0.7,
                           brownout=0.9, hysteresis=0.1), eng)
        assert gov.update() == GREEN
        uid_blocks = eng.state.extend(0, 8 * 8).blocks  # 8/10 live
        assert gov.update() == RED
        eng.state.extend(1, 8 * 2)  # 10/10
        assert gov.update() == BROWNOUT
        assert gov.max_level == BROWNOUT
        # relax ONE level per update, only past entry - hysteresis
        eng.state.flush(1)  # back to 0.8: below brownout-0.1? no (0.8)
        assert gov.update() == BROWNOUT
        eng.state.flush(0)  # 0.0 — relaxation is still stepwise
        assert gov.update() == RED
        assert gov.update() == YELLOW
        assert gov.update() == GREEN
        assert gov.counters["transitions"] >= 5
        assert len(uid_blocks) == 8

    def test_yellow_trims_parked_prefix_blocks(self):
        eng = _fake_engine(num_blocks=10, prefix=True)
        sm = eng.state
        toks = list(range(16))  # 2 full blocks
        seq, _ = sm.extend(0, 16, token_ids=toks)
        sm.commit(0, 16, token_ids=toks)
        sm.flush(0)  # both blocks park (index-addressed)
        assert sm.allocator.cached_blocks == 2
        sm.extend(1, 8 * 4)  # 4/10 live: inside the YELLOW band
        gov = PressureGovernor(
            PressureConfig(enabled=True, yellow=0.3, red=0.6,
                           brownout=0.9), eng)
        assert gov.update() == YELLOW
        assert gov.counters["parked_trimmed"] == 2
        assert sm.allocator.cached_blocks == 0
        assert sm.indexed_blocks == 0  # evict_cb dropped the keys
        assert len(seq.blocks) == 2

    def test_s004_footprint_scales_watermarks_down(self):
        budget = 100
        eng = _fake_engine(footprints={8: {"peak_hbm_bytes": 100.0}})
        gov = PressureGovernor(
            PressureConfig(enabled=True, static_headroom=0.8), eng,
            budget_bytes=budget)
        # peak == budget: overshoot 0.2 past the headroom -> scale 0.8
        assert gov.watermark_scale() == pytest.approx(0.8)
        # no footprints / no budget -> no scaling
        assert PressureGovernor(
            PressureConfig(enabled=True), eng).watermark_scale() == 1.0
        eng2 = _fake_engine(footprints={8: {"peak_hbm_bytes": 50.0}})
        assert PressureGovernor(
            PressureConfig(enabled=True), eng2,
            budget_bytes=budget).watermark_scale() == 1.0


class TestSpillStore:
    def _payload(self, nbytes=64):
        return {"seen_tokens": 3, "n_blocks": 1,
                "k": np.zeros((nbytes // 8,), np.float32),
                "v": np.zeros((nbytes // 8,), np.float32)}

    def test_round_trip_and_byte_accounting(self):
        store = HostKvSpillStore(1024)
        p = self._payload()
        assert store.put(1, p)
        assert store.used_bytes == HostKvSpillStore.payload_nbytes(p)
        got = store.get(1)
        assert got is p
        assert store.used_bytes == 0
        assert store.get(1) is None  # popped
        st = store.stats()
        assert st["spill_puts"] == 1 and st["spill_gets"] == 1

    def test_bounded_budget_rejects_not_evicts(self):
        store = HostKvSpillStore(100)
        assert store.put(1, self._payload(64))
        assert not store.put(2, self._payload(64))  # over budget
        assert store.counters["rejects"] == 1
        assert store.get(1) is not None  # resident entry untouched

    def test_discard_and_restore(self):
        store = HostKvSpillStore(1024)
        p = self._payload()
        store.put(1, p)
        got = store.get(1)
        store.restore(1, got)  # defer path: re-insert, no accounting
        assert store.counters["puts"] == 1
        store.discard(1)
        assert store.used_bytes == 0 and store.counters["discards"] == 1

    def test_spill_io_faults_fire_on_put_and_get(self):
        store = HostKvSpillStore(1024)
        plan = {"faults": [
            {"point": "spill.io", "kind": "raise", "error": "io",
             "where": {"op": "put"}, "at": 1, "times": 1},
            {"point": "spill.io", "kind": "raise", "error": "io",
             "where": {"op": "get"}, "at": 1, "times": 1}]}
        with armed(plan):
            with pytest.raises(RuntimeError):
                store.put(1, self._payload())
            store.put(2, self._payload())  # fault consumed
            with pytest.raises(RuntimeError):
                store.get(2)
        # the failed get DROPPED the entry first (no wedged budget)
        assert store.used_bytes == 0


def _pressure_sched(model, sampling=None, seed=0, pressure=None,
                    **over):
    eng = engine_for(model, num_kv_blocks=6, **over)
    return ServingScheduler(
        eng,
        ServingSchedulerConfig(
            prefill_chunk=3, max_num_batched_tokens=8, warmup=False,
            pressure=pressure or dict(PRESSURE)),
        sampling=sampling, seed=seed)


class TestSpillResume:
    """Preempt-to-host under RED is token-identical to the unpressured
    run — and every failure leg (fault, corruption, budget) falls back
    to flush-and-recompute, which is also token-identical."""

    def _want(self, model, rng, **kw):
        prompts = [list(rng.integers(0, 128, n)) for n in (6, 9, 4)]
        return prompts, engine_for(model).generate(
            prompts, max_new_tokens=10, **kw)

    def test_spill_resume_token_identical(self, model, rng):
        prompts, want = self._want(model, rng)
        sched = _pressure_sched(model)
        rids = [sched.submit(p, 10) for p in prompts]
        sched.run()
        assert [sched.finished[r].output for r in rids] == want
        assert sched.counters["spills"] >= 1
        assert sched.counters["spill_resumes"] >= 1
        assert sched.governor.max_level >= RED
        assert sched.spill_store.used_bytes == 0  # nothing stranded

    def test_spill_resume_sampled_token_identical(self, model, rng):
        kw = dict(do_sample=True, temperature=0.9, top_k=12)
        prompts, want = self._want(model, rng, seed=7, **kw)
        sched = _pressure_sched(model, sampling=kw, seed=7)
        rids = [sched.submit(p, 10) for p in prompts]
        sched.run()
        assert [sched.finished[r].output for r in rids] == want
        assert sched.counters["spill_resumes"] >= 1

    def test_spill_fault_falls_back_to_recompute(self, model, rng):
        prompts, want = self._want(model, rng)
        sched = _pressure_sched(model)
        rids = [sched.submit(p, 10) for p in prompts]
        with armed({"faults": [
                {"point": "spill.io", "kind": "raise", "error": "io",
                 "where": {"op": "put"}, "times": -1}]}):
            sched.run()
        assert [sched.finished[r].output for r in rids] == want
        assert sched.counters["spills"] == 0
        assert sched.counters["spill_fallbacks"] >= 1

    def test_corrupt_spill_payload_detected_and_recomputed(
            self, model, rng):
        """A bit flipped while the payload sat in host DRAM: the PR-9
        digest envelope rejects it at import (before any page is
        scattered) and the request recomputes token-identically."""
        prompts, want = self._want(model, rng)
        sched = _pressure_sched(model)
        rids = [sched.submit(p, 10) for p in prompts]
        with armed({"faults": [
                {"point": "handoff.payload", "kind": "corrupt",
                 "times": -1}]}):
            sched.run()
        assert [sched.finished[r].output for r in rids] == want
        assert sched.counters["spill_integrity_failures"] >= 1
        assert sched.counters["spill_fallbacks"] >= 1

    def test_zero_budget_tier_rejects_and_recomputes(self, model, rng):
        prompts, want = self._want(model, rng)
        sched = _pressure_sched(
            model, pressure=dict(PRESSURE, spill_host_mb=0.0))
        rids = [sched.submit(p, 10) for p in prompts]
        sched.run()
        assert [sched.finished[r].output for r in rids] == want
        assert sched.counters["spills"] == 0
        assert sched.counters["spill_rejects"] >= 1

    def test_export_ships_only_written_blocks(self, model):
        """A sequence holding reserved-but-unwritten blocks (the spill
        victim shape) exports ceil(seen/bs) pages, and a peer import
        reconstructs exactly that much."""
        eng_a, eng_b = engine_for(model), engine_for(model)
        eng_a.state.extend(0, 20)  # 3 blocks reserved (bs=8)
        eng_a.state.commit(0, 8)   # only 1 block written
        payload = eng_a.export_kv(0)
        assert payload["n_blocks"] == 1
        assert payload["seen_tokens"] == 8
        eng_b.import_kv(5, payload)
        seq = eng_b.state.get(5)
        assert seq.seen_tokens == 8 and len(seq.blocks) == 1


class TestDeadlineAdmission:
    def test_unservable_deadline_rejected_without_blocks(self, model,
                                                         rng):
        sched = ServingScheduler(
            engine_for(model),
            ServingSchedulerConfig(max_num_batched_tokens=8,
                                   warmup=False))
        # build a queue deep enough that the TTFT estimate blows past
        # the deadline (everything below is host counter arithmetic)
        for _ in range(10):
            sched.submit(list(rng.integers(0, 128, 40)), 8)
        alloc = sched.engine.state.allocator
        assert alloc.available_blocks == alloc.total_blocks
        est = estimate_ttft(sched, 6)
        rid = sched.submit(list(rng.integers(0, 128, 6)), 8,
                           deadline_s=est / 2)
        req = sched.finished[rid]
        assert req.done and req.finish_reason == "deadline"
        assert req.uid is None and req.output == []
        # zero KV blocks touched by the rejection
        assert alloc.available_blocks == alloc.total_blocks
        assert sched.counters["deadline_rejections"] == 1

    def test_servable_deadline_admits_and_completes(self, model, rng):
        sched = ServingScheduler(
            engine_for(model),
            ServingSchedulerConfig(warmup=False))
        prompt = list(rng.integers(0, 128, 6))
        want = engine_for(model).generate([prompt], max_new_tokens=5)
        rid = sched.submit(prompt, 5, deadline_s=10 * C_DISPATCH)
        sched.run()
        assert sched.finished[rid].output == want[0]
        assert sched.finished[rid].finish_reason != "deadline"
        assert sched.counters["deadline_rejections"] == 0

    def test_slo_class_resolves_through_config(self, model, rng):
        sched = ServingScheduler(
            engine_for(model),
            ServingSchedulerConfig(
                max_num_batched_tokens=8, warmup=False,
                slo_classes={"interactive": 1e-9, "batch": 100.0}))
        for _ in range(6):
            sched.submit(list(rng.integers(0, 128, 40)), 8)
        rid = sched.submit(list(rng.integers(0, 128, 6)), 4,
                           slo_class="interactive")
        assert sched.finished[rid].finish_reason == "deadline"
        rid2 = sched.submit(list(rng.integers(0, 128, 6)), 4,
                            slo_class="batch")
        assert rid2 not in sched.finished  # queued
        with pytest.raises(ValueError, match="slo_class"):
            sched.submit([1, 2, 3], 4, slo_class="nope")


class TestStarvationBound:
    """The satellite regression: youngest-first victim selection plus
    requeue-front lets a pair of similar-age requests ping-pong —
    a re-admitted victim is again the youngest, so the next reserve
    failure takes it again, and its preemption count grows without
    bound while it makes zero forward progress. The aging bound
    (config.max_preemptions) marks such a request PROTECTED: it is
    skipped in victim selection, the requester yields instead, and the
    protected sequence runs to completion."""

    def _full_sched(self, model, rng, bound):
        """Three 16-token prompts filling a 6-block pool exactly —
        any further reservation must preempt someone."""
        eng = engine_for(model, kv_block_size=8, num_kv_blocks=6,
                         max_seq_len=128)
        sched = ServingScheduler(
            eng,
            ServingSchedulerConfig(prefill_chunk=4,
                                   max_num_batched_tokens=16,
                                   warmup=False,
                                   max_preemptions=bound))
        prompts = [list(rng.integers(0, 128, 16)) for _ in range(3)]
        rids = [sched.submit(p, 12) for p in prompts]
        sched._admit()
        assert len(sched.active) == 3
        assert sched.engine.state.allocator.available_blocks == 0
        return sched, rids, prompts

    def test_legacy_policy_revictimizes_regardless_of_history(
            self, model, rng):
        """bound=0 (the pre-fix policy): the youngest is taken even
        after arbitrarily many prior preemptions — the ping-pong rule
        this satellite exists to break."""
        sched, rids, _ = self._full_sched(model, rng, bound=0)
        victim = sched.active[-1]
        victim.preemptions = 99
        assert sched._reserve(sched.active[0], 8 * 3) is True
        assert victim.state == "waiting"  # re-victimized anyway
        assert victim.preemptions == 100
        assert sched.counters["starvation_protected"] == 0

    def test_aged_victims_are_protected_and_requester_yields(
            self, model, rng):
        sched, rids, _ = self._full_sched(model, rng, bound=2)
        oldest = sched.active[0]
        for req in sched.active[1:]:
            req.preemptions = 2  # at the bound: protected
        assert sched._reserve(oldest, 8 * 3) is False
        # the requester yielded; the protected pair kept their blocks
        assert oldest.state == "waiting"
        assert all(r.preemptions == 2 and r.state != "waiting"
                   for r in sched.active)
        assert sched.counters["starvation_protected"] == 1

    def test_protected_victims_run_to_completion(self, model, rng):
        """Forward-progress guarantee end to end: with every other
        active request already at the bound, the run still drains with
        token-identical outputs and no protected request is preempted
        again."""
        r = np.random.default_rng(3)
        want_prompts = [list(r.integers(0, 128, 16)) for _ in range(3)]
        want = engine_for(model).generate(want_prompts,
                                          max_new_tokens=12)
        sched, rids, prompts = self._full_sched(
            model, np.random.default_rng(3), bound=2)
        protected = list(sched.active[1:])
        for req in protected:
            req.preemptions = 2
        sched.run()
        assert prompts == want_prompts
        assert [sched.finished[rid].output for rid in rids] == want
        # protected requests were never VICTIMIZED again (they may
        # still yield as requesters, which is the bounded, progress-
        # making direction)
        assert sched.counters["starvation_protected"] >= 1
        assert len(protected) == 2


class TestAllocatorEdges:
    def test_exhaustion_raises_typed_error(self):
        alloc = BlockedAllocator(2)
        alloc.allocate(2)
        with pytest.raises(KVCacheExhaustedError):
            alloc.allocate(1)  # zero free + zero parked
        assert issubclass(KVCacheExhaustedError, RuntimeError)

    def test_zero_pool_cap_never_parks(self):
        alloc = BlockedAllocator(2, cache_pool_blocks=0)
        b = alloc.allocate(1)
        alloc.mark_cached(b[0])
        alloc.free(b)
        assert alloc.cached_blocks == 0  # parked then instantly evicted
        assert alloc.free_blocks == 2

    def test_trim_parked_runs_evict_callback(self):
        evicted = []
        alloc = BlockedAllocator(4, evict_cb=evicted.append)
        blocks = alloc.allocate(3)
        for b in blocks:
            alloc.mark_cached(b)
        alloc.free(blocks)
        assert alloc.cached_blocks == 3
        assert alloc.trim_parked(2) == 2
        assert evicted == blocks[:2]  # LRU order
        assert alloc.trim_parked(10) == 1  # drains, then stops
        assert alloc.free_blocks == 4

    def test_scheduler_surfaces_non_capacity_runtime_errors(
            self, model, rng):
        """The reserve/admission loops answer ONLY the typed
        exhaustion error with preemption; the tracked-sequence cap
        (a plain RuntimeError) must surface, not silently requeue."""
        eng = engine_for(model, max_tracked_sequences=1)
        sched = ServingScheduler(
            eng, ServingSchedulerConfig(warmup=False))
        for _ in range(2):
            sched.submit(list(rng.integers(0, 128, 6)), 4)
        with pytest.raises(RuntimeError, match="tracked"):
            sched.run()


def _build_router(model, n, cfg=None, **sched_over):
    scfg = dict(warmup=False, pressure=dict(PRESSURE))
    scfg.update(sched_over)
    rcfg = {"replicas": n, "scheduler": scfg}
    rcfg.update(cfg or {})
    return ServingRouter([engine_for(model) for _ in range(n)], rcfg)


class TestRouterPressure:
    def test_routing_avoids_pressured_replicas(self, model, rng):
        router = _build_router(model, 2)
        router.schedulers[0].governor.level = BROWNOUT
        gid = router.submit(list(rng.integers(0, 128, 8)), 4)
        assert router._where[gid] == 1  # brownout replica skipped
        router.schedulers[0].governor.level = RED
        router.schedulers[1].governor.level = GREEN
        gid2 = router.submit(list(rng.integers(0, 128, 8)), 4)
        assert router._where[gid2] == 1  # pressure fold in the score

    def test_fleet_brownout_engages_fair_shed(self, model, rng):
        router = _build_router(model, 2)
        for s in router.schedulers:
            s.governor.level = BROWNOUT
        bound = sum(s.engine.config.max_batch_size
                    for s in router.schedulers)
        from deepspeed_tpu.inference import RequestShedError

        with pytest.raises(RequestShedError):
            for _ in range(bound + 2):  # sessionless: new req is shed
                router.submit(list(rng.integers(0, 128, 8)), 4)
        assert router.counters["brownout_shed_engaged"] >= 1
        assert router.counters["shed_requests"] >= 1
        # calm fleet -> unbounded again
        for s in router.schedulers:
            s.governor.level = GREEN
        router.submit(list(rng.integers(0, 128, 8)), 4)

    def test_handoff_backpressure_parks_until_decode_drains(
            self, model, rng):
        # decode replica with a 2-slot batch (geometry stays
        # homogeneous — max_batch is a scheduler knob, not a KV page
        # shape): once both slots fill, pump must PARK the remaining
        # prefill-complete sequences instead of force-recomputing them
        engines = [engine_for(model), engine_for(model,
                                                 max_batch_size=2)]
        router = ServingRouter(engines, {
            "replicas": 2, "mode": "disaggregated",
            "prefill_replicas": 1, "max_handoff_backlog": 2,
            "scheduler": {"warmup": False}})
        gids = [router.submit(list(rng.integers(0, 128, 8)), 12)
                for _ in range(4)]
        saw_backpressure = 0
        for _ in range(100):
            router.step()
            saw_backpressure = max(
                saw_backpressure,
                router.counters["handoff_backpressure"])
            if not router.has_work:
                break
        assert saw_backpressure >= 1
        assert all(router.result(g).done for g in gids)
        assert router.counters["handoff_fallbacks"] == 0
        assert router.counters["handoffs"] == 4

    def test_prefill_backlog_bound_redirects_routing(self, model, rng):
        from deepspeed_tpu.inference import Request

        router = _build_router(
            model, 3, cfg={"mode": "disaggregated",
                           "prefill_replicas": 2,
                           "max_handoff_backlog": 1})
        router.schedulers[0].handoff_ready.append(
            Request(rid=99, prompt=[1], max_new_tokens=1,
                    eos_token_id=None, stream=99, arrival=0.0))
        gid = router.submit(list(rng.integers(0, 128, 8)), 4)
        assert router._where[gid] == 1
        assert router.counters["prefill_backpressure"] >= 1

    def test_deadline_passes_through_router(self, model, rng):
        router = _build_router(model, 2)
        # deep queue on both replicas, then an unservable deadline
        for _ in range(12):
            router.submit(list(rng.integers(0, 128, 40)), 8)
        gid = router.submit(list(rng.integers(0, 128, 8)), 4,
                            deadline_s=1e-9)
        req = router.result(gid)
        assert req.done and req.finish_reason == "deadline"
        m = router.metrics()
        assert m["fleet/deadline_rejections"] >= 1


class TestObservability:
    def test_scheduler_metrics_and_monitor_events(self, model, rng):
        from deepspeed_tpu.monitor.monitor import serving_events

        sched = _pressure_sched(model)
        rids = [sched.submit(list(rng.integers(0, 128, n)), 10)
                for n in (6, 9, 4)]
        sched.run()
        m = sched.metrics()
        for key in ("pressure_level", "pressure_max_level",
                    "pressure_occupancy", "pressure_parked_trimmed",
                    "spills", "spill_resumes", "spill_fallbacks",
                    "spill_used_bytes", "spill_peak_bytes",
                    "deadline_rejections", "starvation_protected"):
            assert key in m, key
        assert m["pressure_max_level"] >= RED
        events = serving_events(sched, step=1)
        names = {n for n, _, _ in events}
        assert "inference/serving/pressure_level" in names
        assert "inference/serving/spills" in names
        assert len(rids) == 3

    def test_router_fleet_aggregates(self, model, rng):
        router = _build_router(model, 2)
        router.submit(list(rng.integers(0, 128, 8)), 4)
        m = router.metrics()
        for key in ("fleet/spills", "fleet/spill_resumes",
                    "fleet/deadline_rejections",
                    "fleet/max_pressure_level",
                    "fleet/handoff_backpressure",
                    "fleet/prefill_backpressure",
                    "fleet/brownout_shed_engaged"):
            assert key in m, key
        assert "replica0/pressure_level" in m


class TestOverloadBaseline:
    """The committed OVERLOAD.json must stay consistent with the lane
    (scripts/ds_gate.py overload gates the full run; this keeps the cheap
    structural contract in the fast lane)."""

    def test_committed_baseline_shape(self):
        path = os.path.join(_REPO, "OVERLOAD.json")
        doc = json.load(open(path))
        assert {"faults", "workload", "expect"} <= set(doc)
        points = {f["point"] for f in doc["faults"]}
        assert points == {"spill.io"}
        exp = doc["expect"]
        # the lane is meaningless unless it actually exercised the
        # spill, fallback, and rejection paths
        assert exp["clean_spills"] >= 1
        assert exp["clean_spill_resumes"] >= 1
        assert exp["spill_fallbacks"] >= 1
        assert exp["deadline_rejections"] >= 1
        assert exp["max_pressure_level"] >= RED
        assert doc["workload"]["pressure"]["enabled"] is True
