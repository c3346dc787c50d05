"""Resilience lane: deterministic fault injection, the self-healing
serving router, crash-consistent checkpointing, offload I/O retry, and
elastic-agent boundary cases (docs/fault_tolerance.md).

Everything here is fast-lane: tiny models, injectable clocks, seeded
fault plans — the point of the chaos harness is that recovery paths
run in CI deterministically, so these tests never sleep through real
backoffs or kill real processes (tests/test_elastic_agent.py owns the
slow multi-process journeys)."""

import json
import os
import sys
import time

import numpy as np
import pytest

from deepspeed_tpu.resilience import (
    CLOSED,
    HALF_OPEN,
    HELD,
    OPEN,
    BreakerConfig,
    CheckpointCrashError,
    FaultPlan,
    FleetHealth,
    InjectedFault,
    InjectedIOError,
    ReplicaBreaker,
    ReplicaDeadError,
    armed,
    corrupt_file,
    disarm,
    fault_point,
)


@pytest.fixture(autouse=True)
def _always_disarmed():
    """A test that dies mid-plan must not leak chaos into the next."""
    disarm()
    yield
    disarm()


# ---------------------------------------------------------------------------
# faults.py units
# ---------------------------------------------------------------------------

class TestFaultPlan:
    def test_disarmed_fault_point_is_none(self):
        assert fault_point("scheduler.step", replica=0) is None

    def test_at_times_where_deterministic(self):
        plan = FaultPlan([
            {"point": "p", "kind": "raise", "error": "generic",
             "where": {"replica": 1}, "at": 2, "times": 2}])
        with armed(plan):
            fault_point("p", replica=0)      # no match (where)
            fault_point("p", replica=1)      # match 1 < at
            for _ in range(2):               # matches 2, 3: fire
                with pytest.raises(InjectedFault):
                    fault_point("p", replica=1)
            fault_point("p", replica=1)      # match 4: window over
        assert len(plan.fired) == 2

    def test_times_forever_and_reset_replay(self):
        plan = FaultPlan([{"point": "p", "at": 1, "times": -1,
                           "error": "replica_dead"}])
        with armed(plan):
            for _ in range(3):
                with pytest.raises(ReplicaDeadError):
                    fault_point("p")
        plan.reset()
        with armed(plan):
            with pytest.raises(ReplicaDeadError):
                fault_point("p")
        assert plan.fired == ["p#1:raise:replica_dead"]

    def test_delay_and_skip_actions(self):
        plan = FaultPlan([
            {"point": "d", "kind": "delay", "value": 0.25},
            {"point": "s", "kind": "skip"}])
        with armed(plan):
            act = fault_point("d")
            assert act.kind == "delay" and act.value == 0.25
            assert fault_point("s").kind == "skip"
            assert fault_point("other") is None

    def test_armed_disarms_on_exception(self):
        plan = FaultPlan([{"point": "p", "times": -1}])
        with pytest.raises(InjectedFault):
            with armed(plan):
                fault_point("p")
        assert fault_point("p") is None  # disarmed despite the raise

    def test_json_roundtrip(self, tmp_path):
        doc = {"name": "x", "seed": 7,
               "budget": {"min_goodput_ratio": 0.5},
               "faults": [{"point": "p", "kind": "delay", "value": 1.0}]}
        p = tmp_path / "plan.json"
        p.write_text(json.dumps(doc))
        plan = FaultPlan.from_json(str(p))
        assert plan.seed == 7 and plan.budget["min_goodput_ratio"] == 0.5
        assert plan.to_dict()["faults"][0]["point"] == "p"

    def test_bad_specs_rejected(self):
        with pytest.raises(ValueError):
            FaultPlan([{"point": "p", "kind": "nope"}])
        with pytest.raises(ValueError):
            FaultPlan([{"point": "p", "error": "nope"}])
        with pytest.raises(ValueError):
            FaultPlan([{"point": "p", "at": 0}])

    def test_corrupt_file_flips_bytes_deterministically(self, tmp_path):
        p = tmp_path / "blob.bin"
        p.write_bytes(bytes(range(256)) * 16)
        orig = p.read_bytes()
        n1 = corrupt_file(str(p), seed=3)
        first = p.read_bytes()
        assert n1 >= 1 and first != orig
        p.write_bytes(orig)
        corrupt_file(str(p), seed=3)
        assert p.read_bytes() == first  # same seed = same flips


# ---------------------------------------------------------------------------
# health.py units
# ---------------------------------------------------------------------------

def _bcfg(**kw):
    base = dict(failure_threshold=3, dispatch_deadline_s=0.0,
                backoff_s=1.0, backoff_mult=2.0, backoff_max_s=8.0)
    base.update(kw)
    return BreakerConfig(**base)


class TestBreaker:
    def test_threshold_opens_and_success_resets(self):
        b = ReplicaBreaker(_bcfg())
        assert b.observe(False, 0.0, now=0.0) is None
        assert b.observe(True, 0.0, now=1.0) is None   # streak broken
        assert b.observe(False, 0.0, now=2.0) is None
        assert b.observe(False, 0.0, now=3.0) is None
        assert b.observe(False, 0.0, now=4.0) == "open"
        assert b.state == OPEN and b.opens == 1

    def test_deadline_counts_as_failure(self):
        b = ReplicaBreaker(_bcfg(dispatch_deadline_s=0.1,
                                 failure_threshold=2))
        b.observe(True, 0.5, now=0.0)   # ok=True but over deadline
        assert b.observe(True, 0.5, now=1.0) == "open"

    def test_backoff_probe_close_and_reopen_doubles(self):
        b = ReplicaBreaker(_bcfg(failure_threshold=1))
        assert b.observe(False, 0.0, now=10.0) == "open"
        assert not b.due_probe(10.5)           # backoff 1.0 not elapsed
        assert b.due_probe(11.1)               # -> HALF_OPEN
        assert b.state == HALF_OPEN
        assert not b.due_probe(99.0)           # one probe at a time
        assert b.probe_result(False, now=11.1) == "reopen"
        assert b.state == OPEN and b.backoff_s == 2.0
        assert b.due_probe(13.2)
        assert b.probe_result(True, now=13.2) == "close"
        assert b.state == CLOSED and b.backoff_s == 1.0 and b.closes == 1

    def test_backoff_caps(self):
        b = ReplicaBreaker(_bcfg(failure_threshold=1, backoff_max_s=3.0))
        b.observe(False, 0.0, now=0.0)
        for _ in range(5):
            b.state = HALF_OPEN
            b.probe_result(False, now=0.0)
        assert b.backoff_s == 3.0

    def test_held_ignores_observations_and_probes(self):
        b = ReplicaBreaker(_bcfg(failure_threshold=1))
        b.hold()
        assert b.observe(False, 0.0, now=0.0) is None
        assert b.state == HELD and not b.due_probe(100.0)
        b.reset()
        assert b.state == CLOSED

    def test_fleet_transitions_audit(self):
        h = FleetHealth(2, _bcfg(failure_threshold=1))
        assert h.observe(1, False, 0.0, now=0.0) == "open"
        assert h.due_probes(1.5) == [1]
        h.probe_result(1, True, now=1.5)
        assert h.transitions == ["1:open", "1:probe_close"]
        assert h.metrics()["breaker_opens"] == 1.0


# ---------------------------------------------------------------------------
# router self-healing (tiny engines, virtual clock)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def fleet_bits():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T

    mcfg = T.TransformerConfig(vocab_size=64, n_layers=2, n_heads=2,
                               d_model=32, max_seq=64, variant="llama",
                               use_flash=False)
    params = T.init(mcfg, jax.random.PRNGKey(0))

    def build():
        from deepspeed_tpu.inference import init_inference

        return init_inference(
            params, mcfg,
            dict(max_seq_len=48, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=4),
            dtype=jnp.float32)

    return build


class _VClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _mk_router(build, cfg_extra=None, n=2, seed=7):
    from deepspeed_tpu.inference import ServingRouter

    cfg = {"replicas": n, "policy": "prefix_aware",
           "health_enabled": True, "failure_threshold": 2,
           "breaker_backoff_s": 0.5,
           "scheduler": {"warmup": False}}
    cfg.update(cfg_extra or {})
    vc = _VClock()
    return ServingRouter([build() for _ in range(n)], cfg, seed=seed,
                         clock=vc), vc


def _drive(router, vc, max_sweeps=800, dt=0.01):
    n = 0
    while router.has_work and n < max_sweeps:
        router.step()
        vc.t += dt
        n += 1
    assert n < max_sweeps, "fleet did not drain"


class TestRouterSelfHealing:
    def _ref_outputs(self, build, prompts, seed=7):
        router, vc = _mk_router(build)
        gids = [router.submit(p, 8) for p in prompts]
        _drive(router, vc)
        return [list(router.result(g).output) for g in gids]

    def test_auto_failover_on_injected_death_token_identical(
            self, fleet_bits, rng):
        prompts = [list(rng.integers(0, 64, 12)) for _ in range(6)]
        ref = self._ref_outputs(fleet_bits, prompts)
        router, vc = _mk_router(fleet_bits)
        plan = FaultPlan([
            {"point": "scheduler.step", "kind": "raise",
             "error": "replica_dead", "where": {"replica": 1},
             "at": 3, "times": -1},
            {"point": "router.probe", "kind": "raise",
             "error": "replica_dead", "where": {"replica": 1},
             "times": -1}])
        with armed(plan):
            gids = [router.submit(p, 8) for p in prompts]
            _drive(router, vc)
        m = router.metrics()
        assert m["fleet/auto_failovers"] == 1.0
        assert m["fleet/live_replicas"] == 1.0
        assert m["fleet/breaker_opens"] == 1.0
        assert [list(router.result(g).output) for g in gids] == ref
        assert all(router.result(g).done for g in gids)
        # the event is audited as automatic
        assert router._failover_events[0]["auto"] is True

    def test_straggler_deadline_open_probe_restore(self, fleet_bits, rng):
        prompts = [list(rng.integers(0, 64, 12)) for _ in range(6)]
        ref = self._ref_outputs(fleet_bits, prompts)
        router, vc = _mk_router(
            fleet_bits, {"dispatch_deadline_s": 0.05,
                         "breaker_backoff_s": 0.3})
        plan = FaultPlan([
            {"point": "scheduler.step", "kind": "delay", "value": 0.2,
             "where": {"replica": 1}, "at": 2, "times": 4}])
        with armed(plan):
            gids = [router.submit(p, 8) for p in prompts]
            n = 0
            while (router.has_work or router.dead) and n < 2000:
                router.step()
                vc.t += 0.01
                n += 1
        m = router.metrics()
        assert m["fleet/breaker_opens"] >= 1.0
        assert m["fleet/replica_restores"] >= 1.0
        assert not router.dead                 # straggler rejoined
        assert m["replica1/health_state"] == 0.0   # CLOSED
        assert m["fleet/recovery_p50_ms"] > 0.0
        assert [list(router.result(g).output) for g in gids] == ref

    def test_manual_fail_holds_breaker_until_restore(self, fleet_bits):
        router, vc = _mk_router(fleet_bits)
        router.fail_replica(1)
        assert router.health.state(1) == HELD
        vc.t += 100.0
        assert router.poll_health() == []      # held: never auto-probed
        assert 1 in router.dead
        router.restore_replica(1)
        assert 1 not in router.dead
        assert router.health.state(1) == CLOSED
        assert router.counters["replica_restores"] == 1

    def test_health_disabled_propagates_step_errors(self, fleet_bits):
        router, _ = _mk_router(fleet_bits, {"health_enabled": False})
        plan = FaultPlan([{"point": "scheduler.step", "times": -1,
                           "error": "replica_dead"}])
        router.submit([1, 2, 3], 4)
        with armed(plan):
            with pytest.raises(ReplicaDeadError):
                router.step()


class TestHandoffGuards:
    def _disagg(self, build, extra=None):
        return _mk_router(build, dict(
            {"mode": "disaggregated", "prefill_replicas": 1,
             "failure_threshold": 3}, **(extra or {})), n=2)

    def test_export_failure_falls_back_token_identical(
            self, fleet_bits, rng):
        prompts = [list(rng.integers(0, 64, 12)) for _ in range(4)]
        router, vc = self._disagg(fleet_bits)
        gids = [router.submit(p, 8) for p in prompts]
        _drive(router, vc)
        ref = [list(router.result(g).output) for g in gids]

        router2, vc2 = self._disagg(fleet_bits)
        plan = FaultPlan([
            {"point": "engine.export_kv", "kind": "raise",
             "error": "handoff", "at": 1, "times": 2}])
        with armed(plan):
            gids2 = [router2.submit(p, 8) for p in prompts]
            _drive(router2, vc2)
        assert router2.counters["handoff_fallbacks"] >= 2
        assert [list(router2.result(g).output) for g in gids2] == ref
        # no page leak on the prefill engine after the failed exports
        assert not router2.schedulers[0].engine.state.tracked_uids

    def test_import_failure_falls_back_token_identical(
            self, fleet_bits, rng):
        prompts = [list(rng.integers(0, 64, 12)) for _ in range(4)]
        router, vc = self._disagg(fleet_bits)
        gids = [router.submit(p, 8) for p in prompts]
        _drive(router, vc)
        ref = [list(router.result(g).output) for g in gids]

        router2, vc2 = self._disagg(fleet_bits)
        plan = FaultPlan([
            {"point": "engine.import_kv", "kind": "raise",
             "error": "handoff", "at": 1, "times": 2}])
        with armed(plan):
            gids2 = [router2.submit(p, 8) for p in prompts]
            _drive(router2, vc2)
        assert router2.counters["handoff_fallbacks"] >= 2
        assert [list(router2.result(g).output) for g in gids2] == ref

    def test_export_timeout_falls_back(self, fleet_bits, rng):
        prompts = [list(rng.integers(0, 64, 10)) for _ in range(2)]
        # wall-clock limits: a sound export takes milliseconds, and took
        # 11 ms once under six busy workers, so the limit stands well
        # above that and the injected delay well above the limit
        router, vc = self._disagg(
            fleet_bits, {"handoff_timeout_s": 0.25})
        plan = FaultPlan([
            {"point": "engine.export_kv", "kind": "delay",
             "value": 0.6, "at": 1, "times": 1}])
        with armed(plan):
            gids = [router.submit(p, 6) for p in prompts]
            _drive(router, vc)
        assert router.counters["handoff_timeouts"] == 1
        assert router.counters["handoff_fallbacks"] >= 1
        assert all(router.result(g).done for g in gids)


class TestOverloadShed:
    def test_fair_shed_evicts_heaviest_session(self, fleet_bits):
        from deepspeed_tpu.inference import RequestShedError

        router, _ = _mk_router(
            fleet_bits, {"max_fleet_queue": 4, "scheduler": {
                "warmup": False}})
        # fill the queue: session A holds 3 waiting, session B holds 1
        # (nothing is stepped, so everything stays waiting)
        a = [router.submit([1, 2, 3], 4, session="A") for _ in range(3)]
        router.submit([1, 2, 3], 4, session="B")
        # C submits at the bound: A (heaviest) loses its NEWEST request
        gid_c = router.submit([4, 5, 6], 4, session="C")
        shed = router.result(a[-1])
        assert shed.done and shed.finish_reason == "shed"
        assert shed.output == []
        assert router.counters["shed_requests"] == 1
        assert not router.result(gid_c).done
        # B (1 waiting) submits again while A still ties for heaviest:
        # still admitted at B's expense? no — A has 2 > B's 2 after one
        # more B submit ties; the tie goes against the SUBMITTER
        router.submit([7, 8], 4, session="B")
        with pytest.raises(RequestShedError):
            router.submit([9, 9], 4, session="B")

    def test_sessionless_submit_at_bound_is_rejected(self, fleet_bits):
        from deepspeed_tpu.inference import RequestShedError

        router, _ = _mk_router(fleet_bits, {"max_fleet_queue": 2})
        router.submit([1, 2], 4, session="A")
        router.submit([1, 2], 4, session="A")
        with pytest.raises(RequestShedError):
            router.submit([3, 4], 4)
        assert router.counters["shed_requests"] == 1

    def test_reject_policy_never_evicts(self, fleet_bits):
        from deepspeed_tpu.inference import RequestShedError

        router, _ = _mk_router(
            fleet_bits, {"max_fleet_queue": 2, "shed_policy": "reject"})
        router.submit([1, 2], 4, session="A")
        router.submit([1, 2], 4, session="A")
        with pytest.raises(RequestShedError):
            router.submit([3, 4], 4, session="B")
        assert sum(len(s.waiting) for s in router.schedulers) == 2

    def test_under_bound_no_shed(self, fleet_bits, rng):
        router, vc = _mk_router(fleet_bits, {"max_fleet_queue": 64})
        gids = [router.submit(list(rng.integers(0, 64, 8)), 4,
                              session=i % 2) for i in range(6)]
        _drive(router, vc)
        assert router.counters["shed_requests"] == 0
        assert all(router.result(g).done for g in gids)


# ---------------------------------------------------------------------------
# checkpoint commit protocol (runtime/checkpoint.py)
# ---------------------------------------------------------------------------

def _state():
    return {"w": np.arange(64, dtype=np.float32),
            "b": np.ones((8,), np.float32)}


def _largest_state_file(tag_dir):
    files = [os.path.join(r, n)
             for r, _, ns in os.walk(os.path.join(tag_dir, "state"))
             for n in ns]
    return max(files, key=os.path.getsize)


class TestCheckpointCommitProtocol:
    def test_sync_save_is_verified_and_loads(self, tmp_path):
        from deepspeed_tpu.runtime.checkpoint import (
            CheckpointEngine, verify_tag)

        eng = CheckpointEngine()
        eng.save(str(tmp_path), "t1", _state(), {"step": 1})
        ok, why = verify_tag(str(tmp_path), "t1")
        assert ok, why
        state, meta, tag = eng.load(str(tmp_path), None, _state())
        assert tag == "t1" and meta == {"step": 1}
        np.testing.assert_array_equal(state["w"], _state()["w"])

    def test_async_crash_window_regression(self, tmp_path):
        """The PR-7 satellite bugfix: pre-hardening, async save wrote
        meta.json BEFORE the background orbax commit — a crash in that
        window left a tag that looked complete. Now the commit
        sequence (meta/manifest/COMMITTED/latest) is deferred to
        wait(); an injected crash there leaves INCOMPLETE residue,
        'latest' still on the previous tag, and resume falls back."""
        from deepspeed_tpu.runtime.checkpoint import (
            CheckpointEngine, verify_tag)

        eng = CheckpointEngine(async_save=True)
        eng.save(str(tmp_path), "t1", _state(), {"step": 1})
        eng.wait()
        plan = FaultPlan([
            {"point": "checkpoint.commit", "kind": "raise",
             "error": "ckpt_crash", "where": {"tag": "t2"}}])
        with armed(plan):
            with pytest.raises(CheckpointCrashError):
                eng.save(str(tmp_path), "t2", _state(), {"step": 2})
                eng.wait()
        # the window is detectable, latest never moved, meta absent
        assert (tmp_path / "latest").read_text() == "t1"
        assert (tmp_path / "t2" / "INCOMPLETE").exists()
        assert not (tmp_path / "t2" / "meta.json").exists()
        ok, why = verify_tag(str(tmp_path), "t2")
        assert not ok and "uncommitted" in why
        state, meta, tag = eng.load(str(tmp_path), None, _state())
        assert tag == "t1" and meta["step"] == 1

    def test_corrupt_latest_falls_back_to_verified(self, tmp_path):
        from deepspeed_tpu.runtime.checkpoint import (
            CheckpointCorruptError, CheckpointEngine, verify_tag)

        eng = CheckpointEngine()
        eng.save(str(tmp_path), "t1", _state(), {"step": 1})
        eng.save(str(tmp_path), "t2", _state(), {"step": 2})
        corrupt_file(_largest_state_file(str(tmp_path / "t2")))
        ok, why = verify_tag(str(tmp_path), "t2")
        assert not ok and "mismatch" in why
        state, meta, tag = eng.load(str(tmp_path), None, _state())
        assert tag == "t1" and meta["step"] == 1
        # the explicit bad tag is the caller's choice: it raises
        with pytest.raises(CheckpointCorruptError):
            eng.load(str(tmp_path), "t2", _state())

    def test_injected_corruption_fault_detected(self, tmp_path):
        from deepspeed_tpu.runtime.checkpoint import (
            CheckpointEngine, verify_tag)

        eng = CheckpointEngine()
        eng.save(str(tmp_path), "t1", _state(), {"step": 1})
        plan = FaultPlan([
            {"point": "checkpoint.corrupt", "kind": "corrupt",
             "where": {"tag": "t2"}}])
        with armed(plan):
            eng.save(str(tmp_path), "t2", _state(), {"step": 2})
        ok, why = verify_tag(str(tmp_path), "t2")
        assert not ok, "injected bitrot must fail verification"
        _, meta, tag = eng.load(str(tmp_path), None, _state())
        assert tag == "t1"

    def test_no_verified_fallback_raises(self, tmp_path):
        from deepspeed_tpu.runtime.checkpoint import (
            CheckpointCorruptError, CheckpointEngine)

        eng = CheckpointEngine()
        eng.save(str(tmp_path), "t1", _state(), {"step": 1})
        corrupt_file(_largest_state_file(str(tmp_path / "t1")))
        with pytest.raises(CheckpointCorruptError):
            eng.load(str(tmp_path), None, _state())

    def test_save_retry_heals_transient_io(self, tmp_path):
        from deepspeed_tpu.runtime.checkpoint import (
            CheckpointEngine, verify_tag)

        eng = CheckpointEngine(retry_backoff_s=0.001)
        plan = FaultPlan([
            {"point": "checkpoint.save", "kind": "raise",
             "error": "io", "times": 2}])
        with armed(plan) as p:
            eng.save(str(tmp_path), "t1", _state(), {"step": 1})
        assert len(p.fired) == 2
        assert verify_tag(str(tmp_path), "t1")[0]

    def test_save_retry_budget_surfaces_persistent_io(self, tmp_path):
        from deepspeed_tpu.runtime.checkpoint import CheckpointEngine

        eng = CheckpointEngine(save_retries=2, retry_backoff_s=0.001)
        plan = FaultPlan([
            {"point": "checkpoint.save", "kind": "raise",
             "error": "io", "times": -1}])
        with armed(plan):
            with pytest.raises(InjectedIOError):
                eng.save(str(tmp_path), "t1", _state(), {"step": 1})

    def test_legacy_tag_accepted(self, tmp_path):
        from deepspeed_tpu.runtime.checkpoint import verify_tag

        (tmp_path / "old" / "state").mkdir(parents=True)
        (tmp_path / "old" / "meta.json").write_text("{}")
        ok, why = verify_tag(str(tmp_path), "old")
        assert ok and "legacy" in why

    def test_tiered_fast_tier_corruption_falls_to_durable(self, tmp_path):
        from deepspeed_tpu.runtime.checkpoint import TieredCheckpointEngine

        fast, durable = tmp_path / "fast", tmp_path / "durable"
        eng = TieredCheckpointEngine(
            persistent_storage_path=str(durable),
            persistent_time_interval=0.0, async_save=False)
        eng.save(str(fast), "t1", _state(), {"step": 1})
        corrupt_file(_largest_state_file(str(fast / "t1")))
        state, meta, tag = eng.load(str(fast), None, _state())
        assert tag == "t1" and meta["step"] == 1  # served by durable
        np.testing.assert_array_equal(state["w"], _state()["w"])


# ---------------------------------------------------------------------------
# offload store I/O retry (inference/offload_store.py)
# ---------------------------------------------------------------------------

class TestOffloadIORetry:
    def _store(self, tmp_path, **kw):
        from deepspeed_tpu.inference.offload_store import NvmeLayerStore

        store = NvmeLayerStore(str(tmp_path), 2, n_threads=1,
                               retry_backoff_s=0.001, **kw)
        layers = []
        rng = np.random.default_rng(0)
        for l in range(2):
            lp = {"w": rng.normal(size=(4, 8)).astype(np.float32)}
            store.stage_layer(l, lp)
            layers.append(lp)
        store.finish_staging()
        return store, layers

    def test_transient_read_error_heals(self, tmp_path):
        store, layers = self._store(tmp_path)
        plan = FaultPlan([
            {"point": "offload.io", "kind": "raise", "error": "io",
             "times": 2}])
        try:
            with armed(plan) as p:
                got = store.read_layer(0)
            np.testing.assert_array_equal(got["w"], layers[0]["w"])
            assert len(p.fired) == 2  # healed within the retry budget
        finally:
            store.close()

    def test_persistent_read_error_surfaces(self, tmp_path):
        store, _ = self._store(tmp_path, io_retries=2)
        plan = FaultPlan([
            {"point": "offload.io", "kind": "raise", "error": "io",
             "times": -1}])
        try:
            with armed(plan):
                with pytest.raises(InjectedIOError):
                    store.read_layer(0)
        finally:
            disarm()
            store.close()

    def test_close_drain_logs_but_releases(self, tmp_path):
        store, _ = self._store(tmp_path)
        store._submit(0)  # leave an in-flight read for the drain
        plan = FaultPlan([
            {"point": "offload.io", "kind": "raise", "error": "io",
             "times": -1}])
        with armed(plan):
            store.close()  # must not raise; terminal error is logged
        assert store.aio is None and not os.path.isdir(store.dir)


# ---------------------------------------------------------------------------
# elastic-agent boundary cases (elasticity/agent.py)
# ---------------------------------------------------------------------------

class TestElasticBoundaries:
    def test_staleness_exactly_at_threshold_not_stale(self):
        """`now - last_change > timeout` is STRICT: a beat observed
        exactly timeout seconds ago is still healthy — detection
        latency is bounded by timeout + scan interval, never less."""
        from deepspeed_tpu.elasticity.agent import StalenessTracker

        tr = StalenessTracker(timeout_s=2.0)
        hb = {1: {"step": 5, "time": 100.0}}
        assert tr.observe(hb, now=0.0) == []
        assert tr.observe(hb, now=2.0) == []      # == threshold: fresh
        assert tr.observe(hb, now=2.0001) == [1]  # past it: stale
        # content change resets the staleness clock
        hb2 = {1: {"step": 6, "time": 101.0}}
        assert tr.observe(hb2, now=3.0) == []
        assert tr.observe(hb2, now=5.0) == []
        assert tr.observe(hb2, now=5.1) == [1]

    def test_heartbeat_stall_fault_detected_by_tracker(self, tmp_path):
        from deepspeed_tpu.elasticity import Heartbeat, scan_heartbeats
        from deepspeed_tpu.elasticity.agent import StalenessTracker

        hb = Heartbeat(str(tmp_path), rank=0)
        tr = StalenessTracker(timeout_s=0.5)
        hb.beat(1)
        tr.observe(scan_heartbeats(str(tmp_path), 1), now=0.0)
        plan = FaultPlan([{"point": "heartbeat.beat", "kind": "skip",
                           "where": {"rank": 0}, "times": -1}])
        with armed(plan):
            hb.beat(2)  # suppressed: the wedged-controller simulation
        got = scan_heartbeats(str(tmp_path), 1)
        assert got[0]["step"] == 1  # the stalled beat never landed
        assert tr.observe(got, now=1.0) == [0]

    def test_monitor_flip_during_inflight_async_save(self, tmp_path):
        """A peer dies while an async checkpoint is committing: the
        step loop's check() raises BEFORE the next collective, and the
        in-flight save still commits to a verified tag on teardown —
        the survivor's exit leaves a resumable checkpoint."""
        from deepspeed_tpu.elasticity import (
            HealthMonitor, Heartbeat, WorldDegradedError)
        from deepspeed_tpu.runtime.checkpoint import (
            CheckpointEngine, verify_tag)

        hb_dir = tmp_path / "hb"
        ckpt_dir = tmp_path / "ckpt"
        Heartbeat(str(hb_dir), 0).beat(1)
        Heartbeat(str(hb_dir), 1).beat(1)
        mon = HealthMonitor(str(hb_dir), rank=0, world=2, timeout_s=0.2,
                            interval_s=0.02).start()
        eng = CheckpointEngine(async_save=True)
        try:
            eng.save(str(ckpt_dir), "step3", _state(), {"step": 3})
            # commit in flight; peer 1 goes silent
            deadline = time.time() + 5
            while not mon.degraded and time.time() < deadline:
                time.sleep(0.02)
            assert mon.failed_ranks == [1]
            with pytest.raises(WorldDegradedError):
                mon.check()
        finally:
            mon.stop()
        eng.wait()  # the clean-exit path finalizes the save
        ok, why = verify_tag(str(ckpt_dir), "step3")
        assert ok, why
        _, meta, tag = eng.load(str(ckpt_dir), None, _state())
        assert tag == "step3" and meta["step"] == 3

    def test_supervisor_generation_bump_on_consecutive_restarts(
            self, tmp_path, capsys):
        """Two consecutive failures: the supervisor bumps the
        generation each relaunch (workers see DS_ELASTIC_GENERATION
        0,1,2) and shrinks the world by one per failure."""
        from deepspeed_tpu.elasticity import run_elastic

        probe = tmp_path / "probe.py"
        probe.write_text(
            "import os, sys\n"
            "print('GEN', os.environ['DS_ELASTIC_GENERATION'],\n"
            "      'WORLD', os.environ['WORLD_SIZE'], flush=True)\n"
            "sys.exit(9)\n")
        rc = run_elastic(
            [sys.executable, str(probe)], num_procs=3,
            heartbeat_dir=str(tmp_path / "hb"),
            resume_dir=str(tmp_path),
            first_beat_timeout_s=0, max_restarts=2, min_procs=1)
        cap = capsys.readouterr()
        assert rc == 9
        gens = [l for l in cap.out.splitlines() if "GEN" in l]
        assert any("GEN 0 WORLD 3" in l for l in gens)
        assert any("GEN 1 WORLD 2" in l for l in gens)
        assert any("GEN 2 WORLD 1" in l for l in gens)
        assert "restarting at world=2 (generation 1" in cap.err
        assert "restarting at world=1 (generation 2" in cap.err
        assert "giving up after 3 generations" in cap.err
