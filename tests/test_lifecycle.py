"""Lifecycle analyzer (L-series), the ds_lifecycle gate CLI, and the
leak-family regression tests for the fixes the analyzer drove: spill
payloads released on every router re-route path (shed / failover /
drain / rebalance), host-tier drain at replica retirement, counted
look-ahead fallbacks, and the quiesce-residual audit the bench
serving/chaos/overload lanes gate on (docs/lifecycle.md)."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from deepspeed_tpu.analysis import lifecycle as L
from deepspeed_tpu.analysis.lint import lint_source

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _l001(src, rel="deepspeed_tpu/inference/fixture.py"):
    findings, _ = L.l001_findings([(rel, src)])
    return findings


# ---------------------------------------------------------------------------
# L001: exception-path resource leaks
# ---------------------------------------------------------------------------

class TestL001:
    def test_unprotected_allocate_on_raising_path_fires_once(self):
        f = _l001('''
class S:
    def grab(self, uid):
        blk = self.allocator.allocate()
        self.state.extend(uid, 1)
        self.table[uid] = blk
''')
        assert len(f) == 1
        assert f[0].rule == "L001" and "kv-block" in f[0].message

    def test_try_finally_release_is_protected(self):
        assert _l001('''
class S:
    def grab(self, uid):
        blk = self.allocator.allocate()
        try:
            self.state.extend(uid, 1)
        finally:
            self.allocator.free(blk)
        self.table[uid] = blk
''') == []

    def test_except_handler_release_is_protected(self):
        assert _l001('''
class S:
    def grab(self, uid):
        blk = self.allocator.allocate()
        try:
            self.state.extend(uid, 1)
        except KVCacheExhaustedError:
            self.allocator.free(blk)
            raise
        self.table[uid] = blk
''') == []

    def test_transfer_before_raise_is_safe(self):
        # ownership stored into a field before the raising call: the
        # container owns it now, a raise strands nothing
        assert _l001('''
class S:
    def grab(self, uid):
        blk = self.allocator.allocate()
        self.table[uid] = blk
        self.state.extend(uid, 1)
''') == []

    def test_transfer_via_adopting_call_is_safe(self):
        assert _l001('''
class S:
    def grab(self, uid):
        blk = self.allocator.allocate()
        self.rollback.append(blk)
        self.state.extend(uid, 1)
''') == []

    def test_interprocedural_release_summary(self):
        # the helper releases its parameter, so handing the resource
        # to it counts as a transfer — the call-graph edge
        assert _l001('''
def _undo(alloc, blk):
    alloc.free(blk)


class S:
    def grab(self, uid):
        blk = self.allocator.allocate()
        _undo(self.allocator, blk)
        self.state.extend(uid, 1)
''') == []

    def test_import_kv_reservation_leak_fires(self):
        f = _l001('''
class S:
    def adopt_seq(self, uid, payload):
        self.engine.import_kv(uid, payload)
        self.engine.export_kv(uid)
''')
        assert len(f) == 1 and "kv-sequence" in f[0].message

    def test_return_is_ownership_transfer(self):
        assert _l001('''
class S:
    def grab(self, uid):
        blk = self.allocator.allocate()
        return blk
''') == []

    def test_pragma_suppresses(self):
        src = '''
class S:
    def grab(self, uid):
        blk = self.allocator.allocate()
        self.state.extend(uid, 1)  # ds-lint: ok L001 intentional
        self.table[uid] = blk
'''
        rep = L.analyze_sources([("deepspeed_tpu/inference/x.py", src)])
        assert rep.findings == []
        assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# L002: pool-accounting invariants
# ---------------------------------------------------------------------------

class TestL002:
    def test_undeclared_counter_key_fires_once(self):
        f, auth = L.l002_findings([("x.py", '''
class S:
    def __init__(self):
        self.counters = {"hits": 0}

    def poke(self):
        self.counters["oops"] += 1
''')])
        assert len(f) == 1 and "oops" in f[0].message
        assert auth["x.py::S"] == ["hits"]

    def test_declared_mutation_is_silent(self):
        f, _ = L.l002_findings([("x.py", '''
class S:
    def __init__(self):
        self.counters = {"hits": 0}

    def poke(self):
        self.counters["hits"] += 1
''')])
        assert f == []

    def test_external_accounting_write_fires(self):
        f, _ = L.l002_findings([("x.py", '''
class Other:
    def hack(self, store):
        store.used_bytes = 0
''')])
        assert len(f) == 1 and "used_bytes" in f[0].message

    def test_self_accounting_write_is_silent(self):
        f, _ = L.l002_findings([("x.py", '''
class Store:
    def reset(self):
        self.used_bytes = 0
''')])
        assert f == []


# ---------------------------------------------------------------------------
# L003: fault-coverage audit
# ---------------------------------------------------------------------------

class TestL003:
    def test_uncovered_registered_point_fires(self):
        f, cov = L.l003_findings(
            {"a.b": {}}, {}, {"a.b": [("x.py", 1)]})
        assert len(f) == 1 and "ZERO committed" in f[0].message
        assert cov == {"a.b": []}

    def test_covered_point_is_silent(self):
        f, cov = L.l003_findings(
            {"a.b": {}}, {"PLAN.json": {"a.b": {0}}},
            {"a.b": [("x.py", 1)]})
        assert f == []
        assert cov == {"a.b": ["PLAN.json"]}

    def test_registered_point_with_no_call_site_fires(self):
        f, _ = L.l003_findings(
            {"a.b": {}}, {"PLAN.json": {"a.b": {0}}}, {})
        assert len(f) == 1 and "no" in f[0].message.lower()

    def test_unregistered_point_in_committed_plan_fires(self):
        f, _ = L.l003_findings(
            {}, {"PLAN.json": {"typo.point": {3}}}, {})
        assert len(f) == 1 and "typo.point" in f[0].message

    def test_unregistered_point_in_unit_test_lane_is_ok(self):
        # tests may arm synthetic points for harness unit coverage
        f, _ = L.l003_findings(
            {}, {"tests/test_x.py": {"synthetic.p": {3}}}, {})
        assert f == []

    def test_unregistered_call_site_fires(self):
        f, _ = L.l003_findings({}, {}, {"ghost.p": [("m.py", 7)]})
        assert len(f) == 1 and "ghost.p" in f[0].message

    def test_isolated_hot_mutator_component_fires(self):
        f = L.l003_component_findings([("x.py", '''
class Q:
    def pump_backlog(self):
        self.q.pop()
''')])
        assert len(f) == 1 and "NO fault point" in f[0].message

    def test_component_with_fault_point_is_silent(self):
        assert L.l003_component_findings([("x.py", '''
class Q:
    def pump_backlog(self):
        fault_point("q.pump")
        self.q.pop()
''')]) == []

    def test_nested_closure_calls_join_the_component(self):
        # the engine._sample_fn shape: the hot method is invoked only
        # from a nested closure of a method that carries a fault point
        assert L.l003_component_findings([("x.py", '''
class E:
    def put(self, req):
        fault_point("e.put")

        def sample_rows(rows):
            return self._sample_fn(rows)
        return sample_rows([req])

    def _sample_fn(self, rows):
        return rows
''')]) == []


# ---------------------------------------------------------------------------
# L004: swallowed typed failures (+ the ds-lint R009 shim)
# ---------------------------------------------------------------------------

class TestL004:
    def test_swallowing_broad_except_fires_once(self):
        f = L.l004_findings([("x.py", '''
class S:
    def pull(self, uid):
        try:
            self.engine.import_kv(uid, None)
        except Exception:
            return None
''')])
        assert len(f) == 1 and "import_kv" in f[0].message

    def test_counted_absorb_is_silent(self):
        assert L.l004_findings([("x.py", '''
class S:
    def pull(self, uid):
        try:
            self.engine.import_kv(uid, None)
        except Exception:
            self.counters["import_failures"] += 1
            return None
''')]) == []

    def test_logged_absorb_is_silent(self):
        assert L.l004_findings([("x.py", '''
class S:
    def pull(self, uid):
        try:
            self.engine.import_kv(uid, None)
        except Exception as e:
            log_dist(f"import failed: {e}")
            return None
''')]) == []

    def test_reraise_is_silent(self):
        assert L.l004_findings([("x.py", '''
class S:
    def pull(self, uid):
        try:
            self.engine.import_kv(uid, None)
        except Exception:
            self.rollback()
            raise
''')]) == []

    def test_narrow_typed_except_is_silent(self):
        assert L.l004_findings([("x.py", '''
class S:
    def pull(self, uid):
        try:
            self.engine.import_kv(uid, None)
        except KVCacheExhaustedError:
            return None
''')]) == []

    def test_del_is_exempt(self):
        assert L.l004_findings([("x.py", '''
class S:
    def __del__(self):
        try:
            self.store.drain()
        except Exception:
            pass
''')]) == []

    R009_SRC = '''
class P:
    def tick(self):
        try:
            self.engine.export_kv(0)
        except Exception:
            return None
'''

    def test_r009_shim_fires_on_hot_nonroot_file(self):
        findings, _ = lint_source(
            self.R009_SRC, "deepspeed_tpu/runtime/pipe.py")
        r9 = [f for f in findings if f.rule == "R009"]
        assert len(r9) == 1 and r9[0].severity == "warning"

    def test_r009_skips_lifecycle_roots(self):
        # scheduler.py is a lifecycle root: the gate audits it at
        # error level, the lint shim must not double-report
        findings, _ = lint_source(
            self.R009_SRC, "deepspeed_tpu/inference/scheduler.py")
        assert [f for f in findings if f.rule == "R009"] == []

    def test_r009_accepts_l004_pragma_spelling(self):
        src = self.R009_SRC.replace(
            "except Exception:",
            "except Exception:  # ds-lint: ok L004 teardown")
        findings, suppressed = lint_source(
            src, "deepspeed_tpu/runtime/pipe.py")
        assert [f for f in findings if f.rule == "R009"] == []
        assert [f for f in suppressed if f.rule == "R009"]


# ---------------------------------------------------------------------------
# the real tree is clean, coverage is total
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree_report():
    return L.analyze_tree(_REPO)


class TestRealTree:
    def test_tree_has_zero_active_findings(self, tree_report):
        rep = tree_report
        assert rep.ok, "\n".join(f.render() for f in rep.findings)

    def test_every_registered_point_is_covered(self, tree_report):
        rep = tree_report
        uncovered = [p for p, lanes in rep.coverage.items() if not lanes]
        assert uncovered == []
        assert len(rep.coverage) >= 21

    def test_every_registered_point_has_a_call_site(self):
        registry, _ = L.load_registry(_REPO)
        sites = L.scan_call_sites(_REPO)
        assert sorted(registry) == sorted(
            p for p in registry if p in sites)

    def test_registry_helpers_single_authority(self):
        from deepspeed_tpu.resilience.faults import (
            FAULT_POINTS, registered_points, registry_markdown_table)
        assert registered_points() == tuple(sorted(FAULT_POINTS))
        table = registry_markdown_table()
        for p in FAULT_POINTS:
            assert f"`{p}`" in table

    def test_docs_registry_table_renders_from_the_constant(self):
        from deepspeed_tpu.resilience.faults import (
            registry_markdown_table)
        doc = open(os.path.join(_REPO, "docs",
                                "fault_tolerance.md")).read()
        assert registry_markdown_table() in doc, (
            "docs/fault_tolerance.md registry table drifted from "
            "faults.FAULT_POINTS — regenerate it with "
            "registry_markdown_table()")


# ---------------------------------------------------------------------------
# gate CLI roundtrip
# ---------------------------------------------------------------------------

GATE = os.path.join(_REPO, "scripts", "ds_gate.py")


def _gate(*args):
    return subprocess.run(
        [sys.executable, GATE, "lifecycle", *args], capture_output=True,
        text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"})


@pytest.mark.slow
class TestGateCLI:
    def test_check_against_committed_ledger_is_green(self):
        r = _gate("--check", "--strict")
        assert r.returncode == 0, r.stderr
        assert '"ok": true' in r.stderr

    def test_capture_is_byte_stable_and_matches_committed(self, tmp_path):
        b1 = tmp_path / "a.json"
        b2 = tmp_path / "b.json"
        assert _gate("--capture", "--baseline", str(b1)).returncode == 0
        assert _gate("--capture", "--baseline", str(b2)).returncode == 0
        assert b1.read_bytes() == b2.read_bytes()
        committed = open(os.path.join(_REPO, "LIFECYCLE.json"),
                         "rb").read()
        assert b1.read_bytes() == committed

    def test_partial_capture_refused(self, tmp_path):
        b = tmp_path / "partial.json"
        r = _gate("--rules", "L003", "--capture", "--baseline", str(b))
        assert r.returncode == 1
        assert "refusing to capture a partial ledger" in r.stderr
        assert not b.exists()

    def test_suppression_drift_warns_then_strict_fails(self, tmp_path):
        committed = json.load(open(os.path.join(_REPO,
                                                "LIFECYCLE.json")))
        committed["ledger"]["suppressions"].append(
            "deepspeed_tpu/inference/scheduler.py::ServingScheduler.step L001")
        b = tmp_path / "drift.json"
        b.write_text(json.dumps(committed))
        r = _gate("--check", "--baseline", str(b))
        assert r.returncode == 0
        assert "suppression drift" in r.stderr
        r = _gate("--check", "--strict", "--baseline", str(b))
        assert r.returncode == 1

    def test_ledger_drift_fails_even_non_strict(self, tmp_path):
        committed = json.load(open(os.path.join(_REPO,
                                                "LIFECYCLE.json")))
        committed["ledger"]["authorities"].append("x.py::Nobody")
        b = tmp_path / "drift.json"
        b.write_text(json.dumps(committed))
        r = _gate("--check", "--baseline", str(b))
        assert r.returncode == 1
        assert "drift" in r.stderr

    def test_injected_leak_turns_a_tree_red(self, tmp_path):
        # a synthetic mini-repo with one leaky root: analyze_tree must
        # go red with NO baseline involved
        pkg = tmp_path / "deepspeed_tpu"
        (pkg / "inference").mkdir(parents=True)
        (pkg / "resilience").mkdir(parents=True)
        (pkg / "inference" / "scheduler.py").write_text('''
class S:
    def grab(self, uid):
        blk = self.allocator.allocate()
        self.state.extend(uid, 1)
        self.table[uid] = blk
''')
        (pkg / "resilience" / "faults.py").write_text(
            "FAULT_POINTS = {}\n")
        rep = L.analyze_tree(str(tmp_path))
        assert not rep.ok
        assert rep.by_rule().get("L001") == 1


# ---------------------------------------------------------------------------
# regression tests for the leak-family fixes (the L001/L004 true
# positives the analyzer drove in-tree)
# ---------------------------------------------------------------------------

PRESSURE = {"enabled": True, "yellow": 0.5, "red": 0.8,
            "brownout": 0.97, "spill_host_mb": 4.0}


@pytest.fixture(scope="module")
def model():
    import jax

    from deepspeed_tpu.models import transformer as T

    cfg = T.TransformerConfig(
        vocab_size=128, n_layers=2, n_heads=4, d_model=64, max_seq=128,
        variant="llama", use_flash=False)
    return cfg, T.init(cfg, jax.random.PRNGKey(0))


def _engine(model, **over):
    import jax.numpy as jnp

    from deepspeed_tpu.inference import init_inference

    cfg, params = model
    kw = dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
              min_prefill_bucket=8, max_batch_size=8)
    kw.update(over)
    return init_inference(params, cfg, kw, dtype=jnp.float32)


def _router(model, n=2, **cfg_over):
    from deepspeed_tpu.inference import ServingRouter

    rcfg = {"replicas": n,
            "scheduler": {"warmup": False, "pressure": dict(PRESSURE)}}
    rcfg.update(cfg_over)
    return ServingRouter([_engine(model) for _ in range(n)], rcfg)


def _spill(sched, req):
    """Manufacture a host-tier spill payload owned by `req` (the
    preempt-to-spill postcondition, without staging real pressure)."""
    payload = {"seen_tokens": 3, "n_blocks": 1,
               "k": np.zeros((64,), np.float32),
               "v": np.zeros((64,), np.float32)}
    assert sched.spill_store.put(req.rid, payload)
    req.spill_key = req.rid
    assert sched.spill_store.used_bytes > 0


class TestSpillReleasedOnReroute:
    def test_release_spill_drops_payload_and_counts(self, model):
        from deepspeed_tpu.inference import (ServingScheduler,
                                             ServingSchedulerConfig)

        sched = ServingScheduler(
            _engine(model),
            ServingSchedulerConfig(warmup=False,
                                   pressure=dict(PRESSURE)))
        rid = sched.submit([1, 2, 3], 4)
        req = sched.waiting[0]
        _spill(sched, req)
        sched.release_spill(req)
        assert req.spill_key is None
        assert sched.spill_store.used_bytes == 0
        assert sched.counters["spill_releases"] == 1
        sched.release_spill(req)  # idempotent no-op
        assert sched.counters["spill_releases"] == 1

    def test_failover_releases_orphan_payloads(self, model):
        router = _router(model)
        gid = router.submit([1, 2, 3], 4)
        i = router._where[gid]
        s = router.schedulers[i]
        req = s.waiting[0]
        _spill(s, req)
        router.fail_replica(i)
        assert s.spill_store.used_bytes == 0
        assert s.counters["spill_releases"] == 1
        # the orphan requeued elsewhere with no dangling spill claim
        j = router._where[gid]
        assert j != i
        assert all(r.spill_key is None
                   for r in router.schedulers[j].waiting)

    def test_drain_releases_waiting_payloads(self, model):
        router = _router(model)
        gid = router.submit([1, 2, 3], 4)
        i = router._where[gid]
        s = router.schedulers[i]
        _spill(s, s.waiting[0])
        router.drain_replica(i)
        assert s.spill_store.used_bytes == 0
        assert s.counters["spill_releases"] == 1

    def test_shed_releases_victim_payload(self, model):
        router = _router(model)
        g1 = router.submit([1, 2, 3], 4, session="a")
        router.submit([4, 5, 6], 4, session="a")
        i = router._where[g1]
        s = router.schedulers[i]
        victim = s.waiting[-1]
        _spill(s, victim)
        router._shed_for_room("b", bound=1)
        assert victim.finish_reason == "shed"
        assert victim.spill_key is None
        assert s.spill_store.used_bytes == 0

    def test_rebalance_releases_donor_payload(self, model):
        router = _router(model)
        gids = [router.submit([1, 2, 3, k], 4) for k in range(6)]
        donors = {router._where[g] for g in gids}
        i = donors.pop()
        s = router.schedulers[i]
        # park everything on one replica's queue for a clear donor
        for j, sj in enumerate(router.schedulers):
            if j != i:
                while sj.waiting:
                    s.waiting.append(sj.waiting.pop())
        _spill(s, s.waiting[-1])
        target = 1 - i
        router.schedulers[target].waiting.clear()
        moved = router._rebalance_to(target)
        assert moved >= 1
        assert s.spill_store.used_bytes == 0
        assert s.counters["spill_releases"] == 1

    def test_restore_drains_stale_tier(self, model):
        router = _router(model)
        gid = router.submit([1, 2, 3], 4)
        i = router._where[gid]
        s = router.schedulers[i]
        router.fail_replica(i)
        # stale bytes that survived failover (no owner will resume)
        payload = {"k": np.zeros((16,), np.float32)}
        s.spill_store.put(999, payload)
        router.restore_replica(i)
        assert s.spill_store.used_bytes == 0


class TestHostStoreDrain:
    def test_drain_counts_and_zeroes(self):
        from deepspeed_tpu.inference.offload_store import (
            HostKvSpillStore)

        store = HostKvSpillStore(4096)
        for k in range(3):
            assert store.put(k, {"k": np.zeros((8,), np.float32)})
        d0 = store.counters["discards"]
        assert store.drain() == 3
        assert store.used_bytes == 0
        assert store.stats()["spill_entries"] == 0
        assert store.counters["discards"] == d0 + 3
        assert store.drain() == 0


class TestLookaheadFallbackCounted:
    """A look-ahead dispatch whose decode row cannot reserve its KV
    room (pool pressure, or a row whose KV died under it) is not
    absorbed silently: the step is handed back to the normal order
    (readback, then dispatch, which may preempt) and counted (L004)."""

    def _sched_with_a_step_in_flight(self, model):
        from deepspeed_tpu.inference import (ServingScheduler,
                                             ServingSchedulerConfig)

        sched = ServingScheduler(
            _engine(model), ServingSchedulerConfig(warmup=False))
        sched.submit([1, 2, 3, 4, 5], 8)
        prev = sched._dispatch()
        assert sched._can_look_ahead(prev)
        return sched, prev

    @pytest.mark.parametrize("error", ["exhausted", "runtime"])
    def test_failed_reservation_falls_back_and_counts(self, model, error):
        from deepspeed_tpu.inference import KVCacheExhaustedError

        sched, prev = self._sched_with_a_step_in_flight(model)
        steps, extend = sched.counters["steps"], sched.engine.state.extend

        def boom(uid, n, **kw):
            if error == "exhausted":
                raise KVCacheExhaustedError("full")
            raise RuntimeError("row died under the look-ahead")

        sched.engine.state.extend = boom
        assert sched._dispatch(ahead_of=prev) is None
        assert sched.counters["lookahead_fallbacks"] == 1
        assert sched.counters["lookahead_steps"] == 0
        assert sched.counters["steps"] == steps  # the step was not run
        assert prev.settled == {}                # nothing fed ahead
        # the normal order picks it up: readback, then dispatch
        sched.engine.state.extend = extend
        sched._finalize(prev)
        assert sched._dispatch() is not None
        assert sched.counters["steps"] == steps + 1


class TestQuiesceResiduals:
    def _fake_sched(self, leaked=0, tracked=0, spill=0, backlog=0):
        alloc = types.SimpleNamespace(total_blocks=10,
                                      available_blocks=10 - leaked)
        state = types.SimpleNamespace(allocator=alloc,
                                      n_tracked=tracked)
        store = types.SimpleNamespace(
            stats=lambda: {"spill_used_bytes": spill,
                           "spill_entries": 1 if spill else 0})
        return types.SimpleNamespace(
            engine=types.SimpleNamespace(state=state),
            spill_store=store,
            waiting=[0] * backlog, active=[], handoff_ready=[])

    def test_clean_sched_has_no_residuals(self):
        assert L.quiesce_residuals(self._fake_sched()) == {}

    def test_each_residual_class_is_named(self):
        r = L.quiesce_residuals(self._fake_sched(
            leaked=2, tracked=1, spill=64, backlog=3))
        assert r == {"leaked_blocks": 2, "tracked_seqs": 1,
                     "spill_bytes": 64, "spill_entries": 1,
                     "backlog_waiting": 3}

    def test_fleet_skips_dead_replicas(self):
        router = types.SimpleNamespace(
            dead={0},
            schedulers=[self._fake_sched(leaked=5),
                        self._fake_sched()])
        assert L.fleet_quiesce_residuals(router) == {}
        router.dead = set()
        assert "replica0" in L.fleet_quiesce_residuals(router)

    @pytest.mark.slow  # the bench serving/chaos/overload exit gates
    # assert the same empty-residual postcondition on every tier-1 run
    def test_real_scheduler_quiesces_after_serving(self, model):
        from deepspeed_tpu.inference import (ServingScheduler,
                                             ServingSchedulerConfig)

        rng = np.random.default_rng(0)
        sched = ServingScheduler(
            _engine(model, num_kv_blocks=6),
            ServingSchedulerConfig(
                prefill_chunk=3, max_num_batched_tokens=8,
                warmup=False, pressure=dict(PRESSURE)))
        for n in (6, 9, 4):
            sched.submit(list(rng.integers(0, 128, n)), 8)
        sched.run()
        assert sched.counters["spills"] >= 0  # lane ran
        assert L.quiesce_residuals(sched) == {}
