"""Silent-data-corruption guardian (resilience/integrity.py,
docs/fault_tolerance.md SDC section): seeded dtype-aware bit flips,
blake2b integrity envelopes, the EMA z-score anomaly detector, the
digest-verified peer-mirror reconstruct, handoff payload verification,
and the ElasticTrainer guardian journey (veto -> verified-mirror
rollback -> bitwise-clean replay). The full multi-fault lane is gated
end-to-end by `bench.py --sdc-chaos` / scripts/ds_gate.py sdc (tier-1
pre-test gate); here the pieces are proven fast and in isolation.
"""

import json
import os

import numpy as np
import pytest

from deepspeed_tpu.resilience import (
    AnomalyDetector,
    FaultPlan,
    HandoffIntegrityError,
    PeerRedundantStore,
    PersistentAnomalyError,
    UnrecoverableWorldError,
    armed,
    corrupt_payload,
    corrupt_tree,
    fault_point,
    flip_bits,
    payload_digest,
    tree_digest,
)


# ---------------------------------------------------------------------------
# seeded dtype-aware bit flips
# ---------------------------------------------------------------------------

class TestFlipBits:
    def test_same_key_same_flips(self):
        a = np.linspace(1, 2, 16).astype(np.float32)
        c1, l1 = flip_bits(a, seed=7, invocation=3, path="p")
        c2, l2 = flip_bits(a, seed=7, invocation=3, path="p")
        np.testing.assert_array_equal(c1, c2)
        assert l1 == l2 and len(l1) == 1

    def test_different_invocation_or_path_differs(self):
        a = np.linspace(1, 2, 4096).astype(np.float32)
        c1, l1 = flip_bits(a, 7, 3, "p")
        c2, l2 = flip_bits(a, 7, 4, "p")
        c3, l3 = flip_bits(a, 7, 3, "q")
        assert l1 != l2 and l1 != l3  # (index, bit) draws diverge

    def test_original_untouched_and_dtype_preserved(self):
        a = np.ones((8,), np.float32)
        c, _ = flip_bits(a, 0, 1, "x")
        assert np.all(a == 1.0)
        assert c.dtype == a.dtype and not np.array_equal(a, c)

    def test_exponent_class_moves_orders_of_magnitude(self):
        a = np.full((4,), 1.5, np.float32)
        c, [(idx, bit)] = flip_bits(a, 1, 1, "g", bit_class="exponent")
        assert 23 <= bit <= 30  # f32 exponent field, sign excluded
        ratio = abs(float(c[idx])) / 1.5
        assert ratio > 2.0 or ratio < 0.5

    def test_bfloat16_flips_in_its_own_word(self):
        ml_dtypes = pytest.importorskip("ml_dtypes")
        a = np.ones((4,), ml_dtypes.bfloat16)
        c, [(idx, bit)] = flip_bits(a, 0, 1, "b")
        assert c.dtype == a.dtype and bit < 16
        assert float(np.asarray(c, np.float32)[idx]) != 1.0

    def test_corrupt_tree_flips_one_leaf_and_logs_path(self):
        t = {"w": np.arange(6, dtype=np.float32),
             "b": np.arange(3, dtype=np.float32)}
        d0 = tree_digest(t)
        ct, log = corrupt_tree(t, seed=1, invocation=1)
        assert tree_digest(t) == d0          # original untouched
        assert tree_digest(ct) != d0 and len(log) == 1
        assert "^bit" in log[0]

    def test_corrupt_payload_breaks_its_digest(self):
        p = {"seen_tokens": 5, "n_blocks": 1, "token_ids": [1, 2],
             "k": np.ones((2, 1, 4), np.float32),
             "v": np.zeros((2, 1, 4), np.float32)}
        p["digest"] = payload_digest(p)
        cp, log = corrupt_payload(p, seed=0, invocation=1)
        assert payload_digest(cp) != cp["digest"] and log
        assert payload_digest(p) == p["digest"]  # original untouched


# ---------------------------------------------------------------------------
# integrity envelopes
# ---------------------------------------------------------------------------

class TestDigests:
    def test_tree_digest_sensitive_to_value_dtype_shape_path(self):
        base = {"a": np.arange(4, dtype=np.float32)}
        d = tree_digest(base)
        assert tree_digest({"a": np.arange(4, dtype=np.float32)}) == d
        v = {"a": np.arange(4, dtype=np.float32)}
        v["a"][2] = np.nextafter(v["a"][2], 4)  # one ULP: still caught
        assert tree_digest(v) != d
        assert tree_digest({"a": np.arange(4, dtype=np.float64)}) != d
        assert tree_digest(
            {"a": np.arange(4, dtype=np.float32).reshape(2, 2)}) != d
        assert tree_digest({"b": np.arange(4, dtype=np.float32)}) != d

    def test_payload_digest_excludes_envelope_and_orders_keys(self):
        p = {"x": np.ones(3, np.float32), "n": 2}
        d = payload_digest(p)
        p["digest"] = d
        assert payload_digest(p) == d  # the envelope rides inside
        assert payload_digest({"n": 2, "x": np.ones(3, np.float32)}) == d

    def test_none_and_scalar_leaves(self):
        a = payload_digest({"token_ids": None, "n": 1})
        b = payload_digest({"token_ids": [0], "n": 1})
        assert a != b


# ---------------------------------------------------------------------------
# the anomaly detector
# ---------------------------------------------------------------------------

class TestAnomalyDetector:
    def test_warmup_is_exempt_then_spike_trips(self):
        # warmup observations only feed the window — they can never
        # trip, however wild (the compile/init-transient exemption)
        det = AnomalyDetector(zscore=8.0, window=8, warmup=3)
        assert det.observe({"loss": 1e30}) == "ok"
        assert det.trips == 0
        det = AnomalyDetector(zscore=8.0, window=8, warmup=3)
        for i in range(6):
            assert det.observe({"loss": 4.0 - 0.01 * i}) == "ok"
        assert det.observe({"loss": 4e8}) == "anomaly"
        assert det.trips == 1

    def test_trip_not_absorbed_into_window(self):
        det = AnomalyDetector(zscore=8.0, window=8, warmup=2)
        for i in range(6):
            det.observe({"g": 1.0 + 0.01 * i})
        assert det.observe({"g": 1e20}) == "anomaly"
        # had the spike widened sigma, a second spike would pass
        assert det.observe({"g": 1e20}) == "anomaly"
        assert det.consecutive_trips == 2
        assert det.observe({"g": 1.05}) == "ok"
        assert det.consecutive_trips == 0

    def test_nonfinite_trips_regardless_of_window(self):
        det = AnomalyDetector(warmup=1)
        assert det.observe({"loss": float("nan")}) == "nonfinite"
        assert det.nonfinite_trips == 1

    def test_skip_counts_without_touching_stats(self):
        det = AnomalyDetector(warmup=2)
        det.observe({"loss": 4.0})
        stats = dict(det._stats)
        det.note_skip()
        assert det.skips == 1 and det._stats == stats

    def test_benign_training_drift_never_trips(self):
        det = AnomalyDetector(zscore=8.0, window=16, warmup=4)
        rng = np.random.default_rng(0)
        loss, g = 5.0, 2.0
        for _ in range(200):
            loss *= 0.995
            g *= float(rng.uniform(0.97, 1.03))
            assert det.observe({"loss": loss, "grad_norm": g}) == "ok"

    def test_bad_params_rejected(self):
        with pytest.raises(ValueError):
            AnomalyDetector(zscore=0)
        with pytest.raises(ValueError):
            AnomalyDetector(warmup=0)


# ---------------------------------------------------------------------------
# digest-verified peer-mirror reconstruct
# ---------------------------------------------------------------------------

def _payloads(world, step=0):
    return {r: {"w": np.full((4,), 100 * step + r, np.float32)}
            for r in range(world)}


class TestMirrorIntegrity:
    def test_corrupted_holder_falls_over_to_next(self):
        st = PeerRedundantStore(world=4, spare=2)
        st.snapshot(3, _payloads(4, 3), shared={"k": 1})
        # rank 2's first holder (rank 3) took a silent flip
        st._mirror[3][2] = corrupt_tree(st._mirror[3][2], 9, 1)[0]
        st.lose([2])
        _, out, _ = st.reconstruct()
        np.testing.assert_array_equal(
            out[2]["w"], np.full((4,), 302, np.float32))
        assert st.integrity_failures == 1

    def test_local_copy_verified_too(self):
        st = PeerRedundantStore(world=2, spare=1)
        st.snapshot(1, _payloads(2))
        st._local[0] = corrupt_tree(st._local[0], 9, 1)[0]
        _, out, _ = st.reconstruct()  # falls over to rank 1's mirror
        np.testing.assert_array_equal(
            out[0]["w"], np.zeros((4,), np.float32))
        assert st.integrity_failures == 1

    def test_all_copies_corrupt_is_unrecoverable(self):
        st = PeerRedundantStore(world=2, spare=1)
        st.snapshot(1, _payloads(2))
        st.lose([0])
        st._mirror[1][0] = corrupt_tree(st._mirror[1][0], 9, 1)[0]
        with pytest.raises(UnrecoverableWorldError) as ei:
            st.reconstruct()
        assert ei.value.missing_ranks == [0]
        assert st.integrity_failures == 1

    def test_verify_false_skips_digests(self):
        st = PeerRedundantStore(world=2, spare=1)
        st.snapshot(1, _payloads(2))
        st._local[0] = corrupt_tree(st._local[0], 9, 1)[0]
        _, out, _ = st.reconstruct(verify=False)
        assert st.integrity_failures == 0  # trusted as-is

    def test_mirror_fault_point_corrupts_exact_entry(self):
        plan = FaultPlan([{"point": "mirror.payload", "kind": "corrupt",
                           "where": {"holder": 1, "owner": 0},
                           "at": 1, "times": 1}], seed=5)
        st = PeerRedundantStore(world=2, spare=1)
        with armed(plan) as p:
            st.snapshot(1, _payloads(2))
        assert p.fired == ["mirror.payload#1:corrupt:corrupt"]
        # the holder's copy diverged; the local copy did not
        assert tree_digest(st._mirror[1][0]) != st._digests[0]
        assert tree_digest(st._local[0]) == st._digests[0]
        # same plan, fresh store: byte-identical corruption
        st2 = PeerRedundantStore(world=2, spare=1)
        with armed(FaultPlan(plan.to_dict()["faults"], seed=5)):
            st2.snapshot(1, _payloads(2))
        np.testing.assert_array_equal(
            st._mirror[1][0]["w"], st2._mirror[1][0]["w"])


# ---------------------------------------------------------------------------
# verified control-plane broadcast (comm layer envelope)
# ---------------------------------------------------------------------------

class TestVerifiedBroadcast:
    def test_envelope_rides_the_guarded_collective(self):
        import deepspeed_tpu.comm as comm

        v = {"resume_step": np.int32(7),
             "order": np.arange(4, dtype=np.int32)}
        got = comm.broadcast_host(v, verify=True)
        np.testing.assert_array_equal(got["order"], v["order"])
        # the verified variant goes through the same timeout+retry
        # guard (its own op name, so plans can target it)
        plan = FaultPlan([{"point": "comm.collective", "kind": "raise",
                           "error": "io",
                           "where": {"op": "broadcast_host[verified]"},
                           "times": 1}])
        with armed(plan) as p:
            assert comm.broadcast_host({"a": 1}, verify=True) == {"a": 1}
        assert len(p.fired) == 1  # fired once, healed by the retry


# ---------------------------------------------------------------------------
# fault-plan corrupt determinism through the FaultAction channel
# ---------------------------------------------------------------------------

class TestCorruptActionDeterminism:
    def test_action_carries_seed_and_invocation(self):
        plan = FaultPlan([{"point": "x.y", "kind": "corrupt",
                           "times": -1}], seed=42)
        with armed(plan):
            a1 = fault_point("x.y")
            a2 = fault_point("x.y")
        assert (a1.seed, a1.invocation) == (42, 1)
        assert (a2.seed, a2.invocation) == (42, 2)
        t = {"w": np.ones((16,), np.float32)}
        c1 = corrupt_tree(t, a1.seed, a1.invocation)[0]
        c2 = corrupt_tree(t, a2.seed, a2.invocation)[0]
        # replaying the plan reproduces each invocation's flip exactly
        plan.reset()
        with armed(plan):
            b1 = fault_point("x.y")
        np.testing.assert_array_equal(
            c1["w"], corrupt_tree(t, b1.seed, b1.invocation)[0]["w"])
        assert not np.array_equal(c1["w"], c2["w"])


# ---------------------------------------------------------------------------
# KV handoff envelope (inference engine level)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def kv_engines():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import init_inference
    from deepspeed_tpu.models import transformer as T

    cfg = T.TransformerConfig(
        vocab_size=64, n_layers=1, n_heads=2, d_model=32, max_seq=32,
        variant="llama", use_flash=False)
    params = T.init(cfg, jax.random.PRNGKey(0))

    def mk():
        return init_inference(
            params, cfg,
            dict(max_seq_len=32, kv_block_size=8, num_kv_blocks=16,
                 min_prefill_bucket=8, max_batch_size=4),
            dtype=jnp.float32)

    return mk


class TestHandoffEnvelope:
    def test_export_attaches_digest_import_verifies(self, kv_engines):
        src, dst = kv_engines(), kv_engines()
        prompt = np.arange(1, 11, dtype=np.int32)
        src.put([7], [prompt], return_tokens=True)
        payload = src.export_kv(7)
        assert payload["digest"] == payload_digest(payload)
        dst.import_kv(7, payload)  # verifies + adopts cleanly
        assert dst.state.get(7).seen_tokens == payload["seen_tokens"]

    def test_tampered_payload_rejected_before_allocation(self, kv_engines):
        src, dst = kv_engines(), kv_engines()
        src.put([3], [np.arange(1, 11, dtype=np.int32)],
                return_tokens=True)
        payload = src.export_kv(3)
        evil = dict(payload)
        evil["k"] = np.array(payload["k"])
        evil["k"].reshape(-1)[0] += 1e-6  # sub-noise nudge: still caught
        free_before = dst.state.free_blocks
        with pytest.raises(HandoffIntegrityError):
            dst.import_kv(3, evil)
        assert dst.state.get(3) is None  # nothing allocated
        assert dst.state.free_blocks == free_before

    def test_fault_point_corrupt_detected(self, kv_engines):
        src, dst = kv_engines(), kv_engines()
        src.put([1], [np.arange(1, 11, dtype=np.int32)],
                return_tokens=True)
        payload = src.export_kv(1)
        plan = FaultPlan([{"point": "handoff.payload",
                           "kind": "corrupt", "times": 1}])
        with armed(plan) as p:
            with pytest.raises(HandoffIntegrityError):
                dst.import_kv(1, payload)
        assert p.fired == ["handoff.payload#1:corrupt:corrupt"]
        # the caller's payload object was not mutated: a retry works
        dst.import_kv(1, payload)


# ---------------------------------------------------------------------------
# the trainer guardian journey (veto -> verified rollback -> clean replay)
# ---------------------------------------------------------------------------

ELASTIC = {"enabled": True, "max_train_batch_size": 8,
           "micro_batch_sizes": [2, 4], "min_gpus": 1, "max_gpus": 8}
GUARD = {"zscore": 8.0, "window": 16, "warmup": 2, "persistent_trips": 2}


def _make_engine(world, **over):
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.platform.mesh import build_mesh

    mcfg = T.TransformerConfig(vocab_size=64, n_layers=1, n_heads=2,
                               d_model=32, max_seq=16, variant="llama",
                               use_flash=False)
    mesh = build_mesh({"data": world}, devices=jax.devices()[:world])
    cfg = {"optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "elasticity": dict(ELASTIC),
           "zero_optimization": {"stage": 1},
           "seed": 3, "steps_per_print": 10**9}
    cfg.update(over)
    return ds.initialize(
        cfg,
        loss_fn=T.make_loss_fn(mcfg),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
        mesh=mesh)


def _make_loader():
    from deepspeed_tpu.runtime.dataloader import (
        DeepSpeedTPUDataLoader,
        RepeatingLoader,
    )

    class Tok:
        def __init__(self, n=24):
            r = np.random.default_rng(9)
            self.items = [
                {"tokens": r.integers(0, 64, (17,)).astype(np.int32)}
                for _ in range(n)]

        def __len__(self):
            return len(self.items)

        def __getitem__(self, i):
            return self.items[i]

    return RepeatingLoader(DeepSpeedTPUDataLoader(
        Tok(), batch_size=8, shuffle=True, seed=5))


class TestTrainerGuardian:
    def test_grad_flip_vetoed_rollback_replay_bitwise(self):
        from deepspeed_tpu.elasticity import ElasticTrainer
        from deepspeed_tpu.monitor.monitor import (
            training_resilience_events,
        )

        T_STEPS = 6
        clean = ElasticTrainer(_make_engine, 2, _make_loader(),
                               every_k_steps=2,
                               elastic_block=dict(ELASTIC),
                               guardian=dict(GUARD))
        clean.run(T_STEPS)
        plan = FaultPlan([{"point": "engine.grads", "kind": "corrupt",
                           "where": {"step": 4}, "times": 1}])
        chaos = ElasticTrainer(_make_engine, 2, _make_loader(),
                               every_k_steps=2,
                               elastic_block=dict(ELASTIC),
                               guardian=dict(GUARD))
        with armed(plan) as p:
            chaos.run(T_STEPS)
        assert p.fired == ["engine.grads#1:corrupt:corrupt"]
        assert chaos.anomalies_detected == 1
        assert chaos.integrity_rollbacks == 1
        assert chaos.last_rollback_steps <= 2  # mirror cadence K=2
        # the corrupted update never committed: trajectory and sample
        # ledger are byte-identical to the clean run
        assert sorted(chaos.history) == list(range(1, T_STEPS + 1))
        assert all(clean.history[s] == chaos.history[s]
                   for s in range(1, T_STEPS + 1))
        assert json.dumps(sorted(clean.ledger.items())) \
            == json.dumps(sorted(chaos.ledger.items()))
        # guardian counters flow through the monitor feed
        names = {n for n, _, _ in
                 training_resilience_events(chaos, step=T_STEPS)}
        assert {"train/resilience/anomalies_detected",
                "train/resilience/integrity_rollbacks",
                "train/resilience/mirror_integrity_failures",
                "train/resilience/skipped_steps"} <= names

    def test_persistent_anomaly_escalates(self):
        from deepspeed_tpu.elasticity import ElasticTrainer

        # times=-1: the same step's readout corrupts on EVERY replay —
        # after persistent_trips verified rollbacks the guardian must
        # escalate instead of looping forever (step 4 sits past the
        # detector's warmup window)
        plan = FaultPlan([{"point": "engine.grads", "kind": "corrupt",
                           "where": {"step": 4}, "times": -1}])
        tr = ElasticTrainer(_make_engine, 2, _make_loader(),
                            every_k_steps=1,
                            elastic_block=dict(ELASTIC),
                            guardian={**GUARD, "persistent_trips": 1})
        with armed(plan):
            with pytest.raises(PersistentAnomalyError):
                tr.run(5)
        assert tr.integrity_rollbacks == 1  # one verified attempt


# ---------------------------------------------------------------------------
# found-inf skipped step: ledger stays in sync, EMA window unpolluted
# ---------------------------------------------------------------------------

class TestFoundInfSkip:
    def test_fp16_overflow_skip_keeps_ledger_and_window_clean(self):
        import jax

        from deepspeed_tpu.elasticity import ElasticTrainer

        # 2^20 loss scale overflows f16 immediately (hysteresis=1 so
        # the scale halves on the first overflow and recovers fast)
        tr = ElasticTrainer(
            lambda w: _make_engine(
                w, fp16={"enabled": True, "initial_scale_power": 20,
                         "hysteresis": 1, "loss_scale_window": 1000}),
            2, _make_loader(), every_k_steps=2,
            elastic_block=dict(ELASTIC), guardian=dict(GUARD))
        master_before = jax.device_get(tr.engine.state.master)
        assert tr.step() is None  # overflow -> in-graph skip
        assert tr.skipped_steps == 1
        assert tr.engine.global_steps == 0  # host re-synced to device
        master_after = jax.device_get(tr.engine.state.master)
        assert all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(master_before),
            jax.tree.leaves(master_after)))  # update really skipped
        for _ in range(40):
            if tr.engine.global_steps >= 3:
                break
            tr.step()
        # committed steps number 1..3 with no gap or duplicate, each
        # with exactly one ledger entry; the skipped batches were
        # consumed (reference overflow semantics) but never committed
        assert sorted(tr.history) == [1, 2, 3]
        assert sorted(tr.ledger) == [1, 2, 3]
        # the skips never reached the anomaly window
        assert tr.guardian.skips == tr.skipped_steps >= 1
        assert tr.guardian.trips == 0
        assert tr.guardian.observed == 3

    def test_nonfinite_guard_skips_in_graph_outside_fp16(self):
        import dataclasses

        import jax

        eng = _make_engine(2, integrity={"enabled": True})
        # poison one weight: the loss goes non-finite, so grads do too
        flat, treedef = jax.tree_util.tree_flatten(eng.state.params)
        bad = [np.full(np.shape(l), np.inf, np.asarray(l).dtype)
               if i == 0 else l for i, l in enumerate(flat)]
        eng.state = dataclasses.replace(
            eng.state, params=jax.tree_util.tree_unflatten(treedef, bad))
        before = jax.device_get(eng.state.params)
        batch = {"tokens": np.random.default_rng(0).integers(
            0, 64, (8, 17)).astype(np.int32)}
        metrics = eng.train_batch(batch)
        assert metrics["skipped"] == 1  # found_inf_in_grads tripped
        after = jax.device_get(eng.state.params)
        assert all(np.array_equal(a, b) for a, b in zip(
            jax.tree.leaves(before), jax.tree.leaves(after)))

    def test_guard_off_by_default(self):
        from deepspeed_tpu.config.config import DeepSpeedTPUConfig

        assert DeepSpeedTPUConfig().integrity.enabled is False
        with pytest.raises(ValueError):
            DeepSpeedTPUConfig(integrity={"zscore": -1})
        with pytest.raises(ValueError):
            DeepSpeedTPUConfig(integrity={"persistent_trips": 0})


# ---------------------------------------------------------------------------
# gate CLI + committed baseline consistency
# ---------------------------------------------------------------------------

class TestSdcGate:
    def test_committed_baseline_parses_and_matches_plan(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(root, "SDCCHAOS.json")
        assert os.path.exists(path), "SDCCHAOS.json must be committed"
        raw = json.load(open(path))
        plan = FaultPlan.from_dict(raw)
        points = {f.point for f in plan.faults}
        assert {"engine.grads", "mirror.payload",
                "handoff.payload"} <= points
        expect = raw["expect"]
        # the committed ledger asserts 100% detection per flip class
        for cls in ("grad", "mirror", "handoff"):
            assert expect[f"{cls}_flips_detected"] \
                == expect[f"{cls}_flips_injected"] > 0

    def test_default_plan_round_trips(self):
        import bench

        d = bench._default_sdc_chaos_plan()
        plan = FaultPlan.from_dict(d)
        assert FaultPlan.from_dict(plan.to_dict()).to_dict() \
            == plan.to_dict()

    def test_cli_help_exits_zero(self):
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        r = subprocess.run(
            [sys.executable, os.path.join(root, "scripts", "ds_gate.py"),
             "sdc", "--help"], capture_output=True, text=True, timeout=120)
        assert r.returncode == 0
        assert "--capture" in r.stdout and "--strict" in r.stdout
