"""Compile-time cost model tests (analysis/costmodel.py, S004-S006).

Same contract as the sanitizer suite: every check fires EXACTLY ONCE on
a deliberately seeded violation and stays silent on the real training /
decode / serving step programs. The ds_budget gate is exercised
end-to-end through its CLI against the committed MEMBUDGET.json and an
injected regression.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.analysis.costmodel import (
    CostReport,
    build_cost_report,
    check_against_baseline,
    check_collective_volume,
    check_hbm_budget,
    check_roofline,
    roofline,
)
from deepspeed_tpu.models import transformer as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 128


def model_cfg(**kw):
    base = dict(vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=64,
                max_seq=32, variant="llama", use_flash=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def mesh8(shape=(8,), names=("d",)):
    return Mesh(np.array(jax.devices()[:8]).reshape(*shape), names)


# ----------------------------------------------------------------------
# hlo.py extensions: collective metadata + entry-param hardening
# ----------------------------------------------------------------------

class TestCollectiveMetadata:
    def test_explicit_replica_groups(self):
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        hlo = ("%ag = bf16[16,64]{1,0} all-gather(bf16[4,64]{1,0} %x), "
               "replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}")
        recs = parse_hlo_collectives(hlo)
        assert len(recs) == 1
        assert recs[0]["group_size"] == 4
        assert recs[0]["operand_bytes"] == 4 * 64 * 2
        assert recs[0]["bytes"] == 16 * 64 * 2

    def test_iota_replica_groups(self):
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        hlo = ("%rs = f32[2,8]{1,0} reduce-scatter(f32[8,8]{1,0} %x), "
               "replica_groups=[2,4]<=[8], dimensions={0}")
        recs = parse_hlo_collectives(hlo)
        assert recs[0]["group_size"] == 4

    def test_flat_world_group_is_zero(self):
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        hlo = "%ar = f32[4]{0} all-reduce(f32[4]{0} %x), replica_groups={}"
        recs = parse_hlo_collectives(hlo)
        assert recs[0]["group_size"] == 0


class TestEntryParamHardening:
    def test_token_typed_param(self):
        from deepspeed_tpu.profiling.hlo import parse_entry_parameters

        hlo = ("ENTRY %main (p0: f32[4], t: token[]) -> f32[4] {\n"
               "  %p0 = f32[4]{0} parameter(0)\n"
               "  %t = token[] parameter(1)\n"
               "}\n")
        recs = parse_entry_parameters(hlo)
        assert [r["index"] for r in recs] == [0, 1]
        assert recs[1]["dtype"] == "token"
        assert recs[1]["nbytes"] == 0
        assert recs[0]["nbytes"] == 16

    def test_tuple_nested_param(self):
        from deepspeed_tpu.profiling.hlo import parse_entry_parameters

        hlo = ("ENTRY %main (p: (f32[2,4], s32[])) -> f32[2,4] {\n"
               "  %p = (f32[2,4]{1,0}, s32[]) parameter(0), "
               "sharding={{replicated}, {replicated}}\n"
               "}\n")
        recs = parse_entry_parameters(hlo)
        assert len(recs) == 1
        assert recs[0]["dtype"] == "tuple"
        assert recs[0]["nbytes"] == 2 * 4 * 4 + 4


class TestSafeArtifactWrappers:
    class _Broken:
        def memory_analysis(self):
            raise NotImplementedError("unimplemented for this backend")

        def cost_analysis(self):
            raise NotImplementedError("unimplemented for this backend")

        def as_text(self):
            return ("HloModule m\n\nENTRY %main (p0: f32[8]) -> f32[8] {\n"
                    "  %p0 = f32[8]{0} parameter(0)\n}\n")

    def test_unimplemented_returns_none_not_crash(self):
        from deepspeed_tpu.profiling.hlo import (
            compiled_cost_stats,
            compiled_memory_stats,
        )

        assert compiled_memory_stats(self._Broken()) is None
        assert compiled_cost_stats(self._Broken()) is None

    def test_real_compiled_artifacts(self):
        from deepspeed_tpu.profiling.hlo import (
            compiled_cost_stats,
            compiled_memory_stats,
        )

        c = jax.jit(lambda x: x @ x).lower(
            jnp.zeros((16, 16), jnp.float32)).compile()
        mem = compiled_memory_stats(c)
        assert mem is not None and mem["argument_bytes"] == 16 * 16 * 4
        cost = compiled_cost_stats(c)
        assert cost is not None and cost["flops"] > 0

    def test_cost_list_form_normalized(self):
        from deepspeed_tpu.profiling.hlo import compiled_cost_stats

        class Listy:
            def cost_analysis(self):
                return [{"flops": 7.0, "bytes accessed": 3.0}]

        assert compiled_cost_stats(Listy()) == {"flops": 7.0,
                                                "bytes_accessed": 3.0}

    def test_estimated_fallback_report(self):
        rep = build_cost_report(self._Broken(), label="fallback")
        assert rep is not None and rep.estimated
        assert rep.arg_bytes == 8 * 4  # rebuilt from the entry params
        assert rep.peak_hbm_bytes == rep.arg_bytes


# ----------------------------------------------------------------------
# CostReport construction + projection
# ----------------------------------------------------------------------

class TestCostReport:
    def test_real_program_report(self):
        mesh = mesh8()
        w = jax.device_put(jnp.zeros((8, 64), jnp.float32),
                           NamedSharding(mesh, P("d")))
        c = jax.jit(lambda v: v * 2).lower(w).compile()
        rep = build_cost_report(c, label="x2")
        assert rep is not None and not rep.estimated
        assert rep.n_devices == 8
        assert rep.arg_bytes == 64 * 4  # per-shard: 1 of 8 rows
        assert rep.sharded_arg_bytes > 0 and rep.replicated_arg_bytes == 0
        assert rep.peak_hbm_bytes > 0

    def test_projection_shrinks_sharded_keeps_replicated(self):
        rep = CostReport(label="p", arg_bytes=1000, sharded_arg_bytes=800,
                         replicated_arg_bytes=200, n_devices=8)
        # 8 -> 64 devices: the sharded 800 shrinks 8x, the 200 stays
        assert rep.projected_arg_bytes(64) == 800 // 8 + 200
        # projecting DOWN concentrates shards (8 -> 2: 4x growth)
        assert rep.projected_arg_bytes(2) == 800 * 4 + 200


# ----------------------------------------------------------------------
# S004: per-device HBM budget
# ----------------------------------------------------------------------

class TestHbmBudgetCheck:
    def _report(self):
        # a replicated 1 MiB weight: every device holds the full copy
        w = jnp.zeros((256, 1024), jnp.float32)
        c = jax.jit(lambda v: v + 1).lower(w).compile()
        return build_cost_report(c, label="big_replicated")

    def test_over_budget_fires_exactly_once(self):
        rep = self._report()
        out = check_hbm_budget(rep, budget_bytes=256 * 1024)
        assert len(out.findings) == 1
        f = out.findings[0]
        assert f.rule == "S004" and f.severity == "error"
        assert "exceeds the per-device budget" in f.message

    def test_within_budget_is_silent(self):
        rep = self._report()
        assert check_hbm_budget(rep, budget_bytes=1 << 30).ok

    def test_replicated_floor_survives_projection(self):
        """A replicated-dominated program cannot be saved by a bigger
        mesh: the projected footprint stays over budget at any size."""
        rep = self._report()
        out = check_hbm_budget(rep, budget_bytes=256 * 1024,
                               target_devices=1024)
        assert len(out.findings) == 1
        assert "projected 1024 devices" in out.findings[0].message

    def test_sharded_program_shrinks_at_scale(self):
        mesh = mesh8()
        w = jax.device_put(jnp.zeros((8, 65536), jnp.float32),
                           NamedSharding(mesh, P("d")))
        c = jax.jit(lambda v: v * 2).lower(w).compile()
        rep = build_cost_report(c, label="sharded")
        budget = rep.peak_hbm_bytes // 2  # too small at 8 devices...
        assert not check_hbm_budget(rep, budget_bytes=budget).ok
        # ...but fits once the mesh grows 8x
        assert check_hbm_budget(rep, budget_bytes=budget,
                                target_devices=64).ok


# ----------------------------------------------------------------------
# S005: collective-volume blowups
# ----------------------------------------------------------------------

class TestCollectiveVolumeCheck:
    def test_seeded_full_gather_of_sharded_table_fires(self):
        """The accidental-replication class: a [64, 4096] f32 table
        sharded over 8 devices is materialized WHOLE (one full
        all-gather) when the consumer only needs a handful of rows."""
        mesh = mesh8()
        table = jax.device_put(jnp.zeros((64, 4096), jnp.float32),
                               NamedSharding(mesh, P("d")))

        def f(t, idx):
            # replicated constraint forces the full gather of t
            full = jax.lax.with_sharding_constraint(
                t, NamedSharding(mesh, P()))
            return full[idx]

        c = jax.jit(f).lower(table, jnp.zeros((4,), jnp.int32)).compile()
        rep = build_cost_report(c, label="lookup")
        assert rep.all_gather_bytes >= table.nbytes * 7 // 8
        # live need: the 4 rows the lookup consumes
        live = 4 * 4096 * 4
        out = check_collective_volume(rep, live_sharded_bytes=live, k=4.0)
        assert len(out.findings) == 1
        f0 = out.findings[0]
        assert f0.rule == "S005" and f0.severity == "error"
        assert "accidental full-gather" in f0.message

    def test_proportional_gather_is_silent(self):
        mesh = mesh8()
        table = jax.device_put(jnp.zeros((8, 4096), jnp.float32),
                               NamedSharding(mesh, P("d")))

        def f(t):
            full = jax.lax.with_sharding_constraint(
                t, NamedSharding(mesh, P()))
            return full.sum()

        c = jax.jit(f).lower(table).compile()
        rep = build_cost_report(c, label="reduce")
        # the whole table IS the live working set here: one gather of it
        # is proportional, not accidental
        out = check_collective_volume(
            rep, live_sharded_bytes=int(table.nbytes), k=4.0)
        assert out.ok

    def test_baseline_regression_fires(self):
        rep = CostReport(label="r", collectives={
            "all-reduce": {"count": 1, "bytes": 1200}})
        out = check_collective_volume(
            rep, baseline={"comm_bytes": 1000}, tolerance=0.10)
        assert len(out.findings) == 1
        assert "regressed" in out.findings[0].message

    def test_baseline_within_tolerance_is_silent(self):
        rep = CostReport(label="r", collectives={
            "all-reduce": {"count": 1, "bytes": 1050}})
        assert check_collective_volume(
            rep, baseline={"comm_bytes": 1000}, tolerance=0.10).ok


# ----------------------------------------------------------------------
# S006: roofline balance
# ----------------------------------------------------------------------

class TestRooflineCheck:
    def _comm_heavy(self):
        return CostReport(label="comm_heavy", flops=1e6, bytes_accessed=1e6,
                          collectives={"all-gather": {"count": 1,
                                                      "bytes": 1e9}})

    def test_comm_bound_program_flagged(self):
        rep = self._comm_heavy()
        out = check_roofline(rep, peak_flops=1e12, hbm_bandwidth=1e12,
                             ici_bandwidth=1e8, expect="compute")
        assert len(out.findings) == 1
        f = out.findings[0]
        assert f.rule == "S006" and "comm-bound" in f.message

    def test_compute_bound_is_silent(self):
        rep = CostReport(label="gemm", flops=1e12, bytes_accessed=1e6)
        assert check_roofline(rep, peak_flops=1e12, hbm_bandwidth=1e12,
                              expect="compute").ok

    def test_comm_only_tolerates_memory_bound(self):
        """Toy verification slices are legitimately memory-bound;
        comm_only keeps S006 quiet about that while still catching
        collective domination."""
        rep = CostReport(label="toy", flops=1e3, bytes_accessed=1e9)
        out = check_roofline(rep, peak_flops=1e12, hbm_bandwidth=1e9,
                             expect="compute", comm_only=True)
        assert out.ok
        out = check_roofline(rep, peak_flops=1e12, hbm_bandwidth=1e9,
                             expect="compute", comm_only=False)
        assert len(out.findings) == 1

    def test_no_cost_artifacts_is_silent(self):
        rep = CostReport(label="empty")
        assert check_roofline(rep, peak_flops=1e12,
                              hbm_bandwidth=1e12).ok

    def test_roofline_ratios(self):
        r = roofline(self._comm_heavy(), peak_flops=1e12,
                     hbm_bandwidth=1e12, ici_bandwidth=1e8)
        assert r["bound"] == "comm"
        assert r["t_ici"] == pytest.approx(10.0)


# ----------------------------------------------------------------------
# baseline regression form (ds_budget's S004)
# ----------------------------------------------------------------------

class TestBaselineCheck:
    def test_regression_fires(self):
        rep = CostReport(label="p", arg_bytes=1200)
        out = check_against_baseline(rep, {"peak_hbm_bytes": 1000},
                                     tolerance=0.10)
        assert len(out.findings) == 1
        assert out.findings[0].rule == "S004"

    def test_within_tolerance_silent(self):
        rep = CostReport(label="p", arg_bytes=1050)
        assert check_against_baseline(rep, {"peak_hbm_bytes": 1000},
                                      tolerance=0.10).ok


# ----------------------------------------------------------------------
# the real step programs stay silent (acceptance: S004/S005/S006 quiet
# on every real train/decode/serving step)
# ----------------------------------------------------------------------

class TestRealProgramsSilent:
    def test_train_step_cost_clean(self):
        mcfg = model_cfg()
        engine = ds.initialize(
            {"train_micro_batch_size_per_gpu": 1,
             "gradient_accumulation_steps": 2,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 3,
                                   "param_persistence_threshold": 64},
             "bf16": {"enabled": True},
             "mesh": {"data": 4, "model": 2},
             "steps_per_print": 1000},
            loss_fn=T.make_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg))
        batch = {"tokens": np.zeros(
            (engine.config.train_batch_size, 33), np.int32)}
        rep = engine.sanitize(batch)
        assert rep.ok, rep.render()
        assert rep.cost is not None
        assert rep.cost.peak_hbm_bytes > 0
        assert "peak" in rep.render()  # cost rides the report rendering

    def test_train_step_over_budget_fires_once(self):
        """The SAME healthy program becomes the seeded S004 violation
        under a deliberately impossible budget — exactly one finding."""
        mcfg = model_cfg()
        engine = ds.initialize(
            {"train_micro_batch_size_per_gpu": 1,
             "gradient_accumulation_steps": 1,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "steps_per_print": 1000,
             "mesh": {"data": 8}},
            loss_fn=T.make_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg))
        batch = {"tokens": np.zeros(
            (engine.config.train_batch_size, 33), np.int32)}
        rep = engine.sanitize(batch, hbm_budget_bytes=1024)
        s004 = [f for f in rep.findings if f.rule == "S004"]
        assert len(s004) == 1, rep.render()


class TestServingBudget:
    @pytest.fixture(scope="class")
    def model(self):
        cfg = model_cfg(max_seq=64)
        return cfg, T.init(cfg, jax.random.PRNGKey(0))

    def _engine(self, model):
        from deepspeed_tpu.inference import init_inference

        cfg, params = model
        return init_inference(
            params, cfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32)

    def test_warmup_captures_footprints_and_budget_clean(self, model):
        from deepspeed_tpu.inference import (
            ServingScheduler,
            ServingSchedulerConfig,
        )

        sched = ServingScheduler(
            self._engine(model),
            ServingSchedulerConfig(max_num_batched_tokens=16))
        assert sched.engine.warmup_footprints  # per-bucket reports exist
        assert all(f["peak_hbm_bytes"] > 0
                   for f in sched.engine.warmup_footprints.values())
        assert sched.budget_report.ok, sched.budget_report.render()
        m = sched.metrics()
        assert m["hbm_peak_mb"] > 0
        assert any(k.startswith("hbm_w") for k in m)
        assert m["budget_findings"] == 0.0

    def test_over_budget_config_flagged_once(self, model):
        from deepspeed_tpu.inference import (
            ServingScheduler,
            ServingSchedulerConfig,
        )

        sched = ServingScheduler(
            self._engine(model),
            ServingSchedulerConfig(max_num_batched_tokens=16,
                                   hbm_budget_gb=1e-6))  # ~1 KB budget
        s004 = [f for f in sched.budget_report.findings
                if f.rule == "S004" and f.severity == "error"]
        assert len(s004) == 1
        assert sched.metrics()["budget_findings"] >= 1.0

    def test_token_budget_overcommit_warns(self, model):
        from deepspeed_tpu.inference import (
            ServingScheduler,
            ServingSchedulerConfig,
        )

        sched = ServingScheduler(
            self._engine(model),
            ServingSchedulerConfig(max_num_batched_tokens=10_000,
                                   warmup=False))
        assert any("overcommit" in f.message
                   for f in sched.budget_report.findings)


# ----------------------------------------------------------------------
# ds_budget CLI gate
# ----------------------------------------------------------------------

@pytest.mark.slow
class TestDsBudgetScript:
    """Slow lane: each subprocess rebuilds EVERY canonical program
    (two engine compiles + two inference compiles since the MoE
    program joined) — and the pre-test gate lane already runs
    `ds_gate.py budget --check --strict` on every PR, so the fast lane
    carries no coverage gap."""

    def _run(self, *args):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # the script sets its own device count
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "ds_gate.py"),
             "budget", *args],
            capture_output=True, text=True, env=env, cwd=REPO, timeout=600)

    def test_check_passes_on_committed_tree(self):
        r = self._run("--check", "--strict")
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(r.stdout.strip().splitlines()[-1])
        assert doc["ok"] and doc["findings"] == []

    def test_check_fails_on_injected_regression(self, tmp_path):
        base = json.load(open(os.path.join(REPO, "MEMBUDGET.json")))
        # shrink the recorded baseline so the (unchanged) tree reads as
        # a >= 10% peak-HBM regression
        for prog in base["programs"].values():
            prog["peak_hbm_bytes"] = int(prog["peak_hbm_bytes"] * 0.8)
        injected = tmp_path / "membudget.json"
        injected.write_text(json.dumps(base))
        r = self._run("--check", "--baseline", str(injected))
        assert r.returncode != 0, r.stdout + r.stderr
        doc = json.loads(r.stdout.strip().splitlines()[-1])
        assert not doc["ok"]
        assert any(f["rule"] == "S004" and "regressed" in f["message"]
                   for f in doc["findings"])

    def test_capture_roundtrip(self, tmp_path):
        out = tmp_path / "fresh.json"
        r = self._run("--capture", "--baseline", str(out))
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(out.read_text())
        assert set(doc["programs"]) == {"train_step", "train_step_moe",
                                        "train_step_pipe3d",
                                        "serving_decode_w8",
                                        "serving_decode_w8_int8"}
        assert all(p["peak_hbm_bytes"] > 0
                   for p in doc["programs"].values())
        # int8-KV capacity ratio committed + above the floor
        b = doc["budgets"]
        assert b["kv_bytes_per_token_ref"] >= 1.8 * \
            b["kv_bytes_per_token_int8"] > 0
        r = self._run("--check", "--strict", "--baseline", str(out))
        assert r.returncode == 0, r.stdout + r.stderr
