"""Schedule-analyzer tests (analysis/schedule.py, S007-S009).

Same contract as the sanitizer/cost-model suites: every check fires
EXACTLY ONCE on a deliberately seeded violation — a serialized
collective with hideable compute (S007), a DCN-straddling replica
group (S008), a comm-dominated critical path (S009) — and stays silent
on the real training / decode programs. The ds_schedule gate is
exercised end-to-end through its CLI against the committed
SCHEDULE.json and an injected regression, and the autotuner's AOT
score is checked to rank a known-good config above a deliberately
comm-bound one with a deterministic top-k list.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.analysis.schedule import (
    PodTopology,
    ScheduleAnalysis,
    analyze_compiled,
    analyze_schedule,
    check_exposed_comm,
    check_hierarchy_placement,
    check_step_time,
)
from deepspeed_tpu.models import transformer as T

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 32 MiB all-gather over 8 devices whose consumer is scheduled at the
# END of the program, with two 4 MiB compute instructions in the gap —
# the serialized-but-hideable shape S007 exists to catch
_SERIALIZED_HLO = """\
HloModule seeded, is_scheduled=true, num_partitions=8

ENTRY %main (p: f32[1024,1024]) -> f32[1024,1024] {
  %p = f32[1024,1024]{1,0} parameter(0)
  %ag = f32[8192,1024]{1,0} all-gather(f32[1024,1024]{1,0} %p), replica_groups=[1,8]<=[8], dimensions={0}
  %m1 = f32[1024,1024]{1,0} multiply(f32[1024,1024]{1,0} %p, f32[1024,1024]{1,0} %p)
  %m2 = f32[1024,1024]{1,0} add(f32[1024,1024]{1,0} %m1, f32[1024,1024]{1,0} %m1)
  ROOT %use = f32[1024,1024]{1,0} slice(f32[8192,1024]{1,0} %ag), slice={[0:1024], [0:1024]}
}
"""

# the same program with the consumer scheduled IMMEDIATELY after the
# collective: nothing to hide behind, S007 stays quiet
_NO_SLACK_HLO = """\
HloModule seeded, is_scheduled=true, num_partitions=8

ENTRY %main (p: f32[1024,1024]) -> f32[1024,1024] {
  %p = f32[1024,1024]{1,0} parameter(0)
  %ag = f32[8192,1024]{1,0} all-gather(f32[1024,1024]{1,0} %p), replica_groups=[1,8]<=[8], dimensions={0}
  %use = f32[1024,1024]{1,0} slice(f32[8192,1024]{1,0} %ag), slice={[0:1024], [0:1024]}
  %m1 = f32[1024,1024]{1,0} multiply(f32[1024,1024]{1,0} %use, f32[1024,1024]{1,0} %use)
  ROOT %m2 = f32[1024,1024]{1,0} add(f32[1024,1024]{1,0} %m1, f32[1024,1024]{1,0} %m1)
}
"""

# async pair: the 32 MiB gather runs across an explicit start..done
# window holding the two compute instructions
_ASYNC_HLO = """\
HloModule seeded, is_scheduled=true, num_partitions=8

ENTRY %main (p: f32[1024,1024]) -> f32[1024,1024] {
  %p = f32[1024,1024]{1,0} parameter(0)
  %ag-start = (f32[1024,1024]{1,0}, f32[8192,1024]{1,0}) all-gather-start(f32[1024,1024]{1,0} %p), replica_groups=[1,8]<=[8], dimensions={0}
  %m1 = f32[1024,1024]{1,0} multiply(f32[1024,1024]{1,0} %p, f32[1024,1024]{1,0} %p)
  %m2 = f32[1024,1024]{1,0} add(f32[1024,1024]{1,0} %m1, f32[1024,1024]{1,0} %m1)
  %ag-done = f32[8192,1024]{1,0} all-gather-done((f32[1024,1024]{1,0}, f32[8192,1024]{1,0}) %ag-start)
  ROOT %use = f32[1024,1024]{1,0} slice(f32[8192,1024]{1,0} %ag-done), slice={[0:1024], [0:1024]}
}
"""

# 64 MiB world all-reduce next to almost no compute: the critical path
# is wire time — the S009 comm-dominated shape
_COMM_BOUND_HLO = """\
HloModule seeded, is_scheduled=true, num_partitions=8

ENTRY %main (p: f32[4096,4096]) -> f32[4096,4096] {
  %p = f32[4096,4096]{1,0} parameter(0)
  ROOT %ar = f32[4096,4096]{1,0} all-reduce(f32[4096,4096]{1,0} %p), replica_groups={}, to_apply=%sum
}
"""


def _seeded_analysis(text, bytes_accessed=1e9, hbm=1e9,
                     hide_sync_slack=True):
    """Analysis with a 1-second compute leg (unit weights scale off
    bytes_accessed/hbm) over a synthetic scheduled module."""
    return analyze_schedule(
        text, flops=0.0, bytes_accessed=bytes_accessed, peak_flops=1e12,
        hbm_bandwidth=hbm, n_devices=8, label="seeded",
        hide_sync_slack=hide_sync_slack)


# ----------------------------------------------------------------------
# hlo.py DAG extraction
# ----------------------------------------------------------------------

class TestComputationParser:
    def test_entry_and_regions_split(self):
        from deepspeed_tpu.profiling.hlo import parse_hlo_computations

        hlo = ("HloModule m, is_scheduled=true\n\n"
               "%region_0.6 (a: f32[], b: f32[]) -> f32[] {\n"
               "  %a = f32[] parameter(0)\n"
               "  %b = f32[] parameter(1)\n"
               "  ROOT %add.9 = f32[] add(f32[] %a, f32[] %b)\n"
               "}\n\n"
               "ENTRY %main (p: f32[4,8]) -> f32[] {\n"
               "  %p = f32[4,8]{1,0} parameter(0)\n"
               "  %c = f32[] constant(0)\n"
               "  ROOT %r = f32[] reduce(f32[4,8]{1,0} %p, f32[] %c), "
               "dimensions={0,1}, to_apply=%region_0.6\n"
               "}\n")
        comps, entry = parse_hlo_computations(hlo)
        assert entry == "main"
        assert set(comps) == {"region_0.6", "main"}
        r = comps["main"][-1]
        assert r["root"] and r["op"] == "reduce"
        assert r["operands"] == ["p", "c"]
        assert r["called"] == ["region_0.6"]
        assert comps["main"][0]["op"] == "parameter"
        assert comps["main"][0]["nbytes"] == 4 * 8 * 4

    def test_real_compiled_module_parses(self):
        c = jax.jit(lambda x: (x @ x).sum()).lower(
            jnp.zeros((16, 16), jnp.float32)).compile()
        from deepspeed_tpu.profiling.hlo import parse_hlo_computations

        comps, entry = parse_hlo_computations(c.as_text())
        assert entry is not None and comps[entry]
        ops = {i["op"] for i in comps[entry]}
        assert "parameter" in ops

    def test_replica_group_forms(self):
        from deepspeed_tpu.profiling.hlo import parse_replica_groups

        assert parse_replica_groups(
            "replica_groups={{0,1},{2,3}}") == [[0, 1], [2, 3]]
        assert parse_replica_groups(
            "replica_groups=[2,4]<=[8]") == [[0, 1, 2, 3], [4, 5, 6, 7]]
        # transposed iota: iota(8).reshape(2,4).T.reshape(4,2)
        assert parse_replica_groups(
            "replica_groups=[4,2]<=[2,4]T(1,0)") == \
            [[0, 4], [1, 5], [2, 6], [3, 7]]
        assert parse_replica_groups("replica_groups={}") == []

    def test_source_target_pairs(self):
        from deepspeed_tpu.profiling.hlo import parse_source_target_pairs

        assert parse_source_target_pairs(
            "source_target_pairs={{0,1},{1,2},{2,0}}") == \
            [(0, 1), (1, 2), (2, 0)]


class TestCollectiveParsingHardening:
    """Satellite: async start/done pairs must not double-count in the
    S005 volume totals, and collectives wrapped inside fusions /
    while-loop bodies must still be attributed."""

    def test_async_start_done_counts_once(self):
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        hlo = ("%ag-start = (f32[4,64]{1,0}, f32[16,64]{1,0}) "
               "all-gather-start(f32[4,64]{1,0} %p), "
               "replica_groups={{0,1,2,3}}, dimensions={0}\n"
               "%ag-done = f32[16,64]{1,0} all-gather-done("
               "(f32[4,64]{1,0}, f32[16,64]{1,0}) %ag-start)\n")
        recs = parse_hlo_collectives(hlo)
        assert len(recs) == 1
        assert recs[0]["op"] == "all-gather"
        assert recs[0]["bytes"] == 16 * 64 * 4  # the OUTPUT, once

    def test_start_with_calls_body_not_double_counted(self):
        """Async sugar printed with its wrapped computation: the start
        site carries the bytes, the body's inner collective must not
        count again."""
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        hlo = ("HloModule m\n\n"
               "%wrapped_ag (wp: f32[4,64]) -> f32[16,64] {\n"
               "  %wp = f32[4,64]{1,0} parameter(0)\n"
               "  ROOT %ag.inner = f32[16,64]{1,0} all-gather("
               "f32[4,64]{1,0} %wp), replica_groups={{0,1,2,3}}, "
               "dimensions={0}\n"
               "}\n\n"
               "ENTRY %main (p0: f32[4,64]) -> f32[16,64] {\n"
               "  %p0 = f32[4,64]{1,0} parameter(0)\n"
               "  %ags = (f32[4,64]{1,0}, f32[16,64]{1,0}) "
               "all-gather-start(f32[4,64]{1,0} %p0), "
               "replica_groups={{0,1,2,3}}, dimensions={0}, "
               "calls=%wrapped_ag\n"
               "  ROOT %agd = f32[16,64]{1,0} all-gather-done("
               "(f32[4,64]{1,0}, f32[16,64]{1,0}) %ags)\n"
               "}\n")
        recs = parse_hlo_collectives(hlo)
        assert len(recs) == 1
        assert recs[0]["bytes"] == 16 * 64 * 4

    def test_fusion_wrapped_collective_attributed(self):
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        hlo = ("HloModule m\n\n"
               "%fused_computation (fp: f32[8,8]) -> f32[8,8] {\n"
               "  %fp = f32[8,8]{1,0} parameter(0)\n"
               "  ROOT %ar = f32[8,8]{1,0} all-reduce(f32[8,8]{1,0} "
               "%fp), replica_groups={{0,1}}, to_apply=%sum\n"
               "}\n\n"
               "ENTRY %main (p: f32[8,8]) -> f32[8,8] {\n"
               "  %p = f32[8,8]{1,0} parameter(0)\n"
               "  ROOT %f = f32[8,8]{1,0} fusion(f32[8,8]{1,0} %p), "
               "kind=kLoop, calls=%fused_computation\n"
               "}\n")
        recs = parse_hlo_collectives(hlo)
        assert len(recs) == 1
        assert recs[0]["op"] == "all-reduce"
        assert recs[0]["bytes"] == 8 * 8 * 4

    def test_while_body_collective_attributed_once(self):
        """Collectives inside a while body (the gas microstep loop)
        count once — trip counts are not statically known, matching
        the S005 convention."""
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        hlo = ("HloModule m\n\n"
               "%while_body (wb: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {\n"
               "  %wb = (s32[], f32[4,4]{1,0}) parameter(0)\n"
               "  %i = s32[] get-tuple-element((s32[], f32[4,4]{1,0}) "
               "%wb), index=0\n"
               "  %x = f32[4,4]{1,0} get-tuple-element((s32[], "
               "f32[4,4]{1,0}) %wb), index=1\n"
               "  %ar = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} %x), "
               "replica_groups={{0,1,2,3}}, to_apply=%sum\n"
               "  ROOT %t = (s32[], f32[4,4]{1,0}) tuple(s32[] %i, "
               "f32[4,4]{1,0} %ar)\n"
               "}\n\n"
               "ENTRY %main (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {\n"
               "  %p = (s32[], f32[4,4]{1,0}) parameter(0)\n"
               "  ROOT %w = (s32[], f32[4,4]{1,0}) while((s32[], "
               "f32[4,4]{1,0}) %p), condition=%cond, body=%while_body\n"
               "}\n")
        recs = parse_hlo_collectives(hlo)
        assert len(recs) == 1
        assert recs[0]["bytes"] == 4 * 4 * 4


# ----------------------------------------------------------------------
# schedule analysis mechanics
# ----------------------------------------------------------------------

class TestAnalyzeSchedule:
    def test_sync_collective_serialized_mode_fully_exposed(self):
        """hide_sync_slack=False models serialized execution (the
        engine's overlap_comm: false twin): the wire time is fully
        exposed even though a hideable window exists."""
        a = _seeded_analysis(_SERIALIZED_HLO, hide_sync_slack=False)
        assert a.n_sync == 1 and a.n_async == 0
        c = a.collectives[0]
        assert c.payload_bytes == 8192 * 1024 * 4
        assert c.t_comm_s == pytest.approx(
            c.payload_bytes * (7 / 8) / 100e9)
        assert c.exposed_s == pytest.approx(c.t_comm_s)  # no overlap
        # the two 4 MiB instructions sit between it and its consumer:
        # 2/3 of the program's 1s compute leg
        assert c.slack_s == pytest.approx(2 / 3, rel=1e-3)
        assert a.step_time_s == pytest.approx(1.0 + c.t_comm_s)

    def test_sync_collective_slack_credited_by_default(self):
        """The default models XLA's latency-hiding scheduler: a sync
        collective with a real consumer window is credited
        min(slack, wire) of achieved overlap."""
        a = _seeded_analysis(_SERIALIZED_HLO)
        c = a.collectives[0]
        assert c.slack_s == pytest.approx(2 / 3, rel=1e-3)
        assert c.overlap_s == pytest.approx(c.t_comm_s)
        assert c.exposed_s == 0.0
        assert a.n_hidden_sync == 1
        assert a.step_time_s == pytest.approx(1.0)

    def test_async_window_overlap_reduces_exposure(self):
        a = _seeded_analysis(_ASYNC_HLO)
        assert a.n_async == 1 and a.n_sync == 0
        c = a.collectives[0]
        # the whole compute leg sits inside the start..done window and
        # dwarfs the wire time: fully hidden
        assert c.overlap_s == pytest.approx(2 / 3, rel=1e-3)
        assert c.exposed_s == 0.0
        assert a.step_time_s == pytest.approx(1.0)

    def test_identity_groups_carry_no_wire_time(self):
        hlo = ("%ar = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} %x), "
               "replica_groups={{0},{1},{2},{3}}, to_apply=%sum\n"
               "ENTRY %main (x: f32[4,4]) -> f32[4,4] {\n"
               "  %x = f32[4,4]{1,0} parameter(0)\n"
               "  ROOT %ar2 = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} "
               "%x), replica_groups={{0},{1},{2},{3}}, to_apply=%sum\n"
               "}\n")
        a = _seeded_analysis(hlo)
        assert all(c.t_comm_s == 0.0 for c in a.collectives)

    def test_analyze_compiled_real_program(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:8]), ("d",))
        w = jax.device_put(jnp.zeros((8, 256), jnp.float32),
                           NamedSharding(mesh, P("d")))

        def f(t):
            full = jax.lax.with_sharding_constraint(
                t, NamedSharding(mesh, P()))
            return (full @ full.T).sum()

        a = analyze_compiled(jax.jit(f).lower(w).compile(), label="x")
        assert a is not None and a.n_devices == 8
        assert a.n_collectives >= 1
        assert a.step_time_s > 0
        assert a.collectives[0].groups  # iota form expanded


# ----------------------------------------------------------------------
# S007: exposed-collective time
# ----------------------------------------------------------------------

class TestExposedCommCheck:
    def test_serialized_collective_fires_exactly_once(self):
        a = _seeded_analysis(_SERIALIZED_HLO, hide_sync_slack=False)
        out = check_exposed_comm(a)
        assert len(out.findings) == 1
        f = out.findings[0]
        assert f.rule == "S007" and f.severity == "error"
        assert "could overlap" in f.message

    def test_no_hideable_compute_is_silent(self):
        """Exposed but with its consumer scheduled right behind it:
        there is nothing to hide behind — not a schedule bug."""
        a = _seeded_analysis(_NO_SLACK_HLO)
        assert check_exposed_comm(a).ok

    def test_hidden_async_collective_is_silent(self):
        a = _seeded_analysis(_ASYNC_HLO)
        assert check_exposed_comm(a).ok

    def test_below_floor_is_silent(self):
        a = _seeded_analysis(_SERIALIZED_HLO, hide_sync_slack=False)
        out = check_exposed_comm(a, min_exposed_us=1e6)
        assert out.ok

    def test_baseline_regression_fires(self):
        a = _seeded_analysis(_SERIALIZED_HLO,
                             hide_sync_slack=False)  # ~293us exposed
        out = check_exposed_comm(a, baseline={"exposed_us": 10.0})
        msgs = [f.message for f in out.findings]
        assert any("regressed" in m for m in msgs)

    def test_baseline_within_tolerance_silent(self):
        a = _seeded_analysis(_NO_SLACK_HLO)
        cur = a.exposed_s * 1e6
        out = check_exposed_comm(a, baseline={"exposed_us": cur})
        assert out.ok


# ----------------------------------------------------------------------
# S008: hierarchy-aware placement
# ----------------------------------------------------------------------

class TestHierarchyPlacementCheck:
    def _analysis(self, groups):
        a = ScheduleAnalysis(label="t", n_devices=8)
        from deepspeed_tpu.analysis.schedule import CollectiveNode

        a.collectives.append(CollectiveNode(
            name="ar", op="all-reduce", computation="main",
            payload_bytes=64 << 20,
            group_size=len(groups[0]) if groups else 0,
            groups=groups))
        return a

    def test_dcn_straddling_group_fires_exactly_once(self):
        a = self._analysis([[0, 1, 2, 3, 4, 5, 6, 7]])
        out = check_hierarchy_placement(
            a, PodTopology(slice_devices=4), target_devices=[256])
        assert len(out.findings) == 1
        f = out.findings[0]
        assert f.rule == "S008" and f.severity == "error"
        assert "straddle" in f.message and "256dev" in f.message

    def test_within_slice_groups_silent(self):
        a = self._analysis([[0, 1, 2, 3], [4, 5, 6, 7]])
        assert check_hierarchy_placement(
            a, PodTopology(slice_devices=4)).ok

    def test_degree_one_crossing_silent(self):
        """One member per slice is ALREADY the hierarchical layout —
        nothing to decompose."""
        a = self._analysis([[0, 4], [1, 5], [2, 6], [3, 7]])
        assert check_hierarchy_placement(
            a, PodTopology(slice_devices=4)).ok

    def test_no_topology_is_silent(self):
        a = self._analysis([[0, 1, 2, 3, 4, 5, 6, 7]])
        assert check_hierarchy_placement(a, None).ok

    def test_flat_world_group_projects_to_pod(self):
        """An unstated (flat) replica group spans every slice of the
        projected world — the ZeRO-over-DCN shape."""
        a = self._analysis([])
        out = check_hierarchy_placement(
            a, PodTopology(slice_devices=8, num_slices=4))
        assert len(out.findings) == 1
        assert "8x" in out.findings[0].message  # 32/4 members per slice

    def test_permute_pairs_classified(self):
        from deepspeed_tpu.analysis.schedule import CollectiveNode

        a = ScheduleAnalysis(label="t", n_devices=8)
        a.collectives.append(CollectiveNode(
            name="cp", op="collective-permute", computation="main",
            payload_bytes=64 << 20, group_size=0,
            pairs=[(0, 4), (4, 0)]))
        # cross-slice pairs but degree 2/2=1: hierarchical decomposition
        # cannot help a point-to-point edge — silent
        assert check_hierarchy_placement(
            a, PodTopology(slice_devices=4)).ok


# ----------------------------------------------------------------------
# S009: critical-path step-time
# ----------------------------------------------------------------------

class TestStepTimeCheck:
    def test_comm_dominated_fires_exactly_once(self):
        a = _seeded_analysis(_COMM_BOUND_HLO, bytes_accessed=1e5)
        out = check_step_time(a)
        assert len(out.findings) == 1
        f = out.findings[0]
        assert f.rule == "S009" and f.severity == "error"
        assert "comm-dominated" in f.message

    def test_compute_dominated_is_silent(self):
        a = _seeded_analysis(_SERIALIZED_HLO)  # 1s compute vs 293us
        assert check_step_time(a).ok

    def test_drift_growth_fires_error(self):
        a = _seeded_analysis(_SERIALIZED_HLO)
        cur = a.step_time_s * 1e6
        out = check_step_time(a, baseline={"step_time_us": cur * 0.7})
        assert len(out.findings) == 1
        assert out.findings[0].severity == "error"
        assert "drifted" in out.findings[0].message

    def test_drift_shrink_warns(self):
        a = _seeded_analysis(_SERIALIZED_HLO)
        cur = a.step_time_s * 1e6
        out = check_step_time(a, baseline={"step_time_us": cur * 1.5})
        assert len(out.findings) == 1
        assert out.findings[0].severity == "warning"

    def test_within_tolerance_silent(self):
        a = _seeded_analysis(_SERIALIZED_HLO)
        cur = a.step_time_s * 1e6
        assert check_step_time(
            a, baseline={"step_time_us": cur * 1.05}).ok

    def test_step_time_replaces_three_leg_sum(self):
        """The projection is serial-roofline + EXPOSED comm — a fully
        hidden collective costs nothing, unlike the leg sum."""
        hidden = _seeded_analysis(_ASYNC_HLO)
        serial = _seeded_analysis(_SERIALIZED_HLO, hide_sync_slack=False)
        assert hidden.t_comm_s > 0
        assert hidden.step_time_s == pytest.approx(hidden.t_compute_s)
        assert serial.step_time_s > serial.t_compute_s


# ----------------------------------------------------------------------
# real programs stay silent / wiring
# ----------------------------------------------------------------------

class TestRealProgramsSilent:
    @pytest.fixture(scope="class")
    def engine(self):
        mcfg = T.TransformerConfig(
            vocab_size=128, n_layers=2, n_heads=4, d_model=64,
            max_seq=32, variant="llama", use_flash=False)
        return ds.initialize(
            {"train_micro_batch_size_per_gpu": 1,
             "gradient_accumulation_steps": 2,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 3,
                                   "param_persistence_threshold": 64},
             "bf16": {"enabled": True},
             "mesh": {"data": 4, "model": 2},
             "steps_per_print": 10**9},
            loss_fn=T.make_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg)), mcfg

    def test_train_step_schedule_clean_with_aligned_topology(self, engine):
        """The real zero-3+TP step carries S007/S009 silently, and with
        a topology whose DCN tier spans the data axis (model innermost
        = ICI) S008 is silent too — every model-axis group stays inside
        one slice and the data-axis groups run one member per slice."""
        eng, _ = engine
        batch = {"tokens": np.zeros(
            (eng.config.train_batch_size, 33), np.int32)}
        rep = eng.sanitize(
            batch, target_topology=PodTopology(slice_devices=2))
        sched_rules = [f for f in rep.findings
                       if f.rule in ("S007", "S008", "S009")]
        assert sched_rules == [], rep.render()
        assert rep.cost is not None
        assert rep.cost.step_time_s > 0
        assert rep.cost.schedule["n_collectives"] > 0

    def test_misaligned_topology_fires_s008(self, engine):
        """The SAME healthy program under a topology that puts slice
        boundaries through the replica groups: S008 must surface the
        DCN-straddling collectives."""
        eng, _ = engine
        batch = {"tokens": np.zeros(
            (eng.config.train_batch_size, 33), np.int32)}
        rep = eng.sanitize(
            batch, target_topology=PodTopology(slice_devices=4,
                                               min_saving_us=0.0))
        s008 = [f for f in rep.findings if f.rule == "S008"]
        assert len(s008) >= 1
        assert all(f.rule == "S008" for f in s008)

    def test_serving_decode_schedule_clean(self):
        from deepspeed_tpu.inference import init_inference

        cfg = T.TransformerConfig(
            vocab_size=128, n_layers=2, n_heads=4, d_model=64,
            max_seq=32, variant="llama", use_flash=False)
        params = T.init(cfg, jax.random.PRNGKey(0))
        eng = init_inference(
            params, cfg,
            dict(max_seq_len=32, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32)
        eng.warmup(widths=[8], chunked=False, decode_chunks=(),
                   footprint=True)
        fp = eng.warmup_footprints[8]
        assert "step_time_us" in fp and fp["step_time_us"] > 0
        assert fp["exposed_comm_us"] < 50.0  # silent on the decode bucket


# ----------------------------------------------------------------------
# expert-axis collective parsing (dropless MoE, moe/dropless.py)
# ----------------------------------------------------------------------

class TestExpertCollectiveParsing:
    """The dropless wire's all-to-all must be attributed EXACTLY ONCE
    with 'expert'-axis replica groups — the contract engine.sanitize's
    S005/S007/S009 checks (and the committed train_step_moe baselines)
    depend on. Under the installed XLA the partitioner lowers this
    forward's resharding pair as ONE tuple all-to-all (two operands,
    iota-form groups `[2,2]<=[4]`) plus a collective-permute/all-gather
    leg — the a2a record is what these tests pin."""

    EP = 2

    @pytest.fixture(scope="class")
    def moe_compiled(self):
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from deepspeed_tpu.moe import dropless_moe_ffn

        devs = np.array(jax.devices()[:4]).reshape(2, self.EP)
        mesh = Mesh(devs, ("data", "expert"))

        def sh(x, *spec):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*spec)))

        r = np.random.default_rng(0)
        rw = jnp.asarray(r.normal(size=(16, 4)), jnp.float32)
        w_in = jnp.asarray(r.normal(size=(4, 16, 32)), jnp.float32) * 0.1
        w_gate = jnp.asarray(r.normal(size=(4, 16, 32)), jnp.float32) * 0.1
        w_out = jnp.asarray(r.normal(size=(4, 32, 16)), jnp.float32) * 0.1

        def fwd(t):
            return dropless_moe_ffn(
                t, rw, w_in, w_out, w_gate=w_gate, act=jax.nn.silu,
                top_k=2, shard=sh, ep_size=self.EP).out

        toks = jnp.zeros((64, 16), jnp.float32)
        with mesh:
            return jax.jit(fwd).lower(toks).compile()

    def test_a2a_counted_once_with_expert_groups(self, moe_compiled):
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        recs = parse_hlo_collectives(moe_compiled.as_text())
        a2a = [c for c in recs if c["op"] == "all-to-all"]
        # one tuple op, counted once (its get-tuple-element consumers
        # and any async -start/-done forms must not double-count), with
        # both operands' bytes
        assert len(a2a) == 1, recs
        assert a2a[0]["group_size"] == self.EP
        assert a2a[0]["bytes"] == 2 * (2 * 32 * 32 * 4)

    def test_replica_groups_are_expert_pairs(self, moe_compiled):
        """The a2a replica groups pair devices ALONG the expert axis —
        {2k, 2k+1} under the (data=2, expert=2) mesh — never across
        data rows. The text carries them in iota form."""
        from deepspeed_tpu.profiling.hlo import parse_replica_groups

        groups = [
            tuple(g)
            for line in moe_compiled.as_text().splitlines()
            if " all-to-all(" in line
            for g in parse_replica_groups(line)]
        assert groups, "no a2a replica groups parsed"
        for g in groups:
            assert len(g) == self.EP
            assert g[1] == g[0] + 1 and g[0] % self.EP == 0, groups

    def test_s005_quiet_on_expert_dispatch(self, moe_compiled):
        """The a2a pair is a legitimate dispatch, not an accidental-
        replication all-gather blowup: S005 stays silent."""
        from deepspeed_tpu.analysis.costmodel import (
            build_cost_report,
            check_collective_volume,
        )

        rep = build_cost_report(moe_compiled, label="moe[fwd]")
        assert rep is not None
        chk = check_collective_volume(rep, live_sharded_bytes=None,
                                      k=6.0, label="moe[fwd]")
        assert chk.ok, chk.render()
        # the a2a's bytes land in the report's per-op volume table
        a2a = rep.collectives.get("all-to-all", {})
        assert a2a.get("count") == 1 and a2a.get("bytes", 0) > 0


# ----------------------------------------------------------------------
# autotuner AOT score (satellite)
# ----------------------------------------------------------------------

class TestAutotunerAot:
    def _tuner(self):
        from deepspeed_tpu.autotuning.autotuner import Autotuner

        mcfg = T.TransformerConfig(
            vocab_size=128, n_layers=2, n_heads=4, d_model=64,
            max_seq=32, variant="llama", use_flash=False)
        base = {
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 10**9,
            "mesh": {"data": 8},
        }
        return Autotuner(
            base, loss_fn=T.make_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg),
            make_batch=lambda b: {"tokens": np.zeros((b, 33), np.int32)},
        )

    # known-good pure-DP config vs a deliberately comm-bound one:
    # zero-3 with zero persistence + TP over a toy d_model re-gathers
    # every param and psums every activation — far more wire bytes per
    # sample than the plain data-parallel step
    GOOD = {"zero_stage": 1, "micro_batch_size": 2, "mesh": {"data": 8}}
    BAD = {"zero_stage": 3, "micro_batch_size": 1,
           "mesh": {"data": 2, "model": 4}}

    def test_aot_ranks_good_above_comm_bound(self, tmp_path):
        tuner = self._tuner()
        tuner.results_dir = str(tmp_path)
        ranked = tuner.aot_rank([self.BAD, self.GOOD])
        assert ranked[0].get("aot_ok"), ranked[0]
        assert ranked[0]["mesh"] == {"data": 8}
        assert ranked[0]["aot_samples_per_sec"] > \
            ranked[1]["aot_samples_per_sec"]
        # the comm-bound candidate pays more exposed wire time AND more
        # projected step time per sample
        good_batch = 2 * 8   # micro 2 x dp 8
        bad_batch = 1 * 2    # micro 1 x dp 2
        assert ranked[1]["aot_exposed_comm_s"] / bad_batch > \
            ranked[0]["aot_exposed_comm_s"] / good_batch
        assert ranked[1]["aot_step_time_s"] / bad_batch > \
            ranked[0]["aot_step_time_s"] / good_batch

    def test_tune_aot_skips_trials_and_is_deterministic(self, tmp_path):
        """trial=False must never execute a step — and the ranked
        top-k list must be byte-deterministic for equal inputs."""
        tuner = self._tuner()
        tuner.results_dir = str(tmp_path)

        def boom(*a, **k):
            raise AssertionError("trial execution must be stubbed out")

        tuner._measure = boom
        cfg = tuner.tune_aot(candidates=[self.BAD, self.GOOD],
                             trial=False)
        assert cfg["mesh"] == {"data": 8}
        assert cfg["train_micro_batch_size_per_gpu"] == 2
        ledger = [r for r in tuner.results if r.get("phase") == "aot"]
        assert len(ledger) == 2
        assert os.path.exists(os.path.join(str(tmp_path), "exps.jsonl"))

    def test_rank_order_deterministic_under_ties(self):
        """Equal scores sort by the canonical candidate key — the
        top-k trial list cannot depend on dict order or randomness."""
        from deepspeed_tpu.autotuning.autotuner import Autotuner

        tuner = Autotuner({"train_micro_batch_size_per_gpu": 1},
                          loss_fn=None, param_init_fn=None,
                          make_batch=lambda b: None)
        cands = [{"zero_stage": s, "micro_batch_size": m}
                 for s in (3, 1, 2) for m in (4, 1)]
        tuner.aot_score = lambda c, **k: {
            **c, "aot_ok": True, "aot_samples_per_sec": 7.0}
        first = [tuner._aot_key(e) for e in tuner.aot_rank(cands)]
        second = [tuner._aot_key(e) for e in tuner.aot_rank(
            list(reversed(cands)))]
        assert first == second == sorted(first)


# ----------------------------------------------------------------------
# link-table single authority (satellite)
# ----------------------------------------------------------------------

class TestLinkAuthority:
    def test_costmodel_reexports_links(self):
        from deepspeed_tpu.analysis.costmodel import ICI_GBPS
        from deepspeed_tpu.platform.accelerator import LINKS

        assert ICI_GBPS == LINKS["ici_bytes_per_s"]
        assert LINKS["dcn_bytes_per_s"] < LINKS["ici_bytes_per_s"]

    def test_accelerator_methods_read_table(self):
        from deepspeed_tpu.platform.accelerator import (
            LINKS,
            get_accelerator,
        )

        acc = get_accelerator()
        assert acc.ici_bandwidth() == LINKS["ici_bytes_per_s"]
        assert acc.dcn_bandwidth() == LINKS["dcn_bytes_per_s"]

    def test_no_consumer_redeclares_the_constant(self):
        """The drift guard: only platform/accelerator.py may spell the
        link bandwidths; every consumer imports the table."""
        import deepspeed_tpu.analysis.costmodel as cm
        import deepspeed_tpu.analysis.schedule as sc

        consumers = [
            cm.__file__, sc.__file__,
            os.path.join(REPO, "scripts", "ici_projection.py"),
        ]
        for path in consumers:
            src = open(path, "r", encoding="utf-8").read()
            assert "100e9" not in src and "6.25e9" not in src, (
                f"{path} re-declares a link constant; import "
                "platform.accelerator.LINKS instead")
            assert "LINKS" in src

    def test_default_topology_uses_links(self):
        from deepspeed_tpu.platform.accelerator import LINKS

        t = PodTopology(slice_devices=4)
        assert t.ici_bandwidth == LINKS["ici_bytes_per_s"]
        assert t.dcn_bandwidth == LINKS["dcn_bytes_per_s"]


# ----------------------------------------------------------------------
# ds_schedule CLI gate
# ----------------------------------------------------------------------

@pytest.mark.slow
class TestDsScheduleScript:
    """Slow lane: each subprocess rebuilds EVERY canonical program via
    the budget gate's builder (the MoE zero3+EP+TP engine included) — and
    the pre-test gate lane already runs `ds_gate.py schedule --check
    --strict` on every PR, so the fast lane carries no coverage gap."""

    def _run(self, *args):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # the script sets its own device count
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "ds_gate.py"),
             "schedule", *args],
            capture_output=True, text=True, env=env, cwd=REPO,
            timeout=600)

    def test_check_passes_on_committed_tree(self):
        r = self._run("--check", "--strict")
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(r.stdout.strip().splitlines()[-1])
        assert doc["ok"] and doc["findings"] == []

    def test_check_fails_on_injected_regression(self, tmp_path):
        base = json.load(open(os.path.join(REPO, "SCHEDULE.json")))
        # shrink the recorded projection so the (unchanged) tree reads
        # as a >= 10% step-time regression
        for prog in base["programs"].values():
            prog["step_time_us"] = prog["step_time_us"] * 0.7
        injected = tmp_path / "schedule.json"
        injected.write_text(json.dumps(base))
        r = self._run("--check", "--baseline", str(injected))
        assert r.returncode != 0, r.stdout + r.stderr
        doc = json.loads(r.stdout.strip().splitlines()[-1])
        assert not doc["ok"]
        assert any(f["rule"] == "S009" and "drifted" in f["message"]
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
        assert all(p["step_time_us"] > 0
                   for p in doc["programs"].values())
        assert doc["programs"]["train_step"]["n_collectives"] > 0
        assert doc["programs"]["train_step_moe"]["n_collectives"] > 0
        # the interleaved-pipeline entry commits the interleave-wins
        # pin: V=2's projection strictly below its V=1 twin's
        pp = doc["programs"]["train_step_pipe3d"]["pipe_projection"]
        assert pp["v2_step_time_us"] < pp["v1_step_time_us"]
        # the fused int8-KV decode entry commits its S006 verdict and
        # the gather-materialization probe
        q = doc["programs"]["serving_decode_w8_int8"]
        assert q["s006_bound"] == "memory"
        assert 0 < q["max_gather_bytes"] <= q["gather_bytes_limit"]
        r = self._run("--check", "--strict", "--baseline", str(out))
        assert r.returncode == 0, r.stdout + r.stderr
