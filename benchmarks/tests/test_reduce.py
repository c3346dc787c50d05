"""The trace reducer on hand-made cases and on events recorded from a
chip run (benchmarks/trace/recorded/)."""

import pathlib

import pytest

from benchmarks.trace import reduce as R
from benchmarks.trace.capture import load_recorded

RECORDED = pathlib.Path(__file__).resolve().parents[1] / "trace" / "recorded"


def E(name, start, dur):
    return R.Event(name, start, dur)


def test_interval_arithmetic():
    assert R.merge([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert R.union_s([(0, 2), (1, 3), (5, 6)]) == 4
    assert R.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert R.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert R.clip([(0, 5), (8, 12), (20, 30)], (4, 10)) == [(4, 5), (8, 10)]


def test_busy_union_and_idle_gaps_named_by_covering_span():
    ops = {0: [E("fusion.1", 1.0, 1.0), E("fusion.2", 1.5, 1.0),   # overlap
               E("fusion.3", 4.0, 2.0)]}
    spans = [E("bench.trace_window", 0.0, 10.0), E("bench.sched_run", 0.0, 10.0),
             E("bench.submit", 2.6, 1.0)]
    td = R.from_events(ops, {0: []}, spans)
    assert td.window == (0.0, 10.0)
    assert td.busy_s == pytest.approx(1.5 + 2.0)
    gaps = R.idle_gaps(td)
    assert gaps[0] == ("bench.sched_run", pytest.approx(4.0))     # 6..10
    assert ("bench.submit", pytest.approx(1.5)) in gaps           # 2.5..4
    assert sum(s for _, s in gaps) == pytest.approx(10.0 - 3.5)
    b = R.breakdown(td)
    assert b["device_ops"][0] == ["fusion.3", pytest.approx(2.0)]
    assert len(b["idle_gaps"]) <= 5


def test_busy_is_averaged_over_chips():
    ops = {0: [E("a", 0.0, 2.0)], 1: [E("a", 0.0, 1.0)]}
    td = R.from_events(ops, {}, [E("bench.trace_window", 0.0, 4.0)])
    assert td.busy_s == pytest.approx(1.5)


def test_exposed_collective_arithmetic():
    # all-gather 0..4 with compute over 1..3: 2 of its 4 seconds exposed;
    # all-reduce 6..7 fully hidden; reduce-scatter 9..10 fully exposed
    ops = {0: [E("all-gather.1", 0.0, 4.0), E("fusion.1", 1.0, 2.0),
               E("fusion.2", 5.5, 2.0), E("all-reduce.7", 6.0, 1.0),
               E("reduce-scatter.2", 9.0, 1.0)]}
    td = R.from_events(ops, {}, [E("bench.trace_window", 0.0, 10.0)])
    total, exposed = R.collective_seconds(td)
    assert total == pytest.approx(6.0)
    assert exposed == pytest.approx(2.0 + 0.0 + 1.0)


def test_control_flow_containers_are_not_leaves():
    ops = [E("while.3", 0.0, 10.0), E("fusion.1", 1.0, 2.0),
           E("jvp_flash_fwd_.1", 4.0, 3.0), E("copy.2", 11.0, 1.0)]
    assert [e.name for e in R.leaves(ops)] == ["fusion.1", "jvp_flash_fwd_.1", "copy.2"]
    td = R.from_events({0: ops}, {}, [E("bench.trace_window", 0.0, 12.0)])
    assert R.kernel_seconds(td, ("flash_fwd",)) == pytest.approx(3.0)
    assert R.kernel_seconds(td, ("paged_kv_write",)) is None
    assert R.base_name("%flash_fwd.12") == "flash_fwd"
    assert R.base_name("fusion.3.1") == "fusion"


def test_programs_are_told_apart_by_the_kernel_inside():
    mods = {0: [E("jit_step(1)", 0.0, 1.0), E("jit_step(2)", 2.0, 3.0),
                E("jit_step(1)", 6.0, 1.2)]}
    ops = {0: [E("paged_decode_fused.1", 0.1, 0.2), E("paged_kv_write.1", 2.1, 0.1),
               E("paged_decode_grid.1", 2.3, 0.5), E("paged_decode_fused.1", 6.1, 0.2)]}
    td = R.from_events(ops, mods, [E("bench.trace_window", 0.0, 8.0)])
    assert [m.dur for m in R.modules_with(td, "paged_decode_fused")] == [1.0, 1.2]
    assert [m.dur for m in R.modules_with(td, "paged_decode_grid")] == [3.0]
    assert R.median([1.0, 1.2]) == pytest.approx(1.1)


@pytest.mark.parametrize("name", sorted(p.name for p in RECORDED.glob("*.json")))
def test_recorded_chip_trace_reduces(name):
    td = load_recorded(RECORDED / name)
    assert 0 < td.busy_s <= td.window_s
    b = R.breakdown(td)
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 5
    idle = sum(s for _, s in R.idle_gaps(td))
    assert idle + td.busy_s == pytest.approx(td.window_s, rel=1e-6)
    expect = {"train": ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
              "serve": ("paged_decode_grid", "paged_kv_write")}
    for kind, kernels in expect.items():
        if name.startswith(kind):
            for k in kernels:
                assert R.kernel_seconds(td, (k,)) > 0, k
    if "zero3" in name:   # the four-chip cell: gathers, mostly exposed
        total, exposed = R.collective_seconds(td)
        assert 0 < exposed <= total < td.window_s


def test_grid_roofline_and_idle_readers_on_the_recorded_serving_trace():
    """The readers of the serving cell's per-layer metrics, on events a
    chip run left (fake ticks and peaks: the share's arithmetic, not a
    device number)."""
    from benchmarks import harness

    td = load_recorded(RECORDED / "serve-doc-steady.json")
    metrics = pathlib.Path(__file__).resolve().parents[1] / "metrics"

    def read(name, obs):
        return harness.load_module(metrics / f"{name}.py").read(obs)

    n = len(R.modules_with(td, "paged_decode_grid"))
    per_iter = R.kernel_seconds(td, ("paged_decode_grid",)) / n
    obs = {"trace": td, "ticks": [(0.0, 1000, 3, 0), (0.1, 3000, 4, 0)],
           "kv_bytes_per_token": 65536, "peaks": {"hbm_bytes_per_s": 819e9},
           "hbm_in_use_bytes": 13_466_472_448}
    need = 2000 * 65536 / 819e9
    assert read("paged_decode_grid_roofline", obs) == pytest.approx(
        100.0 * need / per_iter)
    assert read("paged_decode_grid_roofline", dict(obs, ticks=[])) is None
    assert read("paged_decode_grid_roofline", dict(obs, peaks=None)) is None
    idle = read("serve_device_idle_share", obs)
    assert idle == pytest.approx(100.0 * (1 - td.busy_s / td.window_s))
    assert read("train_device_idle_share", obs) == idle
    assert read("serve_hbm_in_use_gb", obs) == pytest.approx(13.466472448)
    assert read("train_hbm_in_use_gb", {}) is None
    assert 0 < read("mixed_step_share", obs) <= 100
    assert read("paged_grid_ms_per_step", obs) >= 1e3 * per_iter
