"""The trace reducer on hand-made cases and on events recorded from a
chip run (benchmarks/trace/recorded/)."""

import pathlib
import re

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
    assert b["device_ops"][0] == ["1x fusion.3", pytest.approx(2.0)]
    assert len(b["idle_gaps"]) <= 5


def test_breakdown_names_carry_the_count_the_scope_and_what_is_computed():
    """`16x fusion.403 ...`: three PRs read 16 executions as one."""
    path = "jit(step_fn)/while/body/closed_call/transpose(jvp(lm_head))/while/body/dot_general:"
    ops = [E("while.2", 0.0, 9.0)] + [
        R.Event("fusion.403", float(i), 0.25, path) for i in range(8)] + [
        E("copy.7", 8.5, 0.4)]
    td = R.from_events({0: ops}, {}, [E("bench.trace_window", 0.0, 10.0)])
    td.details = {"fusion.403": "bf16[4096,8064] fusion(...)"}
    b = R.breakdown(td)["device_ops"]
    assert b[0] == ["8x fusion.403 lm_head/dot_general bf16[4096,8064] fusion(...)",
                    pytest.approx(2.0)]
    assert b[1] == ["1x copy.7", pytest.approx(0.4)]
    assert all(name.split("x ", 1)[0].isdigit() for name, _ in b)


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


def test_scope_paths_lose_their_wrappers_and_the_first_name_wins():
    p = "jit(step_fn)/while/body/closed_call/jvp()/while/body/closed_call/mlp/bsf,fe->bse/dot_general:"
    assert R.scope_components(p)[:5] == ["step_fn", "while", "body", "closed_call", ""]
    assert R.scope_of(p, ("mlp",)) == "mlp"
    assert R.scope_of(p, ("attention", "lm_head")) is None
    assert R.short_scope(p) == "mlp/dot_general"
    # autodiff wraps the scope it was entered under
    for q in ("jit(f)/jvp(mlp)/dot_general", "jit(f)/transpose(jvp(mlp))/mul:",
              "jit(f)/while/body/checkpoint/rematted_computation/mlp/tanh"):
        assert R.scope_of(q, ("mlp",)) == "mlp", q
    assert R.short_scope("jit(f)/transpose(jvp(lm_head))/while/body/checkpoint/neg:") \
        == "lm_head/neg"
    # a scope inside another counts for the outer one, once
    nested = "jit(f)/attention/flash_fwd/pallas_call"
    assert R.scope_of(nested, ("flash_fwd", "attention")) == "attention"
    assert R.scope_of(nested, ("flash_fwd",)) == "flash_fwd"
    # a name is a whole component, never a substring of one
    assert R.scope_of("jit(f)/mlp_gate/dot_general", ("mlp",)) is None
    assert R.scope_of("", ("mlp",)) is None


def test_scope_seconds_counts_leaves_inside_the_window_once():
    S = lambda name, start, dur, scope: R.Event(name, start, dur, scope)  # noqa: E731
    ops = [S("while.3", 0.0, 10.0, "jit(f)/mlp/while"),       # CONTAINS its body
           S("fusion.1", 1.0, 2.0, "jit(f)/while/body/mlp/dot_general"),
           S("fusion.1", 4.0, 2.0, "jit(f)/while/body/mlp/dot_general"),
           S("fusion.2", 6.0, 1.0, "jit(f)/while/body/attention/mlp/add"),
           S("copy.4", 7.0, 1.0, ""),                          # XLA's own: no scope
           S("fusion.9", 8.0, 1.0, "jit(f)/adamw/mul"),
           S("fusion.1", 20.0, 2.0, "jit(f)/while/body/mlp/dot_general")]  # outside
    td = R.from_events({0: ops}, {}, [E("bench.trace_window", 0.0, 12.0)])
    assert R.scope_seconds(td, ("mlp",)) == pytest.approx(5.0)
    assert R.scope_seconds(td, ("attention",)) == pytest.approx(1.0)
    # asked for both, the nested instruction is still counted once
    assert R.scope_seconds(td, ("mlp", "attention")) == pytest.approx(5.0)
    assert [R.scope_of(e.scope, ("mlp", "attention"))
            for e in R.scope_events(td, ("mlp", "attention"))] == [
        "mlp", "mlp", "attention"]
    assert R.scope_seconds(td, ("lm_head",)) is None
    assert R.scope_seconds(td, ("mlp",), device=1) is None
    assert [e.name for e in R.scope_events(td, ("adamw",))] == ["fusion.9"]


def _pb(fields):
    """A protobuf message from (number, value) pairs: int -> varint,
    bytes -> length-delimited."""
    def varint(x):
        out = bytearray()
        while True:
            out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
            x >>= 7
            if not x:
                return bytes(out)
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_metadata_scopes_reads_the_stat_from_the_wire_format(tmp_path):
    """The file's own format: `tf_op` as a string, or as a reference to
    a stat metadata whose NAME is the text; other stats, other planes
    and the lines are passed over."""
    op = "jit(f)/mlp/dot_general:"
    ref = "jit(f)/attention/add:"
    stat_md = [(5, _pb([(1, i), (2, _pb([(1, i), (2, name.encode())]))]))
               for i, name in ((1, "flops"), (26, "tf_op"), (300, ref))]
    ev_md = [
        (4, _pb([(1, 7), (2, _pb([(1, 7), (2, b"%fusion.1 = bf16[8] fusion()"),
                                  (5, _pb([(1, 1), (4, 12345)])),
                                  (5, _pb([(1, 26), (5, op.encode())]))]))])),
        (4, _pb([(1, 8), (2, _pb([(1, 8), (2, b"%fusion.2 = bf16[8] fusion()"),
                                  (5, _pb([(1, 26), (7, 300)]))]))])),
        (4, _pb([(1, 9), (2, _pb([(1, 9), (2, b"%copy.3 = bf16[8] copy()")]))]))]
    line = (3, _pb([(1, 1), (2, b"XLA Ops"), (4, _pb([(1, 7), (2, 10), (3, 20)]))]))
    plane = _pb([(1, 2), (2, b"/device:TPU:0"), line] + ev_md + stat_md)
    other = _pb([(1, 3), (2, b"/host:CPU")])
    f = tmp_path / "t.xplane.pb"
    f.write_bytes(_pb([(1, plane), (1, other), (4, b"host")]))
    assert R.metadata_scopes(str(f)) == {"/device:TPU:0": {
        "%fusion.1 = bf16[8] fusion()": op, "%fusion.2 = bf16[8] fusion()": ref}}


def test_the_four_scope_readers_on_the_recorded_step():
    """One step of `train-seq4k` kept from a chip run with its scopes:
    the readers' arithmetic on real names (never a device number)."""
    from benchmarks import harness

    td = load_recorded(RECORDED / "train-seq4k-scopes.json")
    metrics = pathlib.Path(__file__).resolve().parents[1] / "metrics"
    obs = {"trace": td, "traced_steps": 1}

    def read(name, obs=obs):
        return harness.load_module(metrics / f"{name}.py").read(obs)

    mlp, attn, head = (read(f"{n}_ms_per_step") for n in ("mlp", "attention", "head_loss"))
    step_ms = 1e3 * td.window_s
    assert head > 0
    assert mlp > attn > read("flash_ms_per_step") > 0
    assert mlp + attn + head < step_ms
    share = read("scope_named_share")
    assert 100.0 * (mlp + attn + head) / (1e3 * td.busy_s) < share <= 100.0
    for name in ("mlp_ms_per_step", "attention_ms_per_step",
                 "head_loss_ms_per_step", "scope_named_share"):
        assert read(name, {}) is None
        # a trace kept before PR 26 has no scope: nothing to read
        assert read(name, {"trace": load_recorded(RECORDED / "train-seq4k.json"),
                           "traced_steps": 3}) is None
    # the loss runs in 16 chunks: its instructions run 16 times a step,
    # which every breakdown before PR 26 showed as one slow instruction
    ops = {name.split(" ")[1]: name for name, _ in R.breakdown(td)["device_ops"]}
    assert ops["fusion.328"].startswith("16x fusion.328 lm_head/")
    assert any(name.startswith("2x ") and " mlp/" in name for name in ops.values())


@pytest.mark.parametrize("name", sorted(p.name for p in RECORDED.glob("*.json")))
def test_recorded_chip_trace_reduces(name):
    td = load_recorded(RECORDED / name)
    assert 0 < td.busy_s <= td.window_s
    b = R.breakdown(td)
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 5
    assert all(re.match(r"^\d+x \S", op) for op, _ in b["device_ops"])
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
