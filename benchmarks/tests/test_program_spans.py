"""benchmarks/trace/program_spans.py and the readers built on it: the
clock alignment, the gap naming, and every reader on a hand-made run
and on a run that has nothing to read (a parent commit)."""

import json

import pytest

from benchmarks import harness
from benchmarks.trace import program_spans as PS
from benchmarks.trace import reduce as R

# the program's clock runs 1234.5 s ahead of the trace's
OFFSET = -1234.5
PER_LAYER = {m["name"]: m for m in harness.load_json(
    harness.ROOT / "BENCHMARK.json")["per_layer"]}


def hand_made():
    """Ten 40 ms iterations: a tick of 1 ms, 3 ms of host phases, the
    device busy for 36 ms from 0.2 ms after each launch. Trace clock in
    seconds from 0; the buffer is on the program's clock."""
    ops, modules, bench, prog = [], [], [], []
    sid = iter(range(1, 10_000))
    bench.append(R.Event(R.WINDOW_SPAN, 0.0, 0.41))
    for i in range(10):
        t = 0.040 * i
        it = next(sid)
        p0 = t - OFFSET
        phases = [("sched.tick", 0.0, 1.0), ("sched.select", 1.0, 1.1),
                  ("sched.readback", 1.1, 1.2), ("sched.accept", 1.2, 1.7),
                  ("sched.admit", 1.7, 1.9), ("sched.select", 1.9, 2.2),
                  ("sched.build", 2.2, 3.2),
                  ("sched.launch", 3.2, 3.7), ("sched.commit", 3.7, 4.0),
                  ("sched.readback", 4.0, 40.0)]
        prog.append(PS.PSpan("sched.iteration", p0, p0 + 0.040, it, 0,
                             {"iteration": i + 1}))
        for name, a, b in phases:
            prog.append(PS.PSpan(name, p0 + a * 1e-3, p0 + b * 1e-3,
                                 next(sid), it, {}))
        # the runner's span opens 2 us before the program's stamp
        bench.append(R.Event("bench.sched_iteration", t + 1e-3 - 2e-6, 0.039))
        modules.append(R.Event("jit_step(1)", t + 3.4e-3, 0.036))
        ops.append(R.Event("fusion.1", t + 3.4e-3, 0.036))
    prog.append(PS.PSpan("request", 0.0 - OFFSET, 0.4 - OFFSET, next(sid), 0,
                         {"rid": 1}))
    prog += [
        PS.PSpan("init.inference", 10.0, 14.0, 9001, 0,
                 {"peak_bytes_in_use": 16_000_000_000}),
        PS.PSpan("warmup.program", 20.0, 50.0, 9002, 0,
                 {"kind": "decode", "width": 128,
                  "peak_bytes_in_use": 14_000_000_000}),
        PS.PSpan("warmup.trace", 20.0, 30.0, 9003, 9002, {}),
        PS.PSpan("warmup.lower", 30.0, 45.0, 9004, 9002, {}),
        PS.PSpan("warmup.compile", 45.0, 47.0, 9005, 9002, {}),
        PS.PSpan("warmup.execute", 47.5, 48.0, 9006, 9002, {})]
    td = R.from_events({0: ops}, {0: modules}, bench)
    return td, sorted(prog, key=lambda s: s.start)


def train_buffer():
    spans, sid = [], iter(range(1, 100))
    for i in range(3):
        t, b = 100.0 + 0.5 * i, next(sid)
        spans.append(PS.PSpan("train.batch", t, t + 0.5, b, 0, {"step": i}))
        for name, a, d in (("train.prepare", 0.0, 0.002),
                           ("train.launch", 0.002, 0.001),
                           ("train.readback", 0.003, 0.4965),
                           ("train.post", 0.4995, 0.0005)):
            spans.append(PS.PSpan(name, t + a, t + a + d, next(sid), b, {}))
    spans += [PS.PSpan("train.init", 1.0, 9.0, 200, 0, {}),
              PS.PSpan("train.init.state", 2.0, 8.0, 201, 200, {}),
              PS.PSpan("train.compile", 20.0, 60.0, 202, 0, {}),
              PS.PSpan("train.compile", 70.0, 72.0, 203, 0, {})]
    return sorted(spans, key=lambda s: s.start)


def test_offset_recovered_within_5us():
    td, prog = hand_made()
    # an unpaired leading anchor (the iteration the capture began in)
    # and program anchors from outside the window must not mislead it
    td.spans.append(R.Event("bench.sched_iteration", -0.0395, 0.039))
    extra = [PS.PSpan("sched.tick", 0.6 - OFFSET + 0.041 * k,
                      0.601 - OFFSET + 0.041 * k, 5000 + k, 0, {})
             for k in range(30)]
    got = PS.align(*PS.anchor_times(td, prog + extra))
    assert got is not None
    off, residual, matched = got
    assert abs(off - OFFSET) < 5e-6
    assert matched == 10 and residual < 5e-6


def test_alignment_needs_an_anchor_on_both_sides():
    td, prog = hand_made()
    assert PS.align([], [1.0]) is None and PS.align([1.0], []) is None
    assert PS._load(td, [s for s in prog if s.name != "sched.tick"]) is None
    assert PS._load(None, prog) is None and PS._load(td, None) is None
    # anchors that fit nowhere within the tolerance give no clock
    assert PS.align([0.0, 0.01], [5.0]) is not None  # one pair always fits
    assert PS.align([0.0, 0.013, 0.1], [5.0, 5.05])[2] == 1


def test_gaps_are_named_by_the_shortest_program_span():
    td, prog = hand_made()
    out = PS._load(td, prog)
    gaps = out["gaps"]
    # the device idles from each program's end to the next one's start
    # (4 ms, its midpoint 1.4 ms into the next iteration: `accept`),
    # and for 3.4 ms at the window's start (midpoint in `admit`)
    # and, longest, for 10.6 ms after the last iteration: no span there
    assert gaps[0][0] == PS.NO_SPAN
    assert gaps[0][1] == pytest.approx(0.0106, abs=1e-5)
    assert [g[0] for g in gaps[1:10]] == ["sched.accept"] * 9
    assert gaps[1][1] == pytest.approx(0.004, abs=1e-5)
    assert gaps[10][0] == "sched.admit"
    assert PS.named_share(gaps) == pytest.approx(0.0394 / 0.05, rel=1e-3)
    # a request's span covers everything and names nothing
    assert "request" not in {g[0] for g in gaps}
    # without the leaf spans, the parent alone does not count as named
    parents = [s for s in out["spans"] if s.name in PS.PARENTS]
    assert PS.named_share(PS.name_gaps(td, parents)) == 0.0
    assert PS.named_share([]) is None


def test_self_time_and_phase_medians():
    td, prog = hand_made()
    own = PS.self_s(prog)
    it = PS.named(prog, "sched.iteration")[0]
    assert own[it.sid] == pytest.approx(0.0, abs=1e-9)
    assert own[9002] == pytest.approx(30.0 - 27.5)
    med = PS._load(td, prog)["phase_medians_ms"]
    assert med["sched.select"] == pytest.approx(0.4)
    assert med["sched.readback"] == pytest.approx(36.1)
    assert med["sched.iteration"] == pytest.approx(40.0)


def reader(name):
    return harness.load_module(harness.BENCH_DIR / "metrics" / f"{name}.py")


WANT = {
    "sched_tick_ms_per_step": 1.0, "sched_admit_ms_per_step": 0.2,
    "sched_select_ms_per_step": 0.4, "sched_build_ms_per_step": 1.0,
    "sched_launch_ms_per_step": 0.5, "sched_commit_ms_per_step": 0.3,
    "sched_accept_ms_per_step": 0.5,
    "sched_readback_wait_ms_per_step": 36.1,
    "sched_queue_wait_mean_ms": 2500.0, "sched_slow_iterations": 2.0,
    "serve_launch_to_device_ms": 0.202,  # 2 us of it the anchor's bias
    "serve_idle_named_share": 100 * 0.0394 / 0.05,
    "warmup_trace_lower_s": 25.0, "warmup_compile_s": 2.0,
    "warmup_execute_s": 0.5, "init_inference_s": 4.0,
    "train_host_ms_per_step": 3.5, "train_readback_wait_ms_per_step": 496.5,
    "train_engine_init_s": 8.0, "train_compile_s": 42.0,
}


def test_every_case_is_a_metric_of_the_benchmark():
    """By name, not by position in the file: a later PR adds entries
    anywhere, and the readers it adds bring their own cases."""
    assert set(WANT) <= set(PER_LAYER)
    assert {PER_LAYER[n]["source"] for n in WANT} == {
        "program_span", "program_counter", "device_trace"}


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_a_hand_made_run(name, monkeypatch, capsys):
    td, prog = hand_made()
    train = name.startswith("train_")
    monkeypatch.setattr(PS, "records", lambda: train_buffer() if train else prog)
    steps = 10
    obs = {"trace": td, "counters_delta": {
        "steps": steps, "admitted": 4, "queue_wait_s": 10.0,
        "slow_iterations": 2, "tick_s": 0.010, "admit_s": 0.002,
        "select_s": 0.004, "build_s": 0.010, "launch_s": 0.005,
        "commit_s": 0.003, "accept_s": 0.005, "readback_wait_s": 0.361}}
    assert reader(name).read(obs) == pytest.approx(WANT[name], rel=1e-3)
    capsys.readouterr()


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_on_a_program_without_spans(name, monkeypatch):
    """The parent commit: no buffer, and counters without the time
    sums. Nothing is returned and nothing raises."""
    td, _ = hand_made()
    monkeypatch.setattr(PS, "records", lambda: None)
    for obs in ({}, {"trace": None, "counters_delta": {}},
                {"trace": td, "counters_delta": {"steps": 10, "admitted": 4,
                                                 "batched_tokens": 80}}):
        assert reader(name).read(dict(obs)) is None


def test_records_reads_the_programs_buffer():
    from deepspeed_tpu.utils import profiler

    profiler.clear()
    with profiler.span("init.pool", always=True, width=8):
        pass
    (s,) = PS.records()
    assert (s.name, s.parent, s.ids["width"]) == ("init.pool", 0, 8)
    assert s.end >= s.start
    profiler.clear()


def test_load_is_computed_and_logged_once(monkeypatch, capsys):
    td, prog = hand_made()
    monkeypatch.setattr(PS, "records", lambda: prog)
    obs = {"trace": td}
    first = PS.load(obs)
    out = capsys.readouterr().out
    assert "largest residual" in out and out.count("[bench] idle gap") == 10
    assert "median per traced iteration" in out
    assert PS.load(obs) is first and capsys.readouterr().out == ""
    PS.setup_spans(obs)
    out = capsys.readouterr().out
    assert "set-up span warmup.program" in out
    assert "16.000 GB, first at the end of init.inference" in out
    json.dumps(first["phase_medians_ms"])  # plain numbers


# -- events and spans recorded from a chip run ---------------------------------

RECORDED = harness.BENCH_DIR / "trace" / "recorded" / "serve-chat-saturated-spans.json"


def recorded():
    """Three iterations of `serve-chat-saturated` on a v5e (PR 23): the
    kept device events and `bench.*` spans as `Capture._keep` writes
    them, plus the program's buffer (its clock, nanoseconds)."""
    from benchmarks.trace.capture import load_recorded

    td = load_recorded(RECORDED)
    rows = harness.load_json(RECORDED)["program_spans"]
    return td, [PS.PSpan(n, a * 1e-9, b * 1e-9, sid, parent, ids)
                for n, a, b, sid, parent, ids in rows]


def test_recorded_run_aligns_and_names_its_gaps():
    td, prog = recorded()
    out = PS._load(td, prog)
    assert out["matched"] >= 3 and out["residual_s"] < 5e-6
    # the 4 ms gap of every iteration falls where the host still waits
    # for the tokens, and nearly all idle time is in a leaf span
    # (the iteration the profiler started in was begun untraced: the
    # gap at the window's start has no span, in every traced run)
    t_first = min(s.start for s in out["spans"] if s.name == "sched.iteration")
    gaps = [g for g in out["gaps"] if g[2] >= t_first]
    assert [g[0] for g in out["gaps"] if g[2] < t_first and g[1] > 1e-3] \
        == [PS.NO_SPAN]
    big = [g for g in gaps if g[1] > 1e-3]
    assert len(big) >= 3 and {g[0] for g in big} <= {
        "sched.readback", "sched.accept", "sched.select", "sched.build",
        "sched.launch"}
    assert PS.named_share(gaps) > 0.95
    med = out["phase_medians_ms"]
    phases = sum(v for k, v in med.items() if k != "sched.iteration")
    assert phases == pytest.approx(med["sched.iteration"], rel=0.05)
    assert 30 < med["sched.readback"] < 40 and med["sched.launch"] > 1.0
    xs = PS.launch_to_device_s(td, out["spans"])
    assert len(xs) >= 3 and all(abs(x) < 1e-3 for x in xs)


@pytest.mark.parametrize("name,lo,hi", [
    ("warmup_trace_lower_s", 20, 120), ("warmup_compile_s", 0.5, 60),
    ("warmup_execute_s", 0.001, 2), ("init_inference_s", 2, 20),
    ("serve_idle_named_share", 80, 100),  # 3 of 4 iterations have spans
    ("serve_launch_to_device_ms", -1, 1)])
def test_reader_on_the_recorded_run(name, lo, hi, monkeypatch, capsys):
    td, prog = recorded()
    monkeypatch.setattr(PS, "records", lambda: prog)
    assert lo < reader(name).read({"trace": td}) < hi
    capsys.readouterr()
