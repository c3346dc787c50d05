"""The readers of what a stalled iteration leaves behind (PR 53): the
stall rule's counters, the collector's time, and the idle share with
the named stalls taken out. Their arithmetic on hand-made `obs`, nothing
(no raise) on a program without the counters or the spans, which is
what the parent commit of the PR that added them is, the idle share on
the recorded chip run with a kept span made up over its longest gap,
and the four entries.

The four readers are NOT entries of BENCHMARK.json yet: appended after
`sched_lookahead_share` they break two pins only a PR of kind
`benchmark` may edit (test_sched_lookahead_share.py and
test_qwen3next_readers.py hold `per_layer[-1]` to that entry, the
latter also the count of a cell's per-layer metrics to 24). `ENTRIES`
below is what that PR appends, in this order, after the entries the
older `test_*_readers.py` files wait with."""

import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import program_spans as PS
from benchmarks.trace import reduce as R

BENCH = pathlib.Path(__file__).resolve().parents[1]
NEW = ("sched_stall_iterations", "sched_stall_share", "host_gc_ms_per_step",
       "serve_idle_steady_share")
RECORDED = BENCH / "trace" / "recorded" / "serve-chat-saturated-spans.json"


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


# a run of 51 s that lost 2.1 s to one stall inside `readback` and 90 ms
# to one in `tick`, with the collector in 40 of its 2,550 iterations
DELTA = {"steps": 2550, "stall_iterations": 2, "stall_s": 2.19,
         "stall_readback_s": 2.1, "gc_s": 0.051, "gc_collections": 40,
         "slow_iterations": 1}


@pytest.mark.parametrize("name, obs, want", [
    ("sched_stall_iterations", {"counters_delta": DELTA}, 2),
    ("sched_stall_iterations",
     {"counters_delta": dict(DELTA, stall_iterations=0)}, 0),
    ("sched_stall_share", {"counters_delta": DELTA, "window_s": 51.0},
     100 * 2.19 / 51.0),
    # a steady run: a real 0, not nothing
    ("sched_stall_share",
     {"counters_delta": dict(DELTA, stall_s=0.0), "window_s": 51.0}, 0.0),
    ("host_gc_ms_per_step", {"counters_delta": DELTA}, 0.02),
    ("host_gc_ms_per_step", {"counters_delta": dict(DELTA, gc_s=0.0)}, 0.0),
])
def test_the_counters_arithmetic(name, obs, want):
    assert read(name, obs) == pytest.approx(want)


PARENT = {"steps": 2550, "slow_iterations": 3, "tick_s": 0.4}


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("obs", [
    # the parent: the slow rule's counter alone, no trace
    {"counters_delta": PARENT, "window_s": 51.0, "trace": None},
    {"counters_delta": {"steps": 0}, "window_s": 51.0},
    {"counters_delta": None},
    {},
], ids=["parent", "no_steps", "no_counters", "empty"])
def test_nothing_to_read(name, obs, monkeypatch):
    monkeypatch.setattr(PS, "records", lambda: None)
    assert read(name, obs) is None


def test_the_share_needs_a_window():
    assert read("sched_stall_share", {"counters_delta": DELTA}) is None
    assert read("sched_stall_share",
                {"counters_delta": DELTA, "window_s": 0.0}) is None


# -- the idle share without the named stalls ---------------------------------

def hand_made(stall_ms=0.0):
    """Ten 20 ms iterations whose program runs for 19 ms from 0.5 ms
    after the tick; before the fifth the host stalls for `stall_ms` and
    keeps a `sched.slow_iteration` span over that iteration. Trace
    clock in seconds; the buffer runs 100 s ahead of it."""
    ops, bench, prog = [], [], []
    t, sid = 0.0, iter(range(1, 1000))
    for i in range(10):
        stall = stall_ms * 1e-3 if i == 4 else 0.0
        t0, t = t, t + stall + 0.020
        tick_end = t0 + stall + 0.0002
        prog.append(PS.PSpan("sched.tick", 100 + t0, 100 + tick_end,
                             next(sid), 0, {}))
        bench.append(R.Event("bench.sched_iteration", tick_end, t - tick_end))
        ops.append(R.Event("fusion.1", t0 + stall + 0.0005, 0.019))
        if stall:
            prog.append(PS.PSpan("sched.slow_iteration", 100 + t0, 100 + t,
                                 next(sid), 0, {"rule": "host+stall"}))
    bench.append(R.Event(R.WINDOW_SPAN, 0.0, t))
    return {"trace": R.from_events({0: ops}, {0: [R.Event("jit_step(1)",
                                                          0.0005, 0.019)]},
                                   bench)}, prog


@pytest.mark.parametrize("stall_ms, whole, steady", [
    # 1 ms idle in every 20: both read 5%
    (0.0, 5.0, 5.0),
    # a 90 ms stall: 100 of 290 ms idle; less the 91 ms gap whose
    # midpoint the kept span holds (its 1 ms of ordinary idle goes with
    # it) 9 of 199
    (90.0, 100 * 100 / 290, 100 * 9 / 199),
])
def test_steady_idle_share_on_a_hand_made_run(stall_ms, whole, steady,
                                              monkeypatch, capsys):
    obs, prog = hand_made(stall_ms)
    monkeypatch.setattr(PS, "records", lambda: prog)
    assert read("serve_device_idle_share", obs) == pytest.approx(whole)
    assert read("serve_idle_steady_share", obs) == pytest.approx(steady)
    capsys.readouterr()


def test_steady_idle_share_on_the_recorded_run(monkeypatch, capsys):
    """Three iterations of `serve-chat-saturated` on a v5e (PR 23). The
    one iteration it kept is the profiler's STOP (2 s in `tick`, after
    the window), so the reader reads what `serve_device_idle_share`
    reads; with a kept span made up over the longest gap, that gap
    alone leaves both sides."""
    from benchmarks.trace.capture import load_recorded

    td = load_recorded(RECORDED)
    prog = [PS.PSpan(n, a * 1e-9, b * 1e-9, sid, parent, ids) for
            n, a, b, sid, parent, ids in
            harness.load_json(RECORDED)["program_spans"]]
    monkeypatch.setattr(PS, "records", lambda: prog)
    obs = {"trace": td}
    whole = read("serve_device_idle_share", obs)
    assert read("serve_idle_steady_share", obs) == pytest.approx(whole)
    ps = PS.load(obs)
    (kept,) = PS.named(ps["spans"], "sched.slow_iteration")
    assert kept.start > td.window[1] and kept.ids["tick_ms"] > 2000
    _, dur, mid = ps["gaps"][0]
    idle = sum(g[1] for g in ps["gaps"])
    assert idle == pytest.approx(whole / 100 * td.window_s)
    made_up = PS.PSpan("sched.slow_iteration", mid - ps["offset_s"] - 1e-4,
                       mid - ps["offset_s"] + 1e-4, 10**6, 0,
                       {"rule": "stall"})
    obs = {"trace": td}
    monkeypatch.setattr(PS, "records", lambda: prog + [made_up])
    got = read("serve_idle_steady_share", obs)
    assert got == pytest.approx(100 * (idle - dur) / (td.window_s - dur))
    assert got < whole
    capsys.readouterr()


# -- the entries --------------------------------------------------------------

def _entry(name, unit, source, layer, moves):
    return {"name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": moves}


ENTRIES = [
    _entry("sched_stall_iterations", "count", "program_counter", "scheduler",
           "serve_tokens_per_s"),
    _entry("sched_stall_share", "%", "program_counter", "scheduler",
           "serve_tokens_per_s"),
    _entry("host_gc_ms_per_step", "ms", "program_counter", "scheduler",
           "tpot_p50_ms"),
    _entry("serve_idle_steady_share", "%", "device_trace", "device",
           "serve_tokens_per_s"),
]


def with_cells(entry, doc):
    """The entry with EVERY serving cell as its `workloads` (so that
    each pin of the kind "cell X reports what cell Y reports plus ..."
    keeps holding): the cells that report what it moves."""
    moved = next(m for m in doc["end_to_end"] if m["name"] == entry["moves"])
    return dict(entry, workloads=[w["name"] for w in doc["workloads"]
                                  if w["name"] in moved["workloads"]])


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_the_entry_a_benchmark_pr_appends(entry):
    """Each reader's entry is written down here in the accepted form (a
    layer and a source BENCHMARK.json already names, a reader file by
    its name, all eight serving cells), and BENCHMARK.json either lacks
    it, as this PR must leave it, or holds exactly it."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    assert [e["name"] for e in ENTRIES] == list(NEW)
    assert (BENCH / "metrics" / f"{entry['name']}.py").is_file()
    old = [m for m in doc["per_layer"] if m["name"] not in NEW]
    assert entry["layer"] in {m["layer"] for m in old}
    assert entry["source"] in {m["source"] for m in old}
    full = with_cells(entry, doc)
    assert len(full["workloads"]) == 8
    assert [m for m in doc["per_layer"]
            if m["name"] == entry["name"]] in ([], [full])
