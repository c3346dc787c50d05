"""The program's tracing facility (utils/profiler.py, docs/tracing.md):
spans, phase clocks and the always-on time sums they feed in the
serving loop, the warm-up and the train step."""

import contextlib
import gc
import glob
import logging
import os
import threading
import time
import tracemalloc

import jax
import numpy as np
import pytest

from deepspeed_tpu.inference import ServingScheduler, init_inference
from deepspeed_tpu.inference.scheduler import PHASES
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.resilience.faults import FaultPlan, armed
from deepspeed_tpu.utils import profiler


@pytest.fixture(autouse=True)
def clean_buffer():
    profiler.disable()
    profiler.clear()
    yield
    profiler.disable()
    profiler.clear()


def names(records):
    return [r.name for r in records]


def kept(name):
    return [r for r in profiler.spans() if r.name == name]


def churn(cycles=300_000):
    """Make that many reference cycles and have the collector find
    them: a full collection of tens of milliseconds."""
    junk = []
    for _ in range(cycles):
        cell = []
        cell.append(cell)
        junk.append(cell)
    del junk, cell
    return gc.collect()


@contextlib.contextmanager
def logged():
    """The messages the package's logger is handed while open (it
    writes to the standard output it was created with and does not
    propagate, so pytest's own capture sees nothing of it)."""
    from deepspeed_tpu.utils.logging import logger

    got = []
    handler = logging.Handler()
    handler.emit = lambda rec: got.append(rec.getMessage())
    logger.addHandler(handler)
    try:
        yield got
    finally:
        logger.removeHandler(handler)


class TestSpans:
    def test_inactive_span_records_nothing(self):
        with profiler.span("quiet", rid=1) as sp:
            pass
        assert sp.sid == 0
        assert profiler.spans() == []
        assert profiler.record("late", 0, 1) == 0
        assert not profiler.active()

    def test_enable_records_parent_child_and_ids(self):
        profiler.enable()
        with profiler.span("outer", rid=7) as outer:
            with profiler.span("inner") as inner:
                inner.set(rows=3)
        profiler.disable()
        recs = {r.name: r for r in profiler.spans()}
        assert recs["inner"].parent == recs["outer"].sid == outer.sid
        assert recs["outer"].parent == 0
        assert recs["outer"].ids == {"rid": 7}
        assert recs["inner"].ids == {"rows": 3}
        assert recs["outer"].t0_ns <= recs["inner"].t0_ns \
            <= recs["inner"].t1_ns <= recs["outer"].t1_ns

    def test_always_records_while_inactive_with_its_own_bound(self):
        with profiler.span("setup.phase", always=True, width=8):
            pass
        profiler.enable()
        for i in range(profiler.HOT_SPANS + 10):
            profiler.record("hot", i, i + 1)
        profiler.disable()
        # (a full collection of over 5 ms inside the loop is a kept span of
        # its own once any test of this process has hooked the collector)
        recs = [r for r in profiler.spans() if r.name != "host.gc"]
        # the buffer bound holds, and hot spans never evict a kept one
        assert len(recs) == profiler.HOT_SPANS + 1
        kept = [r for r in recs if r.name == "setup.phase"]
        assert len(kept) == 1 and kept[0].ids["width"] == 8

    def test_self_time_and_split(self):
        R = profiler.SpanRecord
        recs = [R("p", 0, 100, 1, 0, {}), R("a", 10, 30, 2, 1, {}),
                R("b", 20, 50, 3, 1, {}),   # overlaps a: counted once
                R("c", 90, 120, 4, 1, {})]  # clipped to the parent
        own = profiler.self_ns(recs)
        assert own[1] == 100 - (40 + 10)
        assert own[2] == 20 and own[3] == 30
        split = profiler.split_ns(0, 100, [("x", [(10, 30)]),
                                           ("y", [(20, 50), (90, 120)])])
        assert split == {"x": 20, "y": 30, "other": 50}
        assert sum(split.values()) == 100

    def test_parent_stacks_are_per_thread(self):
        profiler.enable()
        gate = threading.Barrier(2, timeout=10)

        def work(tag):
            with profiler.span(f"{tag}.outer"):
                gate.wait()  # both outers are open at once
                with profiler.span(f"{tag}.inner"):
                    gate.wait()

        ts = [threading.Thread(target=work, args=(t,)) for t in ("a", "b")]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=20)
            assert not t.is_alive()
        profiler.disable()
        recs = {r.name: r for r in profiler.spans()}
        for tag in ("a", "b"):
            assert recs[f"{tag}.inner"].parent == recs[f"{tag}.outer"].sid
            assert recs[f"{tag}.outer"].parent == 0

    def test_phases_tile_the_iteration_and_feed_the_sums(self):
        sums = {"a_s": 0.0, "b_wait_s": 0.0}
        ph = profiler.Phases("loop", "pass", {"a": "a_s", "b": "b_wait_s"},
                             sums=sums)
        ph.mark("b")  # outside an iteration: nothing
        assert sums == {"a_s": 0.0, "b_wait_s": 0.0}
        profiler.enable()
        ph.begin("a", n=1)
        time.sleep(0.002)
        ph.mark("b", rows=4)
        time.sleep(0.001)
        ph.mark("a")
        total = ph.end(kind="x")
        profiler.disable()
        assert total == ph.ns["a"] + ph.ns["b"]
        assert sums["a_s"] == pytest.approx(ph.ns["a"] * 1e-9)
        assert sums["b_wait_s"] == pytest.approx(ph.ns["b"] * 1e-9)
        recs = profiler.spans()
        assert names(recs) == ["loop.pass", "loop.a", "loop.b", "loop.a"]
        parent = recs[0]
        assert parent.ids == {"n": 1, "kind": "x"}
        assert recs[2].ids == {"rows": 4}
        assert all(r.parent == parent.sid for r in recs[1:])
        # children tile the parent: no time between or around them
        assert recs[1].t0_ns == parent.t0_ns and recs[3].t1_ns == parent.t1_ns
        assert recs[1].t1_ns == recs[2].t0_ns and recs[2].t1_ns == recs[3].t0_ns
        assert profiler.self_ns(recs)[parent.sid] == 0
        # tracing off: the sums still run, the buffer stays as it was
        ph.begin("a")
        ph.mark("b")
        ph.end()
        assert len(profiler.spans()) == 4 and sums["a_s"] > ph.ns["a"] * 1e-9

    def test_compile_spans_merge_nested_and_adjacent_reports(self):
        trace = "/jax/core/compile/jaxpr_trace_duration"
        lower = "/jax/core/compile/jaxpr_to_mlir_module_duration"
        with profiler.span("warmup.program", always=True) as sp, \
                profiler.compile_spans("warmup") as got:
            for _ in range(3):  # back to back: one span
                t = time.perf_counter()
                time.sleep(0.002)
                # the duration the stage really took, so the next report
                # starts where this one ends however late sleep() wakes
                # (a loaded machine overshoots by more than STAGE_GAP_NS)
                jax.monitoring.record_event_duration_secs(
                    trace, time.perf_counter() - t)
            time.sleep(0.005)
            jax.monitoring.record_event_duration_secs(trace, 0.001)  # nested
            jax.monitoring.record_event_duration_secs(lower, 0.004)
            jax.monitoring.record_event_duration_secs("/other/event", 1.0)
        assert names(got) == ["warmup.trace", "warmup.lower"]
        assert all(r.parent == sp.sid for r in got)
        assert got[0].t1_ns - got[0].t0_ns >= 5e6
        assert got[0].t1_ns <= got[1].t0_ns
        assert [r for r in profiler.spans() if r.parent == sp.sid] == got
        # the listener is gone: a later report leaves nothing
        jax.monitoring.record_event_duration_secs(trace, 0.5)
        assert len(profiler.spans()) == 3

    def test_dump_writes_chrome_trace(self, tmp_path):
        import json

        profiler.enable()
        with profiler.span("sched.iteration", iteration=1):
            pass
        profiler.disable()
        path = profiler.dump(str(tmp_path / "out" / "spans.json"))
        (ev,) = json.load(open(path))["traceEvents"]
        assert ev["name"] == "sched.iteration" and ev["ph"] == "X"
        assert ev["args"]["iteration"] == 1 and ev["tid"] == "sched"

    def test_profiler_session_alone_activates_spans(self, tmp_path):
        with profiler.span("before"):
            pass
        with profiler.trace(str(tmp_path)):
            assert profiler.active()
            with profiler.span("sched.launch", rows=5, kind="mixed"):
                time.sleep(0.001)
        assert not profiler.active()
        with profiler.span("after"):
            pass
        assert names(profiler.spans()) == ["sched.launch"]
        (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True)
        found = [e for plane in jax.profiler.ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for e in line.events
                 if e.name == "ds.sched.launch"]
        assert len(found) == 1
        assert dict(found[0].stats) == {"rows": 5, "kind": "mixed"}
        assert found[0].duration_ns >= 1e6


class Clock:
    """`profiler.time` for a test of the rule: a clock that moves only
    when the test says so, so no pause of a shared machine is in it."""

    def __init__(self):
        self.t = 1_000

    def perf_counter_ns(self):
        return self.t

    def ms(self, ms):
        self.t += int(ms * 1e6)


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(profiler, "time", c)
    return c


def one_pass(ph, clock, a_ms, wait_ms=0.0, **kw):
    ph.begin("a")
    clock.ms(a_ms)
    ph.mark("wait")
    clock.ms(wait_ms)
    return ph.end(**kw)


class TestStallRule:
    """`Phases.end()` judges an iteration against what its loop
    typically takes (docs/tracing.md "The serving loop")."""

    def make(self):
        return profiler.Phases("loop", "pass", ("a", "wait"), wait="wait")

    def test_the_first_iterations_never_fire(self, clock):
        ph = self.make()
        for i in range(profiler.STALL_MIN_SEEN):
            # the third takes a hundred times the others
            one_pass(ph, clock, 2000.0 if i == 2 else 20.0)
            assert ph.excess_ns == 0 and ph.stalls == 0
        # the pace they leave is theirs without the largest
        assert ph.typical_ns == 20_000_000

    def test_a_stall_is_over_three_times_the_pace_and_does_not_move_it(
            self, clock):
        ph = self.make()
        for _ in range(20):
            one_pass(ph, clock, 18.0, 2.0)
        typical = ph.typical_ns
        assert typical == 20_000_000
        one_pass(ph, clock, 18.0, 41.9)       # 59.9 ms: under 3 x
        assert ph.excess_ns == 0
        assert 0 < ph.typical_ns - typical < 3_000_000  # it was fed
        typical = ph.typical_ns
        total = one_pass(ph, clock, 108.0, 2.0)
        assert ph.excess_ns == total - typical == 110_000_000 - typical
        assert ph.excess_wait_ns == 0         # the wait was a typical one
        assert ph.typical_ns == typical and ph.stalls == 1
        one_pass(ph, clock, 18.0, 2002.0)     # the same inside the wait
        assert ph.excess_ns == 2_020_000_000 - typical
        assert 0 <= ph.excess_ns - ph.excess_wait_ns < 3_000_000
        assert ph.typical_ns == typical and ph.stalls == 2
        one_pass(ph, clock, 18.0, 2.0)
        assert ph.excess_ns == ph.excess_wait_ns == 0

    def test_a_loop_of_microseconds_needs_the_floor_too(self, clock):
        ph = self.make()
        for _ in range(20):
            one_pass(ph, clock, 0.05)
        one_pass(ph, clock, 4.9)              # 98 x, under the floor
        assert ph.excess_ns == 0
        one_pass(ph, clock, 6.0)
        assert ph.excess_ns > profiler.STALL_FLOOR_NS and ph.stalls == 1

    def test_a_steady_loop_of_200_fires_nothing(self, clock):
        ph = self.make()
        rng = np.random.default_rng(0)
        for ms in rng.uniform(14.0, 30.0, 200):   # up to 2.1 x apart
            one_pass(ph, clock, float(ms), 1.0)
            assert ph.excess_ns == 0
        assert ph.stalls == 0 and 18e6 < ph.typical_ns < 28e6

    def test_an_unfed_iteration_is_judged_and_leaves_the_pace(self, clock):
        ph = self.make()
        for _ in range(10):
            one_pass(ph, clock, 20.0)
        typical = ph.typical_ns
        for _ in range(10):                   # idle passes: no sample
            one_pass(ph, clock, 0.01, feed=False)
        assert ph.typical_ns == typical and ph.excess_ns == 0
        one_pass(ph, clock, 90.0, feed=False)
        assert ph.excess_ns == 90_000_000 - typical

    def test_stalls_in_a_row_are_the_new_pace(self, clock):
        ph = self.make()
        for _ in range(10):
            one_pass(ph, clock, 5.0)
        for _ in range(profiler.STALL_RESEED):    # the prompts got longer
            one_pass(ph, clock, 40.0)
            assert ph.excess_ns == 35_000_000
        for _ in range(profiler.STALL_MIN_SEEN):  # learned anew, unjudged
            one_pass(ph, clock, 40.0)
            assert ph.excess_ns == 0
        assert ph.typical_ns == 40_000_000
        assert ph.stalls == profiler.STALL_RESEED

    def test_the_ninth_stall_logs_a_count_not_a_line(self, clock):
        ph = self.make()
        for _ in range(10):
            one_pass(ph, clock, 20.0, 1.0)
        with logged() as lines:
            for i in range(16):
                one_pass(ph, clock, 20.0, 1.0)    # between two stalls
                one_pass(ph, clock, 20.0, 201.0)
                ph.log_stall(f"pass {i}", "7 rows")
        assert ph.stalls == 16
        assert len(lines) == profiler.STALL_LOG_LINES + 2
        assert lines[0].startswith(
            "loop: pass 0 took 221.0 ms (typical 21.0): wait 201.0, a 20.0; "
            "gc 0.0 ms; 7 rows")
        assert all(" took " in m for m in lines[:8])
        assert lines[8].startswith("loop: 9 stalls so far")
        assert lines[9].startswith("loop: 16 stalls so far")

    def test_end_allocates_nothing_with_tracing_off(self):
        sums = {"a_s": 0.0, "b_s": 0.0}
        ph = profiler.Phases("loop", "pass", {"a": "a_s", "b": "b_s"},
                             sums=sums, wait="b")

        def cycles(n):
            for _ in range(n):
                ph.begin("a")
                ph.mark("b")
                ph.end()

        cycles(100)
        gc.collect()
        profiler.clear()  # a full collection may have left `host.gc`
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            cycles(1000)
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        here = [tracemalloc.Filter(True, profiler.__file__)]
        grown = sum(s.size_diff for s in after.filter_traces(here).compare_to(
            before.filter_traces(here), "filename"))
        # what lives on is the last value of each int and float it
        # holds, not a list or a record an iteration
        assert grown < 1024, grown
        assert profiler.spans() == []

    def test_the_collectors_clock_and_a_long_full_collection(self):
        ph = self.make()
        ns0, n0 = profiler.gc_clock()
        ph.begin("a")
        t0 = time.perf_counter_ns()
        assert churn() >= 300_000
        ph.end()
        ns1, n1 = profiler.gc_clock()
        assert n1 > n0 and ph.gc_n >= 1
        assert 0 < ph.gc_ns <= ns1 - ns0 < time.perf_counter_ns() - t0
        assert profiler.gc_generation_since(t0) == 2
        assert profiler.gc_generation_since(time.perf_counter_ns()) is None
        full = [r for r in kept("host.gc") if r.t1_ns > t0]
        assert full and full[-1].ids["generation"] == 2
        assert full[-1].ids["collected"] >= 300_000
        assert full[-1].t1_ns - full[-1].t0_ns > profiler.STALL_FLOOR_NS


# -- the hot paths ---------------------------------------------------------------

MCFG = T.TransformerConfig(vocab_size=256, n_layers=2, n_heads=4, d_model=64,
                           max_seq=128, variant="llama", use_flash=False)


@pytest.fixture(scope="module")
def engine():
    params = T.init(MCFG, jax.random.PRNGKey(0))
    eng = init_inference(params, MCFG, {
        "max_batch_size": 8, "num_kv_blocks": 64, "kv_block_size": 8,
        "max_seq_len": 128})
    eng.warmup_report = eng.warmup(widths=[8], footprint=False)
    return eng


def make_sched(engine, **cfg):
    return ServingScheduler(
        engine, dict({"warmup": False, "max_num_batched_tokens": 32,
                      "prefill_chunk": 8}, **cfg))


def submit_some(sched, n=6, new=12):
    return [sched.submit(list(range(1, 20 + i)), max_new_tokens=new)
            for i in range(n)]


class TestServingLoop:
    def test_time_sums_add_up_to_the_loops_wall_time(self, engine):
        sched = make_sched(engine)
        submit_some(sched)
        sched.run()  # every shape compiled: the timed pass below is steady
        base = dict(sched.counters)
        submit_some(sched, new=24)
        ticks = []
        t0 = time.perf_counter()
        sched.run(tick=lambda s: ticks.append(time.sleep(0.0005)))
        wall = time.perf_counter() - t0
        d = {k: sched.counters[k] - base[k] for k in base}
        phases = sum(d[k] for k in PHASES.values())
        assert phases == pytest.approx(wall, rel=0.02)
        assert d["tick_s"] >= 0.0005 * len(ticks)
        assert d["readback_wait_s"] > 0 and d["launch_s"] > 0
        assert d["queue_wait_s"] > 0 and d["admitted"] == 6
        m = sched.metrics()
        for k in list(PHASES.values()) + ["queue_wait_s", "slow_iterations"]:
            assert m[k] == float(sched.counters[k])
        # tracing was off all along: at most a stall, or a full
        # collection of over 5 ms, left its name
        assert {r.name for r in profiler.spans()} <= {"sched.slow_iteration",
                                                      "host.gc"}

    def test_spans_of_an_iteration_and_of_a_request(self, engine):
        sched = make_sched(engine)
        rids = submit_some(sched, n=3)
        profiler.enable()
        sched.run()
        profiler.disable()
        recs = profiler.spans()
        its = [r for r in recs if r.name == "sched.iteration"]
        assert [r.ids["iteration"] for r in its] == list(range(1, len(its) + 1))
        assert {r.ids["kind"] for r in its} <= {"mixed", "idle"}
        assert its[0].ids["rows"] > 0
        by_parent = {}
        for r in recs:
            by_parent.setdefault(r.parent, []).append(r)
        kids = by_parent[its[0].sid]
        assert {k.name for k in kids} <= {f"sched.{p}" for p in PHASES}
        assert {"sched.admit", "sched.select", "sched.build",
                "sched.launch", "sched.commit"} <= {k.name for k in kids}
        assert profiler.self_ns(recs)[its[0].sid] == 0
        launch = [k for k in kids if k.name == "sched.launch"][0]
        assert launch.ids["kind"] == "mixed" and launch.ids["rows"] > 0
        # the first iteration has no step in flight; later ones say so
        assert launch.ids["ahead"] == 0
        ahead = [r.ids["ahead"] for r in recs if r.name == "sched.launch"
                 and r.ids.get("kind") == "mixed"]
        assert set(ahead[1:]) == {1}
        for rid in rids:
            mine = [r for r in recs if r.ids.get("rid") == rid]
            assert sorted(names(mine)) == [
                "request", "request.decode", "request.prefill",
                "request.queue"]
            root = [r for r in mine if r.name == "request"][0]
            req = sched.finished[rid]
            assert root.t0_ns == int(req.arrival * 1e9)
            assert root.t1_ns == int(req.finish_t * 1e9)
            assert req.arrival <= req.admit_t <= req.first_token_t

    def test_slow_iteration_is_counted_and_kept_with_tracing_off(self, engine):
        sched = make_sched(engine)
        submit_some(sched, n=2, new=4)
        with armed(FaultPlan([{"point": "scheduler.step", "kind": "delay",
                               "value": 0.2, "at": 2}])):
            while sched.has_work:
                sched.step()
        assert sched.counters["slow_iterations"] >= 1
        slow = [r for r in profiler.spans()
                if r.name == "sched.slow_iteration"
                and r.ids["fault_delay_s"] == pytest.approx(0.2)]
        assert len(slow) == 1
        assert {f"{p}_ms" for p in PHASES} <= set(slow[0].ids)

    # -- the stall rule in the loop (tracing off throughout) ------------------

    NEW_COUNTERS = ("stall_iterations", "stall_s", "stall_readback_s",
                    "gc_s", "gc_collections")

    def test_every_new_counter_is_there_from_construction(self, engine):
        sched = make_sched(engine)
        m = sched.metrics()
        for k in self.NEW_COUNTERS:
            assert sched.counters[k] == 0 and m[k] == 0.0
        # a runner takes snap1 - snap0 over every key: none may appear later
        before = set(sched.counters)
        submit_some(sched, n=2, new=3)
        sched.run()
        assert set(sched.counters) == before

    def test_a_steady_loop_of_200_iterations_leaves_nothing(
            self, engine, monkeypatch):
        # the factor is held by TestStallRule on a clock of its own;
        # here the loop is real and so are the pauses of a machine
        # shared with five other workers (a 10 ms sleep that takes 40),
        # which only marks far above them keep out
        from deepspeed_tpu.inference import scheduler as S

        monkeypatch.setattr(profiler, "STALL_FLOOR_NS", 400_000_000)
        monkeypatch.setattr(S, "SLOW_ITERATION_NS", 400_000_000)
        warm = make_sched(engine)
        submit_some(warm, new=100)
        warm.run()  # every width the tail of a run narrows to, compiled
        profiler.clear()
        sched = make_sched(engine)
        with logged() as lines:
            for _ in range(2):
                submit_some(sched, new=100)
                sched.run(tick=lambda s: time.sleep(0.002))
        assert sched._iteration >= 200
        assert sched._phases.typical_ns > 2_000_000
        assert sched.counters["stall_iterations"] == 0
        assert sched.counters["stall_s"] == 0.0
        assert sched.counters["stall_readback_s"] == 0.0
        assert kept("sched.slow_iteration") == [] and lines == []

    def stalled_run(self, engine, where, work=None):
        """A loop whose `where`-th tick runs `work` (default: sleeps
        ten typical iterations); returns (scheduler, the span of that
        iteration, the log, the seconds asked for)."""
        sched = make_sched(engine)
        submit_some(sched, new=40)
        at = {}

        def tick(s):
            if s._iteration == where:
                at["typical"] = s._phases.typical_ns
                at["asked"] = 10 * at["typical"] * 1e-9 + 0.02
                (work or (lambda: time.sleep(at["asked"])))()

        with logged() as lines:
            sched.run(tick=tick)
        (span,) = [r for r in kept("sched.slow_iteration")
                   if r.ids["iteration"] == where]
        mine = [m for m in lines
                if m.startswith(f"sched: iteration {where} took ")]
        assert at["typical"] > 0
        return sched, span, mine, at

    def test_a_stalled_tick_is_counted_kept_and_logged(self, engine):
        sched, span, lines, at = self.stalled_run(engine, where=20)
        c, ids = sched.counters, span.ids
        assert c["stall_iterations"] >= 1
        assert c["stall_s"] >= at["asked"] * 0.9
        # the readback wait of that iteration was no longer than usual.
        # The counters sum EVERY stalled iteration, and on a loaded
        # machine another one stalls too: each is a kept span, so the
        # counters are held to the spans, and another iteration's share
        # to no more than its own readback and its own excess
        stalls = [r.ids for r in kept("sched.slow_iteration")
                  if "stall" in r.ids["rule"].split("+")]
        assert c["stall_iterations"] == len(stalls)
        assert c["stall_s"] == pytest.approx(
            sum(i["excess_ms"] for i in stalls) * 1e-3)
        others = sum(min(i["excess_ms"], i["readback_ms"]) for i in stalls
                     if i["iteration"] != 20) * 1e-3
        assert c["stall_readback_s"] - others < 0.1 * ids["excess_ms"] * 1e-3
        assert ids["readback_ms"] < 0.1 * ids["tick_ms"]
        assert "stall" in ids["rule"].split("+")
        assert max(PHASES, key=lambda p: ids[f"{p}_ms"]) == "tick"
        assert ids["tick_ms"] >= at["asked"] * 1e3
        assert ids["typical_ms"] == pytest.approx(at["typical"] * 1e-6)
        assert ids["excess_ms"] == pytest.approx(
            (span.t1_ns - span.t0_ns - at["typical"]) * 1e-6)
        assert ids["rows"] > 0 and ids["kind"] == "mixed"
        assert ids["active"] == 6 and ids["waiting"] == 0
        assert ids["gc_ms"] >= 0 and "majflt" not in ids  # PERF.md §6
        # the span is the iteration's own stamps: it holds the tick
        assert (span.t1_ns - span.t0_ns) * 1e-6 == pytest.approx(
            sum(ids[f"{p}_ms"] for p in PHASES))
        (line,) = lines
        assert f"(typical {ids['typical_ms']:.1f}): tick " in line
        assert "step ahead ready=" in line and "6 active, 0 waiting" in line

    def test_a_stalled_readback_says_whether_the_device_ran_through(
            self, engine, monkeypatch):
        from deepspeed_tpu.inference import scheduler as S

        calls = {"n": 0, "at": None}

        def late(x):
            calls["n"] += 1
            if calls["n"] == calls["at"]:
                time.sleep(0.25)   # the host's sight of the tokens is late
            return serving_readback(x)

        serving_readback = S.serving_readback
        monkeypatch.setattr(S, "serving_readback", late)
        # under the look-ahead the next step is launched, and done, by
        # the time the late tokens are seen
        calls["at"] = 20
        sched = make_sched(engine)
        submit_some(sched, new=40)
        with logged() as lines:
            sched.run()
        (span,) = [r for r in kept("sched.slow_iteration")
                   if r.ids["readback_ms"] >= 250]
        assert span.ids["rule"] == "stall"        # the host held nothing
        assert span.ids["ahead_ready"] is True
        assert sched.counters["stall_readback_s"] >= 0.2
        assert sched.counters["stall_s"] >= sched.counters["stall_readback_s"]
        assert any("step ahead ready=True" in m for m in lines)
        # step() reads back before it launches again: nothing is ahead
        profiler.clear()
        calls.update(n=0, at=20)
        sched = make_sched(engine)
        submit_some(sched, new=40)
        while sched.has_work:
            sched.step()
        (span,) = [r for r in kept("sched.slow_iteration")
                   if r.ids["readback_ms"] >= 250]
        assert span.ids["ahead_ready"] is None
        assert sched.counters["stall_readback_s"] >= 0.2

    def test_the_collector_inside_a_tick_is_timed_and_named(self, engine):
        sched, span, lines, _ = self.stalled_run(engine, where=20, work=churn)
        assert sched.counters["gc_s"] > 0
        assert sched.counters["gc_collections"] >= 1
        assert span.ids["gc_ms"] > 0 and span.ids["gc_gen"] == 2
        assert span.ids["gc_ms"] <= span.ids["tick_ms"]
        assert [r for r in kept("host.gc")
                if span.t0_ns <= r.t0_ns and r.t1_ns <= span.t1_ns]
        assert len(lines) == 1 and "; gc " in lines[0]

    def test_latency_lists_are_bounded(self, engine):
        from deepspeed_tpu.inference.scheduler import LATENCY_WINDOW

        sched = make_sched(engine)
        assert sched._ttft.maxlen == sched._tpot.maxlen == LATENCY_WINDOW
        submit_some(sched, n=2, new=3)
        sched.run()
        assert len(sched._ttft) == 2 and sched.metrics()["ttft_p50_ms"] > 0


class TestWarmupAndInit:
    def test_warmup_split_sums_to_its_seconds(self, engine):
        rep = engine.warmup_report
        assert rep["programs"] == len(rep["per_program"]) == 4
        assert set(rep["split"]) == {"trace_s", "lower_s", "compile_s",
                                     "execute_s", "other_s"}
        assert sum(rep["split"].values()) == pytest.approx(rep["seconds"])
        assert all(v >= 0 for v in rep["split"].values())
        for pp in rep["per_program"]:
            parts = sum(pp[f"{k}_s"] for k in
                        ("trace", "lower", "compile", "execute", "other"))
            assert parts == pytest.approx(pp["seconds"])
        kinds = [(pp["kind"], pp.get("unique")) for pp in rep["per_program"]]
        assert kinds == [("decode", 1), ("decode", 0), ("sample", None),
                         ("tokens", None)]

    def test_setup_spans_are_kept_with_tracing_off(self):
        params = T.init(MCFG, jax.random.PRNGKey(1))
        eng = init_inference(params, MCFG, {
            "max_batch_size": 8, "num_kv_blocks": 16, "kv_block_size": 8,
            "max_seq_len": 64})
        eng.warmup(widths=[8], chunked=False, footprint=False)
        recs = profiler.spans()
        by_name = {}
        for r in recs:
            by_name.setdefault(r.name, []).append(r)
        (root,) = by_name["init.inference"]
        assert by_name["init.transform"][0].parent == root.sid
        assert by_name["init.pool"][0].parent == root.sid
        progs = by_name["warmup.program"]
        assert [(p.ids["kind"], p.ids["width"]) for p in progs] == [
            ("decode", 8), ("sample", 8), ("tokens", 8)]
        for p in progs:
            kids = {r.name for r in recs if r.parent == p.sid}
            assert "warmup.execute" in kids
            assert kids <= {"warmup.trace", "warmup.lower", "warmup.compile",
                            "warmup.execute"}
        assert "warmup.compile" in {r.name for r in recs
                                    if r.parent == progs[0].sid}


class TestTrainStep:
    def test_train_batch_spans_and_setup_spans(self):
        import deepspeed_tpu as ds

        mcfg = T.TransformerConfig(vocab_size=128, n_layers=1, n_heads=2,
                                   d_model=32, max_seq=16, variant="llama",
                                   use_flash=False)
        engine = ds.initialize(
            {"train_micro_batch_size_per_gpu": 2,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "steps_per_print": 10**9},
            loss_fn=T.make_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg))
        batch = {"tokens": np.random.default_rng(0).integers(
            0, 128, (engine.config.train_batch_size, 17)).astype(np.int32)}
        engine.train_batch(batch)  # compiles: train.compile, tracing off
        kept = {r.name: r for r in profiler.spans()}
        assert {"train.init", "train.init.shapes", "train.init.state",
                "train.compile", "train.compile.compile"} <= set(kept)
        assert kept["train.init.state"].parent == kept["train.init"].sid
        assert "train.batch" not in kept
        profiler.enable()
        engine.train_batch(batch)
        profiler.disable()
        recs = [r for r in profiler.spans() if r.name.startswith("train.")
                and r.name not in kept]
        assert names(recs) == ["train.batch", "train.prepare", "train.launch",
                               "train.readback", "train.post"]
        assert recs[0].ids == {"step": 2}
        assert all(r.parent == recs[0].sid for r in recs[1:])
        # BATCH_TIMER books the phases' own stamps: prepare+launch+readback
        booked = engine.timers.timers["train_batch"]._record[-1]
        assert booked == pytest.approx(
            sum(r.t1_ns - r.t0_ns for r in recs[1:4]) * 1e-9)

    def test_a_stalled_batch_is_kept_and_logged(self, monkeypatch):
        import deepspeed_tpu as ds
        from deepspeed_tpu.runtime import engine as E

        mcfg = T.TransformerConfig(vocab_size=128, n_layers=1, n_heads=2,
                                   d_model=32, max_seq=16, variant="llama",
                                   use_flash=False)
        engine = ds.initialize(
            {"train_micro_batch_size_per_gpu": 2,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "steps_per_print": 10**9},
            loss_fn=T.make_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg))
        batch = {"tokens": np.random.default_rng(0).integers(
            0, 128, (engine.config.train_batch_size, 17)).astype(np.int32)}
        device_get = jax.device_get

        def late(x):
            if engine.global_steps == 11:   # the twelfth step's loss
                time.sleep(0.4)
            return device_get(x)

        monkeypatch.setattr(E.jax, "device_get", late)
        with logged() as lines:
            for _ in range(14):
                engine.train_batch(batch)
        # the first batch compiled: the pace is the others', and no
        # batch but the late one is over three times it
        assert 0 < engine._phases.typical_ns < 130_000_000
        (span,) = [r for r in kept("train.slow_batch")
                   if r.ids["readback_ms"] >= 400]
        ids = span.ids
        assert ids["step"] == 12
        assert {"prepare_ms", "launch_ms", "readback_ms", "post_ms",
                "typical_ms", "excess_ms", "gc_ms", "gc_gen"} <= set(ids)
        assert ids["excess_ms"] == pytest.approx(
            (span.t1_ns - span.t0_ns) * 1e-6 - ids["typical_ms"])
        (line,) = [m for m in lines if m.startswith("train: step 12 took ")]
        assert "): readback 4" in line
