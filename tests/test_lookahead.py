"""ServingScheduler.run()'s look-ahead (step n+1 launched before step n
is read back) against a loop of step() calls, which always reads back
first: the same seeded arrivals must give the same tokens, finish
reasons, finish order, prefix index and free blocks, whatever mix of
look-ahead and fall-back iterations run() went through.

Fast lane: tiny model, f32, CPU; the control plane is host-side."""

import time

import jax
import numpy as np
import pytest

from _serving_models import engine_for as _engine_for
from _serving_models import small_model
from deepspeed_tpu.analysis import lifecycle as L
from deepspeed_tpu.inference import ServingScheduler, ServingSchedulerConfig

# the event benchmarks/harness.py's CompileCounter counts
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@pytest.fixture(scope="module")
def model():
    return small_model(max_seq=64)


def engine_for(model, **over):
    kw = dict(num_kv_blocks=64, prefix_cache={"enabled": True})
    kw.update(over)
    return _engine_for(*model, **kw)


def arrivals(n, seed=0, prompt=(3, 30), new=(2, 20)):
    """n seeded (prompt, max_new_tokens) pairs; every fourth prompt
    opens with one shared 16-token head, so the prefix index is hit."""
    rng = np.random.default_rng(seed)
    head = [int(t) for t in rng.integers(0, 128, 16)]
    out = []
    for i in range(n):
        p = [int(t) for t in rng.integers(0, 128, int(rng.integers(*prompt)))]
        if i % 4 == 3:
            p = head + p[:8]
        out.append((p, int(rng.integers(*new))))
    return out


def drive(model, how, arr, burst, per_tick, eos=None, engine=None,
          sched=None, **skw):
    """Serve `arr` on a fresh engine: `burst` requests up front, then
    `per_tick` more before every iteration, by run(tick=) or by a loop
    of tick + step() (one tick an iteration either way)."""
    cfg = dict(prefill_chunk=4, max_num_batched_tokens=16, warmup=False)
    cfg.update(sched or {})
    s = ServingScheduler(engine_for(model, **(engine or {})),
                         ServingSchedulerConfig(**cfg), **skw)
    pend = list(arr)
    eos = eos or {}

    def submit(i, p, m):
        s.submit(p, m, eos_token_id=eos.get(i))

    for i in range(min(burst, len(pend))):
        submit(i, *pend[i])
    nxt = [min(burst, len(pend))]

    def tick(_):
        for _ in range(per_tick):
            if nxt[0] < len(pend):
                submit(nxt[0], *pend[nxt[0]])
                nxt[0] += 1

    if how == "run":
        while s.has_work or nxt[0] < len(pend):
            s.run(tick=tick)
    else:
        while s.has_work or nxt[0] < len(pend):
            tick(s)
            s.step()
    return s


def same_outcome(a, b, order=True):
    assert set(a.finished) == set(b.finished)
    for rid, r in a.finished.items():
        assert r.output == b.finished[rid].output, rid
        assert r.finish_reason == b.finished[rid].finish_reason, rid
    if order:
        assert list(a.finished) == list(b.finished)
    sa, sb = a.engine.state, b.engine.state
    assert set(sa._index) == set(sb._index)
    assert sa.free_blocks == sb.free_blocks
    assert sa.allocator.free_blocks == sb.allocator.free_blocks
    assert L.quiesce_residuals(a) == {} == L.quiesce_residuals(b)


def share(s):
    return s.counters["lookahead_steps"] / s.counters["steps"]


CASES = {
    # the benchmark cells' shape: a burst, then a queue that never
    # empties, chunked prefill beside decode rows, rows ending by
    # length and their places refilled in the same iteration
    "saturated": dict(arr=arrivals(40), burst=12, per_tick=2),
    # one request: its last prompt chunk ends in step n, it decodes in
    # n+1 on the token of the chunk-end row
    "prompt_end_then_decode": dict(
        arr=[(list(range(1, 10)), 6)], burst=1, per_tick=0),
    # sampled, not greedy: the draw counters of rows fed from the device
    "sampled": dict(arr=arrivals(24, seed=3), burst=6, per_tick=1,
                    sampling={"do_sample": True, "temperature": 0.9,
                              "top_k": 12}, seed=7),
    # contexts that run into max_seq_len: ended by the context's count
    "context_capacity": dict(
        arr=arrivals(10, seed=5, prompt=(40, 60), new=(30, 40)),
        burst=4, per_tick=1, engine=dict(num_kv_blocks=96)),
    # pressure governor on, pool never under pressure
    "pressure_idle": dict(
        arr=arrivals(16, seed=6), burst=8, per_tick=1,
        sched=dict(pressure=dict(enabled=True))),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_run_matches_a_loop_of_steps(model, name):
    kw = CASES[name]
    a, b = drive(model, "run", **kw), drive(model, "step", **kw)
    assert a.counters["finished"] == len(kw["arr"])
    same_outcome(a, b)
    assert b.counters["lookahead_steps"] == 0
    assert a.counters["steps"] == b.counters["steps"]
    # every iteration but the first had a step in flight to look past
    assert a.counters["lookahead_steps"] == a.counters["steps"] - 1
    assert a.counters["lookahead_fallbacks"] == 0
    for r in a.finished.values():
        assert len(r.output) <= r.max_new_tokens
        assert r.first_token_t <= r.finish_t


def test_stamps_are_taken_when_the_host_reads(model):
    """first_token_t / finish_t / len(output) belong to the readback,
    not to the launch: seen from the tick, an output only ever grows by
    the one token of the step read back in between, and a request's
    first-token stamp is never older than the tick that first saw one."""
    s = ServingScheduler(engine_for(model), ServingSchedulerConfig(
        prefill_chunk=4, max_num_batched_tokens=16, warmup=False))
    rids = [s.submit(p, m) for p, m in arrivals(6, seed=9)]
    seen, first_seen = {}, {}

    def tick(sch):
        now = time.perf_counter()
        for r in list(sch.active) + list(sch.finished.values()):
            assert len(r.output) - seen.get(r.rid, 0) in (0, 1)
            seen[r.rid] = len(r.output)
            if r.output:
                first_seen.setdefault(r.rid, now)
                assert r.first_token_t is not None
            else:
                assert r.first_token_t is None

    s.run(tick=tick)
    assert share(s) > 0.9
    for rid in rids:
        r = s.finished[rid]
        assert r.first_token_t <= first_seen.get(rid, r.finish_t)
        assert r.first_token_t <= r.finish_t


def test_eos_mid_stream(model):
    """A request with an eos_token_id is looked past like any other; if
    the readback shows EOS, its row of the step already launched is
    discarded, nothing past EOS is kept and its place refills (one
    iteration later than in the normal order, so finish ORDER is not
    compared here)."""
    arr = arrivals(24, seed=11, new=(8, 20))
    free = drive(model, "step", arr, 8, 1)
    eos = {}
    for rid, r in free.finished.items():
        if rid % 2 == 0 and len(r.output) > 4:
            eos[rid] = r.output[len(r.output) // 2]
    assert len(eos) >= 6
    a = drive(model, "run", arr, 8, 1, eos=eos)
    b = drive(model, "step", arr, 8, 1, eos=eos)
    same_outcome(a, b, order=False)
    hit = [r for r in a.finished.values() if r.finish_reason == "eos"]
    assert len(hit) >= 6
    for r in hit:
        assert r.output[-1] == r.eos_token_id
        assert r.eos_token_id not in r.output[:-1]
        assert len(r.output) < len(free.finished[r.rid].output)
    assert a.counters["finished"] == len(arr)  # the places refilled
    assert share(a) > 0.9


def test_a_pool_that_forces_preemption_falls_back(model):
    """A reservation that does not fit has to preempt, which needs every
    token read: those iterations take the normal order, counted."""
    arr = arrivals(12, seed=2, prompt=(8, 16), new=(12, 24))
    kw = dict(arr=arr, burst=12, per_tick=0,
              engine=dict(num_kv_blocks=12, prefix_cache={"enabled": False}))
    a, b = drive(model, "run", **kw), drive(model, "step", **kw)
    assert b.counters["preemptions"] > 0
    assert a.counters["preemptions"] > 0
    assert a.counters["lookahead_fallbacks"] > 0
    assert 0 < a.counters["lookahead_steps"] < a.counters["steps"]
    same_outcome(a, b)


def test_spill_under_pressure_falls_back(model):
    """The same with the pressure governor's host spill tier: a victim
    is exported only from the normal order, where its ids are whole."""
    arr = arrivals(10, seed=4, prompt=(8, 16), new=(12, 24))
    pressure = dict(enabled=True, yellow=0.3, red=0.5, brownout=0.98,
                    spill_enabled=True, spill_host_mb=4)
    kw = dict(arr=arr, burst=10, per_tick=0,
              engine=dict(num_kv_blocks=10, prefix_cache={"enabled": False}),
              sched=dict(pressure=pressure))
    a, b = drive(model, "run", **kw), drive(model, "step", **kw)
    assert a.counters["preemptions"] > 0
    assert a.counters["lookahead_fallbacks"] > 0
    same_outcome(a, b)


def test_the_governor_updates_once_an_iteration(model):
    """A look-ahead handed back to the normal order must not update the
    pressure governor a second time: an update moves the level a step,
    trims parked blocks and counts itself. run() differs from the
    step() loop only by its last look at the emptied pool."""
    arr = arrivals(14, seed=2, prompt=(8, 16), new=(12, 24))
    pressure = dict(enabled=True, yellow=0.3, red=0.97, brownout=0.99,
                    spill_enabled=False)
    kw = dict(arr=arr, burst=10, per_tick=1,
              engine=dict(num_kv_blocks=12, prefix_cache={"enabled": False}),
              sched=dict(pressure=pressure))
    a, b = drive(model, "run", **kw), drive(model, "step", **kw)
    same_outcome(a, b)
    assert a.counters["lookahead_fallbacks"] >= 5
    ga, gb = a.governor.counters, b.governor.counters
    for k in ("steps_yellow", "steps_red", "steps_brownout"):
        assert ga[k] == gb[k] > 0, k
    assert 0 <= ga["transitions"] - gb["transitions"] <= 1


NEVER = {
    "presence": dict(sampling={"repetition_penalty": 1.3}),
    "speculation": dict(speculative={"ngram": 2, "draft_len": 3},
                        sched=dict(prefill_mode="wave")),
}


@pytest.mark.parametrize("name", sorted(NEVER))
def test_never_engages(model, name):
    """The presence bitmap needs the host's token before the next draw,
    speculation verifies values on the host: every iteration reads back
    first, and the outputs are the step() loop's."""
    kw = dict(arr=arrivals(10, seed=8), burst=4, per_tick=1, **NEVER[name])
    a, b = drive(model, "run", **kw), drive(model, "step", **kw)
    assert a.counters["lookahead_steps"] == 0
    assert a.counters["lookahead_fallbacks"] == 0
    same_outcome(a, b)


def test_wave_and_fused_parts_keep_the_normal_order(model):
    """A wave prefill or a fused decode_multi program in either step is
    launched only after the readback; between them the pure-decode and
    chunk iterations still look ahead."""
    kw = dict(arr=arrivals(10, seed=8), burst=3, per_tick=1)
    wave = dict(kw, sched=dict(prefill_mode="wave"))
    a, b = drive(model, "run", **wave), drive(model, "step", **wave)
    same_outcome(a, b)
    assert a.counters["wave_prefills"] > 0
    assert a.counters["lookahead_fallbacks"] > 0
    assert 0 < a.counters["lookahead_steps"] < a.counters["steps"]

    fused = dict(arr=arrivals(4, seed=8, new=(12, 13)), burst=4, per_tick=0,
                 sched=dict(decode_chunk=4))
    a, b = drive(model, "run", **fused), drive(model, "step", **fused)
    same_outcome(a, b)
    assert a.counters["fused_steps"] > 0
    assert a.counters["fused_steps"] == b.counters["fused_steps"]


def test_a_handoff_request_is_not_looked_past(model):
    """A disaggregated-prefill request parks after its FIRST token
    instead of decoding here: the step that samples it is read back
    before anything else is launched."""
    s = ServingScheduler(engine_for(model), ServingSchedulerConfig(
        prefill_chunk=4, max_num_batched_tokens=16, warmup=False))
    s.submit(list(range(1, 12)), 6)
    rid = s.submit(list(range(20, 29)), 6, handoff=True)
    s.run()
    (parked,) = s.handoff_ready
    assert parked.rid == rid and len(parked.output) == 1
    assert s.engine.state.get(parked.uid).seen_tokens == 9
    assert 0 < s.counters["lookahead_steps"] < s.counters["steps"] - 1


def test_a_raising_tick_leaks_nothing(model):
    """The tick raises with a step in flight (the benchmark's runner
    leaves run() that way): no request is between the batch and the
    finished table, and flushing what is active gives the pool back."""
    class Stop(Exception):
        pass

    s = ServingScheduler(engine_for(model), ServingSchedulerConfig(
        prefill_chunk=4, max_num_batched_tokens=16, warmup=False))
    arr = arrivals(30, seed=1)
    for p, m in arr:
        s.submit(p, m)
    n = [0]

    def tick(_):
        n[0] += 1
        if n[0] == 25:
            raise Stop

    with pytest.raises(Stop):
        s.run(tick=tick)
    assert s.counters["lookahead_steps"] >= 20
    assert s.counters["finished"] > 0
    assert len(s.finished) + len(s.active) + len(s.waiting) == len(arr)
    assert all(r.uid is not None for r in s.active)
    assert sorted(s.engine.state.tracked_uids) == sorted(
        r.uid for r in s.active)
    for r in list(s.active):
        s.engine.flush(r.uid)
    s.active.clear()
    s.waiting.clear()
    assert L.quiesce_residuals(s) == {}


class TestWarmupCoversTheLoop:
    def test_a_saturated_run_compiles_nothing(self, model):
        """After engine.warmup(widths=[w]) a saturated run(tick=) at
        width w builds no program (jax.monitoring's compile event, the
        listener the benchmark's runner counts with) and trips no
        recompile finding, and nearly all of it is look-ahead."""
        eng = engine_for(model)
        eng.warmup(widths=[8], footprint=False)
        built = []

        def listener(event, duration, **_):
            if event == COMPILE_EVENT:
                built.append(event)

        s = ServingScheduler(eng, ServingSchedulerConfig(
            prefill_chunk=4, max_num_batched_tokens=8, warmup=False))
        arr = arrivals(40, seed=12)
        for p, m in arr[:12]:
            s.submit(p, m)
        pend = arr[12:]

        def tick(_):
            if pend:
                s.submit(*pend.pop(0))

        jax.monitoring.register_event_duration_secs_listener(listener)
        try:
            s.run(tick=tick)
        finally:
            from jax._src import monitoring

            monitoring.unregister_event_duration_listener(listener)
        assert s.counters["finished"] == 40
        assert built == []
        assert eng.recompile_tracker.findings == []
        assert share(s) > 0.9

    def test_warmup_lists_the_token_program_per_width_pair(self, model):
        eng = engine_for(model, max_batch_size=16)
        rep = eng.warmup(widths=[8, 16], footprint=False)
        pairs = [(pp["source"], pp["width"]) for pp in rep["per_program"]
                 if pp["kind"] == "tokens"]
        assert sorted(pairs) == [(8, 8), (8, 16), (16, 8), (16, 16)]
        # no further LARGE program: two decode variants a width
        assert sum(pp["kind"] == "decode" for pp in rep["per_program"]) == 4
