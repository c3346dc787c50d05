"""The readers of a Phi-4-mini-flash model (PR 62): the selective scan
and its step kernel, the walks over an owner, its readers and the
rings, the cross layers, the gated memory units, differential
attention's combine and the share of the readers' rows whose logits
were read, on a hand-made traced run whose arithmetic is known, and on
a program that names no such scope or counts no such tokens (a parent
commit, another family: nothing is returned, nothing raises).

The seven readers are NOT entries of BENCHMARK.json, and no PR but one
of kind `benchmark` can make them so: the driver holds each accepted
entry to its place and test_sched_lookahead_share.py pins the last one
(PERF.md section 7 (a); the readers of PRs 33-58 wait for the same PR).
`ENTRIES` below is what that PR appends AFTER those, in this order."""

import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import reduce as R

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = harness.load_json(
    BENCH / "configs" / "phi-4-mini-flash-reasoning-serve-l32.json")
OTHER_HF = harness.load_json(
    BENCH / "configs" / "granite-4.0-h-small-serve-l10-ep4.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
CELL = "serve-phi4flash-reasoning-saturated-r128"
NEW = ("selective_scan_ms_per_step", "sscan_state_roofline",
       "shared_walk_roofline", "cross_attn_ms_per_step",
       "gated_memory_ms_per_step", "diff_combine_ms_per_step",
       "cross_rows_needed_share")
SLOT = 9 * 358_400     # what a sequence holds over the 9 scan layers


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def hand_made(hf=HF):
    """Two 25 ms shared-table programs. Each: the scan's mixer of
    project 2 ms, convolution 0.3 ms, the step kernel 1.5 ms with 0.2 ms
    of relayout beside it, gate 0.1 ms, out 0.9 ms; a gated unit of
    1.2 ms; attention of a 1 ms projection, a 0.1 ms write, walks of
    1 ms (windowed), 0.8 ms (full) and 5.6 ms (cross), a combine of
    0.4 ms OUTSIDE the walks' scopes and a 0.4 ms W_o; 7 ms of FFN; 1 ms
    of head."""
    S, ops, modules = R.Event, [], []
    for i in range(2):
        t = 0.030 * i
        J, L, A = "jit(step)/", "jit(step)/selective_scan/", \
            "jit(step)/attention/"
        walk = "paged_decode_grid/pallas_call"
        ops += [
            S("fusion.1", t, 0.002, L + "sscan_project/dot_general"),
            S("conv_carry.2", t + 0.002, 0.0003,
              L + "sscan_conv/jit(_conv_carry)/conv_carry/pallas_call"),
            S("fusion.3", t + 0.0023, 0.0002, L + "sscan_state/transpose"),
            S("sscan_state.4", t + 0.0025, 0.0015,
              L + "sscan_state/jit(_sscan_step)/sscan_state/pallas_call"),
            S("fusion.5", t + 0.004, 0.0001, L + "sscan_gate/mul"),
            S("fusion.6", t + 0.0041, 0.0009, L + "sscan_out/dot_general"),
            S("fusion.7", t + 0.005, 0.0012, J + "gated_memory/dot_general"),
            S("fusion.8", t + 0.0062, 0.001, A + "dot_general"),
            S("paged_kv_write.2", t + 0.0072, 0.0001,
              A + "attn_window/paged_kv_write/pallas_call"),
            S("paged_decode_grid.3", t + 0.0073, 0.001, A + "attn_window/" + walk),
            S("paged_decode_grid.4", t + 0.0083, 0.0008, A + "attn_full/" + walk),
            S("paged_decode_grid.5", t + 0.0091, 0.0056, A + "attn_cross/" + walk),
            S("fusion.9", t + 0.0147, 0.0004, A + "diff_combine/mul"),
            S("fusion.10", t + 0.0151, 0.0004, A + "dot_general"),
            S("fusion.11", t + 0.0155, 0.007, J + "mlp/dot_general"),
            S("fusion.12", t + 0.0225, 0.001, J + "lm_head/dot_general"),
        ]
        modules.append(S("jit_step(1)", t, 0.025))
    td = R.from_events({0: ops}, {0: modules},
                       [S(R.WINDOW_SPAN, 0.0, 0.060)])
    return {"trace": td, "hf": hf, "n_layers": 32, "peaks": PEAKS,
            "ticks": [(0.0, 120_000, 64, 90), (0.03, 130_000, 64, 90)],
            "counters_delta": {
                "steps": 10, "batched_tokens": 800,
                "state_slots_live": 640,
                "state_bytes_moved": 10 * 64 * 2 * SLOT,
                "sscan_run_tokens": 160,
                "kv_full_tokens": 1_250_000, "kv_shared_tokens": 8_750_000,
                "kv_window_tokens": 320_000,
                "cross_rows_run": 800, "cross_rows_needed": 650}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    # the mixer: 2 + 0.3 + 0.2 + 1.5 + 0.1 + 0.9
    assert read("selective_scan_ms_per_step", obs) == pytest.approx(5.0)
    assert read("gated_memory_ms_per_step", obs) == pytest.approx(1.2)
    assert read("cross_attn_ms_per_step", obs) == pytest.approx(5.6)
    assert read("diff_combine_ms_per_step", obs) == pytest.approx(0.4)
    assert read("cross_rows_needed_share", obs) == pytest.approx(81.25)
    # the step kernel, 9 layers: 64 sequences' 327,680 B of state in and
    # out + 80 rows' dt, dt x, y (5,120 float32 each) and B, C (16):
    # 0.384 GB = 0.469 ms by bytes; 80 rows x 6 x 5,120 x 16 operations
    # are nothing: memory-bound, of the KERNEL's 1.5 ms (the relayout
    # beside it is the scope's, not the kernel's)
    step_bytes = 2 * 64 * 327_680 + 4 * 80 * (3 * 5120 + 2 * 16)
    by_bytes = 1e3 * 9 * step_bytes / PEAKS["hbm_bytes_per_s"]
    assert read("sscan_state_roofline", obs) == pytest.approx(
        100 * by_bytes / 1.5)
    assert "sscan_state: memory-bound" in capsys.readouterr().out
    # the walks: (125,000 + 875,000) tokens a step over the owner and its
    # seven readers + 8 windowed layers x 32,000, at 5,120 B a token
    need = (125_000 + 875_000 + 8 * 32_000) * 5_120
    by_bytes = 1e3 * need / PEAKS["hbm_bytes_per_s"]
    assert read("shared_walk_roofline", obs) == pytest.approx(
        100 * by_bytes / (1.0 + 0.8 + 5.6))
    out = capsys.readouterr().out
    assert "paged_decode_grid + paged_decode_fused over an owner" in out


def test_the_walks_share_counts_the_fused_write_programs_walks_too():
    """The step has two programs: the shared-table one (`hand_made`'s:
    every walk a `paged_decode_grid`) and, when no chunk rides along,
    the fused-write one, whose owner and rings walk inside
    `paged_decode_fused` (their row write with them) while the readers'
    walks stay `paged_decode_grid`. The needed bytes count every walk
    of every step, so both kernels' time is the denominator, over the
    programs that ran a reader's walk (both kinds do)."""
    obs = hand_made()
    S, A = R.Event, "jit(step)/attention/"
    t = 0.060
    obs["trace"] = R.from_events(
        {0: list(obs["trace"].ops[0]) + [
            S("paged_decode_fused.7", t, 0.0035,
              A + "attn_window/paged_decode_fused/pallas_call"),
            S("paged_decode_fused.8", t + 0.0035, 0.0009,
              A + "attn_full/paged_decode_fused/pallas_call"),
            S("paged_decode_grid.9", t + 0.0044, 0.0054,
              A + "attn_cross/paged_decode_grid/pallas_call")]},
        {0: list(obs["trace"].modules[0]) + [S("jit_step(2)", t, 0.022)]},
        [S(R.WINDOW_SPAN, 0.0, 0.090)])
    need = (125_000 + 875_000 + 8 * 32_000) * 5_120
    by_bytes = 1e3 * need / PEAKS["hbm_bytes_per_s"]
    taken = (2 * (1.0 + 0.8 + 5.6) + 3.5 + 0.9 + 5.4) / 3
    assert read("shared_walk_roofline", obs) == pytest.approx(
        100 * by_bytes / taken)
    # the readers' walks alone: both kinds of program name the scope
    assert read("cross_attn_ms_per_step", obs) == pytest.approx(
        (2 * 5.6 + 5.4) / 3)


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_reads_nothing(name):
    """What the parent commit and the other families give: a trace with
    no `selective_scan` scope, counters without the shared tokens; and
    another state-space family's configuration on a run that has them
    all (the two rooflines read this family's file alone)."""
    S = R.Event
    td = R.from_events(
        {0: [S("paged_decode_grid.3", 0.0, 0.01,
               "jit(step)/attention/paged_decode_grid/pallas_call"),
             S("ssm_state.4", 0.01, 0.01,
               "jit(step)/state_space/ssm_state/pallas_call")]},
        {0: [S("jit_step(1)", 0.0, 0.02)]}, [S(R.WINDOW_SPAN, 0.0, 0.05)])
    obs = {"trace": td, "hf": OTHER_HF, "n_layers": 10, "peaks": PEAKS,
           "ticks": [(0.0, 1000, 8, 0)],
           "counters_delta": {"steps": 10, "batched_tokens": 1280,
                              "state_bytes_moved": 10 ** 9,
                              "kv_shared_tokens": 0, "cross_rows_run": 0}}
    assert read(name, obs) is None
    if name.endswith("_roofline"):
        assert read(name, hand_made(OTHER_HF)) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None
    assert read(name, {}) is None


def test_the_needs_at_the_published_widths():
    shapes = harness.load_module(BENCH / "kernels" / "phi4flash.py")
    assert shapes.layer_counts(HF) == {
        "selective_scan": 9, "gated_memory": 7, "window": 8, "full": 1,
        "cross": 7}
    assert shapes.mamba_sizes(HF) == (5120, 16, 4, 160)
    assert shapes.state_bytes_per_sequence_per_layer(HF) == 327_680
    assert shapes.slot_bytes_per_sequence_per_layer(HF) == 358_400
    assert shapes.kv_bytes_per_token_per_layer(HF) == 5_120
    assert shapes.scan_step_flops_and_bytes(HF, 80, 64) == {
        "flops": 6.0 * 5120 * 16 * 80,
        "bytes": 2.0 * 64 * 327_680 + 4.0 * 80 * (3 * 5120 + 32)}
    assert shapes.walk_bytes(HF, 1000, 7000, 500) == 5120 * (8000 + 8 * 500)
    # the issue's figures: a mixer of each kind, the FFN, the whole tree
    assert shapes.scan_parameters(HF) == 41_241_600
    assert shapes.attention_parameters(HF) == 19_660_800 + 7_680 + 384
    assert shapes.gated_memory_parameters(HF) == 26_214_400
    assert shapes.cross_parameters(HF) == 13_107_200 + 5_120 + 384
    assert shapes.ffn_parameters(HF) == 78_643_200
    assert shapes.parameters(HF) == 3_852_562_944
    # what a sequence holds, what a decode row's walks read a token of
    # context: the full layer's pages eight times
    assert 9 * shapes.slot_bytes_per_sequence_per_layer(HF) == 3_225_600
    assert 8 * shapes.kv_bytes_per_token_per_layer(HF) == 40_960


def _entry(name, unit, better, layer, source="device_trace"):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "tpot_p50_ms", "workloads": [CELL]}


ENTRIES = [
    _entry("selective_scan_ms_per_step", "ms", "lower",
           "serve entry + serving model"),
    _entry("sscan_state_roofline", "%", "higher", "paged kernels"),
    _entry("shared_walk_roofline", "%", "higher", "paged kernels"),
    _entry("cross_attn_ms_per_step", "ms", "lower",
           "serve entry + serving model"),
    _entry("gated_memory_ms_per_step", "ms", "lower",
           "serve entry + serving model"),
    _entry("diff_combine_ms_per_step", "ms", "lower",
           "serve entry + serving model"),
    _entry("cross_rows_needed_share", "%", "higher", "scheduler",
           "program_counter"),
]


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_the_entry_a_benchmark_pr_appends(entry):
    """Each reader's entry is written down here in the accepted form (a
    layer and a source BENCHMARK.json already names, the new cell alone,
    a reader file by its name), and BENCHMARK.json either lacks it, as
    this PR must leave it, or holds exactly it."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    assert [e["name"] for e in ENTRIES] == list(NEW)
    assert (BENCH / "metrics" / f"{entry['name']}.py").is_file()
    assert (not entry["name"].endswith("_roofline")) or entry["unit"] == "%"
    old = [m for m in doc["per_layer"] if m["name"] not in NEW]
    assert entry["layer"] in {m["layer"] for m in old}
    assert entry["source"] in {m["source"] for m in old}
    cells = {w["name"] for w in doc["workloads"]}
    assert set(entry["workloads"]) <= cells
    moved = next(m for m in doc["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert [m for m in doc["per_layer"]
            if m["name"] == entry["name"]] in ([], [entry])


def test_the_cell_reports_what_the_other_ring_cell_reports():
    """The accepted lists the new cell joined: exactly those
    `serve-mellum2-mixedlen-saturated-r256` is in (two end-to-end, 24
    per-layer: times, counters and shares that read TRUE of this
    model), so not `windowed_walk_roofline`'s kind, which counts a walk
    once a layer that owns a pool, nor `paged_decode_grid_roofline`
    (K/V in EVERY layer); and nothing but appends, the new cell last."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    like = "serve-mellum2-mixedlen-saturated-r256"
    for group, n in (("end_to_end", 2), ("per_layer", 24)):
        mine = {m["name"] for m in doc[group] if CELL in m.get("workloads", ())}
        its = {m["name"] for m in doc[group] if like in m.get("workloads", ())}
        assert mine == its and len(mine) == n
        assert all(m["workloads"][-1] == CELL for m in doc[group]
                   if CELL in m.get("workloads", ()))
    assert doc["per_layer"][-1]["name"] == "sched_lookahead_share"
    assert CELL not in doc["per_layer"][-1]["workloads"]
    assert doc["workloads"][-1] == {
        "name": CELL, "config": "phi-4-mini-flash-reasoning-serve-l32",
        "traffic": "reasoning-saturated-phi4flash", "chips": 1,
        "why": doc["workloads"][-1]["why"]}
    assert "32 of 32 layers" in doc["workloads"][-1]["why"]
    assert doc["configs"][-1]["name"] == "phi-4-mini-flash-reasoning-serve-l32"
    assert doc["configs"][-1]["reduced"] == []
    assert len(doc["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def test_the_older_cells_lists_are_appended_to_and_nothing_else():
    """What test_olmohybrid_readers.py pinned until this cell came after
    its own (tests/conftest.py _OUTGROWN_BENCHMARK_PINS): the Olmo-Hybrid
    cell is on exactly the lists the other DeltaNet cell is on, and it
    stands where it stood: twelfth, before the new one."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    cell, like = ("serve-olmohybrid-chat-saturated-r128",
                  "serve-qwen3next-chat-saturated-r256")
    for group, n in (("end_to_end", 2), ("per_layer", 24)):
        mine = {m["name"] for m in doc[group] if cell in m.get("workloads", ())}
        its = {m["name"] for m in doc[group] if like in m.get("workloads", ())}
        assert mine == its and len(mine) == n
    assert doc["workloads"][11]["name"] == cell
    assert doc["configs"][11]["name"] == "olmo-hybrid-7b-serve-l12"


def test_the_cells_traffic_is_the_issues():
    """Section 5 of the issue, number by number."""
    mix = harness.load_json(
        BENCH / "traffic" / "reasoning-saturated-phi4flash.json")
    assert mix["runner"] == "serve_longctx" and mix["burst_at_start"] == 128
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 512,
                                 "sigma": 0.6, "min": 128, "max": 2048}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 2048,
                                 "sigma": 0.5, "min": 256, "max": 6144}
    assert (mix["prompt_tokens"], mix["ramp_s"], mix["drain_s"],
            mix["count"]) == ("unique_random", 8.0, 0.0, "finished_in_window")
    # `knee_rps` is a capacity (tokens/s over the mean answer): no rate
    # of the sweep was sustained, the burst alone outlasts the window.
    # 2.0 x it is 2.45, on the 0.5 grid 2.5, and 2.5 x 59 s is a
    # half-integer count of arrivals that seeds deal differently (the
    # mix's `knee_how`, test_serve_aliases.py's rule): the rate is the
    # next point of the grid upward that deals every seed one count
    assert mix["knee_multiple"] == 2.0 and mix["knee_rps"] == 1.225
    assert "147.5" in mix["knee_how"] and "CAPACITY" in mix["knee_how"]
    grid = 0.5 * -(-2.0 * mix["knee_rps"] // 0.5)
    assert (grid, mix["rate_rps"]) == (2.5, 3.0)
    from benchmarks.traffic import generate
    counts = lambda rate: {len(generate.serve_schedule(
        mix, seed, 59.0, 1000, rate_rps=rate).due_s) for seed in range(1, 9)}
    assert len(counts(2.5)) > 1 and counts(3.0) == {128 + 177}
    chk = mix["logits_check"]
    assert (chk["prompt_lens"], chk["chunk"], chk["decode_steps"]) == (
        [300, 1317], 5, 10)
    # the longer prompt is 2.6 windows deep and 1.7 turns of the ring
    assert 1317 / 512 > 2.5 and 1317 / (6 * 128) > 1.7
    # (the issue asked [128]: 64 live decode rows alone are the 64-row
    # bucket, with a chunk beside them the 128-row one: both are warmed)
    assert mix["warmup_widths"] == [64, 128] and "64" in mix["warmup_why"]
