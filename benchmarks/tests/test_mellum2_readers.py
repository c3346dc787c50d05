"""The readers of a model of mixed windows (PR 48): device time of the
windowed and of the full layers' cache write and walk, the walk's share
of what its two kinds of layer need, the routed block at Mellum 2's
expert width, rings live and admissions that waited, on a hand-made
traced run whose arithmetic is known, and on a program that names no
such scope or counts no such tokens (a parent commit, another family:
nothing is returned, nothing raises).

The six readers are NOT entries of BENCHMARK.json, and no PR but one of
kind `benchmark` can make them so: the driver holds each accepted entry
to its place and test_sched_lookahead_share.py pins the last one
(PERF.md section 7 (a); the readers of PR 33, 35, 38, 41 and 44 wait
for the same PR). `ENTRIES` below is what that PR appends AFTER those,
in this order."""

import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import reduce as R

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = harness.load_json(BENCH / "configs" / "mellum2-12b-a2.5b-serve-l8.json")
OTHER_HF = harness.load_json(BENCH / "configs" / "olmoe-1b-7b-serve-l8.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
CELL = "serve-mellum2-mixedlen-saturated-r256"
NEW = ("window_attn_ms_per_step", "full_attn_ms_per_step",
       "windowed_walk_roofline", "mellum2_experts_roofline",
       "kv_rings_live_per_step", "admit_waits_per_step")


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def hand_made():
    """Two 20 ms shared-table programs. Each: windowed layers' writes
    1.5 ms and walks 2.4 ms, full layers' writes 0.5 ms and walks
    2.6 ms, 1 ms of projections in `attention` outside both, a routed
    block of route 1 ms and a 9 ms streamed pass, 1 ms of head."""
    S, ops, modules = R.Event, [], []
    for i in range(2):
        t = 0.030 * i
        J, A = "jit(step)/", "jit(step)/attention/"
        ops += [
            S("fusion.1", t, 0.001, A + "dot_general"),
            S("paged_kv_write.2", t + 0.001, 0.0015,
              A + "attn_window/paged_kv_write/pallas_call"),
            S("paged_decode_grid.3", t + 0.0025, 0.0024,
              A + "attn_window/jit(_attend_live_blocks)/paged_decode_grid"
              "/pallas_call"),
            S("paged_kv_write.4", t + 0.0049, 0.0005,
              A + "attn_full/paged_kv_write/pallas_call"),
            S("paged_decode_grid.5", t + 0.0054, 0.0026,
              A + "attn_full/jit(_attend_live_blocks)/paged_decode_grid"
              "/pallas_call"),
            S("fusion.6", t + 0.008, 0.001, J + "mlp/moe_route/top_k"),
            S("expert_stream.7", t + 0.009, 0.009,
              J + "mlp/moe_experts/expert_stream/pallas_call"),
            S("fusion.8", t + 0.018, 0.001, J + "lm_head/dot_general"),
        ]
        modules.append(S("jit_step(1)", t, 0.020))
    td = R.from_events({0: ops}, {0: modules},
                       [S(R.WINDOW_SPAN, 0.0, 0.060)])
    return {"trace": td, "hf": HF, "n_layers": 8, "peaks": PEAKS,
            "ticks": [(0.0, 200_000, 256, 70), (0.03, 210_000, 256, 70)],
            "counters_delta": {"steps": 10, "batched_tokens": 2560,
                               "moe_token_expert_pairs": 20480,
                               "kv_live_blocks": 18_000,
                               "kv_full_tokens": 10 * 210_000,
                               "kv_window_tokens": 10 * 65_000,
                               "kv_rings_live": 700,
                               "kv_ring_blocks_recycled": 40,
                               "admit_waits_full_pool": 2,
                               "admit_waits_window_pool": 3}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    assert read("window_attn_ms_per_step", obs) == pytest.approx(3.9)
    assert read("full_attn_ms_per_step", obs) == pytest.approx(3.1)
    # the walk: 210,000 tokens a step in each of 2 full layers and
    # 65,000 in each of 6 windowed ones, 2,048 B a token a layer =
    # 1.659 GB = 2.025 ms by bytes, of 5.0 ms of paged_decode_grid
    need = (2 * 210_000 + 6 * 65_000) * 2048
    assert need == 1_658_880_000
    by_bytes = 1e3 * need / PEAKS["hbm_bytes_per_s"]
    assert read("windowed_walk_roofline", obs) == pytest.approx(
        100 * by_bytes / 5.0)
    assert 30 < 100 * by_bytes / 5.0 < 100
    assert "over two kinds of layer: memory-bound" in capsys.readouterr().out
    # the experts, 8 layers: 64 experts of 3 x 2304 x 896 and 256 tokens
    # in and out, of 9 ms taken
    need = (64 * 3 * 2304 * 896 + 2 * 256 * 2304) * 2
    by_bytes = 1e3 * 8 * need / PEAKS["hbm_bytes_per_s"]
    assert read("mellum2_experts_roofline", obs) == pytest.approx(
        100 * by_bytes / 9.0)
    assert 80 < 100 * by_bytes / 9.0 < 100
    assert "mellum2 experts: memory-bound" in capsys.readouterr().out
    assert read("kv_rings_live_per_step", obs) == pytest.approx(70.0)
    assert read("admit_waits_per_step", obs) == pytest.approx(0.5)
    assert "0.300 on the windowed layers' rings" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_reads_nothing(name):
    """What the parent commit and the other families give: a trace with
    no `attn_window` scope, counters without the two kinds of tokens, a
    configuration of another routed family."""
    S = R.Event
    td = R.from_events(
        {0: [S("paged_decode_grid.3", 0.0, 0.01,
               "jit(step)/attention/jit(_attend_live_blocks)/"
               "paged_decode_grid/pallas_call"),
             S("expert_stream.4", 0.01, 0.01,
               "jit(step)/mlp/moe_experts/expert_stream/pallas_call")]},
        {0: [S("jit_step(1)", 0.0, 0.02)]}, [S(R.WINDOW_SPAN, 0.0, 0.05)])
    obs = {"trace": td, "hf": OTHER_HF, "n_layers": 8, "peaks": PEAKS,
           "ticks": [(0.0, 1000, 8, 0)],
           "counters_delta": {"steps": 10, "batched_tokens": 1280,
                              "moe_token_expert_pairs": 10240,
                              "kv_live_blocks": 300}}
    assert read(name, obs) is None
    # the change's scheduler on a model without rings: the keys, at 0
    obs["counters_delta"].update(
        kv_full_tokens=0, kv_window_tokens=0, kv_rings_live=0,
        admit_waits_full_pool=0, admit_waits_window_pool=0)
    assert read(name, obs) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None
    assert read(name, {}) is None


def test_the_needs_at_the_published_widths():
    shapes = harness.load_module(BENCH / "kernels" / "mellum2.py")
    assert shapes.layer_counts(HF) == {"window": 6, "full": 2, "routed": 8,
                                       "dense": 0}
    assert shapes.kv_bytes_per_token_per_layer(HF) == 2048
    assert shapes.ring_blocks(HF) == 10
    # the configuration file's own arithmetic: 1.61 GB of pages, 1.51 GB
    # of rings; a ring costs a sequence 15.7 MB at any length
    pools = shapes.pool_bytes(HF)
    assert pools == {"full": 3072 * 128 * 2 * 2048,
                     "window": 96 * 10 * 128 * 6 * 2048}
    assert (pools["full"], pools["window"]) == (1_610_612_736, 1_509_949_440)
    assert pools["window"] // 96 == 15_728_640
    assert shapes.walk_bytes(HF, 3000, 1024) == (2 * 3000 + 6 * 1024) * 2048
    moe = shapes.expert_flops_and_bytes(HF, 256)
    assert moe["flops"] == 2.0 * 3 * 2304 * 896 * 256 * 8
    # a layer's 64 experts: 792.7 MB in bf16, 6.34 GB over the 8 layers
    assert moe["bytes"] - 2 * 256 * 2304 * 2 == 64 * 3 * 2304 * 896 * 2
    assert 8 * 64 * 3 * 2304 * 896 * 2 == 6_341_787_648


def _entry(name, unit, better, layer, source="device_trace"):
    return {"name": name, "unit": unit, "better": better,
            "source": source, "layer": layer, "moves": "tpot_p50_ms",
            "workloads": [CELL]}


ENTRIES = [
    _entry("window_attn_ms_per_step", "ms", "lower", "paged kernels"),
    _entry("full_attn_ms_per_step", "ms", "lower", "paged kernels"),
    _entry("windowed_walk_roofline", "%", "higher", "paged kernels"),
    _entry("mellum2_experts_roofline", "%", "higher",
           "serve entry + serving model"),
    _entry("kv_rings_live_per_step", "rings", "higher", "scheduler",
           "program_counter"),
    _entry("admit_waits_per_step", "waits", "lower", "scheduler",
           "program_counter"),
]


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_the_entry_a_benchmark_pr_appends(entry):
    """Each reader's entry is written down here in the accepted form (a
    layer BENCHMARK.json already names, the new cell alone, a reader
    file by its name), and BENCHMARK.json either lacks it, as this PR
    must leave it, or holds exactly it."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    assert [e["name"] for e in ENTRIES] == list(NEW)
    assert (BENCH / "metrics" / f"{entry['name']}.py").is_file()
    assert (entry["unit"] == "%") == entry["name"].endswith("_roofline")
    assert entry["layer"] in {m["layer"] for m in doc["per_layer"]
                              if m["name"] not in NEW}
    cells = {w["name"] for w in doc["workloads"]}
    assert set(entry["workloads"]) <= cells
    moved = next(m for m in doc["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert [m for m in doc["per_layer"]
            if m["name"] == entry["name"]] in ([], [entry])


def test_the_cell_reports_what_the_dense_cell_reports_but_its_rooflines():
    """The cell and its configuration are in BENCHMARK.json, and the
    cell is on every list that all six older serving cells are on, and
    on `paged_grid_ms_per_step` (kernel names alone). NOT on
    `paged_decode_grid_roofline`, which counts the whole context in
    every K/V layer, nor on `paged_live_blocks_per_step`."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    like = "serve-granite4h-chat-saturated-r128"
    for group in ("end_to_end", "per_layer"):
        mine = {m["name"] for m in doc[group] if CELL in m.get("workloads", ())}
        its = {m["name"] for m in doc[group] if like in m.get("workloads", ())}
        assert its and mine >= its - {"paged_live_blocks_per_step"}, \
            sorted(its - mine)
        if group == "per_layer":
            assert "paged_grid_ms_per_step" in mine
            assert not mine & {"paged_decode_grid_roofline",
                               "paged_live_blocks_per_step",
                               "moe_experts_roofline"}
    cell = [w for w in doc["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] in {c["name"] for c in doc["configs"]}
    assert len(doc["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
