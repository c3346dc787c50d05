"""The readers of a model whose layers are one mixer each (PR 51): the
streamed pass over a held share of UNGATED experts (a kernel name of
its own) and the state-space step whose B and C come in groups, on a
hand-made traced run whose arithmetic is known, and on a program that
names no such kernel or counts no such bytes (a parent commit, another
family: nothing is returned, nothing raises).

The three readers are NOT entries of BENCHMARK.json, and no PR but one
of kind `benchmark` can make them so: the driver holds each accepted
entry to its place and test_sched_lookahead_share.py pins the last one
(PERF.md section 7 (a); the readers of test_mla_readers.py,
test_lfm2_readers.py, test_qwen3next_readers.py,
test_granite4h_readers.py and test_mellum2_readers.py wait for the same
PR). `ENTRIES` below is what that PR appends AFTER those, in this
order; the span-only readers that PR 44 wrote (`state_space_ms_per_step`,
`ssm_state_ms_per_step`) and `state_slots_live_per_step` read this
cell's trace as they stand, and that PR appends this cell to their
lists."""

import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import reduce as R

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = harness.load_json(
    BENCH / "configs" / "nemotron-3-nano-30b-a3b-serve-l13-ep4.json")
OTHER_HF = harness.load_json(
    BENCH / "configs" / "granite-4.0-h-small-serve-l10-ep4.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
CELL = "serve-nemotron3-chat-saturated-r256"
NEW = ("ungated_experts_ms_per_step", "ungated_held_experts_roofline",
       "grouped_ssm_state_roofline")
SLOT = 6 * 2_134_016      # what a sequence's slots hold over the 6 mixers


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def hand_made():
    """Two 16 ms shared-table programs. Each: the mixers of project
    0.6 ms, convolution 0.4 ms, the step's kernel 8.5 ms with 0.5 ms of
    layout and skip beside it, gated norm 0.3 ms, out 0.3 ms; attention
    of a 0.2 ms projection, a 0.1 ms write and a 0.4 ms walk; the
    routed layers of route 0.5 ms, a 4.0 ms ungated pass and a 0.3 ms
    shared expert; 0.3 ms of head."""
    S, ops, modules = R.Event, [], []
    for i in range(2):
        t = 0.020 * i
        J, L = "jit(step)/", "jit(step)/state_space/"
        ops += [
            S("fusion.1", t, 0.0006, L + "ssm_project/dot_general"),
            S("conv_carry.2", t + 0.0006, 0.0004,
              L + "ssm_conv/jit(_conv_carry)/conv_carry/pallas_call"),
            S("fusion.3", t + 0.001, 0.0005, L + "ssm_state/mul"),
            S("ssm_state.4", t + 0.0015, 0.0085,
              L + "ssm_state/jit(_ssm_step)/ssm_state/pallas_call"),
            S("fusion.5", t + 0.010, 0.0003, L + "ssm_gate_norm/rsqrt"),
            S("fusion.6", t + 0.0103, 0.0003, L + "ssm_out/dot_general"),
            S("fusion.7", t + 0.0106, 0.0002, J + "attention/dot_general"),
            S("paged_kv_write.2", t + 0.0108, 0.0001,
              J + "attention/jit(_kv_write)/paged_kv_write/pallas_call"),
            S("paged_decode_grid.3", t + 0.0109, 0.0004,
              J + "attention/paged_decode_grid/pallas_call"),
            S("fusion.8", t + 0.0113, 0.0005, J + "mlp/moe_route/top_k"),
            S("expert_stream_ungated.4", t + 0.0118, 0.004,
              J + "mlp/moe_experts/jit(_stream_ungated_mlp)/"
              "expert_stream_ungated/pallas_call"),
            S("fusion.9", t + 0.0158, 0.0003, J + "mlp/moe_shared/dot_general"),
            S("fusion.10", t + 0.0161, 0.0003, J + "lm_head/dot_general"),
        ]
        modules.append(S("jit_step(1)", t, 0.0165))
    td = R.from_events({0: ops}, {0: modules},
                       [S(R.WINDOW_SPAN, 0.0, 0.040)])
    return {"trace": td, "hf": HF, "n_layers": 13, "peaks": PEAKS,
            "ticks": [(0.0, 40_000, 256, 190), (0.02, 50_000, 256, 190)],
            "counters_delta": {"steps": 10, "batched_tokens": 2560,
                               "moe_token_expert_pairs": 15360,
                               "kv_live_blocks": 9_000,
                               "state_slots_live": 2560,
                               "state_bytes_moved": 10 * 200 * 2 * SLOT,
                               "ssm_run_tokens": 600}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    assert read("ungated_experts_ms_per_step", obs) == pytest.approx(4.0)
    # the span-only readers PR 44 wrote read this family's trace
    assert read("state_space_ms_per_step", obs) == pytest.approx(10.6)
    assert read("ssm_state_ms_per_step", obs) == pytest.approx(9.0)
    assert read("state_slots_live_per_step", obs) == pytest.approx(256)
    # the held experts, 5 routed layers: 32 experts (384 expected pairs
    # reach them all) of 2 x 2688 x 1,856 (the PUBLISHED width, not the
    # 1,920 the program streams) and 256 tokens in and out = 3.21 GB =
    # 3.915 ms of stream, of 4 ms taken
    need = (32 * 2 * 2688 * 1856 + 2 * 256 * 2688) * 2
    by_bytes = 1e3 * 5 * need / PEAKS["hbm_bytes_per_s"]
    assert by_bytes == pytest.approx(3.915, abs=1e-3)
    assert read("ungated_held_experts_roofline", obs) == pytest.approx(
        100 * by_bytes / 4.0)
    out = capsys.readouterr().out
    # what the pass as written spends on the MXU: 256 rows x 32 experts
    # x 4 E F a layer = 0.83 ms x 5, beside 0.025 ms a layer needed
    assert "4.149 ms of MXU" in out and "384 expected pairs" in out
    # the step, 6 mixers: 200 sequences' 2,097,152 B of matrices in and
    # out + 256 rows' x, y (2 x 4,096), dt (64), 8 groups of B, C (2 x
    # 1,024) in float32 = 5.10 GB = 6.22 ms by bytes; 256 rows x 5 x 64
    # x 64 x 128 operations = 0.02 ms: memory-bound, of 9 ms taken
    step_bytes = 2 * 200 * 2_097_152 + 4 * 256 * (2 * 4096 + 64 + 2048)
    by_bytes = 1e3 * 6 * step_bytes / PEAKS["hbm_bytes_per_s"]
    assert read("grouped_ssm_state_roofline", obs) == pytest.approx(
        100 * by_bytes / 9.0)
    assert 60 < 100 * by_bytes / 9.0 < 100
    assert "grouped state-space step: memory-bound" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_kernel_or_counters_reads_nothing(name):
    """What the parent commit and the other families give: a trace with
    the GATED pass and one group's step, a configuration of another
    family (one WITH state-space layers and a held share)."""
    S = R.Event
    td = R.from_events(
        {0: [S("ssm_state.3", 0.0, 0.01,
               "jit(step)/state_space/ssm_state/ssm_state/pallas_call"),
             S("paged_decode_grid.3", 0.01, 0.001,
               "jit(step)/attention/paged_decode_grid/pallas_call"),
             S("expert_stream.4", 0.011, 0.009,
               "jit(step)/mlp/moe_experts/expert_stream/pallas_call")]},
        {0: [S("jit_step(1)", 0.0, 0.02)]}, [S(R.WINDOW_SPAN, 0.0, 0.05)])
    obs = {"trace": td, "hf": OTHER_HF, "n_layers": 10, "peaks": PEAKS,
           "ticks": [(0.0, 1000, 8, 0)],
           "counters_delta": {"steps": 10, "batched_tokens": 1280,
                              "moe_token_expert_pairs": 12800,
                              "kv_live_blocks": 300,
                              "state_bytes_moved": 10 ** 9,
                              "state_slots_live": 1280}}
    assert read(name, obs) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None
    assert read(name, {}) is None


def test_the_gated_pass_is_not_read_as_the_ungated_one():
    """`expert_stream` and `expert_stream_grouped` END where this
    kernel's name goes on: neither is counted here."""
    S = R.Event
    td = R.from_events(
        {0: [S("paged_decode_grid.3", 0.0, 0.001,
               "jit(step)/attention/paged_decode_grid/pallas_call"),
             S("expert_stream_grouped.2", 0.001, 0.004,
               "jit(step)/mlp/moe_experts/expert_stream_grouped/pallas_call"),
             S("expert_stream.4", 0.005, 0.009,
               "jit(step)/mlp/moe_experts/expert_stream/pallas_call")]},
        {0: [S("jit_step(1)", 0.0, 0.02)]}, [S(R.WINDOW_SPAN, 0.0, 0.05)])
    assert read("ungated_experts_ms_per_step", {"trace": td}) is None


def test_the_needs_at_the_published_widths():
    shapes = harness.load_module(BENCH / "kernels" / "nemotron_h.py")
    assert shapes.layer_counts(HF) == {"state_space": 6, "attention": 2,
                                       "routed": 5}
    assert (shapes.inner(HF), shapes.conv_channels(HF)) == (4096, 6144)
    assert shapes.matrix_bytes_per_sequence_per_layer(HF) == 2_097_152
    assert shapes.slot_bytes_per_sequence_per_layer(HF) == 2_134_016
    # the issue's table: 256 + 1 slots over 6 layers, 12.8 MB a sequence
    assert 6 * shapes.slot_bytes_per_sequence_per_layer(HF) == 12_804_096
    step = shapes.grouped_ssm_step_flops_and_bytes(HF, 256, 200)
    assert step == {"flops": 5.0 * 256 * 64 * 64 * 128,
                    "bytes": 2.0 * 200 * 2_097_152
                    + 4.0 * 256 * (2 * 4096 + 64 + 2 * 8 * 128)}
    moe = shapes.ungated_held_experts_flops_and_bytes(HF, 256, 384)
    assert moe["flops"] == 2.0 * 2 * 2688 * 1856 * 384
    # a layer's 32 held experts: 638.6 MB in bf16, two matrices each
    assert moe["bytes"] - 2 * 256 * 2688 * 2 == 32 * 9_977_856 * 2
    # fewer pairs than held experts reach that many at most
    assert shapes.ungated_held_experts_flops_and_bytes(HF, 8, 12)["bytes"] \
        == (12 * 9_977_856 + 2 * 8 * 2688) * 2


def _entry(name, unit, better, layer):
    return {"name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer, "moves": "tpot_p50_ms",
            "workloads": [CELL]}


ENTRIES = [
    _entry("ungated_experts_ms_per_step", "ms", "lower",
           "serve entry + serving model"),
    _entry("ungated_held_experts_roofline", "%", "higher",
           "serve entry + serving model"),
    _entry("grouped_ssm_state_roofline", "%", "higher", "paged kernels"),
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


def test_the_cell_reports_what_the_state_space_cell_reports():
    """The cell and its configuration are in BENCHMARK.json, and the
    cell is on every list that `serve-granite4h-chat-saturated-r128` is
    on but `paged_live_blocks_per_step` (its count assumes the shapes of
    the cells it lists: ISSUE 51), and on `paged_grid_ms_per_step`,
    which reads kernel names alone."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    like = "serve-granite4h-chat-saturated-r128"
    for group in ("end_to_end", "per_layer"):
        mine = {m["name"] for m in doc[group] if CELL in m.get("workloads", ())}
        its = {m["name"] for m in doc[group] if like in m.get("workloads", ())}
        its -= {"paged_live_blocks_per_step"}
        assert its and mine >= its, sorted(its - mine)
    assert CELL in next(m for m in doc["per_layer"]
                        if m["name"] == "paged_grid_ms_per_step")["workloads"]
    cell = [w for w in doc["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] in {c["name"] for c in doc["configs"]}
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
