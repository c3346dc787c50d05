"""The readers of a state-space model (PR 44): the Mamba-2 mixer and
its state-space step (the kernel over state slots), the held share of
experts beside the ungated shared expert, on a hand-made traced run
whose arithmetic is known, and on a program that names no such scope or
counts no such bytes (a parent commit, another family: nothing is
returned, nothing raises).

The four readers are NOT entries of BENCHMARK.json, and no PR but one
of kind `benchmark` can make them so: the driver holds each accepted
entry to its place and test_sched_lookahead_share.py pins the last one
(ROADMAP Q-bench (c); test_mla_readers.py's seven, test_lfm2_readers.py's
eight and test_qwen3next_readers.py's five wait for the same PR).
`ENTRIES` below is what that PR appends AFTER those, in this order."""

import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import reduce as R

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = harness.load_json(BENCH / "configs" / "granite-4.0-h-small-serve-l10-ep4.json")
OTHER_HF = harness.load_json(BENCH / "configs" / "qwen3-next-80b-a3b-serve-l12-ep8.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
CELL = "serve-granite4h-chat-saturated-r128"
NEW = ("state_space_ms_per_step", "ssm_state_ms_per_step",
       "ssm_state_roofline", "granite4h_held_experts_roofline")
SLOT = 9 * 4_249_600      # what a sequence's slots hold over the 9 layers


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def hand_made():
    """Two 30 ms shared-table programs. Each: the mixer of project
    2 ms, convolution and slots 1 ms, the step's kernel 13 ms with 1 ms
    of layout and skip beside it, gated norm 0.5 ms, out 1.5 ms;
    attention of a 0.4 ms projection, a 0.1 ms write and a 0.3 ms walk;
    a routed block of route 1 ms, a 4.5 ms streamed pass and a 0.5 ms
    shared expert; 1 ms of head."""
    S, ops, modules = R.Event, [], []
    for i in range(2):
        t = 0.040 * i
        J, L = "jit(step)/", "jit(step)/state_space/"
        ops += [
            S("fusion.1", t, 0.002, L + "ssm_project/dot_general"),
            S("conv_carry.2", t + 0.002, 0.001,
              L + "ssm_conv/jit(_conv_carry)/conv_carry/pallas_call"),
            S("fusion.3", t + 0.003, 0.001, L + "ssm_state/mul"),
            S("ssm_state.4", t + 0.004, 0.013,
              L + "ssm_state/ssm_state/pallas_call"),
            S("fusion.5", t + 0.017, 0.0005, L + "ssm_gate_norm/rsqrt"),
            S("fusion.6", t + 0.0175, 0.0015, L + "ssm_out/dot_general"),
            S("fusion.7", t + 0.019, 0.0004, J + "attention/dot_general"),
            S("paged_kv_write.2", t + 0.0194, 0.0001,
              J + "attention/paged_kv_write/pallas_call"),
            S("paged_decode_grid.3", t + 0.0195, 0.0003,
              J + "attention/paged_decode_grid/pallas_call"),
            S("fusion.8", t + 0.0198, 0.001, J + "mlp/moe_route/top_k"),
            S("expert_stream.4", t + 0.0208, 0.0045,
              J + "mlp/moe_experts/expert_stream/pallas_call"),
            S("fusion.9", t + 0.0253, 0.0005, J + "mlp/moe_shared/dot_general"),
            S("fusion.10", t + 0.0258, 0.001, J + "lm_head/dot_general"),
        ]
        modules.append(S("jit_step(1)", t, 0.030))
    td = R.from_events({0: ops}, {0: modules},
                       [S(R.WINDOW_SPAN, 0.0, 0.080)])
    return {"trace": td, "hf": HF, "n_layers": 10, "peaks": PEAKS,
            "ticks": [(0.0, 40_000, 128, 90), (0.04, 50_000, 128, 90)],
            "counters_delta": {"steps": 10, "batched_tokens": 1280,
                               "moe_token_expert_pairs": 12800,
                               "kv_live_blocks": 4_500,
                               "state_slots_live": 1280,
                               "state_bytes_moved": 10 * 125 * 2 * SLOT,
                               "ssm_run_tokens": 60}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    assert read("state_space_ms_per_step", obs) == pytest.approx(19.0)
    assert read("ssm_state_ms_per_step", obs) == pytest.approx(14.0)
    # the step, 9 layers: 125 sequences' 4,194,304 B of matrices in and
    # out + 128 rows' x, y (2 x 8,192), dt (128), B, C (2 x 128) in
    # float32 = 9.51 GB = 11.62 ms by bytes; 128 rows x 5 x 128 x 64 x
    # 128 operations = 0.03 ms: memory-bound, of 14 ms taken
    step_bytes = 2 * 125 * 4_194_304 + 4 * 128 * (2 * 8192 + 128 + 256)
    by_bytes = 1e3 * 9 * step_bytes / PEAKS["hbm_bytes_per_s"]
    assert read("ssm_state_roofline", obs) == pytest.approx(
        100 * by_bytes / 14.0)
    assert 80 < 100 * by_bytes / 14.0 < 100
    out = capsys.readouterr().out
    assert "state-space step: memory-bound" in out
    # the whole mixer adds its weights (102,286,976 parameters, 2 B
    # each), the rows in and out and the carried inputs
    op_bytes = step_bytes + 2 * (102_286_976 + 2 * 128 * 4096
                                 + 2 * 125 * 3 * 8448)
    op_ms = 1e3 * 9 * op_bytes / PEAKS["hbm_bytes_per_s"]
    assert f"the whole mixer memory-bound, {op_ms:.3f} ms needed vs " \
        f"19.000 ms taken" in out
    # the experts, 10 layers: 18 held experts (320 expected pairs reach
    # them all) of 3 x 4096 x 768 and the shared one of 3 x 4096 x 1536,
    # and 128 tokens in and out, of 5 ms taken
    need = (18 * 3 * 4096 * 768 + 3 * 4096 * 1536 + 2 * 128 * 4096) * 2
    by_bytes = 1e3 * 10 * need / PEAKS["hbm_bytes_per_s"]
    assert read("granite4h_held_experts_roofline", obs) == pytest.approx(
        100 * by_bytes / 5.0)
    assert "granite4h held experts: memory-bound" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_reads_nothing(name):
    """What the parent commit and the other families give: a trace with
    no `state_space` scope, counters without the state's bytes, a
    configuration of another family (one WITH state and a held share)."""
    S = R.Event
    td = R.from_events(
        {0: [S("gdn_state.3", 0.0, 0.01,
               "jit(step)/linear_attention/gdn_state/gdn_state/pallas_call"),
             S("expert_stream.4", 0.01, 0.01,
               "jit(step)/mlp/moe_experts/expert_stream/pallas_call")]},
        {0: [S("jit_step(1)", 0.0, 0.02)]}, [S(R.WINDOW_SPAN, 0.0, 0.05)])
    obs = {"trace": td, "hf": OTHER_HF, "n_layers": 12, "peaks": PEAKS,
           "ticks": [(0.0, 1000, 8, 0)],
           "counters_delta": {"steps": 10, "batched_tokens": 2560,
                              "moe_token_expert_pairs": 25600,
                              "kv_live_blocks": 300,
                              "state_bytes_moved": 10 ** 9,
                              "state_slots_live": 2560}}
    assert read(name, obs) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None
    assert read(name, {}) is None


def test_the_needs_at_the_published_widths():
    shapes = harness.load_module(BENCH / "kernels" / "granite_moe_hybrid.py")
    assert shapes.layer_counts(HF) == {"state_space": 9, "attention": 1,
                                       "routed": 10}
    assert (shapes.inner(HF), shapes.conv_channels(HF)) == (8192, 8448)
    assert shapes.matrix_bytes_per_sequence_per_layer(HF) == 4_194_304
    assert shapes.state_bytes_per_sequence_per_layer(HF) == 4_244_992
    assert shapes.slot_bytes_per_sequence_per_layer(HF) == 4_249_600
    assert shapes.kv_bytes_per_token_per_layer(HF) == 4096
    step = shapes.ssm_step_flops_and_bytes(HF, 128, 125)
    assert step == {"flops": 5.0 * 128 * 64 * 128 * 128,
                    "bytes": 2.0 * 125 * 4_194_304
                    + 4.0 * 128 * (2 * 8192 + 128 + 256)}
    op = shapes.mixer_flops_and_bytes(HF, 128, 125)
    matmuls = 4096 * 16768 + 8192 * 4096
    assert op["flops"] == step["flops"] + 2.0 * matmuls * 128
    assert op["bytes"] == step["bytes"] + 2 * (
        102_286_976 + 2 * 128 * 4096 + 2 * 125 * 3 * 8448)
    # the issue's figure for an iteration's state traffic: 128 sequences
    # x 9 layers x 2 x 4,244,992 B = 9.78 GB
    assert 128 * 9 * 2 * shapes.state_bytes_per_sequence_per_layer(HF) \
        == 9_780_461_568
    attn = shapes.attention_flops_and_bytes(HF, 1000, 3000)
    assert attn == {"bytes": 1000 * 4096.0, "flops": 2.0 * 2 * 4096 * 3000}
    moe = shapes.held_experts_flops_and_bytes(HF, 128, 320)
    assert moe["flops"] == 2.0 * 3 * 4096 * (768 * 320 + 1536 * 128)
    # a layer's 18 held experts and the shared one: 377.5 MB in bf16
    assert moe["bytes"] - 2 * 128 * 4096 * 2 == 20 * 3 * 4096 * 768 * 2


def _entry(name, unit, better, layer):
    return {"name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer, "moves": "tpot_p50_ms",
            "workloads": [CELL]}


ENTRIES = [
    _entry("state_space_ms_per_step", "ms", "lower",
           "serve entry + serving model"),
    _entry("ssm_state_ms_per_step", "ms", "lower", "paged kernels"),
    _entry("ssm_state_roofline", "%", "higher", "paged kernels"),
    _entry("granite4h_held_experts_roofline", "%", "higher",
           "serve entry + serving model"),
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


def test_the_cell_reports_what_the_linear_attention_cell_reports():
    """The cell and its configuration are in BENCHMARK.json, wherever
    later PRs' entries put them, and the cell is on every list that
    `serve-qwen3next-chat-saturated-r256` is on (a later benchmark PR
    may put it on more: the `moe_*` time readers, `paged_grid_*`)."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    like = "serve-qwen3next-chat-saturated-r256"
    for group in ("end_to_end", "per_layer"):
        mine = {m["name"] for m in doc[group] if CELL in m.get("workloads", ())}
        its = {m["name"] for m in doc[group] if like in m.get("workloads", ())}
        assert its and mine >= its, sorted(its - mine)
    cell = [w for w in doc["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["config"] in {c["name"] for c in doc["configs"]}
