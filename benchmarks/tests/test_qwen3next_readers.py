"""The readers of a linear-attention model (PR 41): the Gated DeltaNet
operator and its delta rule (the kernel over state slots), the D = 256
walk in the layers that hold K/V, the held share of experts beside the
gated shared expert, on a hand-made traced run whose arithmetic is
known, and on a program that names no such scope or counts no such
bytes (a parent commit, another family: nothing is returned, nothing
raises).

The five readers are NOT entries of BENCHMARK.json, and no PR but one
of kind `benchmark` can make them so: the driver holds each accepted
entry to its place and test_sched_lookahead_share.py pins the last one
(ROADMAP Q-bench (c); test_mla_readers.py's seven and
test_lfm2_readers.py's eight wait for the same PR). `ENTRIES` below is
what that PR appends AFTER those, in this order."""

import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import reduce as R

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = harness.load_json(BENCH / "configs" / "qwen3-next-80b-a3b-serve-l12-ep8.json")
OTHER_HF = harness.load_json(BENCH / "configs" / "lfm2-8b-a1b-serve-l13.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
CELL = "serve-qwen3next-chat-saturated-r256"
NEW = ("linear_attn_ms_per_step", "linear_attn_state_ms_per_step",
       "linear_attn_state_roofline", "gated_attn_roofline",
       "qwen3next_held_experts_roofline")
SLOT = 9 * 2_146_304      # what a sequence holds over the 9 DeltaNet layers


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def hand_made():
    """Two 40 ms shared-table programs. Each: the DeltaNet operator of
    project 2 ms, convolution and slots 1 ms, the delta rule's kernel
    16 ms with 1 ms of relayout beside it, out 1.5 ms; attention of a
    0.4 ms projection, a 0.1 ms write, a 1 ms walk and a 0.05 ms gate; a
    routed block of route 1 ms, an 8 ms streamed pass and a 0.5 ms
    shared expert; 1 ms of head."""
    S, ops, modules = R.Event, [], []
    for i in range(2):
        t = 0.050 * i
        J, L = "jit(step)/", "jit(step)/linear_attention/"
        ops += [
            S("fusion.1", t, 0.002, L + "gdn_project/dot_general"),
            S("fusion.2", t + 0.002, 0.001, L + "gdn_conv/gather"),
            S("fusion.3", t + 0.003, 0.001, L + "gdn_state/transpose"),
            S("gdn_state.4", t + 0.004, 0.016,
              L + "gdn_state/gdn_state/pallas_call"),
            S("fusion.5", t + 0.020, 0.0015, L + "gdn_out/dot_general"),
            S("fusion.6", t + 0.0215, 0.0004, J + "attention/dot_general"),
            S("paged_kv_write.2", t + 0.0219, 0.0001,
              J + "attention/paged_kv_write/pallas_call"),
            S("paged_decode_grid.3", t + 0.022, 0.001,
              J + "attention/paged_decode_grid/pallas_call"),
            S("fusion.7", t + 0.023, 0.00005, J + "attention/attn_gate/mul"),
            S("fusion.8", t + 0.0231, 0.001, J + "mlp/moe_route/top_k"),
            S("expert_stream.4", t + 0.0241, 0.008,
              J + "mlp/moe_experts/expert_stream/pallas_call"),
            S("fusion.9", t + 0.0321, 0.0005, J + "mlp/moe_shared/dot_general"),
            S("fusion.10", t + 0.0326, 0.001, J + "lm_head/dot_general"),
        ]
        modules.append(S("jit_step(1)", t, 0.040))
    td = R.from_events({0: ops}, {0: modules},
                       [S(R.WINDOW_SPAN, 0.0, 0.100)])
    return {"trace": td, "hf": HF, "n_layers": 12, "peaks": PEAKS,
            # (time, summed context of the running sequences, active, waiting)
            "ticks": [(0.0, 80_000, 256, 90), (0.05, 100_000, 256, 90)],
            "counters_delta": {"steps": 10, "batched_tokens": 2560,
                               "moe_token_expert_pairs": 25600,
                               "kv_live_blocks": 9_000,
                               "state_slots_live": 2560,
                               "state_bytes_moved": 10 * 250 * 2 * SLOT,
                               "gdn_run_tokens": 60}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    assert read("linear_attn_ms_per_step", obs) == pytest.approx(21.5)
    assert read("linear_attn_state_ms_per_step", obs) == pytest.approx(17.0)
    # the delta rule, 9 layers: 250 sequences' 2,097,152 B of matrices
    # in and out + 256 rows' q, k, v, o in float32 (4 x 32 x 512 B a
    # row) = 9.45 GB = 11.55 ms by bytes; 256 rows x 7 x 32 x 128 x 128
    # operations = 0.04 ms: memory-bound, of 17 ms taken
    rule_bytes = 2 * 250 * 2_097_152 + 4 * 256 * 32 * 512
    by_bytes = 1e3 * 9 * rule_bytes / PEAKS["hbm_bytes_per_s"]
    assert read("linear_attn_state_roofline", obs) == pytest.approx(
        100 * by_bytes / 17.0)
    out = capsys.readouterr().out
    assert "delta rule: memory-bound" in out
    # the whole operator adds its weights (33,718,464 parameters less
    # nothing: 2 B each), the rows in and out and the carried inputs
    op_bytes = rule_bytes + 2 * (33_718_464 - 32 - 32 - 128 + 2 * 256 * 2048
                                 + 2 * 250 * 3 * 8192)
    op_ms = 1e3 * 9 * op_bytes / PEAKS["hbm_bytes_per_s"]
    assert f"the whole operator memory-bound, {op_ms:.3f} ms needed vs " \
        f"21.500 ms taken" in out
    # the walk, 3 layers: 90,000 cached tokens x 2,048 B = 0.675 ms by
    # bytes; at most 900 blocks x 128 tokens a row-read x 16 x 256 x 4
    # operations: memory-bound, of 1 ms taken
    by_bytes = 1e3 * 3 * 90_000 * 2048 / PEAKS["hbm_bytes_per_s"]
    assert read("gated_attn_roofline", obs) == pytest.approx(
        100 * by_bytes / 1.0)
    assert "gated attention: memory-bound" in capsys.readouterr().out
    # the experts, 12 layers: 64 held experts (320 expected pairs reach
    # them all) and the shared one, 3 x 2048 x 512 each, and 256 tokens
    # in and out, of 8.5 ms taken
    need = (65 * 3 * 2048 * 512 + 2 * 256 * 2048) * 2
    by_bytes = 1e3 * 12 * need / PEAKS["hbm_bytes_per_s"]
    assert read("qwen3next_held_experts_roofline", obs) == pytest.approx(
        100 * by_bytes / 8.5)
    assert "qwen3next held experts: memory-bound" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_reads_nothing(name):
    """What the parent commit and the other families give: a trace with
    no `linear_attention` scope, counters without the state's bytes, a
    configuration of another family."""
    S = R.Event
    td = R.from_events(
        {0: [S("paged_decode_grid.3", 0.0, 0.01,
               "jit(step)/attention/paged_decode_grid/pallas_call"),
             S("expert_stream.4", 0.01, 0.01,
               "jit(step)/mlp/moe_experts/expert_stream/pallas_call")]},
        {0: [S("jit_step(1)", 0.0, 0.02)]}, [S(R.WINDOW_SPAN, 0.0, 0.05)])
    obs = {"trace": td, "hf": OTHER_HF, "n_layers": 12, "peaks": PEAKS,
           "ticks": [(0.0, 1000, 8, 0)],
           "counters_delta": {"steps": 10, "batched_tokens": 5120,
                              "moe_token_expert_pairs": 20480,
                              "kv_live_blocks": 300,
                              "state_slots_live": 4000}}
    assert read(name, obs) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None
    assert read(name, {}) is None


def test_the_needs_at_the_published_widths():
    shapes = harness.load_module(BENCH / "kernels" / "qwen3_next.py")
    assert shapes.layer_counts(HF) == {"linear_attention": 9, "attention": 3,
                                       "routed": 12}
    assert shapes.conv_channels(HF) == 8192
    assert shapes.matrix_bytes_per_sequence_per_layer(HF) == 2_097_152
    assert shapes.state_bytes_per_sequence_per_layer(HF) == 2_146_304
    assert shapes.kv_bytes_per_token_per_layer(HF) == 2048
    rule = shapes.delta_rule_flops_and_bytes(HF, 256, 250)
    assert rule == {"flops": 7.0 * 32 * 128 * 128 * 256,
                    "bytes": 2.0 * 250 * 2_097_152 + 4.0 * 256 * 32 * 512}
    op = shapes.delta_net_flops_and_bytes(HF, 256, 250)
    weights = 2048 * 12288 + 2048 * 64 + 8192 * 4 + 4096 * 2048
    assert op["flops"] == rule["flops"] + 2.0 * (weights - 8192 * 4) * 256
    assert op["bytes"] == rule["bytes"] + 2 * (
        weights + 2 * 256 * 2048 + 2 * 250 * 3 * 8192)
    # the issue's figure for an iteration's state traffic: 256 sequences
    # x 9 layers x 2 x 2,146,304 B = 9.9 GB
    assert 256 * 9 * 2 * shapes.state_bytes_per_sequence_per_layer(HF) \
        == 9_890_168_832
    attn = shapes.attention_flops_and_bytes(HF, 1000, 3000)
    assert attn == {"bytes": 1000 * 2048.0, "flops": 2.0 * 2 * 16 * 256 * 3000}
    moe = shapes.held_experts_flops_and_bytes(HF, 256, 320)
    assert moe["flops"] == 2.0 * 3 * 2048 * (512 * 320 + 512 * 256)
    # a layer's 64 held experts and the shared one: 408.9 MB in bf16
    assert moe["bytes"] - 2 * 256 * 2048 * 2 == 65 * 3 * 2048 * 512 * 2


def _entry(name, unit, better, layer):
    return {"name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer, "moves": "tpot_p50_ms",
            "workloads": [CELL]}


ENTRIES = [
    _entry("linear_attn_ms_per_step", "ms", "lower",
           "serve entry + serving model"),
    _entry("linear_attn_state_ms_per_step", "ms", "lower", "paged kernels"),
    _entry("linear_attn_state_roofline", "%", "higher", "paged kernels"),
    _entry("gated_attn_roofline", "%", "higher", "paged kernels"),
    _entry("qwen3next_held_experts_roofline", "%", "higher",
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


def test_the_cell_reports_what_the_hybrid_cell_reports():
    """The accepted lists the new cell joined: exactly those
    `serve-lfm2-chat-saturated-r512` is in (two end-to-end, 24
    per-layer), so not the `moe_*` four nor `sched_lookahead_share`."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    like = "serve-lfm2-chat-saturated-r512"
    for group, n in (("end_to_end", 2), ("per_layer", 24)):
        mine = {m["name"] for m in doc[group] if CELL in m.get("workloads", ())}
        its = {m["name"] for m in doc[group] if like in m.get("workloads", ())}
        assert mine == its and len(mine) == n
    assert doc["per_layer"][-1]["name"] == "sched_lookahead_share"
    assert CELL not in doc["per_layer"][-1]["workloads"]
