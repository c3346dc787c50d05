"""The readers of an Olmo-Hybrid model (PR 58): the delta rule at heads
of 96 x 192, the Gated DeltaNet operator around it, the full-attention
layers and their walk over 30 KV heads of 128, on a hand-made traced
run whose arithmetic is known, and on a program that names no such
scope or counts no such bytes (a parent commit, the other DeltaNet
family, whose own readers count by other keys: nothing is returned,
nothing raises).

The four readers are NOT entries of BENCHMARK.json, and no PR but one
of kind `benchmark` can make them so: the driver holds each accepted
entry to its place and test_sched_lookahead_share.py pins the last one
(PERF.md section 7 (a); the readers of PRs 33-55 wait for the same PR).
`ENTRIES` below is what that PR appends AFTER those, in this order."""

import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import reduce as R

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = harness.load_json(BENCH / "configs" / "olmo-hybrid-7b-serve-l12.json")
OTHER_HF = harness.load_json(
    BENCH / "configs" / "qwen3-next-80b-a3b-serve-l12-ep8.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
CELL = "serve-olmohybrid-chat-saturated-r128"
NEW = ("gdn_state_roofline", "delta_net_ms_per_step", "nope_attn_ms_per_step",
       "mha_walk_roofline")
SLOT = 9 * 2_280_960      # what a sequence NEEDS over the 9 DeltaNet layers


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def hand_made(hf=HF):
    """Two 25 ms shared-table programs. Each: the DeltaNet operator of
    project 2 ms, convolution and slots 0.5 ms, the delta rule's kernel
    7 ms with 0.5 ms of relayout beside it, out 1 ms; its output norm
    0.2 ms OUTSIDE the operator's scope; attention of a 1 ms
    projection, a 0.1 ms write, a 5 ms walk and a 0.4 ms W_o; 6 ms of
    FFN; 1 ms of head."""
    S, ops, modules = R.Event, [], []
    for i in range(2):
        t = 0.030 * i
        J, L = "jit(step)/", "jit(step)/linear_attention/"
        ops += [
            S("fusion.1", t, 0.002, L + "gdn_project/dot_general"),
            S("conv_carry.2", t + 0.002, 0.0005,
              L + "gdn_conv/jit(_conv_carry)/conv_carry/pallas_call"),
            S("fusion.3", t + 0.0025, 0.0005, L + "gdn_state/transpose"),
            S("gdn_state.4", t + 0.003, 0.007,
              L + "gdn_state/jit(_gated_delta_step)/gdn_state/pallas_call"),
            S("fusion.5", t + 0.010, 0.001, L + "gdn_out/dot_general"),
            S("fusion.6", t + 0.011, 0.0002, J + "norm1_post/mul"),
            S("fusion.7", t + 0.0112, 0.001, J + "attention/dot_general"),
            S("paged_kv_write.2", t + 0.0122, 0.0001,
              J + "attention/paged_kv_write/pallas_call"),
            S("paged_decode_grid.3", t + 0.0123, 0.005,
              J + "attention/paged_decode_grid/pallas_call"),
            S("fusion.8", t + 0.0173, 0.0004, J + "attention/dot_general"),
            S("fusion.9", t + 0.0177, 0.006, J + "mlp/dot_general"),
            S("fusion.10", t + 0.0237, 0.001, J + "lm_head/dot_general"),
        ]
        modules.append(S("jit_step(1)", t, 0.025))
    td = R.from_events({0: ops}, {0: modules},
                       [S(R.WINDOW_SPAN, 0.0, 0.060)])
    return {"trace": td, "hf": hf, "n_layers": 12, "peaks": PEAKS,
            # (time, summed context of the running sequences, active, waiting)
            "ticks": [(0.0, 40_000, 128, 90), (0.03, 50_000, 128, 90)],
            "counters_delta": {"steps": 10, "batched_tokens": 1280,
                               "kv_live_blocks": 4_800,
                               "state_slots_live": 1280,
                               "state_bytes_moved": 10 * 125 * 2 * SLOT,
                               "gdn_run_tokens": 60}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    # the operator: project 2 + conv 0.5 + rule 7.5 + out 1, its output
    # norm not in it; attention: 1 + 0.1 + 5 + 0.4
    assert read("delta_net_ms_per_step", obs) == pytest.approx(11.0)
    assert read("nope_attn_ms_per_step", obs) == pytest.approx(6.5)
    # the delta rule, 9 layers: 125 sequences' 2,211,840 B of matrices
    # in and out + 128 rows' q, k, v, o in float32 (4 x 30 x 576 B a
    # row) = 5.06 GB = 6.17 ms by bytes; 128 rows x 7 x 30 x 96 x 192
    # operations = 0.02 ms: memory-bound, of 7.5 ms taken
    rule_bytes = 2 * 125 * 2_211_840 + 4 * 128 * 30 * 576
    by_bytes = 1e3 * 9 * rule_bytes / PEAKS["hbm_bytes_per_s"]
    assert read("gdn_state_roofline", obs) == pytest.approx(
        100 * by_bytes / 7.5)
    out = capsys.readouterr().out
    assert "delta rule 96 x 192: memory-bound" in out
    # the whole operator adds its weights (88,750,332 parameters less
    # the 252 of A_log, dt_bias and the norm's scale: 2 B each), the
    # rows in and out and the carried inputs
    op_bytes = rule_bytes + 2 * (88_750_332 - 252 + 2 * 128 * 3840
                                 + 2 * 125 * 3 * 11520)
    op_ms = 1e3 * 9 * op_bytes / PEAKS["hbm_bytes_per_s"]
    assert f"the whole operator memory-bound, {op_ms:.3f} ms needed vs " \
        f"11.000 ms taken" in out
    # the walk, 3 layers: 45,000 cached tokens x 15,360 B = 2.53 ms by
    # bytes; at most 480 blocks x 128 tokens a row-read x 30 x 128 x 4
    # operations = 0.014 ms: memory-bound, of 5 ms taken
    by_bytes = 1e3 * 3 * 45_000 * 15_360 / PEAKS["hbm_bytes_per_s"]
    assert read("mha_walk_roofline", obs) == pytest.approx(
        100 * by_bytes / 5.0)
    assert "walk at 30 KV heads: memory-bound" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_reads_nothing(name):
    """What the parent commit and the other families give: a trace with
    no `linear_attention` scope, counters without the state's bytes; and
    the OTHER DeltaNet family's configuration on a run that has them
    all (its readers count by `full_attention_interval` and `head_dim`)."""
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
                              "kv_live_blocks": 300,
                              "state_slots_live": 4000}}
    assert read(name, obs) is None
    assert read(name, hand_made(OTHER_HF)) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None
    assert read(name, {}) is None


@pytest.mark.parametrize("name", ("linear_attn_ms_per_step",
                                  "linear_attn_state_ms_per_step"))
def test_the_accepted_span_readers_read_this_cell_as_they_stand(name):
    """The other family's two readers that read spans alone find this
    model's scopes under the same names."""
    assert read(name, hand_made()) == pytest.approx(
        {"linear_attn_ms_per_step": 11.0,
         "linear_attn_state_ms_per_step": 7.5}[name])


def test_the_needs_at_the_published_widths():
    shapes = harness.load_module(BENCH / "kernels" / "olmo_hybrid.py")
    assert shapes.layer_counts(HF) == {"linear_attention": 9, "attention": 3}
    assert shapes.head_dim(HF) == 128
    assert shapes.conv_channels(HF) == 11_520
    # 192 values a head, never a padded 256
    assert shapes.matrix_bytes_per_sequence_per_layer(HF) == 2_211_840 \
        == 30 * 96 * 192 * 4
    assert shapes.state_bytes_per_sequence_per_layer(HF) == 2_280_960
    assert shapes.kv_bytes_per_token_per_layer(HF) == 15_360
    rule = shapes.delta_rule_flops_and_bytes(HF, 128, 125)
    assert rule == {"flops": 7.0 * 30 * 96 * 192 * 128,
                    "bytes": 2.0 * 125 * 2_211_840 + 4.0 * 128 * 30 * 576}
    op = shapes.delta_net_flops_and_bytes(HF, 128, 125)
    weights = 3840 * 17280 + 3840 * 60 + 11520 * 4 + 5760 * 3840
    assert shapes.delta_net_parameters(HF) == weights + 60 + 192 == 88_750_332
    assert op["flops"] == rule["flops"] + 2.0 * (weights - 11520 * 4) * 128
    assert op["bytes"] == rule["bytes"] + 2 * (
        weights + 2 * 128 * 3840 + 2 * 125 * 3 * 11520)
    # the issue's figures: a layer of each kind, the whole cut, a
    # sequence's state, an iteration's state traffic
    assert shapes.attention_parameters(HF) == 58_982_400 + 7_680
    assert shapes.ffn_parameters(HF) == 126_812_160
    assert shapes.parameters(HF) == 3_268_268_508
    assert 9 * shapes.state_bytes_per_sequence_per_layer(HF) == 20_528_640
    assert 128 * 9 * 2 * shapes.state_bytes_per_sequence_per_layer(HF) \
        == 5_255_331_840
    attn = shapes.attention_flops_and_bytes(HF, 1000, 3000)
    assert attn == {"bytes": 1000 * 15360.0,
                    "flops": 2.0 * 2 * 30 * 128 * 3000}


def _entry(name, unit, better, layer):
    return {"name": name, "unit": unit, "better": better,
            "source": "device_trace", "layer": layer, "moves": "tpot_p50_ms",
            "workloads": [CELL]}


ENTRIES = [
    _entry("gdn_state_roofline", "%", "higher", "paged kernels"),
    _entry("delta_net_ms_per_step", "ms", "lower",
           "serve entry + serving model"),
    _entry("nope_attn_ms_per_step", "ms", "lower",
           "serve entry + serving model"),
    _entry("mha_walk_roofline", "%", "higher", "paged kernels"),
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


def test_the_cell_reports_what_the_other_delta_net_cell_reports():
    """The accepted lists the new cell joined: exactly those
    `serve-qwen3next-chat-saturated-r256` is in (two end-to-end, 24
    per-layer), so not the `moe_*` four, not `paged_grid_ms_per_step`
    nor `paged_decode_grid_roofline` (they count K/V in EVERY layer),
    nor `sched_lookahead_share`; and nothing but appends: twelve cells,
    one on four chips, the new one last."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    like = "serve-qwen3next-chat-saturated-r256"
    for group, n in (("end_to_end", 2), ("per_layer", 24)):
        mine = {m["name"] for m in doc[group] if CELL in m.get("workloads", ())}
        its = {m["name"] for m in doc[group] if like in m.get("workloads", ())}
        assert mine == its and len(mine) == n
        assert all(m["workloads"][-1] == CELL for m in doc[group]
                   if CELL in m.get("workloads", ()))
    assert doc["per_layer"][-1]["name"] == "sched_lookahead_share"
    assert CELL not in doc["per_layer"][-1]["workloads"]
    assert doc["workloads"][-1]["name"] == CELL
    assert doc["configs"][-1]["name"] == "olmo-hybrid-7b-serve-l12"
    assert len(doc["workloads"]) == 12
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1


def test_the_cells_traffic_is_the_issues():
    """Item 4 and item 3 of the issue, number by number."""
    mix = harness.load_json(BENCH / "traffic" / "chat-saturated-olmohybrid.json")
    assert mix["runner"] == "serve" and mix["burst_at_start"] == 128
    assert mix["prompt_len"] == {"dist": "lognormal", "median": 128,
                                 "sigma": 0.6, "min": 32, "max": 512}
    assert mix["answer_len"] == {"dist": "lognormal", "median": 384,
                                 "sigma": 0.5, "min": 64, "max": 1024}
    assert (mix["prompt_tokens"], mix["ramp_s"], mix["drain_s"], mix["count"],
            mix["warmup_widths"]) == ("unique_random", 8.0, 0.0,
                                      "finished_in_window", [128])
    # 2.0 x the knee the sweep found, rounded to 1 request/s
    assert mix["knee_multiple"] == 2.0 and mix["knee_how"]
    assert mix["rate_rps"] == round(2.0 * mix["knee_rps"])
    # (704 blocks asked; the chip's memory beside the logits check's
    # 3.5 GB of top-level leaves forced 560: the file's `kv_pool`)
    assert "forced fewer" in HF["assumed"]["kv_pool"]
    assert HF["serve"]["engine"] == {
        "max_seq_len": 4096, "kv_block_size": 128, "num_kv_blocks": 560,
        "max_batch_size": 128, "max_tracked_sequences": 128,
        "kv_cache_dtype": "auto", "decode_impl": "auto"}
    assert HF["serve"]["scheduler"] == harness.load_json(
        BENCH / "configs" / "mistral-7b-serve-l16.json")["serve"]["scheduler"]
