"""The readers of a model of two kinds of layer (PR 35): the gated
short convolution and its slot traffic, the D = 64 walk in the layers
that hold K/V, the routed experts of `moe_intermediate_size` and the
slot counters, on a hand-made traced run whose arithmetic is known, and
on a program that names no such scope or counts no such slots (a parent
commit, another family: nothing is returned, nothing raises).

The eight readers are NOT entries of BENCHMARK.json yet, and no PR but
one of kind `benchmark` can make them so (PERF.md section 7; the same
reason as test_mla_readers.py's seven). `ENTRIES` below is what that PR
appends AFTER those seven, in this order."""

import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import reduce as R

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = harness.load_json(BENCH / "configs" / "lfm2-8b-a1b-serve-l13.json")
OTHER_HF = harness.load_json(BENCH / "configs" / "olmoe-1b-7b-serve-l8.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
CELL = "serve-lfm2-chat-saturated-r512"
NEW = ("short_conv_ms_per_step", "short_conv_state_ms_per_step",
       "short_conv_roofline", "hybrid_attn_ms_per_step",
       "hybrid_attn_roofline", "hybrid_moe_experts_roofline",
       "state_slots_live_per_step", "state_slot_resets_per_step")


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def hand_made():
    """Two 20 ms shared-table programs. Each: the convolution of
    project 1.5 ms, state 0.5 ms, taps 0.2 ms and out 0.8 ms; attention
    of a 0.4 ms projection, a 0.1 ms write and a 2 ms walk; a routed
    block of route 1 ms and a 12 ms streamed pass; 1.5 ms of head."""
    S, ops, modules = R.Event, [], []
    for i in range(2):
        t = 0.030 * i
        J = "jit(step)/"
        ops += [
            S("fusion.1", t, 0.0015, J + "short_conv/conv_project/dot_general"),
            S("fusion.2", t + 0.0015, 0.0003, J + "short_conv/conv_state/gather"),
            S("fusion.3", t + 0.0018, 0.0002, J + "short_conv/conv_state/scatter"),
            S("fusion.4", t + 0.002, 0.0002, J + "short_conv/mul"),
            S("fusion.5", t + 0.0022, 0.0008, J + "short_conv/conv_out/dot_general"),
            S("fusion.6", t + 0.003, 0.0004, J + "attention/dot_general"),
            S("paged_kv_write.2", t + 0.0034, 0.0001,
              J + "attention/paged_kv_write/pallas_call"),
            S("paged_decode_grid.3", t + 0.0035, 0.002,
              J + "attention/paged_decode_grid/pallas_call"),
            S("fusion.7", t + 0.0055, 0.001, J + "mlp/moe_route/top_k"),
            S("expert_stream.4", t + 0.0065, 0.012,
              J + "mlp/moe_experts/expert_stream/pallas_call"),
            S("fusion.8", t + 0.0185, 0.0015, J + "lm_head/dot_general"),
        ]
        modules.append(S("jit_step(1)", t, 0.020))
    td = R.from_events({0: ops}, {0: modules},
                       [S(R.WINDOW_SPAN, 0.0, 0.060)])
    return {"trace": td, "hf": HF, "n_layers": 12, "peaks": PEAKS,
            # (time, summed context of the running sequences, active, waiting)
            "ticks": [(0.0, 130_000, 400, 90), (0.03, 150_000, 400, 90)],
            "counters_delta": {"steps": 10, "batched_tokens": 5120,
                               "moe_token_expert_pairs": 20480,
                               "kv_live_blocks": 15_000,
                               "state_slots_live": 4000,
                               "state_slot_resets": 25,
                               "state_prefix_credits_refused": 0}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    assert read("short_conv_ms_per_step", obs) == pytest.approx(3.0)
    assert read("short_conv_state_ms_per_step", obs) == pytest.approx(0.5)
    assert read("hybrid_attn_ms_per_step", obs) == pytest.approx(2.0)
    assert read("state_slots_live_per_step", obs) == pytest.approx(400.0)
    assert read("state_slot_resets_per_step", obs) == pytest.approx(2.5)
    # the convolution, 10 layers: (4 x 2048^2 + 2048 x 3 weights + 512
    # rows in and out + 400 sequences' two inputs in and out) x 2 B =
    # 0.469 ms by bytes; 512 rows x 2 x 4 x 2048^2 = 0.872 ms by
    # operations: compute-bound at 512 rows, of 3 ms taken
    conv_bytes = (4 * 2048 * 2048 + 2048 * 3 + 2 * 512 * 2048
                  + 2 * 400 * 2 * 2048) * 2
    by_bytes = 1e3 * 10 * conv_bytes / PEAKS["hbm_bytes_per_s"]
    by_flops = 1e3 * 10 * 512 * 2 * 4 * 2048 * 2048 / PEAKS["bf16_flops_per_s"]
    assert by_flops > by_bytes
    assert read("short_conv_roofline", obs) == pytest.approx(
        100 * by_flops / 3.0)
    assert "short_conv: compute-bound" in capsys.readouterr().out
    # the walk, 3 layers: 140,000 cached tokens x 2,048 B = 1.050 ms by
    # bytes; at most 1,500 blocks x 128 tokens a row-read x 32 x 64 x 4
    # operations = 0.024 ms: memory-bound, of 2 ms taken
    by_bytes = 1e3 * 3 * 140_000 * 2048 / PEAKS["hbm_bytes_per_s"]
    assert read("hybrid_attn_roofline", obs) == pytest.approx(
        100 * by_bytes / 2.0)
    assert "hybrid attention: memory-bound" in capsys.readouterr().out
    # the experts, 12 routed layers: 32 x 3 x 2048 x 1792 weights once
    # and 512 tokens in and out, of 12 ms taken
    need = (32 * 3 * 2048 * 1792 + 2 * 512 * 2048) * 2
    by_bytes = 1e3 * 12 * need / PEAKS["hbm_bytes_per_s"]
    assert read("hybrid_moe_experts_roofline", obs) == pytest.approx(
        100 * by_bytes / 12.0)
    assert "hybrid moe experts: memory-bound" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_reads_nothing(name):
    """What the parent commit and the other families give: a trace with
    no `short_conv` scope, counters without the slot keys, a
    configuration that names no layer kinds."""
    S = R.Event
    td = R.from_events(
        {0: [S("paged_decode_grid.3", 0.0, 0.01,
               "jit(step)/attention/paged_decode_grid/pallas_call"),
             S("while.7", 0.01, 0.01, "jit(step)/mlp/moe_experts/while")]},
        {0: [S("jit_step(1)", 0.0, 0.02)]}, [S(R.WINDOW_SPAN, 0.0, 0.05)])
    obs = {"trace": td, "hf": OTHER_HF, "n_layers": 8, "peaks": PEAKS,
           "ticks": [(0.0, 1000, 8, 0)],
           "counters_delta": {"steps": 10, "batched_tokens": 1280,
                              "moe_token_expert_pairs": 10240,
                              "kv_live_blocks": 300}}
    assert read(name, obs) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None
    assert read(name, {}) is None


def test_the_needs_at_the_published_widths():
    shapes = harness.load_module(BENCH / "kernels" / "lfm2.py")
    assert shapes.layer_counts(HF) == {"conv": 10, "attention": 3,
                                       "routed": 12}
    assert shapes.kv_bytes_per_token_per_layer(HF) == 2048
    conv = shapes.short_conv_flops_and_bytes(HF, 512, 400)
    assert conv["flops"] == 2.0 * 4 * 2048 * 2048 * 512
    assert conv["bytes"] == (4 * 2048 * 2048 + 2048 * 3 + 2 * 512 * 2048
                             + 2 * 400 * 2 * 2048) * 2
    attn = shapes.attention_flops_and_bytes(HF, 1000, 3000)
    assert attn == {"bytes": 1000 * 2048.0, "flops": 2.0 * 2 * 32 * 64 * 3000}
    moe = shapes.experts_flops_and_bytes(HF, 512)
    assert moe["flops"] == 2.0 * 3 * 2048 * 1792 * 2048
    # a routed layer's 32 experts: 704.6 MB in bf16, the issue's figure
    assert moe["bytes"] - 2 * 512 * 2048 * 2 == 704_643_072


def _entry(name, unit, better, source, layer):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "tpot_p50_ms", "workloads": [CELL]}


ENTRIES = [
    _entry("short_conv_ms_per_step", "ms", "lower", "device_trace",
           "serve entry + serving model"),
    _entry("short_conv_state_ms_per_step", "ms", "lower", "device_trace",
           "serve entry + serving model"),
    _entry("short_conv_roofline", "%", "higher", "device_trace",
           "serve entry + serving model"),
    _entry("hybrid_attn_ms_per_step", "ms", "lower", "device_trace",
           "paged kernels"),
    _entry("hybrid_attn_roofline", "%", "higher", "device_trace",
           "paged kernels"),
    _entry("hybrid_moe_experts_roofline", "%", "higher", "device_trace",
           "serve entry + serving model"),
    _entry("state_slots_live_per_step", "slots", "higher", "program_counter",
           "scheduler"),
    _entry("state_slot_resets_per_step", "slots", "lower", "program_counter",
           "scheduler"),
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


def test_the_cell_reports_what_the_latent_cell_reports_and_the_walk():
    """The accepted lists the new cell joined: the latent cell's, and
    the live-block count its walk follows; not the two K/V readers that
    count a pool for every layer."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    names = {m["name"] for m in doc["per_layer"] if CELL in m["workloads"]}
    latent = {m["name"] for m in doc["per_layer"]
              if "serve-pangu-longchat-saturated" in m["workloads"]}
    assert names == latent | {"paged_live_blocks_per_step"}
    assert not names & {"paged_decode_grid_roofline", "paged_grid_ms_per_step"}
