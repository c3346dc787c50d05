"""The latent-attention and held-share readers (PR 33): on a hand-made
traced run whose arithmetic is known, and on a program that names no
such scope or counts no such tokens (a parent commit, another family:
nothing is returned, nothing raises).

The seven readers are NOT entries of BENCHMARK.json yet, and no PR but
one of kind `benchmark` can make them so: the driver holds the accepted
per-layer list to its places (an entry put before the last one reads as
a change to `sched_lookahead_share`), and
`test_sched_lookahead_share.py::test_the_entry_in_benchmark_json` holds
that entry to be the LAST of the list, so an entry appended after it
fails tier-1 (PERF.md section 7). `ENTRIES` below is what that PR
appends, once the pin finds its entry by name."""

import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import reduce as R

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = harness.load_json(BENCH / "configs" / "openpangu-ultra-moe-serve-l5-ep32.json")
OTHER_HF = harness.load_json(BENCH / "configs" / "olmoe-1b-7b-serve-l8.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
NEW = ("mla_ms_per_step", "mla_attend_ms_per_step",
       "mla_cache_write_ms_per_step", "mla_attend_roofline",
       "mla_cache_tokens_per_step", "moe_held_pairs_expected_per_step",
       "moe_held_experts_roofline")


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def hand_made():
    """Two 30 ms shared-table programs. Each: latent attention of
    project 4 ms, cache write 1 ms, the walk 8 ms (the kernel 7.5 of
    it), out 2 ms; a routed block of route 1 ms, a 6 ms scan over the
    held experts (a `while` that CONTAINS two 3 ms bodies) and a 1 ms
    shared expert; 2 ms of head."""
    S, ops, modules = R.Event, [], []
    for i in range(2):
        t = 0.050 * i
        J = "jit(step)/"
        ops += [
            S("fusion.1", t, 0.004, J + "attention/mla_project/dot_general"),
            S("paged_latent_write.2", t + 0.004, 0.001,
              J + "attention/mla_cache_write/paged_latent_write/pallas_call"),
            S("fusion.2", t + 0.005, 0.0005, J + "attention/mla_attend/pad"),
            S("paged_decode_grid.3", t + 0.0055, 0.0075,
              J + "attention/mla_attend/paged_decode_grid/pallas_call"),
            S("fusion.3", t + 0.013, 0.002, J + "attention/mla_out/dot_general"),
            S("fusion.4", t + 0.015, 0.001, J + "mlp/moe_route/top_k"),
            S("while.7", t + 0.016, 0.006, J + "mlp/moe_experts/while"),
            S("fusion.5", t + 0.016, 0.003,
              J + "mlp/moe_experts/while/body/dot_general"),
            S("fusion.5", t + 0.019, 0.003,
              J + "mlp/moe_experts/while/body/dot_general"),
            S("fusion.6", t + 0.022, 0.001, J + "mlp/moe_shared/dot_general"),
            S("fusion.8", t + 0.023, 0.002, J + "lm_head/dot_general"),
        ]
        modules.append(S("jit_step(1)", t, 0.025))
    td = R.from_events({0: ops}, {0: modules},
                       [S(R.WINDOW_SPAN, 0.0, 0.100)])
    return {"trace": td, "hf": HF, "n_layers": 4, "peaks": PEAKS,
            # (time, summed context of the running sequences, active, waiting)
            "ticks": [(0.0, 100_000, 128, 9), (0.05, 140_000, 128, 9)],
            "counters_delta": {"steps": 10, "batched_tokens": 1280,
                               "moe_token_expert_pairs": 10240,
                               "mla_cache_tokens": 2_000_000}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    assert read("mla_ms_per_step", obs) == pytest.approx(15.0)
    assert read("mla_attend_ms_per_step", obs) == pytest.approx(8.0)
    assert read("mla_cache_write_ms_per_step", obs) == pytest.approx(1.0)
    assert read("mla_cache_tokens_per_step", obs) == pytest.approx(200_000)
    assert read("moe_held_pairs_expected_per_step", obs) == pytest.approx(32.0)
    # the walk: 120,000 cached tokens read once x 1,152 B x 5 layers =
    # 691 MB = 0.844 ms; 200,000 row-tokens x 128 heads x 1,088 x 2 x 5
    # = 2.785e11 operations = 1.414 ms: compute-bound, of 8 ms taken
    by_bytes = 1e3 * 5 * 120_000 * 1152 / PEAKS["hbm_bytes_per_s"]
    by_flops = 1e3 * 5 * 200_000 * 128 * 1088 * 2 / PEAKS["bf16_flops_per_s"]
    assert by_flops > by_bytes
    assert read("mla_attend_roofline", obs) == pytest.approx(100 * by_flops / 8.0)
    assert "mla_attend: compute-bound" in capsys.readouterr().out
    # the held share: 8 held + 1 shared expert of 3 x 7680 x 2048 bf16
    # streamed once and 128 tokens in and out, 4 routed layers, of the
    # 7 ms of moe_experts + moe_shared
    need_bytes = (9 * 3 * 7680 * 2048 + 2 * 128 * 7680) * 2
    need_ms = 1e3 * 4 * need_bytes / PEAKS["hbm_bytes_per_s"]
    assert read("moe_held_experts_roofline", obs) == pytest.approx(
        100 * need_ms / 7.0)
    assert "memory-bound" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_reads_nothing(name):
    """What the parent commit and the other families give: a trace with
    no `mla_*` / `moe_shared` scope, counters without the new key, a
    configuration that holds every expert (no `experts_held`)."""
    S = R.Event
    td = R.from_events(
        {0: [S("paged_decode_grid.3", 0.0, 0.01,
               "jit(step)/attention/paged_decode_grid/pallas_call"),
             S("while.7", 0.01, 0.01, "jit(step)/mlp/moe_experts/while")]},
        {0: [S("jit_step(1)", 0.0, 0.02)]}, [S(R.WINDOW_SPAN, 0.0, 0.05)])
    obs = {"trace": td, "hf": OTHER_HF, "n_layers": 4, "peaks": PEAKS,
           "ticks": [(0.0, 1000, 8, 0)],
           "counters_delta": {"steps": 10, "batched_tokens": 1280,
                              "moe_token_expert_pairs": 10240}}
    assert read(name, obs) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None
    assert read(name, {}) is None


def test_the_needs_at_the_published_widths():
    shapes = harness.load_module(BENCH / "kernels" / "mla.py")
    assert shapes.latent_row_bytes(HF) == 1152
    need = shapes.latent_walk_flops_and_bytes(HF, 1000, 3000)
    assert need == {"bytes": 1000 * 1152.0, "flops": 2.0 * 3000 * 128 * 1088}
    # 242 operations a byte where each table has one row: the chip's ridge
    assert need["flops"] / need["bytes"] / 3 == pytest.approx(241.8, abs=0.1)
    held = shapes.held_experts_flops_and_bytes(HF, 128, 32.0)
    assert held["flops"] == 2.0 * 3 * 7680 * 2048 * (32 + 128)
    assert held["bytes"] == (9 * 3 * 7680 * 2048 + 2 * 128 * 7680) * 2


def _entry(name, unit, better, source, layer):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "tpot_p50_ms",
            "workloads": ["serve-pangu-longchat-saturated"]}


ENTRIES = [
    _entry("mla_ms_per_step", "ms", "lower", "device_trace",
           "serve entry + serving model"),
    _entry("mla_attend_ms_per_step", "ms", "lower", "device_trace",
           "paged kernels"),
    _entry("mla_cache_write_ms_per_step", "ms", "lower", "device_trace",
           "paged kernels"),
    _entry("mla_attend_roofline", "%", "higher", "device_trace",
           "paged kernels"),
    _entry("mla_cache_tokens_per_step", "tokens", "lower", "program_counter",
           "scheduler"),
    _entry("moe_held_pairs_expected_per_step", "pairs", "higher",
           "program_counter", "scheduler"),
    _entry("moe_held_experts_roofline", "%", "higher", "device_trace",
           "serve entry + serving model"),
]


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_the_entry_a_benchmark_pr_appends(entry):
    """Each reader's entry is written down here in the accepted form (a
    layer BENCHMARK.json already names, the new cell alone, a reader
    file by its name), and BENCHMARK.json either lacks it, as this PR
    must leave it, or holds exactly it."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    assert entry["name"] in NEW
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
