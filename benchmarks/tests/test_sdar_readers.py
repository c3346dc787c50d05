"""The readers, the mix, the runner kind and the cell of an SDAR-MoE model
(PR 65: generation by diffusion over blocks): the block counters'
ratios, the reveal epilogue's device time and the expert pass against
what the published weights need, on a hand-made traced run whose
arithmetic is known and on a program that counts no blocks (a parent
commit, another family: nothing is returned, nothing raises); the mix of
kind `serve_blocks` held to what test_traffic.py holds kind `serve` to;
the runner end to end on the CPU through its rehearsal cell.

The five readers are NOT entries of BENCHMARK.json, and no PR but one of
kind `benchmark` can make them so: the driver holds each accepted entry
to its place and test_sched_lookahead_share.py pins the last one
(PERF.md section 7 (a); the readers of PRs 33-62 wait for the same PR).
`ENTRIES` below is what that PR appends AFTER those, in this order."""

import json
import pathlib

import jax
import numpy as np
import pytest

from benchmarks import harness
from benchmarks.tests import helpers
from benchmarks.tests import test_runners as TR
from benchmarks.trace import reduce as R
from benchmarks.traffic import generate

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = harness.load_json(BENCH / "configs" / "sdar-30b-a3b-chat-serve-l6.json")
OTHER_HF = harness.load_json(BENCH / "configs" / "olmoe-1b-7b-serve-l8.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
CELL = "serve-sdar-chat-saturated-r256"
MIX = "chat-saturated-sdar"
NEW = ("block_row_passes_per_token", "block_tokens_per_step",
       "block_rows_share", "block_unmask_ms_per_step",
       "sdar_experts_roofline")
REHEARSAL = next(rc for rc in TR.REHEARSALS if rc["runner"] == "serve_blocks")


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def hand_made(hf=HF):
    """Two 12 ms shared-table programs, each followed by a 0.25 ms
    epilogue program of its own: attention of a 1 ms projection, a
    0.1 ms write and a 0.3 ms walk, a routed block of 0.2 ms routing and
    9.6 ms of experts, 0.8 ms of head; over ten iterations 2,500 rows,
    of which 2,400 a block's (1,920 in 480 denoising passes, 480 in 120
    commits) for 470 committed tokens."""
    S, ops, modules = R.Event, [], []
    for i in range(2):
        t = 0.015 * i
        J, A, M = "jit(step)/", "jit(step)/attention/", "jit(step)/mlp/"
        ops += [
            S("fusion.1", t, 0.001, A + "dot_general"),
            S("paged_kv_write.2", t + 0.001, 0.0001,
              A + "paged_kv_write/pallas_call"),
            S("paged_decode_grid.3", t + 0.0011, 0.0003,
              A + "paged_decode_grid/pallas_call"),
            S("fusion.4", t + 0.0014, 0.0002, M + "moe_route/dot_general"),
            S("expert_stream.5", t + 0.0016, 0.0096,
              M + "moe_experts/expert_stream/pallas_call"),
            S("fusion.6", t + 0.0112, 0.0008, J + "lm_head/dot_general"),
            S("fusion.7", t + 0.0121, 0.00025,
              "jit(<lambda>)/block_unmask/reduce"),
        ]
        modules += [S("jit_step(1)", t, 0.012),
                    S("jit__lambda_(2)", t + 0.0121, 0.00025)]
    td = R.from_events({0: ops}, {0: modules},
                       [S(R.WINDOW_SPAN, 0.0, 0.030)])
    return {"trace": td, "hf": hf, "n_layers": 6, "peaks": PEAKS,
            "ticks": [(0.0, 22_000, 64, 90), (0.015, 22_400, 64, 90)],
            "counters_delta": {
                "steps": 10, "batched_tokens": 2500, "block_rows": 2400,
                "block_passes": 480, "block_commits": 120,
                "block_masked_rows": 1200, "block_tokens": 470,
                "block_restarts": 0, "output_tokens": 470}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    assert read("block_row_passes_per_token", obs) == pytest.approx(
        2400 / 470)
    assert read("block_tokens_per_step", obs) == pytest.approx(47.0)
    assert read("block_rows_share", obs) == pytest.approx(96.0)
    assert read("block_unmask_ms_per_step", obs) == pytest.approx(0.25)
    # the expert pass, 6 layers at 250 rows: every expert's weights once
    # (128 x 3 x 2048 x 768 values) and the rows in and out, bf16
    need = 2 * (128 * 3 * 2048 * 768 + 2 * 250 * 2048)
    by_bytes = 1e3 * 6 * need / PEAKS["hbm_bytes_per_s"]
    assert read("sdar_experts_roofline", obs) == pytest.approx(
        100 * by_bytes / 9.6)
    out = capsys.readouterr().out
    assert "sdar experts: memory-bound" in out
    # every expert against all 250 rows is 9.2 ms of the MXU: more than
    # the 8.9 ms the weights take, and the line says so
    assert "held by its all-expert design" in out
    assert read("sdar_experts_roofline", obs) < 100


def test_a_pass_of_few_rows_is_held_by_the_weights(capsys):
    obs = hand_made()
    obs["counters_delta"]["batched_tokens"] = 1000   # 100 rows a step
    read("sdar_experts_roofline", obs)
    assert "held by the weights" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_program_that_counts_no_blocks_gives_nothing(name):
    """A parent commit's counters, another family's run, an untraced
    run, an empty observation."""
    S = R.Event
    td = R.from_events(
        {0: [S("paged_decode_grid.1", 0.0, 0.01,
               "jit(step)/attention/paged_decode_grid/pallas_call"),
             S("expert_stream.2", 0.01, 0.005,
               "jit(step)/mlp/moe_experts/expert_stream/pallas_call")]},
        {0: [S("jit_step(1)", 0.0, 0.02)]}, [S(R.WINDOW_SPAN, 0.0, 0.05)])
    obs = {"trace": td, "hf": OTHER_HF, "n_layers": 8, "peaks": PEAKS,
           "ticks": [(0.0, 1000, 8, 0)],
           "counters_delta": {"steps": 10, "batched_tokens": 1280,
                              "moe_token_expert_pairs": 10240}}
    assert read(name, obs) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None
    assert read(name, {}) is None


def test_the_needs_at_the_published_widths():
    """The issue's arithmetic, recounted from the file."""
    shapes = harness.load_module(BENCH / "kernels" / "sdar_moe.py")
    assert shapes.layer_parameters(HF) == {
        "attention": 18_874_368, "norms": 4_352, "router": 262_144,
        "experts": 603_979_776}
    assert shapes.parameters(HF) == 4_361_055_744
    assert shapes.kv_bytes_per_token(HF) == 12_288
    at_256 = shapes.expert_pass(HF, 256)
    assert at_256["bytes"] == pytest.approx(1.21e9, rel=0.01)
    assert at_256["flops"] == pytest.approx(309e9, rel=0.01)
    assert at_256["needed_flops"] * 16 == at_256["flops"]
    eng = HF["serve"]["engine"]
    pool = eng["num_kv_blocks"] * eng["kv_block_size"] * 12_288
    assert 2.4e9 < pool < 2.45e9
    assert 64 * -(-1536 // 128) == 768 <= eng["num_kv_blocks"]


def _entry(name, unit, better, layer, source="program_counter"):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "tpot_p50_ms", "workloads": [CELL]}


ENTRIES = [
    _entry("block_row_passes_per_token", "rows/token", "lower", "scheduler"),
    _entry("block_tokens_per_step", "tokens", "higher", "scheduler"),
    _entry("block_rows_share", "%", "higher", "scheduler"),
    _entry("block_unmask_ms_per_step", "ms", "lower",
           "serve entry + serving model", "device_trace"),
    _entry("sdar_experts_roofline", "%", "higher",
           "serve entry + serving model",
           "device_trace"),
]


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_the_entry_a_benchmark_pr_appends(entry):
    """Each reader's entry in the accepted form (a layer and a source
    BENCHMARK.json already names, the new cell alone, a reader file by
    its name); BENCHMARK.json lacks it, as this PR must leave it, or
    holds exactly it."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    assert [e["name"] for e in ENTRIES] == list(NEW)
    assert (BENCH / "metrics" / f"{entry['name']}.py").is_file()
    assert (not entry["name"].endswith("_roofline")) or entry["unit"] == "%"
    assert helpers.NAME.match(entry["name"]) and helpers.UNIT.match(
        entry["unit"])
    old = [m for m in doc["per_layer"] if m["name"] not in NEW]
    assert entry["layer"] in {m["layer"] for m in old}
    assert entry["source"] in {m["source"] for m in old}
    assert set(entry["workloads"]) <= {w["name"] for w in doc["workloads"]}
    moved = next(m for m in doc["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert [m for m in doc["per_layer"]
            if m["name"] == entry["name"]] in ([], [entry])


def test_the_cell_reports_what_the_routed_cells_report_and_the_walks_share():
    """The accepted lists the new cell joined: those the later routed
    cells are on (`serve-mellum2-mixedlen-saturated-r256`: two
    end-to-end, 24 per-layer), and the two of the walk that read TRUE of
    a model with K/V in every layer and one pool
    (`paged_decode_grid_roofline`, `paged_live_blocks_per_step`). NOT
    the six that accepted tests hold to the OLMoE cell alone
    (test_moe_readers.py `NEW`: four `moe_*` times and counts,
    `moe_experts_roofline`, whose `kernels/moe.py` would read the unused
    dense width 6,144 as an expert's 768, and `serve_scope_named_share`)
    nor `sched_lookahead_share` (test_sched_lookahead_share.py holds the
    per-layer list's last entry whole); nothing but appends, the new
    cell and its configuration last."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    like = "serve-mellum2-mixedlen-saturated-r256"
    walk = {"paged_decode_grid_roofline", "paged_live_blocks_per_step"}
    for group, extra in (("end_to_end", set()), ("per_layer", walk)):
        mine = {m["name"] for m in doc[group] if CELL in m.get("workloads", ())}
        its = {m["name"] for m in doc[group] if like in m.get("workloads", ())}
        assert mine == its | extra
        assert all(m["workloads"][-1] == CELL for m in doc[group]
                   if CELL in m.get("workloads", ()))
    olmoe = {m["name"] for m in doc["per_layer"]
             if "serve-olmoe-chat-saturated" in m.get("workloads", ())}
    assert walk <= olmoe
    assert doc["per_layer"][-1]["name"] == "sched_lookahead_share"
    assert CELL not in doc["per_layer"][-1]["workloads"]
    assert doc["workloads"][-1] == {
        "name": CELL, "config": "sdar-30b-a3b-chat-serve-l6",
        "traffic": MIX, "chips": 1, "why": doc["workloads"][-1]["why"]}
    assert len(doc["workloads"][-1]["why"]) <= 200
    assert doc["configs"][-1]["name"] == "sdar-30b-a3b-chat-serve-l6"
    assert doc["configs"][-1]["reduced"] == ["num_hidden_layers"]
    assert len(doc["workloads"]) == 14
    assert sum(w["chips"] == 4 for w in doc["workloads"]) == 1
    helpers.check_contract(doc)
    helpers.check_cell(BENCH.parent, CELL)


def test_the_older_cells_stand_where_they_stood():
    """What test_phi4flash_readers.py pinned until this cell came after
    its own (tests/conftest.py _OUTGROWN_BENCHMARK_PINS): the Phi-4-flash
    cell is on exactly the lists the other ring cell is on, thirteenth,
    before the new one."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    cell, like = ("serve-phi4flash-reasoning-saturated-r128",
                  "serve-mellum2-mixedlen-saturated-r256")
    for group, n in (("end_to_end", 2), ("per_layer", 24)):
        mine = {m["name"] for m in doc[group] if cell in m.get("workloads", ())}
        its = {m["name"] for m in doc[group] if like in m.get("workloads", ())}
        assert mine == its and len(mine) == n
    assert doc["workloads"][12]["name"] == cell
    assert doc["configs"][12]["name"] == "phi-4-mini-flash-reasoning-serve-l32"
    assert doc["configs"][12]["reduced"] == []


def test_the_cells_traffic_is_the_issues():
    """The issue's cell, number by number: OLMoE's chat lengths letter
    for letter, a burst of one request a block in flight, twice the
    knee, the steps."""
    mix = harness.load_json(BENCH / "traffic" / f"{MIX}.json")
    olmoe = harness.load_json(BENCH / "traffic" / "chat-saturated-olmoe.json")
    assert mix["runner"] == "serve_blocks" and mix["burst_at_start"] == 64
    for key in ("prompt_len", "answer_len", "prompt_tokens", "ramp_s",
                "drain_s", "count"):
        assert mix[key] == olmoe[key], key
    assert mix["knee_multiple"] == 2.0
    assert mix["rate_rps"] == pytest.approx(2.0 * mix["knee_rps"], abs=0.5)
    # a rate that deals every seed one count of arrivals in 59 s
    counts = {len(generate.serve_schedule(mix, seed, 59.0, 1000).due_s)
              for seed in range(1, 9)}
    assert len(counts) == 1
    assert mix["steps"]["block_length"] == HF["block_length"] == 4
    assert mix["steps"]["denoising_steps"] == \
        HF["serve"]["scheduler"]["denoising_steps"] == 4
    chk = mix["logits_check"]
    assert (chk["prompt_lens"], chk["chunk"], chk["blocks"]) == (
        [300, 1316], 8, 3)
    assert all(n % 4 == 0 for n in chk["prompt_lens"] + [chk["chunk"]])
    assert 256 in mix["warmup_widths"] and mix["warmup_why"]
    eng = HF["serve"]["engine"]
    assert eng["max_batch_size"] == 4 * mix["burst_at_start"]
    helpers.check_logits_limit(mix)


# -- the mix of kind `serve_blocks`, held to what kind `serve` is --------

def _mixes():
    return [p.stem for p in sorted((BENCH / "traffic").glob("*.json"))
            if json.loads(p.read_text())["runner"] == "serve_blocks"]


def test_the_kind_has_its_mix_and_its_rehearsal():
    assert _mixes() == [MIX]
    assert REHEARSAL["reports_as"] == CELL
    assert REHEARSAL["reference"] == HF["reference"] == "sdar_moe"
    real = harness.load_cell(CELL)
    assert real.traffic["runner"] == REHEARSAL["runner"]
    helpers.check_references_rehearsed(BENCH.parent)


def test_the_schedule_is_a_function_of_the_seed_inside_its_clips():
    """test_traffic.py's three tests of a `serve` mix, on this one, the
    context read from the cell's own engine."""
    mix = harness.load_json(BENCH / "traffic" / f"{MIX}.json")
    a = generate.serve_schedule(mix, 7, 30.0, 32000)
    b = generate.serve_schedule(mix, 7, 30.0, 32000)
    c = generate.serve_schedule(mix, 8, 30.0, 32000)
    assert np.array_equal(a.due_s, b.due_s)
    assert np.array_equal(a.answer_len, b.answer_len)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert not np.array_equal(a.prompts[0], c.prompts[0])
    assert not np.array_equal(a.prompt_len[:50], c.prompt_len[:50])
    s = generate.serve_schedule(mix, 1, 60.0, 32000)
    p, ans = mix["prompt_len"], mix["answer_len"]
    assert s.prompt_len.min() >= p["min"] and s.prompt_len.max() <= p["max"]
    assert s.answer_len.min() >= ans["min"] \
        and s.answer_len.max() <= ans["max"]
    # a block more than the answer: the last block is generated whole
    assert p["max"] + ans["max"] + HF["block_length"] \
        <= HF["serve"]["engine"]["max_seq_len"]
    burst = int(mix["burst_at_start"])
    assert np.all(s.due_s[:burst] == 0)
    assert np.all(np.diff(s.due_s[burst:]) >= 0) and s.due_s.max() < 60.0
    assert 0.8 * p["median"] < np.median(s.prompt_len) < 1.25 * p["median"]
    r = generate.serve_schedule(mix, 3, 400.0, 1000, rate_rps=5.0)
    assert abs((len(r.due_s) - burst) / 400.0 - 5.0) < 0.4


# -- the runner, end to end on the CPU ------------------------------------

def test_the_runner_end_to_end(tiny_root):
    """The rehearsal cell through harness.run_cell: a window of blocks
    denoised and committed, then the block check of every row of every
    pass against the reference."""
    line, logs = TR._run(tiny_root, REHEARSAL)
    said = [m for m in logs if m.startswith("[bench] block logits vs")]
    # prefill, chunk, 2 blocks x (4 passes + a commit): 12 feeds of 4
    # rows, of two prompts
    assert len(said) == 1 and "96 positions (12 feeds of 4 rows" in said[0]
    checks = next(m for m in logs if m.startswith("[bench] checks"))
    assert "'blocks_took_their_passes': True" in checks
    notes = json.loads(next(m for m in logs if m.startswith(
        "[bench] notes: "))[len("[bench] notes: "):])
    d = notes["counters_delta"]
    assert d["block_rows"] == 4 * (d["block_passes"] + d["block_commits"])
    assert d["block_tokens"] == d["output_tokens"] > 0
    # five passes a block of four, but a first block that holds a
    # prompt's remainder takes fewer and a last one is cut
    assert 4.0 <= notes["blocks"]["per_token"] <= 9.0


def test_a_broken_served_path_comes_out_not_correct(tiny_root, monkeypatch):
    """test_runners.py's case for kind `serve`, for this kind: every
    vocabulary entry of what put() returns moved by one."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.ops.pallas import interpret_kernels

    put = InferenceEngine.put
    monkeypatch.setattr(
        InferenceEngine, "put",
        lambda self, *a, **k: jax.numpy.roll(put(self, *a, **k), 1, axis=-1))
    cell = harness.load_cell(REHEARSAL["name"], tiny_root)
    logs = []
    with interpret_kernels():
        line = json.loads(harness.run_cell(
            cell, seed=4, seconds=REHEARSAL["seconds"], trace=False,
            devices=jax.devices()[:1], t_process_start=harness.now(),
            log=logs.append, out_root=tiny_root / "out"))
    assert line["correct"] is False and line["failed"] == 0
    false = [m for m in logs if m.startswith("[bench] FALSE: ")]
    assert len(false) == 1 and "matches_reference" in false[0], logs[-4:]


def test_a_causal_configuration_is_refused_by_the_kind(tiny_root):
    from benchmarks.runners import serve_blocks

    cell = harness.load_cell(REHEARSAL["name"], tiny_root)
    cell.config = dict(harness.load_json(
        helpers.data_dir() / "configs" / "tiny-olmoe.json"))
    ctx = harness.RunContext(
        cell=cell, seed=5, seconds=1.0, trace=False,
        devices=jax.devices()[:1], t_process_start=harness.now(),
        compiles=harness.CompileCounter(), out_dir=tiny_root / "out",
        log=lambda s: None)
    with pytest.raises(ValueError, match="diffusion over blocks"):
        serve_blocks.run(ctx)
