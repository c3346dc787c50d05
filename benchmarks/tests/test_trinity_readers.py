"""The routed, windowed TRAINING cell (PR 55): runner kind
`train_routed` end to end at a tiny size on the CPU (kernels in
interpret mode, from files ADDED to a copy of the benchmark), the
family's arithmetic at the published widths, and the eight readers of
its scopes and counters, on a hand-made traced run whose arithmetic is
known and on a program that names no such scope (a parent commit, the
dense cell: nothing is returned, nothing raises).

The eight readers are NOT entries of BENCHMARK.json, and no PR but one
of kind `benchmark` can make them so (test_sched_lookahead_share.py
pins the list's last place; the readers of PR 33-53's test files wait
for the same PR). `ENTRIES` below is what that PR appends after those,
in this order."""

import json
import pathlib

import jax
import pytest

from benchmarks import harness
from benchmarks.tests import helpers
from benchmarks.trace import reduce as R
from deepspeed_tpu.ops.pallas import interpret_kernels

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = harness.load_json(BENCH / "configs" / "trinity-mini-train-l5-ep8.json")
DENSE_HF = harness.load_json(BENCH / "configs" / "mistral-7b-train-l2.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
CELL = "train-trinity-seq8k"
NEW = ("windowed_flash_roofline", "held_experts_train_roofline",
       "moe_train_ms_per_step", "moe_route_combine_train_ms_per_step",
       "window_attn_train_ms_per_step", "full_attn_train_ms_per_step",
       "expert_bias_update_ms_per_step", "moe_held_pairs_per_step")
A = harness.load_module(BENCH / "kernels" / "afmoe.py")


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def test_the_runner_end_to_end_on_the_rehearsal_pair(tiny_root):
    """tiny-trinity / tiny-trinity-train: the same protocol and line as
    the `train` kind, and the added checks each seen to have run."""
    rc = next(rc for rc in helpers.rehearsal_cells()
              if rc["name"] == "tiny-trinity-train")
    assert rc["runner"] == "train_routed" and rc["reference"] == "afmoe"
    cell = harness.load_cell(rc["name"], tiny_root)
    logs = []
    with interpret_kernels():
        line = json.loads(harness.run_cell(
            cell, seed=2_345_678_901, seconds=rc["seconds"], trace=False,
            devices=jax.devices()[:1], t_process_start=harness.now(),
            log=logs.append, out_root=tiny_root / "out"))
    assert line["correct"], logs
    assert line["device"]["platform"] == "cpu"   # never a device number
    assert set(line["metrics"]) == set(rc["expect"]["end_to_end"])
    assert line["attempted"] >= rc["expect"]["min_attempted"]
    assert line["failed"] == 0
    kept = json.loads((tiny_root / "out" / "bench" / rc["name"]
                       / "seed2345678901_trace0.json").read_text())
    checks = kept["notes"]["checks"]
    assert set(checks) >= {
        "finite", "first_loss_as_a_fresh_model", "loss_fell",
        "no_compile_in_window", "matches_reference",
        "census_sums_to_every_pair", "no_held_pair_dropped"}
    d = kept["notes"]["counters_delta"]
    # 2 x 256 tokens x top-2 x 4 routed layers a step; half the experts held
    assert d["moe_pairs_routed"] == d["steps"] * 2 * 256 * 2 * 4
    assert 0 < d["moe_pairs_held"] < d["moe_pairs_routed"]
    assert d["moe_pairs_dropped"] == 0 and d["expert_bias_abs_max"] > 0
    # both states of the parameters were held to the reference, logits and
    # router, and the numbers are in the run's notes and on its last lines
    notes = kept["notes"]
    assert set(notes["reference"]) == {"initial", "trained"}
    for got in notes["reference"].values():
        assert set(got) == {"max_share", "median_share", "ref_max_abs",
                            "flipped_share", "weight_err"}
        assert got["flipped_share"] <= 0.002 and got["weight_err"] <= 2e-4
    assert len(notes["compared"]) == 4
    assert kept["notes"]["params"] == A.model_params(cell.config)
    for state in ("initial", "trained"):
        assert any(s.startswith(f"[bench] compared ({state} parameters): "
                                "logits over 256 positions") for s in logs)


def test_the_comparison_sees_a_routers_precision(tiny_root):
    """The cell's own comparison (benchmarks/afmoe_audit.verdicts: the
    runner's numbers under the traffic file's limits) on the rehearsal
    pair: the system is correct; a bf16 router in its place passes both
    logits limits (a flipped near-tie moves a position, as the system's
    own bf16 activations do) and is NOT correct by the router's rule; a
    router without its scale fails the router's weights too."""
    from benchmarks import afmoe_audit
    from benchmarks.runners import train_routed
    from benchmarks.traffic import generate

    cell = harness.load_cell("tiny-trinity-train", tiny_root)
    engine, mcfg = train_routed.build_engine(cell, jax.devices()[:1], 11)
    toks = next(generate.token_batches(
        cell.traffic, 12, mcfg.vocab_size, 1))["tokens"][:, :-1]
    got = afmoe_audit.verdicts(cell, mcfg, engine.state.params, toks,
                               engine.mesh, "initial",
                               names=("bf16_router", "scale_1"))
    lim = cell.traffic["router_check"]
    assert got["system"]["ok"], got["system"]["line"]
    assert got["system"]["flipped_share"] == 0
    assert got["system"]["weight_err"] < 1e-6
    bf = got["bf16_router"]
    assert not bf["ok"] and "BROKEN: the router chose other experts" in bf["line"]
    assert bf["flipped_share"] > 5 * lim["flipped_share"]
    assert bf["weight_err"] > 5 * lim["weight_atol"]
    assert bf["max_share"] <= cell.traffic["logits_check"]["rtol"]
    assert bf["median_share"] <= cell.traffic["logits_check"]["typical_rtol"]
    assert not got["scale_1"]["ok"] and got["scale_1"]["weight_err"] > 0.5


def test_the_cell_and_its_files():
    helpers.check_cell(helpers.ROOT, CELL)
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["runner"] == "train_routed"
    assert {m["name"] for m in cell.end_to_end} == {
        "train_tokens_per_s_per_chip", "setup_s"}
    # the triangle in every layer would read over 100% here
    assert "flash_roofline" not in {m["name"] for m in cell.per_layer}
    assert "train_mfu" in {m["name"] for m in cell.per_layer}
    assert cell.traffic["seq_len"] == 8192
    assert cell.config["train"]["ds_config"][
        "train_micro_batch_size_per_gpu"] == 2


def test_the_needs_at_the_published_widths():
    """ISSUE 55's arithmetic, and kernels/shapes.py keeps refusing it."""
    from benchmarks.kernels import shapes

    assert A.attention_params(HF) == 27_263_232
    assert A.layer_params(HF, dense=True) == 65_020_160
    assert A.layer_params(HF, dense=False) == 134_488_448
    assert A.model_params(HF) == 705_474_304
    assert A.expected_held_pairs_per_token(HF) == 1.0
    assert A.matmul_params_per_token(HF) == 276_692_992
    assert A.visible_pairs(8192, None) == 8192 * 8193 // 2
    assert A.visible_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048
    assert A.visible_pairs(1024, 2048) == A.visible_pairs(1024, None)
    assert A.windows(HF) == [2048, 2048, 2048, None, 2048]
    assert A.attention_flops_per_token(HF, 8192) == pytest.approx(553.7e6, rel=1e-3)
    assert A.train_flops_per_token(HF, 8192) == pytest.approx(2.214e9, rel=1e-3)
    band = A.flash_flops_and_bytes(HF, 2, 8192, 2048)
    full = A.flash_flops_and_bytes(HF, 2, 8192, None)
    # the triangle as kernels/shapes.py counts it (S^2 / 2), to 1 / S
    assert full["flops"] == pytest.approx(
        shapes.flash_flops_and_bytes(HF, 2, 8192)["flops"], rel=2e-4)
    assert band["bytes"] == full["bytes"] == shapes.flash_flops_and_bytes(
        HF, 2, 8192)["bytes"]
    assert band["flops"] / full["flops"] == pytest.approx(
        A.visible_pairs(8192, 2048) / A.visible_pairs(8192, None))
    moe = A.held_experts_flops_and_bytes(HF, 65_536)
    assert moe["flops"] == 18.0 * 2048 * 1024 * 65_536
    assert moe["bytes"] == 3.0 * 4 * 16 * 6_291_456 * 2
    with pytest.raises(ValueError, match="cannot count a block"):
        shapes.train_flops_per_token(HF, 8192, 5)


def hand_made():
    """Three traced steps of 400 ms. Each: embed 1; the dense layer and
    four routed layers of norm 1 x 2 each, attention projections 10 a
    layer; flash forward 6 (window) or 14 (full) and backward dq 7 / 16,
    dkv 7 / 16 under `attn_window` / `attn_full`; the gate 1; a routed
    block of route 4, experts 12 (of them 8 the grouped products the
    chip renames out of every scope), combine 2, shared 5; the dense MLP 20;
    head 40; optimizer 30; the bias update 0.1."""
    S, ops, modules = R.Event, [], []
    for i in range(3):
        t = [0.5 * i]

        def ev(name, ms, scope):
            ops.append(S(name, t[0], ms * 1e-3, "jit(step_fn)/" + scope))
            t[0] += ms * 1e-3

        ev("fusion.1", 1, "embed/gather")
        for li, w in enumerate(A.windows(HF)):
            where = "attention/" + ("attn_window" if w else "attn_full")
            ev("fusion.2", 1, "layer_stack/while/body/norm1/mul")
            ev("fusion.3", 10, "layer_stack/while/body/attention/dot_general")
            ev("jvp_flash_fwd_.4", 6 if w else 14,
               f"layer_stack/while/body/jvp({where})/flash_fwd/pallas_call")
            ev("transpose_jvp_flash_bwd_dq_.5", 7 if w else 16,
               f"layer_stack/while/body/transpose(jvp({where}))/"
               "flash_bwd_dq/pallas_call")
            ev("transpose_jvp_flash_bwd_dkv_.6", 7 if w else 16,
               f"layer_stack/while/body/transpose(jvp({where}))/"
               "flash_bwd_dkv/pallas_call")
            ev("fusion.7", 1, "layer_stack/while/body/attention/attn_gate/mul")
            ev("fusion.8", 1, "layer_stack/while/body/norm2/mul")
            if li == 0:
                ev("fusion.9", 20, "mlp/dot_general")
                continue
            ev("fusion.10", 4, "layer_stack/while/body/mlp/moe_route/sort")
            ev("fusion.11", 4,
               "layer_stack/while/body/mlp/moe_experts/mul")
            # the grouped products as the chip names them: no scope
            ops.append(S("ragged-dot-none.12", t[0], 8e-3, "ragged-dot-none:"))
            t[0] += 8e-3
            ev("fusion.12", 2,
               "layer_stack/while/body/mlp/moe_combine/scatter-add")
            ev("fusion.13", 5,
               "layer_stack/while/body/mlp/moe_shared/dot_general")
        ev("fusion.14", 40, "lm_head/dot_general")
        ev("fusion.15", 30, "optimizer/mul")
        ev("fusion.16", 0.1, "expert_bias_update/sign")
        modules.append(S("jit_step_fn(1)", 0.5 * i, t[0] - 0.5 * i))
    td = R.from_events({0: ops}, {0: modules}, [S(R.WINDOW_SPAN, 0.0, 1.5)])
    return {"trace": td, "hf": HF, "n_layers": 5, "peaks": PEAKS,
            "traced_steps": 3, "seq_len": 8192, "micro_batch_per_chip": 2,
            "traced_pairs_held": 3 * 65_000,
            "counters_delta": {"steps": 100, "moe_pairs_held": 6_500_000,
                               "moe_pairs_routed": 100 * 16384 * 8 * 4}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    assert read("moe_train_ms_per_step", obs) == pytest.approx(4 * 23)
    assert read("moe_route_combine_train_ms_per_step", obs) == pytest.approx(4 * 6)
    assert read("window_attn_train_ms_per_step", obs) == pytest.approx(4 * 20)
    assert read("full_attn_train_ms_per_step", obs) == pytest.approx(46)
    assert read("expert_bias_update_ms_per_step", obs) == pytest.approx(0.1)
    assert read("moe_held_pairs_per_step", obs) == pytest.approx(65_000)
    # the accepted readers the cell is listed under read the same trace
    assert read("flash_ms_per_step", obs) == pytest.approx(4 * 20 + 46)
    assert read("attention_ms_per_step", obs) == pytest.approx(5 * 11 + 126)
    # (the accepted `mlp` reader goes by scope alone: it misses the grouped
    # products the chip renames, 8 of a routed block's 23 here)
    assert read("mlp_ms_per_step", obs) == pytest.approx(20 + 4 * 15)
    # flash: a band of 2,048 in four layers, the triangle in one
    band = A.flash_flops_and_bytes(HF, 2, 8192, 2048)["flops"]
    full = A.flash_flops_and_bytes(HF, 2, 8192, None)["flops"]
    least = 1e3 * (4 * band + full) / PEAKS["bf16_flops_per_s"]
    assert read("windowed_flash_roofline", obs) == pytest.approx(
        100 * least / 126)
    assert 0 < 100 * least / 126 < 100
    out = capsys.readouterr().out
    assert "a layer of window 2048: bound by compute" in out
    assert "a layer of window none: bound by compute" in out
    # the held experts: 65,000 pairs x 18 E F = 2.45 T operations
    need = 1e3 * 18 * 2048 * 1024 * 65_000 / PEAKS["bf16_flops_per_s"]
    assert read("held_experts_train_roofline", obs) == pytest.approx(
        100 * need / 48)
    assert 0 < 100 * need / 48 < 100
    assert "held experts (train): compute-bound" in capsys.readouterr().out


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_counters_reads_nothing(name):
    """What the parent commit and the dense cell give: flash under
    `attention` alone, a dense `mlp`, no counters."""
    S = R.Event
    td = R.from_events(
        {0: [S("jvp_flash_fwd_.4", 0.0, 0.01,
               "jit(step_fn)/layer_stack/while/body/jvp(attention)/"
               "flash_fwd/pallas_call"),
             S("fusion.9", 0.01, 0.02,
               "jit(step_fn)/layer_stack/while/body/mlp/dot_general"),
             S("fusion.15", 0.03, 0.01, "jit(step_fn)/optimizer/mul")]},
        {0: [S("jit_step_fn(1)", 0.0, 0.04)]}, [S(R.WINDOW_SPAN, 0.0, 0.05)])
    obs = {"trace": td, "hf": DENSE_HF, "n_layers": 2, "peaks": PEAKS,
           "traced_steps": 1, "seq_len": 4096, "micro_batch_per_chip": 4}
    assert read(name, obs) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None
    assert read(name, {}) is None


def _entry(name, unit, better, source="device_trace"):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": "model + flash", "moves": "train_tokens_per_s_per_chip",
            "workloads": [CELL]}


ENTRIES = [
    _entry("windowed_flash_roofline", "%", "higher"),
    _entry("held_experts_train_roofline", "%", "higher"),
    _entry("moe_train_ms_per_step", "ms", "lower"),
    _entry("moe_route_combine_train_ms_per_step", "ms", "lower"),
    _entry("window_attn_train_ms_per_step", "ms", "lower"),
    _entry("full_attn_train_ms_per_step", "ms", "lower"),
    _entry("expert_bias_update_ms_per_step", "ms", "lower"),
    _entry("moe_held_pairs_per_step", "pairs", "higher", "program_counter"),
]


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_the_entry_a_benchmark_pr_appends(entry):
    """Each reader's entry in the accepted form (a layer and a source
    BENCHMARK.json already names, a reader file by its name, the one
    cell that gives it something to read), and BENCHMARK.json either
    lacks it, as this PR must leave it, or holds exactly it."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    assert [e["name"] for e in ENTRIES] == list(NEW)
    assert (BENCH / "metrics" / f"{entry['name']}.py").is_file()
    old = [m for m in doc["per_layer"] if m["name"] not in NEW]
    assert entry["layer"] in {m["layer"] for m in old}
    assert entry["source"] in {m["source"] for m in old}
    moved = next(m for m in doc["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert helpers.NAME.match(entry["name"]) and helpers.UNIT.match(entry["unit"])
    assert [m for m in doc["per_layer"]
            if m["name"] == entry["name"]] in ([], [entry])
