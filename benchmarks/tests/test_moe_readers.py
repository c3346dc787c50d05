"""The routed block's readers (PR 27): on a hand-made traced run whose
arithmetic is known, on a program that names no such scope or counts
no such pairs (a parent commit: nothing is returned, nothing raises),
and on one iteration a chip run of `serve-olmoe-chat-saturated` left
(the readers' arithmetic on real names and paths, not a device number).
"""

import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import reduce as R
from benchmarks.trace.capture import load_recorded

BENCH = pathlib.Path(__file__).resolve().parents[1]
RECORDED = BENCH / "trace" / "recorded" / "serve-olmoe-chat-saturated.json"
HF = harness.load_json(BENCH / "configs" / "olmoe-1b-7b-serve-l8.json")
PEAKS = harness.load_json(BENCH / "peaks.json")["TPU v5 lite"]
NEW = ("moe_ms_per_step", "moe_experts_ms_per_step",
       "moe_route_combine_ms_per_step", "moe_experts_roofline",
       "moe_rows_per_expert", "serve_scope_named_share")


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def hand_made():
    """Two 40 ms shared-table programs. Each: attention 20 ms (the grid
    kernel 12 of it), and a routed block of route 1 ms, a 12 ms scan
    over experts (a `while` that CONTAINS two 6 ms bodies) and a 0.5 ms
    combine; 2 ms of sampler outside every scope."""
    S, ops, modules = R.Event, [], []
    for i in range(2):
        t = 0.050 * i
        J = "jit(step)/"
        ops += [
            S("fusion.1", t, 0.008, J + "attention/se,ehd->shd/dot_general"),
            S("paged_decode_grid.3", t + 0.008, 0.012,
              J + "attention/paged_decode_grid/pallas_call"),
            S("fusion.2", t + 0.020, 0.001, J + "mlp/moe_route/top_k"),
            S("while.7", t + 0.021, 0.012, J + "mlp/moe_experts/while"),
            S("fusion.3", t + 0.021, 0.006,
              J + "mlp/moe_experts/while/body/dot_general"),
            S("fusion.3", t + 0.027, 0.006,
              J + "mlp/moe_experts/while/body/dot_general"),
            S("fusion.4", t + 0.033, 0.0005, J + "mlp/moe_combine/scatter-add"),
            S("fusion.5", t + 0.0335, 0.0045, J + "lm_head/dot_general"),
            S("fusion.9", t + 0.038, 0.002, "jit(sample)/argmax"),
        ]
        modules.append(S("jit_step(1)", t, 0.038))
        modules.append(S("jit_sample(2)", t + 0.038, 0.002))
    td = R.from_events({0: ops}, {0: modules},
                       [S(R.WINDOW_SPAN, 0.0, 0.100)])
    return {"trace": td, "hf": HF, "n_layers": 8, "peaks": PEAKS,
            "counters_delta": {"steps": 10, "batched_tokens": 1280,
                               "moe_token_expert_pairs": 10240}}


def test_the_readers_on_a_hand_made_run(capsys):
    obs = hand_made()
    assert read("moe_ms_per_step", obs) == pytest.approx(13.5)
    assert read("moe_experts_ms_per_step", obs) == pytest.approx(12.0)
    assert read("moe_route_combine_ms_per_step", obs) == pytest.approx(1.5)
    assert read("moe_rows_per_expert", obs) == pytest.approx(16.0)
    # 128 tokens an iteration: all 64 experts' weights (3 x 2048 x 1024
    # x 2 B each) and the tokens in and out, 8 layers, at 819 GB/s
    need_ms = 1e3 * 8 * (64 * 3 * 2048 * 1024 + 2 * 128 * 2048) * 2 / 819e9
    assert need_ms == pytest.approx(7.876, rel=1e-3)
    assert read("moe_experts_roofline", obs) == pytest.approx(
        100 * need_ms / 12.0)
    assert "memory-bound" in capsys.readouterr().out
    # busy 40 of each 50 ms; the sampler's 2 ms carry no model scope
    assert read("serve_scope_named_share", obs) == pytest.approx(95.0)
    # the routed block and the paged kernels are apart, inside a program
    assert read("moe_ms_per_step", obs) + read("paged_grid_ms_per_step", obs) \
        < read("mixed_program_ms", obs)


def test_the_expert_blocks_needs_follow_the_tokens():
    moe = harness.load_module(BENCH / "kernels" / "moe.py")
    one = moe.expert_flops_and_bytes(HF, 1)
    # 8 pairs reach at most 8 experts
    assert one["bytes"] == (8 * 3 * 2048 * 1024 + 2 * 2048) * 2
    assert one["flops"] == 2 * 3 * 2048 * 1024 * 8
    many = moe.expert_flops_and_bytes(HF, 4096)
    assert many["flops"] == 4096 * one["flops"]
    # compute binds once an expert sees ~240 rows: 1,920 tokens and more
    assert many["flops"] / PEAKS["bf16_flops_per_s"] \
        > many["bytes"] / PEAKS["hbm_bytes_per_s"]
    few = moe.expert_flops_and_bytes(HF, 128)
    assert few["flops"] / PEAKS["bf16_flops_per_s"] \
        < few["bytes"] / PEAKS["hbm_bytes_per_s"]


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_the_scopes_or_the_counter_gives_nothing(name):
    """The parent commit, and a CPU rehearsal: no raise, no number."""
    obs = hand_made()
    for e in obs["trace"].ops[0]:
        e.scope = ""
    del obs["counters_delta"]["moe_token_expert_pairs"]
    assert read(name, obs) is None
    assert read(name, {}) is None
    assert read(name, {"trace": None, "counters_delta": {}}) is None


def test_the_new_metrics_are_the_new_cells_alone():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == ["serve-olmoe-chat-saturated"]
        assert by_name[name]["moves"] == "tpot_p50_ms"
    served = {m["name"] for m in bench["per_layer"]
              if "serve-chat-saturated" in m["workloads"]}
    cell = harness.load_cell("serve-olmoe-chat-saturated")
    assert served | set(NEW) == {m["name"] for m in cell.per_layer}


def test_the_readers_on_the_recorded_iteration(capsys):
    """One iteration of the cell on the chip (my chip run, PR 27)."""
    td = load_recorded(RECORDED)
    programs = R.modules_with(td, "paged_decode_grid")
    assert len(programs) >= 1
    obs = {"trace": td, "hf": HF, "n_layers": 8, "peaks": PEAKS,
           "counters_delta": {"steps": 1300, "batched_tokens": 1300 * 128,
                              "moe_token_expert_pairs": 1300 * 1024}}
    moe, experts = read("moe_ms_per_step", obs), read("moe_experts_ms_per_step", obs)
    rest = read("moe_route_combine_ms_per_step", obs)
    assert moe == pytest.approx(experts + rest)
    assert 0 < rest < experts
    # 8 layers x 64 experts' bodies ran under the scope, in a `while`
    bodies = [e for e in R.scope_events(td, ("moe_experts",))
              if "/while/body/" in e.scope]
    assert len(bodies) >= 8 * 64 * len(programs)
    share = read("moe_experts_roofline", obs)
    assert 0 < share <= 100 and "memory-bound" in capsys.readouterr().out
    program_ms = read("mixed_program_ms", obs)
    assert moe + read("paged_grid_ms_per_step", obs) < program_ms
    assert 90 < read("serve_scope_named_share", obs) <= 100
    assert read("moe_rows_per_expert", obs) == pytest.approx(16.0)
    capsys.readouterr()
