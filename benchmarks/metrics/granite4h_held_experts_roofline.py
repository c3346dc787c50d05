"""Share of its roofline a HELD share of routed experts and the ungated
shared expert reach in a Granite 4.0-H model: the larger of the bytes
their weights take to stream once a layer (plus the tokens in and out)
over the HBM peak and the operations the held pairs and the shared
expert's tokens need over the bf16 peak, over the device time of the
scopes `moe_experts` + `moe_shared` per shared-table program. Tokens an
iteration are the scheduler's count (`moe_token_expert_pairs`); the
held pairs are the EXPECTATION `moe_held_pairs_expected_per_step`
reads, so the operations term is assumed, not measured (the bytes term,
which binds at these widths, does not depend on it); it prints which
bound. `moe_held_experts_roofline` and `qwen3next_held_experts_roofline`
read other families' keys."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_pairs = harness.load_module(
    _here.with_name("moe_held_pairs_expected_per_step.py"))
_shapes = harness.load_module(
    _here.parents[1] / "kernels" / "granite_moe_hybrid.py")


def read(obs):
    ms = _moe.per_program_ms(obs, ("moe_experts", "moe_shared"))
    d = obs.get("counters_delta") or {}
    hf = obs.get("hf") or {}
    pairs = _pairs.expected(obs)
    if ms is None or pairs is None or not obs.get("peaks") \
            or "mamba_n_heads" not in hf:
        return None
    tokens = d["moe_token_expert_pairs"] / d["steps"] / hf["num_experts_per_tok"]
    need = _shapes.held_experts_flops_and_bytes(hf, tokens, pairs)
    layers = _shapes.layer_counts(hf)["routed"]
    by_bytes = 1e3 * layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    by_flops = 1e3 * layers * need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    print(f"[bench] granite4h held experts: "
          f"{'memory' if by_bytes >= by_flops else 'compute'}-bound; "
          f"{tokens:.1f} tokens an iteration in {layers} layers, "
          f"{max(by_bytes, by_flops):.3f} ms needed (bytes {by_bytes:.3f}, "
          f"operations {by_flops:.3f}) vs {ms:.3f} ms taken", flush=True)
    return 100.0 * max(by_bytes, by_flops) / ms
