"""Share of its roofline a HELD share of routed experts and the shared
expert reach: the larger of the bytes their weights take to stream once
(plus the tokens in and out) over the HBM peak and the operations the
held pairs and the shared expert's tokens need over the bf16 peak, over
the routed layers, over the device time of the scopes `moe_experts` +
`moe_shared` per shared-table program. Tokens an iteration are the
scheduler's count (`moe_token_expert_pairs`); the held pairs are the
EXPECTATION `moe_held_pairs_expected_per_step` reads, so the operations
term is assumed, not measured (the bytes term, which binds at these
widths, does not depend on it); it prints which bound."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_pairs = harness.load_module(
    _here.with_name("moe_held_pairs_expected_per_step.py"))
_shapes = harness.load_module(_here.parents[1] / "kernels" / "mla.py")


def read(obs):
    ms = _moe.per_program_ms(obs, ("moe_experts", "moe_shared"))
    d = obs.get("counters_delta") or {}
    pairs = _pairs.expected(obs)
    if ms is None or pairs is None or not obs.get("peaks"):
        return None
    hf = obs["hf"]
    tokens = d["moe_token_expert_pairs"] / d["steps"] / hf["num_experts_per_tok"]
    need = _shapes.held_experts_flops_and_bytes(hf, tokens, pairs)
    layers = obs["n_layers"]              # the routed (stacked) layers
    by_bytes = 1e3 * layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    by_flops = 1e3 * layers * need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    print(f"[bench] moe held experts: "
          f"{'memory' if by_bytes >= by_flops else 'compute'}-bound; "
          f"{tokens:.1f} tokens an iteration, {max(by_bytes, by_flops):.3f} ms "
          f"needed (bytes {by_bytes:.3f}, operations {by_flops:.3f}) vs "
          f"{ms:.3f} ms taken", flush=True)
    return 100.0 * max(by_bytes, by_flops) / ms
