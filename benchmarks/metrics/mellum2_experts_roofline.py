"""Share of its roofline the routed block of a Mellum 2 model reaches:
the larger of the bytes its experts' weights take to stream once a
layer (plus the tokens in and out) over the HBM peak and the operations
the routed (token, expert) pairs need over the bf16 peak, over the
device time of scope `moe_experts` per shared-table program, all routed
layers. Experts are `moe_intermediate_size` wide (`kernels/mellum2.py`;
`moe_experts_roofline` reads OLMoE's `intermediate_size`). Tokens an
iteration are the scheduler's (`moe_token_expert_pairs`)."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_shapes = harness.load_module(_here.parents[1] / "kernels" / "mellum2.py")


def read(obs):
    ms = _moe.per_program_ms(obs, ("moe_experts",))
    d = obs.get("counters_delta") or {}
    hf = obs.get("hf") or {}
    if ms is None or not obs.get("peaks") or not d.get("steps") \
            or not d.get("moe_token_expert_pairs") \
            or "mlp_layer_types" not in hf:
        return None
    tokens = d["moe_token_expert_pairs"] / d["steps"] / hf["num_experts_per_tok"]
    need = _shapes.expert_flops_and_bytes(hf, tokens)
    layers = _shapes.layer_counts(hf)["routed"]
    by_bytes = 1e3 * layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    by_flops = 1e3 * layers * need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    print(f"[bench] mellum2 experts: "
          f"{'memory' if by_bytes >= by_flops else 'compute'}-bound; "
          f"{tokens:.1f} tokens an iteration in {layers} layers, "
          f"{max(by_bytes, by_flops):.3f} ms needed (bytes {by_bytes:.3f}, "
          f"operations {by_flops:.3f}) vs {ms:.3f} ms taken", flush=True)
    return 100.0 * max(by_bytes, by_flops) / ms
