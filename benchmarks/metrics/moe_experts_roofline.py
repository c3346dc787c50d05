"""Share of its roofline the expert block reaches: the larger of the
bytes its experts' weights take to stream once (plus the tokens in and
out) over the HBM peak and the operations the routed (token, expert)
pairs need over the bf16 peak, over the device time of scope
`moe_experts`, per shared-table program and over all layers. The
tokens an iteration holds are the scheduler's own count
(`moe_token_expert_pairs` / steps / top-k); it prints which bound."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_shapes = harness.load_module(_here.parents[1] / "kernels" / "moe.py")


def read(obs):
    ms = _moe.per_program_ms(obs, ("moe_experts",))
    d = obs.get("counters_delta") or {}
    if ms is None or not obs.get("peaks") or not d.get("steps") \
            or not d.get("moe_token_expert_pairs"):
        return None
    hf = obs["hf"]
    tokens = d["moe_token_expert_pairs"] / d["steps"] / hf["num_experts_per_tok"]
    need = _shapes.expert_flops_and_bytes(hf, tokens)
    by_bytes = need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    by_flops = need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    need_ms = 1e3 * obs["n_layers"] * max(by_bytes, by_flops)
    print(f"[bench] moe_experts: {'memory' if by_bytes >= by_flops else 'compute'}"
          f"-bound; {tokens:.1f} tokens an iteration, {need_ms:.3f} ms needed "
          f"(bytes {1e3 * obs['n_layers'] * by_bytes:.3f}, operations "
          f"{1e3 * obs['n_layers'] * by_flops:.3f}) vs {ms:.3f} ms taken",
          flush=True)
    return 100.0 * need_ms / ms
