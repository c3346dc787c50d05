"""Share of its roofline the delta rule reaches at the shapes of an
Olmo-Hybrid model (heads of 96 x 192, as many key heads as value
heads): the larger of the bytes it NEEDS (each live sequence's float32
matrices read once and written once a DeltaNet layer, 192 values a head
and never a padded 256, plus the rows' q, k, v and output) over the HBM
peak and its operations (7 x Dk x Dv a row a head) over the bf16 peak,
over the device time of scope `gdn_state` per shared-table program.
Sequences an iteration are the scheduler's (`state_bytes_moved` / steps
/ what a slot NEEDS, read and written: the pool pads the carried
inputs' 90 lane rows to 96, so the count reads 0.2% high); rows its
`batched_tokens` / steps. None unless the configuration names each
layer's kind beside the DeltaNet's widths (`layer_types`,
`linear_num_value_heads`: `linear_attn_state_roofline` reads the family
that counts by `full_attention_interval`). The line it prints also
gives the WHOLE operator's need (state, weights, rows) against scope
`linear_attention`, and which bound each has."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_shapes = harness.load_module(_here.parents[1] / "kernels" / "olmo_hybrid.py")


def of_family(hf) -> bool:
    return "layer_types" in hf and "linear_num_value_heads" in hf \
        and "full_attention_interval" not in hf


def _needed_ms(need, layers, peaks):
    by_bytes = 1e3 * layers * need["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = 1e3 * layers * need["flops"] / peaks["bf16_flops_per_s"]
    return max(by_bytes, by_flops), \
        "memory" if by_bytes >= by_flops else "compute"


def read(obs):
    ms = _moe.per_program_ms(obs, ("gdn_state",))
    whole = _moe.per_program_ms(obs, ("linear_attention",))
    d = obs.get("counters_delta") or {}
    hf = obs.get("hf") or {}
    if ms is None or not obs.get("peaks") or not d.get("steps") \
            or not d.get("state_bytes_moved") or not of_family(hf):
        return None
    layers = _shapes.layer_counts(hf)["linear_attention"]
    tokens = d["batched_tokens"] / d["steps"]
    slot = layers * _shapes.state_bytes_per_sequence_per_layer(hf)
    sequences = d["state_bytes_moved"] / d["steps"] / (2 * slot)
    need, bound = _needed_ms(
        _shapes.delta_rule_flops_and_bytes(hf, tokens, sequences), layers,
        obs["peaks"])
    op_need, op_bound = _needed_ms(
        _shapes.delta_net_flops_and_bytes(hf, tokens, sequences), layers,
        obs["peaks"])
    print(f"[bench] delta rule 96 x 192: {bound}-bound; {tokens:.1f} rows of "
          f"{sequences:.1f} sequences an iteration in {layers} layers, "
          f"{need:.3f} ms needed vs {ms:.3f} ms taken; the whole operator "
          f"{op_bound}-bound, {op_need:.3f} ms needed vs {whole:.3f} ms "
          f"taken ({100 * op_need / whole:.1f}%)", flush=True)
    return 100.0 * need / ms
