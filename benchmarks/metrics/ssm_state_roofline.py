"""Share of its roofline the state-space step reaches: the larger of
the bytes it NEEDS (each live sequence's float32 matrices read once and
written once a Mamba-2 layer, the rows' x, dt, B, C and output) over
the HBM peak and its operations (5 x P x N a row a head) over the bf16
peak, over the device time of scope `ssm_state` per shared-table
program. Sequences an iteration are the scheduler's
(`state_bytes_moved` / steps / what a slot holds, read and written);
rows its `batched_tokens` / steps. The mixer's weights are read under
`ssm_project` and `ssm_out`, not here: the line it prints also gives
the WHOLE operator's need (state, weights, rows) against scope
`state_space`, and which bound each has."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_needed_ms = harness.load_module(
    _here.with_name("linear_attn_state_roofline.py"))._needed_ms
_shapes = harness.load_module(
    _here.parents[1] / "kernels" / "granite_moe_hybrid.py")


def read(obs):
    ms = _moe.per_program_ms(obs, ("ssm_state",))
    whole = _moe.per_program_ms(obs, ("state_space",))
    d = obs.get("counters_delta") or {}
    hf = obs.get("hf") or {}
    if ms is None or not obs.get("peaks") or not d.get("steps") \
            or not d.get("state_bytes_moved") or "mamba_n_heads" not in hf:
        return None
    layers = _shapes.layer_counts(hf)["state_space"]
    tokens = d["batched_tokens"] / d["steps"]
    slot = layers * _shapes.slot_bytes_per_sequence_per_layer(hf)
    sequences = d["state_bytes_moved"] / d["steps"] / (2 * slot)
    need, bound = _needed_ms(
        _shapes.ssm_step_flops_and_bytes(hf, tokens, sequences), layers,
        obs["peaks"])
    op_need, op_bound = _needed_ms(
        _shapes.mixer_flops_and_bytes(hf, tokens, sequences), layers,
        obs["peaks"])
    print(f"[bench] state-space step: {bound}-bound; {tokens:.1f} rows of "
          f"{sequences:.1f} sequences an iteration in {layers} layers, "
          f"{need:.3f} ms needed vs {ms:.3f} ms taken; the whole mixer "
          f"{op_bound}-bound, {op_need:.3f} ms needed vs {whole:.3f} ms "
          f"taken ({100 * op_need / whole:.1f}%)", flush=True)
    return 100.0 * need / ms
