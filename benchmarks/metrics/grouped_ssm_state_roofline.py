"""Share of its roofline the state-space step reaches in a model whose
B and C come in groups: the larger of the bytes it NEEDS (each live
sequence's float32 matrices read once and written once a Mamba-2 layer,
the rows' x, dt and G groups of B and C, and the output) over the HBM
peak and its operations (5 x P x N a row a head) over the bf16 peak,
over the device time of scope `ssm_state` per shared-table program.
Sequences an iteration are the scheduler's (`state_bytes_moved` / steps
/ what a slot holds over the layers THAT HOLD ONE, read and written);
rows its `batched_tokens` / steps. `ssm_state_roofline` reads the keys
of a family with one group. None for a configuration without
`hybrid_override_pattern`, or a program that names no such scope or
counts no such bytes."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_needed_ms = harness.load_module(
    _here.with_name("linear_attn_state_roofline.py"))._needed_ms
_shapes = harness.load_module(_here.parents[1] / "kernels" / "nemotron_h.py")


def read(obs):
    ms = _moe.per_program_ms(obs, ("ssm_state",))
    d = obs.get("counters_delta") or {}
    hf = obs.get("hf") or {}
    if ms is None or not obs.get("peaks") or not d.get("steps") \
            or not d.get("state_bytes_moved") \
            or "hybrid_override_pattern" not in hf:
        return None
    layers = _shapes.layer_counts(hf)["state_space"]
    tokens = d["batched_tokens"] / d["steps"]
    slot = layers * _shapes.slot_bytes_per_sequence_per_layer(hf)
    sequences = d["state_bytes_moved"] / d["steps"] / (2 * slot)
    need, bound = _needed_ms(
        _shapes.grouped_ssm_step_flops_and_bytes(hf, tokens, sequences),
        layers, obs["peaks"])
    print(f"[bench] grouped state-space step: {bound}-bound; {tokens:.1f} "
          f"rows of {sequences:.1f} sequences an iteration in {layers} "
          f"layers of {hf['n_groups']} groups, {need:.3f} ms needed vs "
          f"{ms:.3f} ms taken", flush=True)
    return 100.0 * need / ms
