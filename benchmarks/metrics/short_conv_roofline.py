"""Share of its roofline the gated short convolution reaches: the
larger of the bytes its conv layers NEED (W_in, taps and W_out once a
layer, the rows in and out, each live slot's carried inputs read and
written) over the HBM peak and the rows' projection operations
(2 x 4 x E^2 a row) over the bf16 peak, over the device time of scope
`short_conv` per shared-table program. Rows an iteration are the
scheduler's (`batched_tokens` / steps), sequences its
`state_slots_live` / steps; it prints which bound."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_shapes = harness.load_module(_here.parents[1] / "kernels" / "lfm2.py")


def read(obs):
    ms = _moe.per_program_ms(obs, ("short_conv",))
    d = obs.get("counters_delta") or {}
    if ms is None or not obs.get("peaks") or not d.get("steps") \
            or not d.get("state_slots_live"):
        return None
    hf = obs["hf"]
    tokens = d["batched_tokens"] / d["steps"]
    sequences = d["state_slots_live"] / d["steps"]
    need = _shapes.short_conv_flops_and_bytes(hf, tokens, sequences)
    layers = _shapes.layer_counts(hf)["conv"]
    by_bytes = 1e3 * layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    by_flops = 1e3 * layers * need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    print(f"[bench] short_conv: "
          f"{'memory' if by_bytes >= by_flops else 'compute'}-bound; "
          f"{tokens:.1f} rows of {sequences:.1f} sequences an iteration in "
          f"{layers} layers, {max(by_bytes, by_flops):.3f} ms needed (bytes "
          f"{by_bytes:.3f}, operations {by_flops:.3f}) vs {ms:.3f} ms taken",
          flush=True)
    return 100.0 * max(by_bytes, by_flops) / ms
