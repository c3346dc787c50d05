"""Share of its (memory-bound) roofline the selective scan's step
reaches: the bytes it NEEDS (each live sequence's float32 state
[2E, 16] read once and written once a scan layer, plus the rows' dt,
dt x, B, C and output: `kernels/phi4flash.py
scan_step_flops_and_bytes`) over the HBM peak, or its operations over
the bf16 peak where those are more, over the device time of the KERNEL
`sscan_state` per shared-table program, all layers. Sequences an
iteration are the scheduler's (`state_bytes_moved` / steps / what a
slot holds, read and written); rows its `batched_tokens` / steps. None
where no such kernel ran or the configuration is another family's."""

import pathlib

from benchmarks import harness
from benchmarks.trace import reduce as R

_shapes = harness.load_module(
    pathlib.Path(__file__).resolve().parents[1] / "kernels" / "phi4flash.py")
KERNEL = "sscan_state"


def read(obs):
    td = obs.get("trace")
    d = obs.get("counters_delta") or {}
    hf = obs.get("hf") or {}
    if td is None or not obs.get("peaks") or not d.get("steps") \
            or not d.get("state_bytes_moved") \
            or hf.get("model_type") != "phi4flash":
        return None
    s = R.kernel_seconds(td, (KERNEL,))
    n = len(R.modules_with(td, "paged_decode_grid"))
    if s is None or not n:
        return None
    layers = _shapes.layer_counts(hf)["selective_scan"]
    tokens = d["batched_tokens"] / d["steps"]
    slot = layers * _shapes.slot_bytes_per_sequence_per_layer(hf)
    sequences = d["state_bytes_moved"] / d["steps"] / (2 * slot)
    need = _shapes.scan_step_flops_and_bytes(hf, tokens, sequences)
    by_bytes = layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    by_flops = layers * need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    need_s = max(by_bytes, by_flops)
    print(f"[bench] {KERNEL}: "
          f"{'memory' if by_bytes >= by_flops else 'compute'}-bound; "
          f"{tokens:.1f} rows of {sequences:.1f} sequences an iteration in "
          f"{layers} layers, {need_s * 1e3:.3f} ms needed vs "
          f"{s / n * 1e3:.3f} ms taken", flush=True)
    return 100.0 * need_s / (s / n)
