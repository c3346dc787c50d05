"""Share of its (memory-bound) roofline the shared-table walk reaches
in a model of mixed windows: the cached bytes its sequences NEED a step
(the scheduler's `kv_full_tokens` a step once a full layer,
`kv_window_tokens`, each context clipped to the window, once a windowed
layer: `kernels/mellum2.py walk_bytes`, from the configuration file)
over the HBM peak, over `paged_decode_grid`'s device time per
shared-table program, all layers. `paged_decode_grid_roofline` counts
the whole context in every layer and would read high here. None where
the scheduler counts no such tokens (every other model, a parent)."""

import pathlib

from benchmarks import harness
from benchmarks.trace import reduce as R

_shapes = harness.load_module(
    pathlib.Path(__file__).resolve().parents[1] / "kernels" / "mellum2.py")
KERNEL = "paged_decode_grid"


def read(obs):
    td = obs.get("trace")
    d = obs.get("counters_delta") or {}
    hf = obs.get("hf") or {}
    if td is None or not obs.get("peaks") or not d.get("steps") \
            or not d.get("kv_window_tokens") or "mlp_layer_types" not in hf:
        return None
    s = R.kernel_seconds(td, (KERNEL,))
    n = len(R.modules_with(td, KERNEL))
    if s is None or not n:
        return None
    need_s = _shapes.walk_bytes(
        hf, d["kv_full_tokens"] / d["steps"],
        d["kv_window_tokens"] / d["steps"]) / obs["peaks"]["hbm_bytes_per_s"]
    print(f"[bench] {KERNEL} over two kinds of layer: memory-bound; "
          f"{d['kv_full_tokens'] / d['steps']:.0f} tokens a step in a full "
          f"layer, {d['kv_window_tokens'] / d['steps']:.0f} in a windowed "
          f"one, {need_s * 1e3:.3f} ms needed vs {s / n * 1e3:.3f} ms taken",
          flush=True)
    return 100.0 * need_s / (s / n)
