"""Share of their (memory-bound) roofline the walks reach in a model
whose later layers read an earlier layer's pages: the cached bytes its
sequences NEED a step (the scheduler's `kv_full_tokens` a step once for
the layer that owns the pages, `kv_shared_tokens`, the same contexts
once a layer that reads them, `kv_window_tokens`, each context clipped
to the window, once a windowed layer: `kernels/phi4flash.py
walk_bytes`) over the HBM peak, over the device time of EVERY kernel
that walks them, per step program, all layers: `paged_decode_grid` (a
reader's walk in both step programs; an owner's and a ring's in the
shared-table program) and `paged_decode_fused` (an owner's and a ring's
walk with its row write, in the fused-write program: the bytes of those
walks are in the numerator, so their time is in the denominator). A
step program is one that ran a reader's walk. `windowed_walk_roofline` counts a walk
once a layer that OWNS a pool and would read low here. None where the
scheduler counts no shared tokens (every other model, a parent)."""

import pathlib

from benchmarks import harness
from benchmarks.trace import reduce as R

_shapes = harness.load_module(
    pathlib.Path(__file__).resolve().parents[1] / "kernels" / "phi4flash.py")
KERNEL = "paged_decode_grid"
KERNELS = (KERNEL, "paged_decode_fused")


def read(obs):
    td = obs.get("trace")
    d = obs.get("counters_delta") or {}
    hf = obs.get("hf") or {}
    if td is None or not obs.get("peaks") or not d.get("steps") \
            or not d.get("kv_shared_tokens") \
            or hf.get("model_type") != "phi4flash":
        return None
    s = R.kernel_seconds(td, KERNELS)
    n = len(R.modules_with(td, KERNEL))
    if s is None or not n:
        return None
    a_step = lambda k: d.get(k, 0) / d["steps"]
    need_s = _shapes.walk_bytes(
        hf, a_step("kv_full_tokens"), a_step("kv_shared_tokens"),
        a_step("kv_window_tokens")) / obs["peaks"]["hbm_bytes_per_s"]
    print(f"[bench] {' + '.join(KERNELS)} over an owner, its readers and "
          f"the rings: memory-bound; "
          f"{a_step('kv_full_tokens'):.0f} tokens a step in "
          f"the full layer, {a_step('kv_shared_tokens'):.0f} over its "
          f"readers, {a_step('kv_window_tokens'):.0f} in a windowed one, "
          f"{need_s * 1e3:.3f} ms needed vs {s / n * 1e3:.3f} ms taken",
          flush=True)
    return 100.0 * need_s / (s / n)
