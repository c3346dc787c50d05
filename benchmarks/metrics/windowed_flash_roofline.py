"""Share of its roofline the flash attention kernels (forward and
backward together) reach in a stack of WINDOWED and full layers: the
least time the chip could take for the operations and bytes each layer
needs by its own mask (benchmarks/kernels/afmoe.flash_flops_and_bytes:
the band of the window, or the causal triangle; `flash_roofline` counts
the triangle in every layer) over the kernels' device time. Which bound
holds in each kind of layer is printed."""

import pathlib

from benchmarks import harness
from benchmarks.trace import reduce as R

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
_arith = harness.load_module(
    pathlib.Path(__file__).resolve().parents[1] / "kernels" / "afmoe.py")


def read(obs):
    td = obs.get("trace")
    hf = obs.get("hf") or {}
    if td is None or not obs.get("peaks") or "layer_types" not in hf \
            or "sliding_window" not in hf:
        return None
    s = R.kernel_seconds(td, KERNELS)
    if s is None:
        return None
    least, said = 0.0, {}
    for w in _arith.windows(hf):
        need = _arith.flash_flops_and_bytes(
            hf, obs["micro_batch_per_chip"], obs["seq_len"], w)
        t_flops = need["flops"] / obs["peaks"]["bf16_flops_per_s"]
        t_bytes = need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
        least += max(t_flops, t_bytes)
        said[w] = (t_flops, t_bytes)
    for w, (t_flops, t_bytes) in said.items():
        print(f"[bench] flash roofline, a layer of window {w or 'none'}: "
              f"bound by {'compute' if t_flops >= t_bytes else 'memory'} "
              f"({t_flops * 1e3:.3f} ms vs {t_bytes * 1e3:.3f} ms)",
              flush=True)
    print(f"[bench] flash kernels took {s * 1e3 / obs['traced_steps']:.3f} ms "
          f"a step; {least * 1e3:.3f} ms needed", flush=True)
    return 100.0 * least * obs["traced_steps"] / s
