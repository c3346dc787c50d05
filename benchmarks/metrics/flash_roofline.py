"""Share of its roofline the flash attention kernels (forward and
backward together) reach: the least time the chip could take for the
operations and bytes the algorithm needs
(benchmarks/kernels/shapes.flash_flops_and_bytes, per layer, per step)
over the kernels' device time. Which bound holds is printed on an
earlier line."""

from benchmarks.kernels import shapes
from benchmarks.trace import reduce as R

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(obs):
    td = obs.get("trace")
    if td is None or not obs.get("peaks"):
        return None
    s = R.kernel_seconds(td, KERNELS)
    if s is None:
        return None
    need = shapes.flash_flops_and_bytes(
        obs["hf"], obs["micro_batch_per_chip"], obs["seq_len"])
    t_flops = need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    t_bytes = need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes) * obs["n_layers"] * obs["traced_steps"]
    print(f"[bench] flash roofline: bound by "
          f"{'compute' if t_flops >= t_bytes else 'memory'} "
          f"({t_flops * 1e3:.3f} ms vs {t_bytes * 1e3:.3f} ms per layer), "
          f"kernels took {s * 1e3 / obs['traced_steps']:.3f} ms a step",
          flush=True)
    return 100.0 * least / s
