"""Share of its roofline the HELD experts' grouped products reach in a
train step: the larger of the operations the step's held pairs need (18
E F a pair: three matrices, forward and the two gradients; the pairs
are the traced steps' own count, `moe_pairs_held`) over the bf16 peak
and the bytes of the held experts' weights read three times over the
HBM peak (benchmarks/kernels/afmoe.held_experts_flops_and_bytes), over
the device time of scope `moe_experts` and of the grouped products the
compiler renamed out of it (`ragged-dot-none`), all routed layers. Prints which
bound holds. None without the scope or the count."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_arith = harness.load_module(_here.parents[1] / "kernels" / "afmoe.py")
_moe = harness.load_module(_here.with_name("moe_train_ms_per_step.py"))


def read(obs):
    td, pairs = obs.get("trace"), obs.get("traced_pairs_held")
    if td is None or not pairs or not obs.get("peaks"):
        return None
    # (with the grouped products the compiler renamed: moe_train_ms_per_step)
    s = _moe.seconds(td, ("moe_experts",))
    if s is None:
        return None
    steps = obs["traced_steps"]
    need = _arith.held_experts_flops_and_bytes(obs["hf"], pairs / steps)
    by_flops = need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    by_bytes = need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    print(f"[bench] held experts (train): "
          f"{'compute' if by_flops >= by_bytes else 'memory'}-bound; "
          f"{pairs / steps:.0f} held pairs a step, "
          f"{1e3 * max(by_flops, by_bytes):.3f} ms needed (operations "
          f"{1e3 * by_flops:.3f}, bytes {1e3 * by_bytes:.3f}) vs "
          f"{1e3 * s / steps:.3f} ms taken", flush=True)
    return 100.0 * max(by_flops, by_bytes) * steps / s
