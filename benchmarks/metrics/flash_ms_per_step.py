"""Device time of flash_fwd + flash_bwd_dq + flash_bwd_dkv on device 0
per traced step."""

from benchmarks.trace import reduce as R

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(obs):
    td = obs.get("trace")
    if td is None:
        return None
    s = R.kernel_seconds(td, KERNELS)
    return None if s is None else 1e3 * s / obs["traced_steps"]
