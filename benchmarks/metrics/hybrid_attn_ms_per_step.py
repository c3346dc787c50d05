"""Device time on device 0 of the shared-table attention kernel
(`paged_decode_grid`, here the live-block walk over pools packed two
heads of 64 a row) in a model whose attention layers ALONE hold K/V,
all of them, per shared-table program of the traced window. None
unless the configuration names its layers' kinds (`layer_types`): the
other families' reader is `paged_grid_ms_per_step`."""

from benchmarks.trace import reduce as R

KERNEL = "paged_decode_grid"


def kernel_ms(obs):
    td = obs.get("trace")
    if td is None or "layer_types" not in (obs.get("hf") or {}):
        return None
    s = R.kernel_seconds(td, (KERNEL,))
    n = len(R.modules_with(td, KERNEL))
    if s is None or not n:
        return None
    return 1e3 * s / n


def read(obs):
    return kernel_ms(obs)
