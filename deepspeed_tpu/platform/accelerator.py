"""Accelerator abstraction.

TPU-native analog of the reference accelerator layer
(ref: accelerator/abstract_accelerator.py:12-288 and
accelerator/real_accelerator.py:51-121). On TPU there is no need for the
per-vendor zoo; the abstraction exists so host-side code (offload
tiering, tests on the CPU fake mesh, future platforms) never touches
`jax.devices()` directly, and so the `DS_TPU_ACCELERATOR` env var can
force the CPU platform for testing, mirroring `DS_ACCELERATOR` dispatch.
"""

import functools
import os
from typing import List, Optional

import jax
import numpy as np

# --- interconnect link table: THE single authority ---------------------
#
# Effective per-chip bandwidths (bytes/s) for the two interconnect tiers
# a pod topology exposes: ICI within a slice (the v5p-class conservative
# ~100 GB/s effective figure scripts/ici_projection.py models ring
# collectives with) and DCN across slices (50 Gbit/s-class effective per
# chip). Every consumer — analysis/costmodel.py's `ICI_GBPS` re-export,
# analysis/schedule.py's S007-S009 leg costs, scripts/ici_projection.py
# — imports THIS table; a drift test (tests/test_schedule.py) fails if
# any of them re-declares the constant locally.
LINKS = {
    "ici_bytes_per_s": 100e9,
    "dcn_bytes_per_s": 6.25e9,
}

# --- per-chip roofline tables: THE single authority --------------------
#
# Chip-kind substring -> bf16 dense peak FLOP/s, HBM bytes, HBM bytes/s.
# Accelerator.peak_flops / hbm_per_device / hbm_bandwidth match the
# RUNNING device against these — a TPU whose device_kind is not in the
# tables is an ERROR, never a default; chip_roofline(kind) looks a NAMED
# chip up directly — how the CPU-hosted gates (scripts/ds_gate.py budget S006
# verdict on the fused decode program) project a real serving chip's
# balance point instead of the host's degenerate 1:1 profile.
PEAK_FLOPS = {
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
    "v6": 918e12,
}
HBM_PER_DEVICE = {
    "v5 lite": 16 * 10**9,
    "v5litepod": 16 * 10**9,
    "v5e": 16 * 10**9,
    "v5p": 95 * 10**9,
    "v4": 32 * 10**9,
    "v3": 32 * 10**9,
    "v2": 16 * 10**9,
    "v6": 32 * 10**9,
}
HBM_BANDWIDTH = {
    "v5 lite": 819e9,
    "v5litepod": 819e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v4": 1228e9,
    "v3": 900e9,
    "v2": 700e9,
    "v6": 1640e9,
}


# The CPU backend has no roofline; these nominal constants exist ONLY so
# the CPU-hosted analyzers and tests get finite, deterministic ratios.
# They are never a device's numbers and never printed under a device
# metric's name.
CPU_NOMINAL = {
    "peak_flops": 1e11,
    "hbm_per_device": 16 * 2**30,
    "hbm_bandwidth": 100e9,
}


def chip_roofline(kind: str):
    """(peak_flops, hbm_bandwidth) of a NAMED chip kind — the roofline
    constants for projecting a program's balance point onto a target
    chip from any host (raises KeyError on an unknown kind so a typo'd
    gate config fails loudly)."""
    key = kind.lower()
    for k in PEAK_FLOPS:
        if k in key:
            return PEAK_FLOPS[k], HBM_BANDWIDTH[k]
    raise KeyError(f"unknown chip kind {kind!r}; known: {sorted(PEAK_FLOPS)}")


class Accelerator:
    """Device management / memory stats / dtype support for one platform."""

    def __init__(self, platform: Optional[str] = None):
        self._platform = platform  # None = whatever jax picked

    # --- identification -------------------------------------------------
    @property
    def platform(self) -> str:
        return self.devices()[0].platform

    def device_name(self, index: int = 0) -> str:
        d = self.devices()[index]
        return getattr(d, "device_kind", d.platform)

    def is_tpu(self) -> bool:
        return self.platform == "tpu"

    def communication_backend_name(self) -> str:
        """XLA collectives over ICI/DCN (ref contract:
        accelerator/abstract_accelerator.py communication_backend_name)."""
        return "xla"

    # --- devices --------------------------------------------------------
    def devices(self) -> List[jax.Device]:
        if self._platform is not None:
            return jax.devices(self._platform)
        return jax.devices()

    def local_devices(self) -> List[jax.Device]:
        if self._platform is not None:
            return [d for d in jax.local_devices() if d.platform == self._platform]
        return jax.local_devices()

    def device_count(self) -> int:
        return len(self.devices())

    def local_device_count(self) -> int:
        return len(self.local_devices())

    def process_index(self) -> int:
        return jax.process_index()

    def process_count(self) -> int:
        return jax.process_count()

    def synchronize(self, wait_for=None):
        """Fence: blocks on `wait_for` arrays if given (the reliable way to
        wait for pure compute under async dispatch); otherwise drains the
        effects queue only."""
        if wait_for is not None:
            jax.block_until_ready(wait_for)
        else:
            jax.effects_barrier()

    # --- memory ---------------------------------------------------------
    def memory_stats(self, index: int = 0) -> dict:
        # the CPU backend reports None
        return self.local_devices()[index].memory_stats() or {}

    def available_memory(self, index: int = 0) -> int:
        stats = self.memory_stats(index)
        return stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)

    def total_memory(self, index: int = 0) -> int:
        return self.memory_stats(index).get("bytes_limit", 0)

    # --- dtype support --------------------------------------------------
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        # TPUs compute natively in bf16; fp16 is emulated. Supported for
        # numerics-compat but bf16 is the recommended low-precision dtype.
        return True

    def preferred_dtype(self):
        import jax.numpy as jnp

        return jnp.bfloat16

    # --- perf model -----------------------------------------------------
    def _chip_constant(self, table: dict, name: str, index: int):
        """The running device's entry in a per-chip table. CPU gets the
        CPU_NOMINAL stand-in; any other device that is not in the table
        raises — an unknown chip must not borrow another chip's peaks."""
        kind = self.device_name(index).lower()
        for key, val in table.items():
            if key in kind:
                return val
        if self.devices()[index].platform == "cpu":
            return CPU_NOMINAL[name]
        raise KeyError(
            f"no {name} entry for device kind {self.device_name(index)!r}; "
            f"known: {sorted(table)} (platform/accelerator.py)")

    def peak_flops(self, dtype: str = "bfloat16", index: int = 0) -> float:
        """Per-chip peak matmul FLOP/s, used for MFU accounting."""
        return self._chip_constant(PEAK_FLOPS, "peak_flops", index)

    def hbm_per_device(self, index: int = 0) -> int:
        """Per-device HBM capacity in bytes — the budget the static cost
        model (analysis/costmodel.py S004) checks peak program footprint
        against."""
        return self._chip_constant(HBM_PER_DEVICE, "hbm_per_device", index)

    def hbm_bandwidth(self, index: int = 0) -> float:
        """Per-chip HBM bandwidth in bytes/s (roofline memory leg)."""
        return self._chip_constant(HBM_BANDWIDTH, "hbm_bandwidth", index)

    def ici_bandwidth(self, index: int = 0) -> float:
        """Effective per-chip intra-slice (ICI) bandwidth in bytes/s —
        the roofline/schedule comm leg within one slice (LINKS is the
        single authority)."""
        return LINKS["ici_bytes_per_s"]

    def dcn_bandwidth(self, index: int = 0) -> float:
        """Effective per-chip cross-slice (DCN) bandwidth in bytes/s —
        the tier a replica group pays when it straddles slices
        (analysis/schedule.py S008)."""
        return LINKS["dcn_bytes_per_s"]

    def random_seed(self, seed: int):
        return jax.random.PRNGKey(seed)


@functools.lru_cache(maxsize=None)
def get_accelerator() -> Accelerator:
    """Runtime-selected accelerator (ref: accelerator/real_accelerator.py:51
    get_accelerator with DS_ACCELERATOR env dispatch)."""
    forced = os.environ.get("DS_TPU_ACCELERATOR")
    return Accelerator(platform=forced)


def set_accelerator_platform(platform: Optional[str]):
    """Test hook: force a platform then clear the cache."""
    if platform is None:
        os.environ.pop("DS_TPU_ACCELERATOR", None)
    else:
        os.environ["DS_TPU_ACCELERATOR"] = platform
    get_accelerator.cache_clear()


_MP_PROBE_SRC = """
import os, sys
import jax
jax.config.update("jax_platforms", os.environ.get("DS_MP_PROBE_PLATFORM", "cpu"))
jax.distributed.initialize(
    coordinator_address=os.environ["DS_MP_PROBE_ADDR"],
    num_processes=2, process_id=int(sys.argv[1]),
    initialization_timeout=30)
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np
mesh = Mesh(np.array(jax.devices()), ("data",))
x = jax.device_put(jnp.zeros((2,), jnp.float32),
                   NamedSharding(mesh, P("data")))
with mesh:
    y = jax.jit(lambda v: v + 1)(x)  # the multiprocess jit the e2e lane needs
jax.block_until_ready(y)
print("MP-PROBE-OK", flush=True)
"""


def probe_multiprocess_backend(timeout_s: float = 120.0):
    """Can THIS backend run a 2-OS-process sharded jit? -> (ok, reason).

    The elastic-agent e2e lane (tests/test_elastic_agent.py) needs
    real multi-controller worlds. This probe spawns the minimal
    2-process world once and caches the verdict, so where a backend
    cannot serve one the lane reports skipped with the backend's own
    error. Cached per process (the capability cannot change
    mid-run)."""
    return _probe_multiprocess_cached(float(timeout_s))


@functools.lru_cache(maxsize=None)
def _probe_multiprocess_cached(timeout_s: float):
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env = dict(os.environ)
    env["DS_MP_PROBE_ADDR"] = f"127.0.0.1:{port}"
    env.setdefault("DS_MP_PROBE_PLATFORM", "cpu")
    env["XLA_FLAGS"] = ""  # one device per proc; no forced host devices
    procs = [
        subprocess.Popen([sys.executable, "-c", _MP_PROBE_SRC, str(rank)],
                         env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
        for rank in range(2)
    ]
    outs = []
    try:
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout_s)
                outs.append(out or "")
            except subprocess.TimeoutExpired:
                p.kill()
                outs.append("probe timeout")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if all(p.returncode == 0 for p in procs) and all(
            "MP-PROBE-OK" in o for o in outs):
        return True, "multiprocess sharded jit ok"
    # surface the backend's own words (the INVALID_ARGUMENT line when
    # present) so the skip reason names the limit, not a guess
    detail = ""
    for o in outs:
        for line in o.splitlines():
            if "Error" in line or "error" in line or "timeout" in line:
                detail = line.strip()
        if detail:
            break
    return False, (detail or "multiprocess probe failed "
                   f"(rcs {[p.returncode for p in procs]})")
