"""Collective microbenchmark sweep — the `ds_bench` analog.

TPU-native replacement for the reference's comm benchmark CLI
(ref: bin/ds_bench → benchmarks/communication/run_all.py — sweeps
all_reduce/all_gather/all_to_all/broadcast/pt2pt payload sizes over
torch.distributed and prints achieved algbw/busbw). Here each op is a
one-line shard_map over the ambient mesh and XLA emits the collective;
the sweep validates an actual slice's ICI against the effective-bandwidth
constant the 70B scaling projection assumes (scripts/ici_projection.py;
platform/accelerator.LINKS). Not yet run on the four-chip host.

Bus-bandwidth convention (matches the reference's busbw note —
benchmarks/communication/utils.py): for ring algorithms the wire moves
(n-1)/n of the payload per device, and all_reduce moves it twice:

  all_gather / reduce_scatter: busbw = algbw * (n-1)/n
  all_reduce:                  busbw = algbw * 2(n-1)/n
  all_to_all:                  busbw = algbw * (n-1)/n
  ppermute (pt2pt ring):       busbw = algbw

Timing: each trial is one dispatch synchronized through
`utils.sync.host_sync` (the named end-of-run choke point ds-lint R002
allowlists), and the reported time is the MEDIAN over trials. Nothing
is subtracted from a timing. On a pod (multi-controller), run this
module on every host via the pod launcher:

  python -m deepspeed_tpu.launcher.pod --tpu my-slice --zone us-... \
      -- python -m deepspeed_tpu.comm.bench --sizes-mb 1,16,64

Single host / CPU-virtual (CI shape proof):

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python -m deepspeed_tpu.comm.bench --ops all_gather --sizes-mb 1
"""

import argparse
import json
import sys
import time
from functools import partial
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..utils.sync import host_sync

OPS = ("all_gather", "all_reduce", "reduce_scatter", "all_to_all",
       "ppermute")


def _busbw_factor(op: str, n: int) -> float:
    if op == "all_reduce":
        return 2.0 * (n - 1) / n
    if op in ("all_gather", "reduce_scatter", "all_to_all"):
        return (n - 1) / n
    return 1.0  # ppermute: the payload crosses one link once


def _build(op: str, mesh: Mesh, axis: str) -> Callable:
    """jitted fn taking the axis-sharded operand; the collective is the
    whole program (comm.py wrappers are in-jit ops; shard_map binds the
    axis name exactly as the engine's compiled step does)."""
    n = mesh.shape[axis]

    def body(x):
        if op == "all_gather":
            return jax.lax.all_gather(x, axis, tiled=True)
        if op == "all_reduce":
            return jax.lax.psum(x, axis)
        if op == "reduce_scatter":
            return jax.lax.psum_scatter(x, axis, tiled=True)
        if op == "all_to_all":
            return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                      tiled=True)
        if op == "ppermute":
            return jax.lax.ppermute(
                x, axis, [(i, (i + 1) % n) for i in range(n)])
        raise ValueError(op)

    spec = P(axis)
    out_spec = P(None) if op == "all_gather" else spec
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                                 out_specs=out_spec, check_vma=False))


def _payload_shape(op: str, size_bytes: int, n: int, dtype) -> tuple:
    """GLOBAL operand shape for ~size_bytes per-device payload."""
    itemsize = jnp.dtype(dtype).itemsize
    # per-device rows of width 1024 lanes
    width = 1024
    rows = max(1, size_bytes // (itemsize * width))
    return (n * rows, width)


def sweep(
    ops: List[str],
    sizes_bytes: List[int],
    axis: str = "data",
    mesh: Mesh = None,
    trials: int = 10,
    dtype=jnp.bfloat16,
    ici_assumption_gbps: float = 100.0,
) -> List[Dict]:
    """Run the sweep on the ambient devices; returns one record per
    (op, size) with achieved algbw/busbw GB/s and the ratio to the
    assumed effective ICI bandwidth."""
    if mesh is None:
        devs = np.asarray(jax.devices())
        mesh = Mesh(devs, (axis,))
    n = mesh.shape[axis]
    out: List[Dict] = []
    for op in ops:
        fn = _build(op, mesh, axis)
        for size in sizes_bytes:
            shape = _payload_shape(op, size, n, dtype)
            sharding = NamedSharding(mesh, P(axis))
            x = jax.device_put(
                jnp.ones(shape, dtype), sharding)
            host_sync(fn(x))  # compile + warm
            times = []
            for _ in range(trials):
                t0 = time.perf_counter()
                host_sync(fn(x))  # per-trial boundary (the R002 choke point)
                times.append(time.perf_counter() - t0)
            dt = max(float(np.median(times)), 1e-9)
            per_dev_bytes = (np.prod(shape) // n) * jnp.dtype(dtype).itemsize
            algbw = per_dev_bytes / dt / 1e9
            busbw = algbw * _busbw_factor(op, n)
            out.append({
                "op": op, "bytes_per_device": int(per_dev_bytes),
                "time_us": dt * 1e6,
                "algbw_GBps": algbw, "busbw_GBps": busbw,
                "vs_ici_assumption": busbw / ici_assumption_gbps,
                "devices": int(n),
            })
    return out


def print_table(records: List[Dict], ici_assumption_gbps: float) -> None:
    hdr = (f"{'op':<16}{'MB/dev':>9}{'time(us)':>12}{'algbw GB/s':>12}"
           f"{'busbw GB/s':>12}{'vs assumed':>12}")
    print(hdr)
    print("-" * len(hdr))
    for r in records:
        print(f"{r['op']:<16}{r['bytes_per_device']/2**20:>9.2f}"
              f"{r['time_us']:>12.1f}{r['algbw_GBps']:>12.2f}"
              f"{r['busbw_GBps']:>12.2f}{r['vs_ici_assumption']:>12.3f}")
    print(f"(busbw vs the {ici_assumption_gbps:.0f} GB/s effective-ICI "
          "constant the 70B projection assumes)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--ops", default="all_gather,all_reduce,"
                    "reduce_scatter,all_to_all,ppermute",
                    help=f"comma list from {OPS}")
    ap.add_argument("--sizes-mb", default="1,4,16,64",
                    help="per-device payload MB list")
    ap.add_argument("--axis", default="data")
    ap.add_argument("--trials", type=int, default=10)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--ici-gbps", type=float, default=100.0,
                    help="assumed effective ICI GB/s to compare against")
    ap.add_argument("--json", action="store_true",
                    help="emit one JSON line instead of the table")
    args = ap.parse_args(argv)

    from . import init_distributed

    init_distributed()
    ops = [o.strip() for o in args.ops.split(",") if o.strip()]
    for o in ops:
        if o not in OPS:
            ap.error(f"unknown op {o!r} (choose from {OPS})")
    sizes = [int(float(s) * 2**20) for s in args.sizes_mb.split(",")]
    records = sweep(ops, sizes, axis=args.axis, trials=args.trials,
                    dtype=jnp.dtype(args.dtype),
                    ici_assumption_gbps=args.ici_gbps)
    if jax.process_index() == 0:
        if args.json:
            print(json.dumps({"ds_bench": records}))
        else:
            print_table(records, args.ici_gbps)
    return 0


if __name__ == "__main__":
    sys.exit(main())
