"""Environment / compatibility report.

TPU-native analog of `ds_report` (ref: deepspeed/env_report.py — op
compatibility matrix op_report:30, torch/cuda/nccl version table). The
op table reports the native csrc/ libraries (compiled with the g++ JIT
builder, ops/builder.py) plus the Pallas kernel lanes instead of CUDA
extensions.

Usage: python -m deepspeed_tpu.env_report
"""

import importlib
import shutil
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def _version(mod: str) -> str:
    try:
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "unknown")
    except Exception:
        return "not installed"


def op_report(backend: str = None) -> list:
    """(op name, buildable/compatible, status detail) rows
    (ref: env_report.py op_report:30). `backend` is the platform name
    main() discovered, or None when backend init failed."""
    rows = []
    have_gxx = shutil.which("g++") is not None
    # native aio (csrc/aio)
    try:
        from .ops.aio import AsyncIOHandle

        native = AsyncIOHandle(n_threads=1).native
        rows.append(("async_io (csrc/aio)", native,
                     "g++ JIT build" if native else "fallback python io"))
    except Exception as e:
        rows.append(("async_io (csrc/aio)", False, f"error: {e}"))
    rows.append(("toolchain g++", have_gxx, shutil.which("g++") or "missing"))
    # pallas kernels compile on demand with Mosaic, on a TPU only;
    # anywhere else they run solely under an explicit interpret request
    # (ops/pallas.interpret_kernels), which is a debugging aid
    if backend == "tpu":
        how = "mosaic"
    elif backend:
        how = f"no compiled path on {backend}; interpret on request"
    else:
        how = "backend unavailable"
    rows.append(("pallas flash attention", backend == "tpu", how))
    rows.append(("pallas paged attention", backend == "tpu", how))
    return rows


def main():
    import jax

    print("-" * 64)
    print("DeepSpeed-TPU environment report (ds_report analog)")
    print("-" * 64)
    print("versions:")
    for mod in ("jax", "jaxlib", "flax", "optax", "orbax.checkpoint", "numpy"):
        print(f"  {mod:<18} {_version(mod)}")
    from .version import __version__

    print(f"  {'deepspeed_tpu':<18} {__version__}")
    print(f"  {'python':<18} {sys.version.split()[0]}")
    print("-" * 64)
    print("devices:")
    # a diagnostics tool reports a broken backend instead of dying on
    # it: the op-compatibility section (pure host-side) still prints.
    # ref: ds_report's device block, which has the same job when CUDA
    # is broken.
    backend_snap = None
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"  jax init failed: {type(e).__name__}: {e}")
    else:
        backend_snap = jax.default_backend()
        print(f"  backend            {backend_snap}")
        print(f"  device count       {len(devs)} "
              f"({jax.process_count()} process(es))")
        kinds = sorted({d.device_kind for d in devs})
        print(f"  device kind        {', '.join(kinds)}")
        if backend_snap == "tpu":
            from .platform.accelerator import get_accelerator

            print("  peak bf16 flops    "
                  f"{get_accelerator().peak_flops():.2e}/chip")
    print("-" * 64)
    print("op compatibility:")
    for name, ok, detail in op_report(backend_snap):
        print(f"  {name:<28} {GREEN_OK if ok else RED_NO}  {detail}")
    print("-" * 64)


if __name__ == "__main__":
    main()
