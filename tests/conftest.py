"""Test harness configuration.

TPU translation of the reference's DistributedTest machinery
(ref: tests/unit/common.py:358 DistributedTest — N OS processes with
torch.multiprocessing + NCCL/gloo rendezvous). JAX collectives are
in-program, so "distributed" tests run single-process over a virtual
8-device CPU mesh (`--xla_force_host_platform_device_count=8`), per
SURVEY §4's TPU translation note.

Two lanes, chosen by the caller, never by what hardware happens to be
there:

- default: the CPU lane. It ASKS for the CPU backend and the virtual
  mesh, and tests that name a Pallas kernel ask for interpret mode with
  the `pallas_interpret` fixture.
- `DS_TPU_TESTS=1`: the hardware kernel lane. Sets neither
  JAX_PLATFORMS nor the host-device flag, and refuses to start unless
  the backend is "tpu"; `pallas_interpret` is then a no-op, so the same
  tests compile their kernels with Mosaic.
"""

import contextlib
import os

TPU_LANE = os.environ.get("DS_TPU_TESTS") == "1"

if not TPU_LANE:
    # Must be set before jax initializes.
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)
if TPU_LANE:
    # kernel-vs-oracle checks hold the tests' float32 tolerances only
    # if the XLA oracle's f32 matmuls run at full precision (the TPU
    # default is a single bf16 pass)
    jax.config.update("jax_default_matmul_precision", "highest")


def pytest_sessionstart(session):
    if TPU_LANE and jax.default_backend() != "tpu":
        pytest.exit(
            f"DS_TPU_TESTS=1 needs a TPU; the backend is "
            f"{jax.default_backend()!r}", returncode=3)


# Tests under benchmarks/tests that pin what only a PR of kind
# `benchmark` may edit (no other PR edits a file the benchmark has, its
# tests among them), and that a later PR's ADDED entries outgrow:
# node id's end -> why it is expected to fail until that PR
_OUTGROWN_BENCHMARK_PINS = {
    "test_mellum2_readers.py::"
    "test_the_cell_reports_what_the_dense_cell_reports_but_its_rooflines":
        "pins len(BENCHMARK.json workloads) == 9 (PR 48); PR 51 added the "
        "tenth cell; a `benchmark` PR relaxes the pin and takes this "
        "entry away",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        for end, why in _OUTGROWN_BENCHMARK_PINS.items():
            if item.nodeid.endswith(end):
                item.add_marker(pytest.mark.xfail(reason=why, strict=False))


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@contextlib.contextmanager
def _kernel_lane():
    """How a test that names a Pallas kernel gets to run it: the CPU
    lane's explicit request for the interpreter
    (ops/pallas.interpret_kernels); on the hardware lane nothing — the
    kernels compile with Mosaic."""
    if TPU_LANE:
        yield
        return
    from deepspeed_tpu.ops.pallas import interpret_kernels

    with interpret_kernels():
        yield


@pytest.fixture
def pallas_interpret():
    with _kernel_lane():
        yield


@pytest.fixture(scope="module")
def pallas_interpret_module():
    """Module-scoped twin, for kernel test files whose module/class
    fixtures trace kernels at setup (`pytestmark = pytest.mark.
    usefixtures("pallas_interpret_module")`)."""
    with _kernel_lane():
        yield


@pytest.fixture(autouse=True)
def _reset_comms_logger():
    from deepspeed_tpu.comm.logger import comms_logger

    comms_logger.reset()
    yield
    comms_logger.reset()
