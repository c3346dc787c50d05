"""CPU rehearsals of the benchmark harness (never a device number).

Run with `python -m pytest benchmarks/tests -q`: they guard the
yardstick itself. The driver's tier-1 command names `tests/` and
collects them through the symlink `tests/benchmark_suite ->
../benchmarks/tests` (PR 27), under tests/conftest.py's eight CPU
devices beside this file's four.
"""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import pytest  # noqa: E402



from benchmarks.tests.helpers import make_tiny_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)
