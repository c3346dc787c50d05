"""Each runner kind end to end at a tiny size on the CPU, kernels in
interpret mode, from files ADDED to a copy of the benchmark."""

import json

import jax
import pytest

from benchmarks import harness
from deepspeed_tpu.ops.pallas import interpret_kernels

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(root, name, seconds, trace=False):
    cell = harness.load_cell(name, root)
    logs = []
    with interpret_kernels():
        line = harness.run_cell(cell, seed=3, seconds=seconds, trace=trace,
                                devices=jax.devices()[:cell.chips],
                                t_process_start=harness.now(),
                                log=logs.append, out_root=root / "out")
    return json.loads(line), logs


@pytest.mark.parametrize("name", ["tiny-train", "tiny-train-zero3"])
def test_train_runner_end_to_end(tiny_root, name):
    """One chip under ZeRO-1, and ZeRO-3 over a mesh of four (virtual)
    devices: the layout of the four-chip cell."""
    line, logs = _run(tiny_root, name, seconds=6.0)
    assert set(line) == LINE_KEYS, line
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"   # never a device number
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["correct"], logs
    assert line["attempted"] >= 2 and line["failed"] == 0


@pytest.mark.parametrize("name,e2e", [
    ("tiny-serve", {"ttft_p50_ms", "tpot_p50_ms", "setup_s"}),
    ("tiny-serve-sat", {"serve_tokens_per_s", "tpot_p50_ms", "setup_s"}),
])
def test_serve_runner_end_to_end(tiny_root, name, e2e):
    line, logs = _run(tiny_root, name, seconds=4.0)
    assert set(line) == LINE_KEYS, line
    assert set(line["metrics"]) == e2e
    for m in line["metrics"].values():
        assert m["value"] > 0
    assert line["correct"], logs
    assert line["attempted"] > 0 and line["failed"] == 0, logs


def test_a_request_without_a_first_token_counts_as_the_largest_ttft():
    """Real waits are all kept; each missing request counts as the
    largest of them or as long as it has waited already, whichever is
    longer, even when it was due late in the window."""
    import types

    import numpy as np

    from benchmarks.runners import serve

    def req(rid, first, finish=None, n_out=0):
        return types.SimpleNamespace(rid=rid, first_token_t=first,
                                     finish_t=finish, output=[0] * n_out)

    due = np.array([10.0, 11.0, 58.0, 59.0, 70.0])
    reqs = [req(0, 19.0, 21.0, 5), req(1, 12.0, 12.5, 2),
            req(2, None), req(3, None),
            req(4, 71.0)]                       # due after the window
    got = serve.latency_stats(reqs, due, w0=10.0, w1=60.0, t_stop=65.0)
    assert sorted(got["ttft_s"]) == [1.0, 9.0, 9.0, 9.0]
    assert got["ttft_missing"] == 2
    assert got["tpot_s"] == [pytest.approx(0.5), pytest.approx(0.5)]
    # nothing real is as long as the missing ones have waited
    got = serve.latency_stats(reqs[1:4], due, w0=10.0, w1=60.0, t_stop=65.0)
    assert sorted(got["ttft_s"]) == [1.0, 7.0, 7.0]
