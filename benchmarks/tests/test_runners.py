"""Each runner kind end to end at a tiny size on the CPU, kernels in
interpret mode, from files ADDED to a copy of the benchmark: every
rehearsal cell that tests/data/cells/ lists, by its runner kind."""

import json

import jax
import pytest

from benchmarks import harness
from benchmarks.tests import helpers
from deepspeed_tpu.ops.pallas import interpret_kernels

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}
REHEARSALS = helpers.rehearsal_cells()


def of_kind(runner):
    cells = [rc for rc in REHEARSALS if rc["runner"] == runner]
    return pytest.mark.parametrize("rc", cells, ids=[rc["name"] for rc in cells])


def _run(root, rc, trace=False):
    cell = harness.load_cell(rc["name"], root)
    logs = []
    with interpret_kernels():
        line = harness.run_cell(cell, seed=3, seconds=rc["seconds"], trace=trace,
                                devices=jax.devices()[:cell.chips],
                                t_process_start=harness.now(),
                                log=logs.append, out_root=root / "out")
    line = json.loads(line)
    assert set(line) == LINE_KEYS, line
    assert set(line["device"]) == DEVICE_KEYS
    assert line["device"]["platform"] == "cpu"   # never a device number
    assert set(line["metrics"]) == set(rc["expect"]["end_to_end"])
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["correct"], logs
    assert line["attempted"] >= rc["expect"]["min_attempted"], logs
    assert line["failed"] == 0, logs
    return line, logs


@of_kind("train")
def test_train_runner_end_to_end(tiny_root, rc):
    """Every layout the rehearsal cells state (`why` in each file)."""
    _run(tiny_root, rc)


@of_kind("serve")
def test_serve_runner_end_to_end(tiny_root, rc):
    _run(tiny_root, rc)


def test_a_traffic_file_may_state_its_own_logits_limit(tiny_root):
    """`logits_check.rtol` replaces the runner's constant (a limit no
    run can meet makes the check fail; absent, the constant holds)."""
    from benchmarks.runners import serve

    rc = next(rc for rc in REHEARSALS if rc["runner"] == "serve")
    cell = harness.load_cell(rc["name"], tiny_root)
    ctx = harness.RunContext(
        cell=cell, seed=5, seconds=1.0, trace=False, devices=jax.devices()[:1],
        t_process_start=harness.now(), compiles=harness.CompileCounter(),
        out_dir=tiny_root / "out", log=lambda s: None)
    with interpret_kernels():
        eng, mcfg, host, _ = serve.setup(ctx)
        as_is = serve.logits_check(cell, eng, mcfg, host, 5, ctx.log)
        cell.traffic["logits_check"].update(rtol=1e-9, rtol_why="cannot be met")
        strict = serve.logits_check(cell, eng, mcfg, host, 5, ctx.log)
    assert as_is["ok"] and as_is["rtol"] == serve.LOGITS_RTOL
    assert not strict["ok"] and strict["rtol"] == 1e-9
    assert strict["max_abs_err"] > 0


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
