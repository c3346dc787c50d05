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


def _olmoe_rule():
    return harness.load_json(
        helpers.ROOT / "benchmarks" / "traffic" / "chat-saturated-olmoe.json"
    )["logits_check"]


def _errors(typical, **at):
    """Made-up per-position errors [2 prompts, steps] of a model whose
    largest |reference logit| is 1: `typical` everywhere (a little
    uneven, as rounding noise is), and `at` = {"p,s": value} elsewhere."""
    import numpy as np

    steps = 2 + _olmoe_rule()["decode_steps"]
    err = typical * (1 + 0.1 * np.sin(np.arange(2 * steps))).reshape(2, steps)
    for where, value in at.items():
        err[tuple(int(i) for i in where.split(","))] = value
    return err


# what the chip read of the routed cell (PERF.md §6, PR 29), as shares of
# the largest reference logit: the median position of a sound seed, the
# largest position of any of 48 (a flipped expert) and a little more,
# the SMALLEST median of the reference with one expert fewer, a wrong block
NOISE, FLIP, K_MINUS_1, WRONG_BLOCK = 0.0063, 0.02, 0.0153, 0.25


@pytest.mark.parametrize("case,err,ok", [
    ("sound", _errors(NOISE), True),
    ("one flipped expert", _errors(NOISE, **{"1,4": FLIP}), True),
    ("two flipped experts", _errors(NOISE, **{"0,0": FLIP, "1,5": FLIP}), True),
    ("one expert fewer everywhere", _errors(K_MINUS_1), False),
    ("one wrong block", _errors(NOISE, **{"0,1": WRONG_BLOCK}), False),
    ("float8 weights", _errors(0.2), False),
    ("not a number", _errors(NOISE, **{"0,2": float("nan")}), False)])
def test_the_routed_cells_rule_on_made_up_errors(case, err, ok):
    """The two limits of chat-saturated-olmoe.json: a ceiling on the
    largest position and a limit on the median over the positions. One
    outlying position passes, every position shifted by what a missing
    expert costs fails, one gross position fails."""
    from benchmarks.runners import serve

    rule = _olmoe_rule()
    assert {"rtol", "typical_rtol"} <= set(rule)
    v = serve.logits_verdict(rule, err, 1.0)
    assert v["ok"] == ok, (case, v)
    assert bool(v["broken"]) != ok
    logs = []
    serve.report_false_checks(
        {"pallas": True, "matches_reference": v["ok"]},
        {"pallas": "decode_impl resolved 'pallas'"}, v, logs.append)
    assert len(logs) == (0 if ok else 1)
    if not ok:    # the line names the check, and what broke which limit
        assert logs[0].startswith("[bench] FALSE: matches_reference: ")
        assert "rtol" in logs[0] and "pallas" not in logs[0]


@pytest.mark.parametrize("err,ok", [
    (_errors(0.05), True), (_errors(NOISE, **{"0,3": 0.0801}), False),
    (_errors(0.07), True), (_errors(0.07, **{"1,1": 0.0799}), True)])
def test_a_file_without_limits_of_its_own_gets_the_old_rule(err, ok):
    """No `rtol`, no `typical_rtol`: the largest position against the
    runner's constant and nothing else, as before PR 29."""
    from benchmarks.runners import serve

    v = serve.logits_verdict({"decode_steps": 4}, err, 1.0)
    assert v["ok"] == ok == bool(err.max() <= serve.LOGITS_RTOL)
    assert v["rtol"] == serve.LOGITS_RTOL and "typical_rtol" not in v


@of_kind("serve")
def test_a_broken_served_path_comes_out_not_correct(tiny_root, rc, monkeypatch):
    """The rest of a run with the logits altered where the engine
    produces them (every vocabulary entry moved by one): `correct` is
    false, and the log says which check and which limit."""
    from deepspeed_tpu.inference.engine import InferenceEngine

    put = InferenceEngine.put
    monkeypatch.setattr(
        InferenceEngine, "put",
        lambda self, *a, **k: jax.numpy.roll(put(self, *a, **k), 1, axis=-1))
    cell = harness.load_cell(rc["name"], tiny_root)
    logs = []
    with interpret_kernels():
        line = json.loads(harness.run_cell(
            cell, seed=4, seconds=rc["seconds"], trace=False,
            devices=jax.devices()[:1], t_process_start=harness.now(),
            log=logs.append, out_root=tiny_root / "out"))
    assert line["correct"] is False and line["failed"] == 0
    false = [m for m in logs if m.startswith("[bench] FALSE: ")]
    assert len(false) == 1 and "matches_reference" in false[0], logs[-4:]


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
