"""The command's contract and the data-driven rule: BENCHMARK.json is
well-formed, every configuration keeps its own published widths, every
reference is rehearsed, the command refuses to run without a TPU, and a
later PR adds a configuration, a cell and a per-layer metric as files
only. The checks themselves are in helpers.py, each taking a root."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from benchmarks import harness
from benchmarks.tests import helpers

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_json_keeps_the_contract():
    helpers.check_contract(BENCH)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files_and_reports_enough(name):
    helpers.check_cell(ROOT, name)


def test_every_reference_is_rehearsed_through_its_runner():
    helpers.check_references_rehearsed(ROOT)


def _config(**changes):
    """The first configuration with `changes` applied (a key set to
    None is left out), as check_published_widths takes it."""
    cfg = harness.load_json(ROOT / BENCH["configs"][0]["file"])
    cfg.update(changes)
    return {k: v for k, v in cfg.items() if v is not None}


def _published():
    return {k: v for k, v in harness.load_json(
        ROOT / BENCH["paths"][0] / "configs" / "published"
        / f"{_config()['published']}.json").items() if not k.startswith("_")}


def _first(pattern, also=lambda k, v: True):
    return next(k for k, v in _published().items()
                if pattern.search(k) and also(k, v))


def test_a_width_may_not_be_cut_and_a_count_only_as_a_stated_share():
    bench, pub = ROOT / BENCH["paths"][0], _published()
    helpers.check_published_widths(_config(), bench)
    is_int = lambda k, v: isinstance(v, int) and not isinstance(v, bool)  # noqa: E731
    width = _first(helpers.WIDTH_KEY, is_int)
    count = _first(helpers.COUNT_KEY, is_int)
    plain = next(k for k in _config()["reduced"])

    def cut(key):
        here = pub[key] // 2
        return {key: here, "reduced": dict(
            _config()["reduced"], **{key: {"published": pub[key], "here": here}})}

    with pytest.raises(AssertionError):       # a width, even when listed
        helpers.check_published_widths(_config(**cut(width)), bench)
    with pytest.raises(AssertionError, match="share_of"):
        helpers.check_published_widths(_config(**cut(count)), bench)
    helpers.check_published_widths(
        _config(share_of="one of two tensor-parallel chips", **cut(count)), bench)
    with pytest.raises(AssertionError, match="not in `reduced`"):
        helpers.check_published_widths(
            _config(**{plain: pub[plain], "reduced": {}, count: pub[count] // 2}),
            bench)
    with pytest.raises(AssertionError, match="left out"):
        helpers.check_published_widths(_config(**{count: None}), bench)
    with pytest.raises(AssertionError):       # the stated published value is wrong
        helpers.check_published_widths(_config(reduced={
            plain: {"published": pub[plain] + 1, "here": _config()[plain]}}), bench)
    # a width inside a nested group may not change either
    assert helpers.width_changes({"g": {"head_dim": 128, "n": 1}},
                                 {"g": {"head_dim": 64, "n": 2}}) == ["g.head_dim"]


@pytest.mark.parametrize("key,is_width,is_count", [
    ("hidden_size", 1, 0), ("intermediate_size", 1, 0), ("head_dim", 1, 0),
    ("moe_intermediate_size", 1, 0), ("kv_lora_rank", 1, 0),
    ("qk_rope_head_dim", 1, 0), ("num_experts_per_tok", 1, 0),
    ("sliding_window", 1, 0), ("ssm_state_size", 1, 0), ("expand", 1, 0),
    ("num_attention_heads", 0, 1), ("num_key_value_heads", 0, 1),
    ("num_experts", 0, 1), ("num_local_experts", 0, 1),
    ("n_routed_experts", 0, 1), ("vocab_size", 0, 1),
    ("num_hidden_layers", 0, 0), ("max_position_embeddings", 0, 0),
    ("rope_theta", 0, 0)])
def test_which_keys_are_widths_and_which_are_counts(key, is_width, is_count):
    assert bool(helpers.WIDTH_KEY.search(key)) == bool(is_width)
    assert bool(not is_width and helpers.COUNT_KEY.search(key)) == bool(is_count)


@pytest.mark.parametrize("own,ok", [
    ({}, True), ({"rtol": 0.12}, False), ({"rtol_why": "x"}, False),
    ({"rtol": 0.12, "rtol_why": " "}, False),
    ({"rtol": 0.12, "rtol_why": "a routed model can flip a near-tied "
      "expert under bf16"}, True),
    # PR 29: every limit a file states, not `rtol` alone
    ({"typical_rtol": 0.01}, False),
    ({"typical_rtol_why": "the median parts a wrong model from a flip"}, False),
    ({"rtol": 0.05, "rtol_why": "a flip costs a percent",
      "typical_rtol": 0.01}, False),
    ({"rtol": 0.05, "rtol_why": "a flip costs a percent",
      "typical_rtol": 0.01, "typical_rtol_why": "\t"}, False),
    ({"rtol": 0.05, "rtol_why": "a flip costs a percent",
      "typical_rtol": 0.06, "typical_rtol_why": "above the ceiling"}, False),
    ({"rtol": 0.05, "rtol_why": "a flip costs a percent", "typical_rtol": 0.01,
      "typical_rtol_why": "the median parts a wrong model from a flip"}, True),
    ({"typical_rtol": 0.01,
      "typical_rtol_why": "under the runner's own ceiling"}, True)])
def test_a_logits_limit_of_its_own_needs_its_reason(own, ok):
    if ok:
        helpers.check_logits_limit({"logits_check": own})
    else:
        with pytest.raises((AssertionError, KeyError)):
            helpers.check_logits_limit({"logits_check": own})


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / BENCH["command"][1]), "--workload",
         CELLS[0], "--seed", "0", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode not in (0, 2), p.stderr[-2000:]
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_a_configuration_a_cell_and_a_metric_are_added_as_files(tiny_root):
    real_files = {p.relative_to(ROOT / "benchmarks")
                  for p in (ROOT / "benchmarks").rglob("*")
                  if p.is_file() and "tests" not in p.parts
                  and "__pycache__" not in p.parts}
    for rel in real_files:   # nothing that is there was edited
        assert (tiny_root / "benchmarks" / rel).read_bytes() == \
            (ROOT / "benchmarks" / rel).read_bytes()
    # the rehearsal cell that has no real twin: its own end-to-end
    # metric and reader, added as files and entries
    rc = next(rc for rc in helpers.rehearsal_cells() if rc.get("adds"))
    cell = harness.load_cell(rc["name"], tiny_root)
    tiny = harness.load_json(
        helpers.data_dir() / "configs" / f"{rc['config']}.json")
    assert cell.config == tiny and cell.config["hidden_size"] != \
        harness.load_cell(CELLS[0]).config["hidden_size"]
    assert "dummy_answer" in {m["name"] for m in cell.per_layer}
    logs = []
    got = harness.read_per_layer(
        cell, {"counters_delta": {"steps": 4, "batched_tokens": 10},
               "ttft_s": [], "lateness_s": [0.001]}, logs.append)
    assert got["dummy_answer"] == {"value": 42.0, "unit": "rows"}
    assert got["sched_rows_per_step"]["value"] == 2.5
    # readers that find nothing to read are left out, and said so
    assert "mixed_program_ms" not in got
    assert any("mixed_program_ms" in line for line in logs)
    with pytest.raises(KeyError):
        harness.load_cell("no-such-cell", tiny_root)


def test_a_real_cell_that_no_rehearsal_names_is_skipped(tmp_path):
    """A cell added to BENCHMARK.json with no rehearsal cell of its own
    does not stop the tiny root from being built."""
    src = tmp_path / "src"
    helpers.copy_checkout(src)
    bench = json.loads((src / "BENCHMARK.json").read_text())
    w = dict(bench["workloads"][0], name="a-cell-nobody-rehearses",
             traffic=bench["workloads"][-1]["traffic"])
    bench["workloads"].append(w)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if bench["workloads"][0]["name"] in m.get("workloads", ()):
            m["workloads"].append(w["name"])
    (src / "BENCHMARK.json").write_text(json.dumps(bench))
    tiny = helpers.make_tiny_root(tmp_path / "tiny", root=src)
    for rc in helpers.rehearsal_cells(src):
        assert harness.load_cell(rc["name"], tiny).name == rc["name"]
    # a rehearsal that names a cell which is not there says so
    cells = helpers.data_dir(src) / "cells"
    rc = json.loads(next(iter(sorted(cells.glob("*.json")))).read_text())
    rc.update(name="tiny-orphan", reports_as="no-such-cell")
    (cells / "tiny-orphan.json").write_text(json.dumps(rc))
    with pytest.raises(ValueError, match="no-such-cell"):
        helpers.make_tiny_root(tmp_path / "tiny2", root=src)
