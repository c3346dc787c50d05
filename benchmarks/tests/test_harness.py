"""The command's contract and the data-driven rule: BENCHMARK.json is
well-formed, the command refuses to run without a TPU, and a later PR
adds a configuration, a cell and a per-layer metric as files only."""

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from benchmarks import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["source"] in SOURCES
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
        assert c["file"].startswith(BENCH["paths"][0] + "/")


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files_and_reports_enough(name):
    cell = harness.load_cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert len(cell.per_layer) >= 1
    assert all(m["moves"] in e2e for m in cell.per_layer)
    assert (cell.bench_dir / "runners" / f"{cell.traffic['runner']}.py").is_file()
    assert (cell.bench_dir / "reference" / f"{cell.config['reference']}.py").is_file()
    for m in cell.per_layer:
        assert (cell.bench_dir / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    # every key the cut changed from the source is listed
    entry = {c["name"]: c for c in BENCH["configs"]}[cell.config_name]
    assert sorted(cell.config["reduced"]) == sorted(entry["reduced"])
    for key in ("source", "assumed", "stands_for"):
        assert cell.config[key]
    # no width is cut
    for key, want in {"hidden_size": 4096, "intermediate_size": 14336,
                      "num_attention_heads": 32, "num_key_value_heads": 8,
                      "vocab_size": 32000, "sliding_window": 4096}.items():
        assert cell.config[key] == want


def test_the_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload",
         "train-seq4k", "--seed", "0", "--seconds", "1", "--trace", "0"],
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
    cell = harness.load_cell("tiny-serve", tiny_root)
    assert cell.config["hidden_size"] == 256
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
