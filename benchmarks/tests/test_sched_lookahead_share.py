"""The look-ahead share's reader (PR 32): its arithmetic on counter
deltas, and nothing (no raise) on a program that has no such counter,
which is what the parent commit of the PR that added it is."""

import pathlib

import pytest

from benchmarks import harness

BENCH = pathlib.Path(__file__).resolve().parents[1]


def read(obs):
    return harness.load_module(
        BENCH / "metrics" / "sched_lookahead_share.py").read(obs)


@pytest.mark.parametrize("delta, want", [
    ({"steps": 1880, "lookahead_steps": 1880}, 100.0),
    ({"steps": 1880, "lookahead_steps": 1833}, 97.5),
    # every iteration read back first (speculation, a mesh): a real 0
    ({"steps": 400, "lookahead_steps": 0}, 0.0),
])
def test_share_of_steps_launched_ahead(delta, want):
    assert read({"counters_delta": delta}) == pytest.approx(want)


@pytest.mark.parametrize("obs", [
    # the parent: `chained_steps`, no `lookahead_steps`
    {"counters_delta": {"steps": 1700, "chained_steps": 0}},
    {"counters_delta": {"steps": 0, "lookahead_steps": 0}},
    {"counters_delta": None},
    {},
])
def test_nothing_to_read(obs):
    assert read(obs) is None


def test_the_entry_in_benchmark_json():
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    entry = doc["per_layer"][-1]
    assert entry == {
        "name": "sched_lookahead_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tpot_p50_ms",
        "workloads": ["serve-chat-saturated", "serve-olmoe-chat-saturated"]}
