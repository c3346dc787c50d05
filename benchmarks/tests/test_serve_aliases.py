"""A mix whose runner kind is the `serve` runner under another name
(today `serve_longctx`: runners/serve_longctx.py says why it exists) is
held to EVERYTHING the accepted tests hold kind `serve` to:
test_traffic.py's seed, clip, burst and rate tests and test_runners.py's
two end-to-end rehearsals. One line differs: "prompt + answer never
exceeds the serving context of the cells" is read from the cell's own
`serve.engine.max_seq_len`, where test_traffic.py states the older
cells' 4,096 as a constant. When that line reads the cell's limit, the
alias, its mixes' `runner` and this file go."""

import json
import pathlib

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.tests import helpers
from benchmarks.tests import test_runners as TR
from benchmarks.tests import test_traffic as TT
from benchmarks.traffic import generate

BENCH = pathlib.Path(__file__).resolve().parents[1]


def _aliases():
    """Runner kinds whose `run` IS runners/serve.py's."""
    return sorted(
        p.stem for p in (BENCH / "runners").glob("*.py")
        if p.stem != "serve"
        and getattr(harness.load_module(p), "run", None) is not None
        and harness.load_module(p).run.__module__.endswith("serve"))


ALIASES = _aliases()
MIXES = sorted(p.stem for p in TT.TRAFFIC.glob("*.json")
               if json.loads(p.read_text())["runner"] in ALIASES)
REHEARSALS = [rc for rc in TR.REHEARSALS if rc["runner"] in ALIASES]
of_mix = pytest.mark.parametrize("name", MIXES)
of_cell = pytest.mark.parametrize(
    "rc", REHEARSALS, ids=[rc["name"] for rc in REHEARSALS])


def test_an_alias_adds_nothing_of_its_own():
    assert ALIASES == ["serve_longctx"] and MIXES and REHEARSALS
    for kind in ALIASES:
        src = (BENCH / "runners" / f"{kind}.py").read_text()
        code = [ln for ln in src.split('"""')[2].splitlines()
                if ln.strip() and not ln.startswith(("import ", "from "))]
        assert code == ["run = harness.load_module(",
                        '    pathlib.Path(__file__).with_name("serve.py")).run']


@of_mix
def test_serve_schedule_is_a_function_of_the_seed(name):
    TT.test_serve_schedule_is_a_function_of_the_seed(name)


@of_mix
def test_poisson_rate_is_the_mix_rate(name):
    TT.test_poisson_rate_is_the_mix_rate(name)


@of_mix
def test_serve_lengths_stay_inside_their_clips_and_their_cells_context(name):
    """test_traffic.py's test of that name, line for line, with the
    context read from the cells that offer the mix."""
    mix = TT._mix(name)
    cells = [harness.load_cell(w["name"]) for w in
             harness.load_json(BENCH.parent / "BENCHMARK.json")["workloads"]
             if w["traffic"] == name]
    assert cells
    context = min(c.config["serve"]["engine"]["max_seq_len"] for c in cells)
    s = generate.serve_schedule(mix, 1, 60.0, 32000)
    p, a = mix["prompt_len"], mix["answer_len"]
    assert s.prompt_len.min() >= p["min"] and s.prompt_len.max() <= p["max"]
    assert s.answer_len.min() >= a["min"] and s.answer_len.max() <= a["max"]
    assert all(len(t) == n for t, n in zip(s.prompts, s.prompt_len))
    assert all(0 <= t.min() and t.max() < 32000 for t in s.prompts)
    assert (s.prompt_len + s.answer_len).max() <= context
    assert p["max"] + a["max"] <= context      # whatever the seed deals
    burst = int(mix.get("burst_at_start", 0))
    assert np.all(s.due_s[:burst] == 0)
    assert np.all(np.diff(s.due_s[burst:]) >= 0) and s.due_s.max() < 60.0
    assert 0.8 * p["median"] < np.median(s.prompt_len) < 1.25 * p["median"]
    # the alias is for a context the constant refuses, and nothing else
    assert p["max"] + a["max"] > 4096


@of_mix
def test_every_seed_offers_the_same_load_and_lengths(name):
    mix = TT._mix(name)
    a = generate.serve_schedule(mix, 1, 59.0, 1000)
    b = generate.serve_schedule(mix, 2, 59.0, 1000)
    assert len(a.due_s) == len(b.due_s)
    assert np.array_equal(np.sort(a.prompt_len), np.sort(b.prompt_len))
    assert np.array_equal(np.sort(a.answer_len), np.sort(b.answer_len))
    assert not np.array_equal(a.prompt_len, b.prompt_len)


@of_cell
def test_serve_runner_end_to_end(tiny_root, rc):
    TR._run(tiny_root, rc)


@of_cell
def test_a_broken_served_path_comes_out_not_correct(tiny_root, rc, monkeypatch):
    TR.test_a_broken_served_path_comes_out_not_correct(tiny_root, rc, monkeypatch)


def test_the_real_cell_and_its_rehearsal_state_the_same_kind():
    for rc in REHEARSALS:
        real = harness.load_cell(rc["reports_as"])
        assert real.traffic["runner"] == rc["runner"]
        assert (rc["reference"], rc["runner"]) in {
            (r["reference"], r["runner"]) for r in helpers.rehearsal_cells()}
