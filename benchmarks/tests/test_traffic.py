"""The one traffic generator: seeded, clipped, and data-driven."""

import json
import pathlib

import numpy as np
import pytest

from benchmarks.traffic import generate

TRAFFIC = pathlib.Path(__file__).resolve().parents[1] / "traffic"
SERVE_MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json")
                     if json.loads(p.read_text())["runner"] == "serve")
TRAIN_MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json")
                     if json.loads(p.read_text())["runner"] == "train")


def _mix(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_serve_schedule_is_a_function_of_the_seed(name):
    mix = _mix(name)
    a = generate.serve_schedule(mix, 7, 30.0, 32000)
    b = generate.serve_schedule(mix, 7, 30.0, 32000)
    c = generate.serve_schedule(mix, 8, 30.0, 32000)   # held-out seed
    assert np.array_equal(a.due_s, b.due_s)
    assert np.array_equal(a.prompt_len, b.prompt_len)
    assert np.array_equal(a.answer_len, b.answer_len)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert not np.array_equal(a.prompts[0], c.prompts[0])
    assert not np.array_equal(a.due_s[-20:], c.due_s[-20:])
    assert not np.array_equal(a.prompt_len[:50], c.prompt_len[:50])


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_serve_lengths_stay_inside_their_clips(name):
    mix = _mix(name)
    s = generate.serve_schedule(mix, 1, 60.0, 32000)
    p, a = mix["prompt_len"], mix["answer_len"]
    assert s.prompt_len.min() >= p["min"] and s.prompt_len.max() <= p["max"]
    assert s.answer_len.min() >= a["min"] and s.answer_len.max() <= a["max"]
    assert all(len(t) == n for t, n in zip(s.prompts, s.prompt_len))
    assert all(0 <= t.min() and t.max() < 32000 for t in s.prompts)
    # prompt + answer never exceeds the serving context of the cells
    assert (s.prompt_len + s.answer_len).max() <= 4096
    # the burst is due at 0, the rest ascending inside the horizon
    burst = int(mix.get("burst_at_start", 0))
    assert np.all(s.due_s[:burst] == 0)
    assert np.all(np.diff(s.due_s[burst:]) >= 0) and s.due_s.max() < 60.0
    # the medians are the mix's, roughly (clipping moves them little)
    assert 0.8 * p["median"] < np.median(s.prompt_len) < 1.25 * p["median"]


@pytest.mark.parametrize("name", SERVE_MIXES)
def test_poisson_rate_is_the_mix_rate(name):
    mix = _mix(name)
    s = generate.serve_schedule(mix, 3, 400.0, 1000, rate_rps=5.0)
    n = len(s.due_s) - int(mix.get("burst_at_start", 0))
    assert abs(n / 400.0 - 5.0) < 0.4


@pytest.mark.parametrize("name", TRAIN_MIXES)
def test_token_batches_are_seeded_zipf(name):
    mix = _mix(name)
    a = generate.token_batches(mix, 5, 32000, 2)
    b = generate.token_batches(mix, 5, 32000, 2)
    c = generate.token_batches(mix, 6, 32000, 2)
    a1, a2, b1, c1 = next(a), next(a), next(b), next(c)
    assert a1["tokens"].shape == (2, mix["seq_len"] + 1)
    assert a1["tokens"].dtype == np.int32
    assert np.array_equal(a1["tokens"], b1["tokens"])
    assert not np.array_equal(a1["tokens"], a2["tokens"])   # new every step
    assert not np.array_equal(a1["tokens"], c1["tokens"])
    assert 0 <= a1["tokens"].min() and a1["tokens"].max() < 32000
    # Zipf: the commonest token is far commoner than uniform's 1/V
    _, counts = np.unique(a1["tokens"], return_counts=True)
    assert counts.max() / a1["tokens"].size > 0.02


def test_every_seed_offers_the_same_load_and_lengths():
    """Stratified draws: the seed changes which request comes when, not
    how many there are or how long."""
    mix = dict(_mix(SERVE_MIXES[0]), burst_at_start=0)
    a = generate.serve_schedule(mix, 1, 400.0, 1000, rate_rps=2.0)
    b = generate.serve_schedule(mix, 2, 400.0, 1000, rate_rps=2.0)
    assert abs(len(a.due_s) - len(b.due_s)) <= 0.02 * len(a.due_s)
    n = min(len(a.due_s), len(b.due_s))
    assert abs(np.sort(a.prompt_len)[:n].sum() - np.sort(b.prompt_len)[:n].sum()) \
        <= 0.02 * a.prompt_len.sum()


def test_unknown_distribution_is_an_error():
    with pytest.raises(ValueError):
        generate.draw_lengths(np.random.default_rng(0), {"dist": "nope"}, 3)


def test_the_measured_chat_schedule_is_pinned():
    """PERF.md's chip runs of serve-chat-saturated drew these requests
    (seed 101, 8 s ramp + 51 s window: 541 of them, as the run's notes
    say). A change to the generator that moves them moves the yardstick."""
    import hashlib

    s = generate.serve_schedule(_mix("chat-saturated"), 101, 59.0, 32000)
    assert len(s.due_s) == 541
    digest = hashlib.sha256(
        s.due_s.tobytes() + s.prompt_len.tobytes() + s.answer_len.tobytes()
        + b"".join(t.tobytes() for t in s.prompts)).hexdigest()
    assert digest.startswith("85da8355b571e6e6")
