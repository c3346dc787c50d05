"""utils/frames.py: host work on ONE chunk of the interpreter's frame
stack. CPython frees a 16 KiB frame chunk the moment the frame at its
base returns, so a call made where a chunk is full maps and unmaps a
chunk EVERY time; `on_one_chunk` calls from a frame large enough to own a
chunk with room for the frames above it (PERF.md section 5, PR 49)."""

import time

import jax
import pytest

from deepspeed_tpu.inference import engine as E
from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.utils.frames import on_one_chunk

DEPTHS, CALLS, REPEATS = 260, 2000, 3


def _leaf(a=0, b=1):
    # more locals than `_ns_a_call` has: the last frame a chunk holds
    # leaves less room than this one needs, so every chunk has its depth
    c = d = e = f = g = h = i = j = k = l = m = n = o = p = q = r = a  # noqa: E741,F841
    return r + b


def _ns_a_call(depth: int) -> float:
    """What a trivial call costs `depth` frames up, best of REPEATS."""
    if depth:
        return _ns_a_call(depth - 1)
    best = float("inf")
    for _ in range(REPEATS):
        t = time.perf_counter_ns()
        for _ in range(CALLS):
            _leaf()
        best = min(best, (time.perf_counter_ns() - t) / CALLS)
    return best


def _costs(run) -> list:
    return [run(_ns_a_call, d) for d in range(DEPTHS)]


def _edges(costs) -> list:
    """Depths at which a call costs over 20 x the median: a chunk's edge
    costs hundreds (two system calls for an addition)."""
    median = sorted(costs)[len(costs) // 2]
    return [d for d, c in enumerate(costs) if c > 20 * median]


def test_a_call_from_some_depth_meets_a_chunks_edge():
    """Why the helper exists, on this interpreter: somewhere in 260
    consecutive depths a trivial call maps and unmaps a chunk a call. An
    interpreter on which this fails no longer needs `on_one_chunk`."""
    assert _edges(_costs(lambda f, d: f(d)))


def test_no_call_inside_the_big_frame_meets_an_edge():
    assert _edges(_costs(lambda f, d: on_one_chunk(f, d))) == []


def test_the_call_goes_through_with_its_arguments_and_its_error():
    assert on_one_chunk(lambda a, b=2, *, c: (a, b, c), 1, c=3) == (1, 2, 3)
    with pytest.raises(ZeroDivisionError):
        on_one_chunk(lambda: 1 // 0)


def test_the_warm_up_runs_every_program_on_one_chunk(monkeypatch):
    cfg = T.TransformerConfig(vocab_size=256, n_layers=2, n_heads=4,
                              d_model=64, max_seq=128, variant="llama",
                              use_flash=False)
    eng = init_inference(T.init(cfg, jax.random.PRNGKey(0)), cfg, {
        "max_batch_size": 8, "num_kv_blocks": 16, "kv_block_size": 8,
        "max_seq_len": 64})
    calls = []

    def counted(call, *args, **kwargs):
        calls.append(call)
        return on_one_chunk(call, *args, **kwargs)

    monkeypatch.setattr(E, "on_one_chunk", counted)
    out = eng.warmup(widths=[8], footprint=False)
    assert len(calls) == out["programs"] == len(out["per_program"]) > 0
