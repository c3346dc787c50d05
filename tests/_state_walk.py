"""The row patterns and the check that tests/test_qwen3_next.py,
tests/test_granite_moe_hybrid.py, tests/test_nemotron_h.py and
tests/test_olmo_hybrid_delta.py hold the matrix-state step kernels'
shared walk to (ops/pallas/gated_delta.py state_step_call), each with
its own kernel and oracle.

A pool here has 12 slots and the pad rows' 13th, three matrices a slot.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import gated_delta as GD

SLOTS = 12
# name -> (each row's slot, -1 a pad row; each row's position)
PATTERNS = {
    "all_decode_rows": ([4, 0, 7, 2, 9, 5], [3, 11, 1, 8, 2, 6]),
    "a_chunk_first": ([3, 3, 3, 3, 1, 2, 0], [4, 5, 6, 7, 9, 2, 5]),
    "a_chunk_in_the_middle": ([1, 3, 3, 3, 2], [6, 2, 3, 4, 1]),
    "a_chunk_last": ([1, 2, 3, 3, 3, 3], [5, 9, 1, 2, 3, 4]),
    "two_chunks_adjacent": ([3, 3, 3, 5, 5, 5, 5, 1],
                            [7, 8, 9, 0, 1, 2, 3, 4]),
    # slot 4 was another sequence's: its first token must not read it
    "a_first_token_beside_old_state": ([4, 2], [0, 6]),
    "a_pad_row": ([1, -1, 2], [3, 0, 4]),
    "pad_rows_adjacent": ([1, -1, -1, 2], [3, 0, 0, 4]),
    "pad_rows_last": ([1, 2, -1, -1], [3, 4, 0, 0]),
    "one_row": ([3], [5]),
    # more runs than a batch of any walk here, and fewer
    "more_rows_than_a_batch": (list(range(SLOTS)), [2 + i for i in
                                                    range(SLOTS)]),
    "fewer_rows_than_a_batch": ([6, 1], [4, 2]),
    # a chunk longer than a batch: its copies change hands inside it
    "a_chunk_longer_than_a_batch": ([2, 6, 6, 6, 6, 6, 6, 6, 6, 6, 6, 4],
                                    [5, 0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 3]),
    # the one thing no served step holds: two runs of ONE slot. The
    # second reads what the first left (its fetch must not pass that
    # write-back), or starts from zero and writes after it
    "a_slot_read_after_its_write": ([2, 5, 2, 7], [7, 3, 8, 1]),
    "a_slot_written_twice": ([2, 5, 2], [7, 3, 0]),
}
# name -> the walk's constants (gated_delta.walk_shape) and what they
# give: as they are (slots this small: batches of eight runs, four runs
# of several rows held aside), a run a batch, batches of three and one
# run aside (two chunks are then batched like the rest), none aside
WALKS = {
    "batches_of_eight": ({}, (8, 4)),
    "a_run_a_batch": ({"_MAX_BATCH": 1}, (1, 4)),
    "batches_of_three_one_aside": ({"_MAX_BATCH": 3, "_MAX_ASIDE": 1}, (3, 1)),
    "none_aside": ({"_MAX_BATCH": 2, "_MAX_ASIDE": 0}, (2, 0)),
}
# every pattern in batches of three with one run aside; the other walks
# where an edge falls differently (the interpreter takes seconds a case)
CASES = [pytest.param(p, "batches_of_three_one_aside",
                      id=f"{p}-batches_of_three_one_aside") for p in PATTERNS]
CASES += [pytest.param(p, w, id=f"{p}-{w}") for p, w in (
    ("all_decode_rows", "batches_of_eight"),
    ("two_chunks_adjacent", "batches_of_eight"),
    ("a_slot_read_after_its_write", "batches_of_eight"),
    ("pad_rows_adjacent", "a_run_a_batch"),
    ("a_chunk_longer_than_a_batch", "none_aside"))]


def ssm_inputs(rng, *lead, G=1, H=8, P=16, N=32):
    """x, dt, A, B, C of a Mamba-2 step over `lead` rows: H heads of P
    with a state of N, B and C in G groups; decays exp(dt A) from ~0.2
    to ~0.99 a token."""
    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return (normal(*lead, H, P), jax.nn.softplus(normal(*lead, H) - 1.0),
            -jnp.exp(normal(H) * 0.5), normal(*lead, G * N),
            normal(*lead, G * N))


def ragged(rng, pool_shape):
    """A step's rows over a seeded pool of six slots: a run of five from
    a slot's state (positions 5..9), a decode row, a pad row, a run of
    three from position 0 (the slot's NaN must not be read), another pad
    row: (pool, each row's slot, each row's position, the runs as (rows,
    slot, the state the run starts from or None)). Slots 2 and 4 are in
    no row: a step leaves them as they were."""
    slots = jnp.asarray([3, 3, 3, 3, 3, 1, -1, 0, 0, 0, -1], jnp.int32)
    pos = jnp.asarray([5, 6, 7, 8, 9, 12, 0, 0, 1, 2, 0], jnp.int32)
    pool = jnp.asarray(rng.normal(size=pool_shape), jnp.float32)
    pool = pool.at[0].set(jnp.nan)
    return pool, slots, pos, ((slice(0, 5), 3, pool[3]),
                              (slice(5, 6), 1, pool[1]),
                              (slice(7, 10), 0, None))


def set_walk(monkeypatch, walk: str, pool_shape):
    patches, shape = WALKS[walk]
    for name, value in patches.items():
        monkeypatch.setattr(GD, name, value)
    assert GD.walk_shape(pool_shape) == shape


def check_walk(step, xla, args_of, pool_shape, pattern: str, rng):
    """`step` against `xla`, both (*args_of(rng, rows), pool, slots,
    positions) -> (out, pool): the outputs, every slot a row of the
    step lives in, and bit for bit every slot that none does, the pad
    rows' among them (a pad row writes nothing)."""
    slots, pos = (jnp.asarray(a, jnp.int32) for a in PATTERNS[pattern])
    pool = jnp.asarray(rng.normal(size=pool_shape), jnp.float32)
    rows, at = PATTERNS[pattern]
    # a slot whose sequence starts in this step held another's state
    for s in set(rows) - {-1}:
        if at[rows.index(s)] == 0:
            pool = pool.at[s].set(jnp.nan)
    args = args_of(rng, slots.shape[0])
    # each side ONE program, as a step calls it (op by op, the oracle's
    # loop over rows is a dozen small compiles a case)
    out, new = jax.jit(step)(*args, pool, slots, pos)
    want_out, want = jax.jit(xla)(*args, pool, slots, pos)
    np.testing.assert_allclose(out, want_out, atol=2e-5)
    for s in range(SLOTS + 1):
        if s in rows:
            np.testing.assert_allclose(new[s], want[s], atol=2e-5)
        else:
            np.testing.assert_array_equal(new[s], pool[s])
