"""The streamed pass's second entry (ops/pallas/expert_stream.py
expert_grouped_mlp: an expert's weight tile against that expert's OWN
rows) and the one place that chooses it (inference/model.py
expert_path), on the CPU with the kernel in interpret mode; the helpers
are tests/test_expert_stream.py's:

- the pass against the same float64 sum and the all-expert pass, by
  tokens, top-k and weight rule, beside a shared expert, and over group
  shapes that break a sort-and-pad (an expert with no row, every pair on
  one expert, tokens off the 16-row tile, counts that are whole row
  blocks and one over, 32 experts top-4 at F tile 256, 64 top-8 at 512);
- its buffer's static bound under any routing;
- what it cannot take answers as it did before the entry existed;
- a table of expert_path's answers for every (configuration, width) the
  four serving cells build or check with;
- the scheduler's `moe_grouped_steps` counter and the set-up spans' ids;
- the AOT compile for a DESCRIBED v5e at the hybrid cell's 512 rows.
"""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_expert_stream import (  # noqa: F401 - one_chip is a fixture
    ALWAYS,
    BF,
    BLOCK_RTOL,
    E,
    _both,
    _cfg,
    _layer,
    _reference,
    _refusals,
    _served,
    _tokens,
    one_chip,
)

from deepspeed_tpu.inference import model as M
from deepspeed_tpu.ops.pallas import expert_stream as ES
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "benchmarks/configs"


# -- the second entry: an expert's tile against its own rows ------------------

def _grouped(h, lp, cfg, monkeypatch, **knobs):
    """The grouped pass on these inputs, as float64."""
    monkeypatch.setattr(M, "_STREAM_ROWS_PER_EXPERT", ALWAYS)
    monkeypatch.setattr(M, "_STREAM_RIDGE_TOKENS", 0)
    for name, value in knobs.items():
        monkeypatch.setattr(ES, name, value)
    assert M.expert_path(h.shape[0], cfg, lp, True) == "grouped"
    out = M._mlp(h, lp, cfg, None, True, None)
    assert out.dtype == h.dtype and out.shape == h.shape
    return np.asarray(out.astype(jnp.float32), np.float64)


# 13 and 50: off the 16-row sublane tile; top-8 of 8: every expert, every
# token
@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("norm", [False, True], ids=["raw", "renormalised"])
@pytest.mark.parametrize("top_k", [1, 4, 8])
@pytest.mark.parametrize("n_tokens", [13, 50])
def test_the_grouped_pass_matches_a_float32_sum_and_the_all_expert_pass(
        monkeypatch, n_tokens, top_k, norm):
    cfg = _cfg(moe_top_k=top_k, moe_norm_topk_prob=norm)
    lp, h = _layer(cfg), _tokens(n_tokens)
    stream, _ = _both(h, lp, cfg, monkeypatch)
    grouped = _grouped(h, lp, cfg, monkeypatch)
    want = _reference(h, lp, cfg)
    top = np.abs(want).max()
    assert top > 0.3
    assert np.abs(grouped - want).max() < BLOCK_RTOL * top
    assert np.abs(grouped - stream).max() < BLOCK_RTOL * top


def _routed(choices, n_experts, seed=5):
    """Tokens and a router under which token t chooses exactly
    choices[t] (distinct experts, in that order): the router reads the
    first X of the E inputs alone, which hold k, k - 1, ... 1 at the
    chosen experts and 0 elsewhere (exact in bf16); the other inputs
    are noise the experts multiply."""
    choices = np.asarray(choices)
    n, k = choices.shape
    h = np.random.default_rng(seed).normal(size=(n, E))
    h[:, :n_experts] = 0
    np.put_along_axis(h, choices, np.arange(k, 0, -1.0)[None, :], axis=1)
    w_router = np.zeros((E, n_experts), np.float32)
    w_router[:n_experts] = np.eye(n_experts)
    return jnp.asarray(h, BF), jnp.asarray(w_router)


def _draw(n, k, n_experts, seed=0):
    r = np.random.default_rng(seed)
    return np.stack([r.choice(n_experts, k, replace=False) for _ in range(n)])


# (choices [T, k], experts, d_ff, the F tile asked for, the row block)
def _group_shapes():
    one_over = np.repeat(np.arange(8), [16, 17, 32, 33, 0, 1, 15, 48])
    return {
        # expert 3 of 8 holds nothing, and is large
        "an_expert_with_no_row": (
            np.stack([np.delete(np.arange(8), 3)[[t % 7, (t + 1 + t // 7) % 7]]
                      for t in range(40)]), 8, 256, 256, 64),
        "every_pair_on_one_expert": (np.full((40, 1), 5), 8, 256, 256, 64),
        "every_pair_on_one_expert_blocks_of_16": (
            np.full((40, 1), 5), 8, 256, 256, 16),
        "tokens_off_the_16_row_tile": (_draw(13, 3, 8), 8, 256, 256, 64),
        # counts 16, 17, 32, 33, 0, 1, 15, 48 against row blocks of 16
        "whole_row_blocks_and_one_over": (
            one_over[:, None], 8, 256, 256, 16),
        "whole_row_blocks_and_one_over_blocks_of_32": (
            one_over[:, None], 8, 256, 128, 32),
        "x32_top4_f_tile_256": (_draw(50, 4, 32), 32, 512, 256, 64),
        "x64_top8_f_tile_512": (_draw(33, 8, 64), 64, 1024, 512, 64),
    }


@pytest.mark.usefixtures("pallas_interpret")
@pytest.mark.parametrize("shape", sorted(_group_shapes()))
def test_group_shapes_that_break_a_sort_and_pad(monkeypatch, shape):
    choices, X, f, f_tile, block = _group_shapes()[shape]
    k = choices.shape[1]
    cfg = _cfg(n_experts=X, moe_top_k=k, d_ff=f, moe_norm_topk_prob=k > 1)
    lp = _layer(cfg, seed=len(shape))
    h, lp["w_router"] = _routed(choices, X)
    if shape == "an_expert_with_no_row":
        for name in ("w_gate", "w_in", "w_out"):
            lp[name] = lp[name].at[3].multiply(50.0)
    # the kernel's view of the routing is the one asked for
    logits = h.astype(jnp.float32) @ lp["w_router"]
    _, idx = jax.lax.top_k(logits, k)
    np.testing.assert_array_equal(np.asarray(idx), choices)
    counts = np.bincount(choices.ravel(), minlength=X)
    np.testing.assert_array_equal(
        np.asarray(ES.group_rows(idx, X)[3]), counts)
    # weights of this many lanes of F are the double-buffered share
    knobs = dict(_GROUP_ROW_TILE=block,
                 _STREAM_WEIGHT_BYTES=2 * 3 * E * f_tile * 2)
    grouped = _grouped(h, lp, cfg, monkeypatch, **knobs)
    assert ES.grouped_f_tile(len(choices), k, lp["w_gate"], lp["w_in"],
                             lp["w_out"]) == f_tile
    want = _reference(h, lp, cfg)
    top = np.abs(want).max()
    assert 0.1 < top < 100
    assert np.abs(grouped - want).max() < BLOCK_RTOL * top


@pytest.mark.parametrize("routing", ["uniform", "one_expert", "skewed"])
@pytest.mark.parametrize("n_tokens,top_k,X", [(13, 1, 8), (50, 4, 8),
                                              (512, 4, 32), (33, 8, 64)])
def test_any_routing_fits_the_static_buffer(routing, n_tokens, top_k, X):
    """Capacity-free: whatever the routing, every pair has a row of its
    own inside its expert's window, in the order a stable sort by expert
    gives; windows start on the 16-row tile, do not overlap and end a
    row block short of the buffer's end."""
    r = np.random.default_rng(n_tokens)
    if routing == "one_expert":  # the first choice of all: expert X - 1
        idx = np.stack([(X - 1 - j) * np.ones(n_tokens, np.int64)
                        for j in range(top_k)], axis=1)
    else:
        p = np.ones(X) if routing == "uniform" else r.dirichlet(
            np.full(X, 0.1)) + 1e-9
        idx = np.stack([r.choice(X, top_k, replace=False, p=p / p.sum())
                        for _ in range(n_tokens)])
    row_token, pair_row, starts, counts = map(np.asarray, ES.group_rows(
        jnp.asarray(idx, jnp.int32), X))
    R = ES.grouped_rows(n_tokens, top_k, X)
    assert row_token.shape == (R,) and pair_row.shape == idx.shape
    np.testing.assert_array_equal(counts, np.bincount(idx.ravel(),
                                                      minlength=X))
    assert (starts % 16 == 0).all()
    assert (starts[1:] >= starts[:-1] + counts[:-1]).all()
    assert starts[-1] + counts[-1] <= R - ES._GROUP_ROW_TILE
    # a pair's row lies in its expert's window and holds its token
    assert len(set(pair_row.ravel())) == n_tokens * top_k
    assert ((pair_row >= starts[idx])
            & (pair_row < (starts + counts)[idx])).all()
    np.testing.assert_array_equal(
        row_token[pair_row], np.arange(n_tokens)[:, None].repeat(top_k, 1))
    # the stable sort's order: within an expert, by pair number
    order = np.argsort(idx.ravel(), kind="stable")
    np.testing.assert_array_equal(
        np.argsort(pair_row.ravel(), kind="stable"), order)


@pytest.mark.usefixtures("pallas_interpret")
def test_a_shared_expert_runs_beside_the_grouped_pass(monkeypatch):
    cfg = _cfg(n_shared_experts=1)
    lp, h = _layer(cfg), _tokens(24)
    grouped = _grouped(h, lp, cfg, monkeypatch)
    want = _reference(h, lp, cfg)
    assert np.abs(grouped - want).max() < BLOCK_RTOL * np.abs(want).max()


@pytest.mark.parametrize("what", sorted(_refusals()) + ["held_share"])
def test_what_the_grouped_pass_cannot_take_answers_as_before(
        monkeypatch, what):
    """Past the ridge, inputs the pass refuses and a held share answer
    what they answer with the ridge out of reach."""
    if what == "held_share":
        cfg = _cfg(n_experts=16, moe_top_k=4, experts_held=(4, 4))
        lp, use_kernel, mesh = _layer(cfg), True, None
    else:
        cfg, lp, use_kernel, mesh = _refusals()[what]
    if mesh:
        mesh = jax.make_mesh((2,), ("model",))
    # 3 x 300 / 8 = 112 rows an expert: inside both pairs of bounds
    widths = (24, 257, 300)
    got = [M.expert_path(t, cfg, lp, use_kernel, mesh) for t in widths]
    monkeypatch.setattr(M, "_STREAM_RIDGE_TOKENS", float("inf"))
    assert got == [M.expert_path(t, cfg, lp, use_kernel, mesh)
                   for t in widths]
    assert "grouped" not in got
    if what == "two_device_mesh":
        monkeypatch.undo()
        assert M.expert_path(300, cfg, lp, True,
                             jax.make_mesh((1,), ("model",))) == "grouped"


def test_the_grouped_tile_and_rows_come_from_the_shapes():
    sds = lambda *s: jax.ShapeDtypeStruct(s, BF)
    olmoe = (sds(64, 2048, 1024), sds(64, 2048, 1024), sds(64, 1024, 2048))
    lfm2 = (sds(32, 2048, 1792), sds(32, 2048, 1792), sds(32, 1792, 2048))
    # round16(T x k) + 16 X + one row block: 2,560 + 64 at the hybrid
    # cell's shapes
    assert ES.grouped_rows(512, 4, 32) == 2560 + ES._GROUP_ROW_TILE == 2624
    assert ES.grouped_rows(13, 1, 8) == 16 + 128 + ES._GROUP_ROW_TILE
    assert ES.grouped_f_tile(512, 4, *lfm2) == 256
    assert ES.grouped_f_tile(768, 4, *lfm2) == 256
    assert ES.grouped_f_tile(512, 8, *olmoe) == 512
    # pairs whose rows, in and out, outgrow VMEM
    assert ES.grouped_f_tile(1024, 8, *olmoe) is None
    assert ES.grouped_f_tile(2048, 4, *lfm2) is None
    assert ES.grouped_f_tile(512, 4, lfm2[0], lfm2[1], olmoe[2]) is None


# -- the one place that chooses, pinned --------------------------------------

class _Codes:
    """A stack that is no plain array (QuantizedWeight: codes + scales)."""


def _published(name):
    hf = json.loads((CONFIGS / name).read_text())
    cfg = config_from_hf(hf, **hf["serve"]["model_overrides"])
    X, e = cfg.n_experts_held, cfg.d_model
    f = cfg.d_ff
    sds = lambda *s: jax.ShapeDtypeStruct(s, BF)
    return cfg, {"w_gate": sds(X, e, f), "w_in": sds(X, e, f),
                 "w_out": sds(X, f, e)}


# what each serving cell's window runs (128 rows; the hybrid cell 512) and
# what its logits check builds: the whole-prompt prefill of two prompts
# (2 x 512 rows; openPangu's 2 x 4,096), their chunks of 5 (16 rows) and
# single steps (8 rows); and the widths between, so that a bound that
# moves is seen here first
_CELLS = {
    "olmoe-1b-7b-serve-l8.json": {
        8: "ragged", 16: "stream", 128: "stream", 256: "stream",
        257: "grouped", 512: "grouped", 768: "grouped", 1024: "ragged"},
    "lfm2-8b-a1b-serve-l13.json": {
        8: "ragged", 16: "stream", 128: "stream", 256: "stream",
        257: "grouped", 512: "grouped", 768: "grouped", 1024: "ragged"},
    "openpangu-ultra-moe-serve-l5-ep32.json": {
        8: "stream", 16: "stream", 128: "stream", 304: "stream",
        # tokens whose resident buffers outgrow VMEM at E = 7,680
        512: "scan", 8192: "scan"},
}


# as_served: kernels on, one device, plain bf16 stacks, the cells as they
# run; decode_impl "xla", a mesh of two or int8 stacks: the scan between
# ITS bounds of rows an expert, never a kernel
@pytest.mark.parametrize("how", ["as_served", "decode_impl_xla",
                                 "two_device_mesh", "int8_stacks"])
@pytest.mark.parametrize("config,width", [
    (c, w) for c in sorted(_CELLS) for w in _CELLS[c]])
def test_the_path_of_every_program_the_serving_cells_build(config, width,
                                                           how):
    cfg, lp = _published(config)
    want = _CELLS[config][width]
    use_kernel, mesh = True, None
    if how != "as_served":
        rows = width * cfg.moe_top_k / cfg.n_experts
        want = "scan" if cfg.experts_held or 2 < rows < 128 else "ragged"
    if how == "decode_impl_xla":
        use_kernel = False
    elif how == "two_device_mesh":
        mesh = jax.make_mesh((2,), ("model",))
    elif how == "int8_stacks":
        lp = {name: _Codes() for name in lp}
    assert M.expert_path(width, cfg, lp, use_kernel, mesh) == want



# -- the counter and the ids ------------------------------------------------

@pytest.mark.usefixtures("pallas_interpret")
def test_every_step_of_a_grouped_program_says_so(monkeypatch):
    monkeypatch.setattr(M, "_STREAM_RIDGE_TOKENS", 7)
    eng, sched, spans = _served(_cfg(d_ff=128))
    assert eng.resolved_impl == "pallas" and eng.expert_path(8) == "grouped"
    assert sched.counters["moe_grouped_steps"] == sched.counters["steps"] > 0
    assert sched.counters["moe_stream_steps"] == 0
    (init,) = [s for s in spans if s.name == "init.inference"]
    assert init.ids["moe_expert_path"] == "grouped"
    decode = [s.ids for s in spans if s.name == "warmup.program"
              and s.ids["kind"] == "decode"]
    assert decode and all(p["moe_expert_path"] == "grouped" for p in decode)
    # the rows the program multiplies: round16(8 x 3) + 16 x 8 + a block
    assert all(p["moe_grouped_rows"] == ES.grouped_rows(8, 3, 8) == 224
               for p in decode)
    # the same requests through the all-expert pass: the same tokens
    tokens = {rid: list(r.output) for rid, r in sched.finished.items()}
    monkeypatch.setattr(M, "_STREAM_RIDGE_TOKENS", float("inf"))
    eng2, sched2, _ = _served(_cfg(d_ff=128))
    assert eng2.expert_path(8) == "stream"
    assert sched2.counters["moe_grouped_steps"] == 0
    assert {rid: list(r.output)
            for rid, r in sched2.finished.items()} == tokens


# -- AOT for a described v5e ------------------------------------------------

# the 512-row program of serve-lfm2-chat-saturated-r512 (32 experts of
# 2048 x 1792, top-4: 2,624 rows), and OLMoE's widths at the same rows
@pytest.mark.parametrize("rows,X,k,e,f", [
    (512, 32, 4, 2048, 1792), (512, 64, 8, 2048, 1024)],
    ids=["lfm2_512", "olmoe_512"])
def test_the_grouped_pass_compiles_at_the_cells_shapes(one_chip, rows, X, k,
                                                       e, f):
    sds = lambda *s, dt=BF: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    stacks = (sds(X, e, f), sds(X, e, f), sds(X, f, e))
    assert ES.grouped_f_tile(rows, k, *stacks) is not None
    R = ES.grouped_rows(rows, k, X)
    args = (sds(R, e), sds(X, dt=jnp.int32), sds(X, dt=jnp.int32), *stacks)
    text = jax.jit(ES.expert_grouped_mlp).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "expert_stream_grouped" in calls[0]
    assert " while(" not in text
