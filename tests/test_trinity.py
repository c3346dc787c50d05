"""Trinity-class (`afmoe`) TRAINED through the system's training path
(PR 55): the training forward against the plain reference
(benchmarks/reference/afmoe.py) at a small size with seeded random
weights, float32 unless said: the loss and EVERY leaf's gradient, each
control of benchmarks/afmoe_audit.py failing the same comparison, the
shares of a routed layer adding up to the uncut layer, the step's own
state (`expert_bias`) outside the optimizer and inside checkpoints, the
import and its refusals, and the family served through the engine's
rings and pages.

The step's state, the import, the refusals and the serving test are
tests/test_trinity_step.py (one file is one worker: two halves).

The file's wall time alone: 158 s on the CPU lane (one process, this
sandbox; compiles of five-layer gradients are most of it).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _trinity import (GRAD_RTOL, LOSS_ATOL, highest, ref,  # noqa: F401
                      seeded, tiny, tiny3, tokens_of)
from benchmarks import afmoe_audit
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf


@pytest.fixture(scope="module")
def small(highest):
    """(hf, config, parameters, tokens, the training forward's loss and
    gradients) at 32 positions: four windows deep."""
    hf = tiny()
    mcfg = config_from_hf(hf, use_flash=False, max_seq=64)
    params, toks = seeded(mcfg), tokens_of(hf, (2, 33))
    return hf, mcfg, params, toks, program_grads(mcfg, params, toks)


@pytest.fixture(scope="module")
def small3(highest):
    """`small` at three layers, for the tests that compile a variant a
    case (the controls, the recomputation modes)."""
    hf = tiny3()
    mcfg = config_from_hf(hf, use_flash=False, max_seq=64)
    params, toks = seeded(mcfg, 6), tokens_of(hf, (2, 33), 6)
    return hf, mcfg, params, toks, program_grads(mcfg, params, toks)


def program_grads(mcfg, params, toks, chunks=1):
    loss = T.make_loss_fn(mcfg, loss_chunks=chunks)
    return jax.jit(jax.value_and_grad(
        lambda p: loss(p, {"tokens": toks}, None)))(params)


def reference_grads(params, toks, hf):
    return jax.jit(lambda p: ref.loss_and_grads(p, toks, hf))(params)


def worst(got, want):
    """The comparison: |loss difference|, and the largest gradient
    difference of any leaf as a share of that leaf's largest reference
    gradient (a leaf whose reference gradient is 0 everywhere, as
    `expert_bias`, must be 0)."""
    (gl, gg), (wl, wg) = got, want
    leaves = []
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(gg)[0],
                            jax.tree.leaves(wg)):
        top = float(jnp.max(jnp.abs(b)))
        d = float(jnp.max(jnp.abs(a - b)))
        leaves.append((d / top if top else d, jax.tree_util.keystr(path)))
    return abs(float(gl) - float(wl)), max(leaves)


def agrees(got, want):
    d_loss, (d_grad, _) = worst(got, want)
    return d_loss <= LOSS_ATOL and d_grad <= GRAD_RTOL


def test_loss_and_every_gradient_match_the_reference(small):
    hf, mcfg, params, toks, got = small
    want = reference_grads(params, toks, hf)
    assert agrees(got, want), worst(got, want)
    assert len(jax.tree.leaves(got[1])) == len(jax.tree.leaves(params)) == 36
    # the bias moves the choice alone: no gradient reaches it
    assert not np.asarray(got[1]["layers"]["expert_bias"]).any()
    # the loss is a fresh model's, and the masks bit: 32 > window 8
    assert abs(float(got[0]) - np.log(128)) < 0.5


def test_flash_forward_and_backward_match_the_reference(highest,
                                                        pallas_interpret):
    """The three flash kernels, windowed and full in one stack (a dense
    windowed layer, a full and a windowed routed one), in interpret
    mode at S = 256 (a window of 128, blocks of 128)."""
    hf = tiny3(head_dim=128, num_attention_heads=2, num_key_value_heads=1,
               hidden_size=64, sliding_window=128)
    mcfg = config_from_hf(hf, use_flash=True, flash_block_q=128,
                          flash_block_k=128, max_seq=256,
                          remat="save_attn_qkv")
    params, toks = seeded(mcfg, 2), tokens_of(hf, (1, 257), 2)
    got = program_grads(mcfg, params, toks, chunks=2)
    want = reference_grads(params, toks, hf)
    d_loss, (d_grad, leaf) = worst(got, want)
    # the kernels' own float32 (exp2 softmax, block sums): 1e-4 of a leaf
    assert d_loss <= LOSS_ATOL and d_grad <= 5e-4, (d_loss, d_grad, leaf)


CPU_CONTROLS = ("window_ignored", "rope_on_the_full_layer", "gate_left_out",
                "whole_vector_qk_norm", "bias_in_the_weights", "scale_1",
                "normalised_over_the_held", "shared_expert_left_out",
                "post_norms_left_out", "embedding_unscaled")


@pytest.mark.parametrize("name", CPU_CONTROLS)
def test_a_wrong_model_fails_the_same_comparison(small3, name):
    hf, mcfg, params, toks, got = small3
    with afmoe_audit.control(ref, name):
        want = reference_grads(params, toks, hf)
    d_loss, (d_grad, leaf) = worst(got, want)
    assert not agrees(got, want), (name, d_loss, d_grad)
    # not by a hair: twenty times the limit on some leaf
    assert d_grad > 20 * GRAD_RTOL, (name, d_grad, leaf)


def test_the_controls_are_the_audits():
    assert set(afmoe_audit.controls(ref)) == set(CPU_CONTROLS) | {"bf16_router"}
    assert set(afmoe_audit.CHIP_CONTROLS) <= set(afmoe_audit.controls(ref))
    before = ref.window_of
    with afmoe_audit.control(ref, "window_ignored"):
        assert ref.window_of is not before
    assert ref.window_of is before


@pytest.mark.parametrize("remat", [m for m in T.REMAT_MODES if m != "none"])
def test_every_recomputation_mode_gives_the_same_gradients(small3, remat):
    hf, mcfg, params, toks, got = small3
    again = program_grads(dataclasses.replace(mcfg, remat=remat), params, toks)
    assert agrees(again, got), (remat, worst(again, got))


def test_the_shares_add_up_to_the_uncut_layer(highest):
    """8 shares of a 16-expert layer, 2 experts each: the routed block's
    outputs of all the shares, the shared expert counted once, are the
    uncut reference's; and the censuses agree."""
    hf = tiny(num_experts=16, reduced={}, experts_held=None)
    whole = config_from_hf(hf, use_flash=False, max_seq=64)
    lp = jax.tree.map(lambda a: a[1], seeded(whole, 3)["layers"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 32))
    shared = ref.shared_expert(h, lp)
    want = shared + ref.routed_experts(h, lp, hf)
    top = float(jnp.max(jnp.abs(want - shared)))  # the routed part's size
    total = jnp.zeros_like(h)
    census = None
    for s in range(8):
        cfg = dataclasses.replace(whole, experts_held=(2 * s, 2))
        cut = dict(lp, **{k: lp[k][2 * s:2 * s + 2]
                          for k in ("w_gate", "w_in", "w_out")})
        out, aux = T._moe_mlp_delta(h, cut, cfg)
        # what only this share adds; its census is the whole router's
        total = total + (out - shared)
        census = aux[2:-2] if census is None else census
        assert np.array_equal(aux[2:-2], census) and aux[-2] == 0
        assert aux[-1] == 1  # a share's one chunk ran
        one = ref.routed_experts(h, cut, dict(
            hf, experts_held={"start": 2 * s}))
        assert jnp.max(jnp.abs(out - shared - one)) < 1e-4 * top
    assert jnp.max(jnp.abs(total + shared - want)) < 1e-4 * top
    assert float(census.sum()) == 2 * 24 * 2  # every token chose two


def test_no_held_pair_is_dropped_at_any_skew(highest, monkeypatch):
    """A router that sends EVERY token to the two held experts: the
    buffer's bound is T x min(k, held) rows, and all of them are live."""
    from deepspeed_tpu.moe import dropless

    hf = tiny(num_experts=2, experts_held={"start": 3, "count": 2, "of": 8},
              reduced={"num_experts": {"published": 8, "here": 2}})
    cfg = config_from_hf(hf, use_flash=False, max_seq=64)
    lp = jax.tree.map(lambda a: a[0], seeded(cfg, 4)["layers"])
    lp["expert_bias"] = jnp.zeros(8).at[3:5].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 40, 32))
    out, aux = T._moe_mlp_delta(h, lp, cfg)
    assert dropless.held_rows_bound(40, 2, 2) == 80
    assert np.array_equal(aux[2:-2], [0, 0, 0, 40, 40, 0, 0, 0])
    assert aux[-2] == 0 and aux[-1] == 1  # none dropped, one chunk ran
    want = ref.shared_expert(h, lp) + ref.routed_experts(h, lp, hf)
    assert jnp.max(jnp.abs(out - want)) < 1e-4 * jnp.max(jnp.abs(want))
    # the count of dropped pairs is of the products that RAN: a chunk
    # skipped wrongly (here: every one) shows as its pairs, not as 0
    with monkeypatch.context() as m:
        m.setattr(jax.lax, "cond", lambda pred, run, skip: skip())
        out, aux = T._moe_mlp_delta(h, lp, cfg)
    assert aux[-2] == 80 and aux[-1] == 0
    assert jnp.max(jnp.abs(out - ref.shared_expert(h, lp))) < 1e-6
    # and one that sends none: the first chunk runs all the same (a
    # step's time is its shape's), over no live row, and adds nothing
    lp["expert_bias"] = jnp.zeros(8).at[3:5].set(-10.0)
    out, aux = T._moe_mlp_delta(h, lp, cfg)
    assert aux[2:-2][3:5].sum() == 0 and aux[-2] == 0 and aux[-1] == 1
    assert jnp.max(jnp.abs(out - ref.shared_expert(h, lp))) < 1e-6


def test_bf16_compute_stays_in_a_band_of_the_float32_loss(small3):
    """bf16 parameters and activations, float32 accumulation: within
    0.02 of the float32 reference on the same bf16 values (the cell's
    REF_LOSS_ATOL), a fresh model's loss being ln 128 = 4.85."""
    hf, mcfg, params, toks, (loss32, _) = small3
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    # (the program's side ONE program: op by op it is ninety small compiles)
    got = jax.jit(T.make_loss_fn(mcfg, loss_chunks=1))(
        p16, {"tokens": toks}, None)
    top = {k: v for k, v in p16.items() if k != "layers"}
    want = ref.loss(top, lambda l: jax.tree.map(lambda a: a[l], p16["layers"]),
                    toks, hf)
    assert abs(float(got) - want) < 0.02
    assert abs(float(got) - float(loss32)) < 0.05


