"""Trinity-class (`afmoe`), the rest of tests/test_trinity.py (one file a
worker: two halves): the step's own state (`expert_bias`) outside the
optimizer and inside checkpoints under ZeRO-1, what training still
refuses by name, the configuration and the import with its refusals,
and the family served through the engine's rings and pages.

The file's wall time alone: 73 s on the CPU lane (one process).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from benchmarks import afmoe_audit, harness
from deepspeed_tpu.inference.engine import init_inference
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.platform.mesh import build_mesh
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf
from _trinity import (BENCH, CUT, LOSS_ATOL, PERIOD, PUBLISHED,  # noqa: F401
                      arith, highest, ref, seeded, tiny, tiny3, tokens_of)


# -- the step's own state ----------------------------------------------------

def _engine(mcfg, devices, stage=1, dtype="bf16", lr=1e-2):
    conf = {"train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "adamw",
                          "params": {"lr": lr, "weight_decay": 0.1}},
            "zero_optimization": {"stage": stage},
            "gradient_clipping": 1.0, "steps_per_print": 10 ** 9}
    if dtype == "bf16":
        conf["bf16"] = {"enabled": True}
    return ds.initialize(
        conf, loss_fn=T.make_loss_fn(mcfg, loss_chunks=1, has_aux=True),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
        mesh=build_mesh({"data": len(devices)}, devices=list(devices)),
        init_rng=jax.random.PRNGKey(0), has_aux=True,
        state_rule=T.step_state_rule(mcfg))


def test_the_bias_is_the_steps_state_and_not_the_optimizers(tmp_path):
    """Three steps through ds.initialize under ZeRO-1 on two devices,
    bf16 compute, fp32 master: the loss falls; `expert_bias` follows
    the hand-computed rule from each step's census and nothing else (no
    moment, no decay: lr x wd x b would show at 1e-6 by the third
    step); checkpoints
    carry it."""
    hf = tiny3()
    mcfg = config_from_hf(hf, use_flash=False, max_seq=64)
    eng = _engine(mcfg, jax.devices()[:2])
    assert eng.state.opt["mu"]["layers"]["expert_bias"] is None
    assert eng.state.opt["nu"]["layers"]["expert_bias"] is None
    assert eng.state.opt["mu"]["layers"]["w_router"] is not None
    assert eng.state.master["layers"]["expert_bias"].dtype == jnp.float32
    n_opt = len(jax.tree.leaves(eng.state.opt["mu"]))
    assert n_opt == len(jax.tree.leaves(eng.state.master)) - 1
    batch = {"tokens": tokens_of(hf, (eng.config.train_batch_size, 33))}
    b = np.asarray(eng.state.master["layers"]["expert_bias"], np.float64)
    assert not b.any()  # a bias the step moves starts at 0, as published
    losses = []
    for _ in range(3):
        m = eng.train_batch(batch)
        losses.append(m["loss"])
        c = m["moe_census"].astype(np.float64)       # [2 layers, 8]
        assert c.shape == (2, 8) and c.sum() == 8 * 32 * 2 * 2
        assert m["moe_pairs_routed"] == c.sum()
        assert m["moe_pairs_held"] == c[:, 2:6].sum()
        assert m["moe_rows_per_expert_max"] == c[:, 2:6].max()
        assert m["moe_rows_per_expert_min"] == c[:, 2:6].min()
        assert m["moe_pairs_dropped"] == 0
        # a micro-batch's list is T x min(k, 4 held) = 2 T rows, ONE
        # chunk, which always runs: two routed layers, two micro-batches
        assert m["moe_chunks_run"] == 2 * 2
        d = 0.001 * np.sign(c.mean(-1, keepdims=True) - c)
        b = b + d - d.mean(-1, keepdims=True)
        got = np.asarray(eng.state.master["layers"]["expert_bias"])
        assert np.abs(got - b).max() < 2e-7
        assert m["expert_bias_abs_max"] == pytest.approx(np.abs(b).max(),
                                                         abs=1e-6)
    assert losses[2] < losses[1] < losses[0]
    assert eng.counters["moe_pairs_routed"] == 3 * 8 * 32 * 2 * 2
    assert eng.counters["moe_pairs_dropped"] == 0
    assert eng.counters["moe_chunks_run"] == 3 * 2 * 2
    # the compute copy follows the master; checkpoints round-trip both
    assert np.allclose(
        np.asarray(eng.state.params["layers"]["expert_bias"], np.float32),
        b, atol=2e-4)
    eng.save_checkpoint(str(tmp_path))
    eng.train_batch(batch)
    moved = np.asarray(eng.state.master["layers"]["expert_bias"])
    assert np.abs(moved - b).max() > 5e-4
    eng.load_checkpoint(str(tmp_path))
    back = np.asarray(eng.state.master["layers"]["expert_bias"])
    assert np.abs(back - b).max() < 2e-7
    assert eng.state.opt["mu"]["layers"]["expert_bias"] is None


def test_the_gradient_path_lacks_the_steps_state():
    """What the step differentiates, reduces and clips is the tree
    WITHOUT the bias (its reference gradient is 0, so no norm could
    tell: the tree's own structure does), and the aux rides beside."""
    mcfg = config_from_hf(tiny3(), use_flash=False, max_seq=64)
    eng = _engine(mcfg, jax.devices()[:1], stage=0, dtype="fp32")
    grads, _, aux = jax.eval_shape(
        eng._make_accumulator(), eng.state.params,
        {"tokens": jax.ShapeDtypeStruct((2, 2, 33), jnp.int32)},
        jax.random.PRNGKey(0), jnp.float32(1.0), jnp.int32(0))
    assert grads["layers"]["expert_bias"] is None
    assert grads["layers"]["w_router"].shape == (2, 32, 8)
    assert len(jax.tree.leaves(grads)) == len(jax.tree.leaves(
        eng.state.params)) - 1
    assert aux["moe_census"].shape == (2, 8)
    assert aux["moe_pairs_dropped"].shape == ()
    assert aux["moe_chunks_run"].shape == ()


def test_what_the_rule_cannot_ride_is_refused():
    mcfg = config_from_hf(tiny(), use_flash=False, max_seq=64)
    with pytest.raises(NotImplementedError, match="has_aux"):
        ds.initialize(
            {"train_micro_batch_size_per_gpu": 1,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
            loss_fn=T.make_loss_fn(mcfg), param_init_fn=lambda k: T.init(mcfg, k),
            mesh=build_mesh({}, devices=jax.devices()[:1]),
            state_rule=T.step_state_rule(mcfg))
    dense = T.TransformerConfig(vocab_size=64, n_layers=1, n_heads=2,
                                d_model=16)
    assert T.step_state_rule(dense) is None


# -- what training still refuses ----------------------------------------------

@pytest.mark.parametrize("over,match", [
    (dict(moe_dropless=False), "dropless wire alone"),
    (dict(moe_noisy_gate_policy="RSample"), "noisy gate"),
    (dict(random_ltd_layer_range=(0, 1), attention_window_pattern=None,
          rope_windowed_only=False), "random-LTD"),
    (dict(shared_expert_gate=True), r"shared_expert_gate"),
    (dict(residual_multiplier=0.5), r"residual_multiplier"),
])
def test_the_training_forward_refuses_by_name(over, match):
    mcfg = dataclasses.replace(
        config_from_hf(tiny(), use_flash=False, max_seq=64), **over)
    with pytest.raises(NotImplementedError, match=match):
        T.forward_hidden(
            jax.eval_shape(lambda: T.init(mcfg, jax.random.PRNGKey(0))),
            jnp.zeros((1, 8), jnp.int32), mcfg,
            ltd_idx=jnp.zeros((1, 4), jnp.int32) if "random_ltd_layer_range"
            in over else None)


def test_serving_only_no_longer_names_what_training_computes():
    mcfg = config_from_hf(tiny(), use_flash=False, max_seq=64)
    assert mcfg.serving_only == ()
    assert mcfg.carries_census and T.aux_width(mcfg) == 2 + 8 + 2
    lifted = {"sandwich_norm", "n_shared_experts", "n_dense_layers",
              "experts_held", "moe_expert_bias", "attn_output_gate",
              "moe_scoring", "embedding_multiplier"}
    every = dataclasses.replace(
        mcfg, shared_expert_gate=True, residual_multiplier=2.0,
        logits_scaling=2.0, attention_multiplier=0.1)
    assert not lifted & set(every.serving_only)
    assert {"shared_expert_gate", "residual_multiplier", "logits_scaling",
            "attention_multiplier"} == set(every.serving_only)
    with pytest.raises(ValueError, match="rope_windowed_only"):
        dataclasses.replace(mcfg, position_embedding="none")
    with pytest.raises(ValueError, match="rope_windowed_only"):
        dataclasses.replace(mcfg, attention_window_pattern=None)


# -- the configuration and the import ----------------------------------------

def test_the_cut_keeps_the_published_widths_and_imports():
    mcfg = config_from_hf(CUT, **CUT["train"]["model_overrides"])
    assert (mcfg.n_dense_layers, mcfg.n_layers, mcfg.depth) == (1, 4, 5)
    assert mcfg.attention_window_pattern == (2048, 2048, 2048, 0)
    assert [mcfg.window_for_layer(i) for i in range(5)] == [
        2048, 2048, 2048, 0, 2048]
    assert [mcfg.rope_at(i) for i in range(5)] == [True, True, True, False, True]
    assert (mcfg.n_experts, mcfg.experts_held, mcfg.moe_top_k) == (128, (0, 16), 8)
    assert (mcfg.d_model, mcfg.n_heads, mcfg.kv_heads, mcfg.head_dim) == (
        2048, 32, 4, 128)
    assert (mcfg.d_ff, mcfg.dense_d_ff, mcfg.vocab_size) == (1024, 6144, 25024)
    assert mcfg.routed_scaling_factor == 2.826 and mcfg.moe_norm_topk_prob
    assert mcfg.expert_bias_update_rate == 0.001
    assert mcfg.embedding_multiplier == pytest.approx(2048 ** 0.5)
    assert mcfg.moe_aux_loss_coef == 0.0 and mcfg.moe_dropless
    assert mcfg.serving_only == ()
    # the tree is the arithmetic's, leaf for leaf by count
    assert T.param_count(mcfg) == arith.model_params(CUT) == 705_474_304
    assert len(CUT["reduced"]) == 5
    for key, want in PUBLISHED.items():
        assert CUT[key] == (CUT["reduced"][key]["here"]
                            if key in CUT["reduced"] else want), key
    # the published file itself: 2 dense + 30 routed layers, the stacked
    # ones no whole number of periods (the scan unrolls the last two)
    whole = config_from_hf(dict(PUBLISHED), max_seq=8192)
    assert (whole.n_dense_layers, whole.n_layers) == (2, 30)
    assert whole.experts_held is None and whole.n_experts == 128


def test_a_stack_that_is_no_whole_number_of_periods_trains(highest):
    """2 dense + 5 routed layers under a period of 4: one scanned
    period, one unrolled layer; windows and rotary by the MODEL's
    layer. Forward against the reference (the backward of a scanned
    and of an unrolled body is the five-layer test's)."""
    hf = tiny(num_hidden_layers=7, num_dense_layers=2,
              layer_types=(PERIOD * 2)[:7])
    mcfg = config_from_hf(hf, use_flash=False, max_seq=64)
    assert [mcfg.window_for_layer(i) for i in range(7)] == [8, 8, 8, 0, 8, 8, 8]
    params, toks = seeded(mcfg, 5), tokens_of(hf, (1, 25), 5)
    loss = T.make_loss_fn(mcfg, loss_chunks=1, has_aux=True)
    got, aux = jax.jit(lambda p: loss(p, {"tokens": toks}, None))(params)
    assert aux["moe_census"].shape == (5, 8)
    top = {k: v for k, v in params.items() if k != "layers"}
    want = ref.loss(top, lambda l: jax.tree.map(lambda a: a[l],
                                                params["layers"]), toks, hf)
    assert abs(float(got) - want) < LOSS_ATOL


@pytest.mark.parametrize("over,match", [
    (dict(n_group=2), "n_group"), (dict(topk_group=2), "topk_group"),
    (dict(num_expert_groups=4), "num_expert_groups"),
    (dict(num_limited_groups=2), "num_limited_groups"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(score_func="softmax"), "score_func"),
    (dict(layer_types=PERIOD[::-1] + PERIOD[:1]), "layer_types"),
    (dict(global_attn_every_n_layers=1), "global_attn_every_n_layers"),
    (dict(num_dense_layers=5), "at least one layer is routed"),
    (dict(kv_lora_rank=8), "does not read 'kv_lora_rank'"),
    (dict(mlp_layer_types=["dense"] * 5), "does not read 'mlp_layer_types'"),
    (dict(shared_expert_intermediate_size=64), "does not read"),
])
def test_the_import_refuses_what_it_does_not_read(over, match):
    with pytest.raises(ValueError, match=match):
        config_from_hf(tiny(**over))


@pytest.mark.parametrize("key", ["score_func", "route_scale", "mup_enabled",
                                 "global_attn_every_n_layers",
                                 "load_balance_coeff"])
def test_the_keys_stay_an_error_for_another_architecture(key):
    other = harness.load_json(BENCH / "configs/olmoe-1b-7b-serve-l8.json")
    with pytest.raises(ValueError, match=f"does not read '{key}'"):
        config_from_hf(dict(other, **{key: PUBLISHED[key]}))


def test_every_published_key_is_read_or_decides_nothing():
    """Each key of the catalog row's config either changes the imported
    configuration when it changes, is refused at another value, or is
    one of the few named as deciding nothing here."""
    inert = {"model_type", "use_grouped_mm", "hidden_act", "rope_scaling",
             "score_func", "n_group", "topk_group", "num_expert_groups",
             "num_limited_groups", "layer_types"}
    base = config_from_hf(dict(PUBLISHED))
    other = {"global_attn_every_n_layers": None, "head_dim": 64,
             "hidden_size": 1024, "intermediate_size": 4096,
             "load_balance_coeff": 0.01, "max_position_embeddings": 4096,
             "moe_intermediate_size": 512, "mup_enabled": False,
             "num_attention_heads": 16, "num_dense_layers": 6,
             "num_experts": 64, "num_experts_per_tok": 4,
             "num_hidden_layers": 36, "num_key_value_heads": 2,
             "num_shared_experts": 2, "rms_norm_eps": 1e-6,
             "rope_theta": 500000, "route_norm": False, "route_scale": 1.0,
             "sliding_window": 1024, "tie_word_embeddings": True,
             "vocab_size": 1000}
    assert set(other) | inert == set(PUBLISHED)
    for key, value in other.items():
        hf = dict(PUBLISHED, **{key: value})
        if key == "num_hidden_layers":
            hf["layer_types"] = PERIOD * 9
        try:
            assert config_from_hf(hf) != base, key
        except ValueError:
            assert key == "global_attn_every_n_layers"


# -- served ---------------------------------------------------------------

def test_prefill_chunks_and_single_steps_through_rings_and_pages(highest):
    """The same family through the serving engine, float32, every
    expert held: a whole-prompt prefill, a chunk and single-token steps
    (three turns of a windowed layer's ring) against the reference's
    full forward. Logits, not tokens."""
    hf = tiny(num_experts=8, reduced={}, experts_held=None,
              sliding_window=16, head_dim=16)
    mcfg = config_from_hf(hf, use_flash=False, max_seq=256)
    assert mcfg.mixed_windows and mcfg.ring_layers == (
        True, True, True, False, True)
    params = jax.tree.map(lambda a: a * 4 if a.ndim > 1 else a,
                          T.init(mcfg, jax.random.PRNGKey(0)))
    eng = init_inference(params, mcfg, dict(
        max_seq_len=256, kv_block_size=8, num_kv_blocks=96,
        max_batch_size=32, max_tracked_sequences=4, num_kv_rings=4),
        dtype=jnp.float32)
    rng = np.random.default_rng(0)
    lens, n_dec = [70, 77], 6
    full = [rng.integers(0, 128, n + n_dec).astype(np.int32) for n in lens]
    cuts = [[n - 5, n] + [n + j + 1 for j in range(n_dec)] for n in lens]
    got = []
    for j in range(len(cuts[0])):
        got.append(np.asarray(eng.put(
            [7, 8], [f[(c[j - 1] if j else 0):c[j]]
                     for f, c in zip(full, cuts)])))
    got = np.stack(got, axis=1)
    top = {k: v for k, v in params.items() if k != "layers"}
    layer = lambda l: jax.tree.map(lambda a: a[l], params["layers"])  # noqa: E731
    # one width for both prompts: the reference runs op by op, and
    # another length is a hundred small compiles again (causal: the
    # padding cannot reach the positions read)
    padded = np.zeros((2, 1, 96), np.int32)
    for i, (f, c) in enumerate(zip(full, cuts)):
        padded[i, 0, :len(f)] = f
        want = np.asarray(ref.forward_logits(top, layer, padded[i], hf))[0]
        err = np.abs(got[i] - want[np.asarray(c) - 1]).max()
        assert np.abs(want).max() > 1.0 and err < 5e-4, (i, err)
    # a full layer that rotated would read elsewhere
    with afmoe_audit.control(ref, "rope_on_the_full_layer"):
        wrong = np.asarray(ref.forward_logits(top, layer, padded[0], hf))[0]
    assert np.abs(got[0] - wrong[np.asarray(cuts[0]) - 1]).max() > 0.05
