"""SDAR-MoE (`sdar_moe`: generation by diffusion over blocks) on the
serving path at a tiny size on the CPU, against the plain float32
reference of benchmarks/reference/sdar_moe.py: whole-prompt prefill,
chunks at every block offset, denoising passes and commits through the
paged cache (every row of every pass, teacher-forced by a seeded reveal
order: the benchmark's own protocol, runners/serve_blocks.py), the
scheduler's generation token for token against `reference.generate` at
block lengths 4 and 8, the controls that must fail, the refusals, and the
counters.

Everything is float32 with seeded weights: 2 layers, d 64, 4 heads of 16
over 2 KV heads, 8 experts of 32 top-3, a vocabulary of 256 whose last
id is the mask.
"""

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import _family as F
import pytest
from _family import (  # noqa: F401 - the contract's fixtures and cases
    engines, family, pytest_generate_tests,
    test_the_cuts_file_keeps_the_published_widths,
    test_the_training_forward_refuses_the_family,
    test_what_the_mapping_cannot_serve_is_an_error)

from benchmarks.reference import sdar_moe as ref
from benchmarks.runners import serve_blocks as SB
from deepspeed_tpu.inference import (
    ServingScheduler,
    ServingSchedulerConfig,
)
from deepspeed_tpu.inference import model as M
from deepspeed_tpu.inference import scheduler as S
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.utils import hf_checkpoint
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

PUBLISHED = F.BENCH / "configs/published/sdar-30b-a3b-chat.json"
HF = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
      "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
      "max_position_embeddings": 256, "max_window_layers": 2,
      "mlp_only_layers": [], "model_type": "sdar_moe",
      "moe_intermediate_size": 32, "norm_topk_prob": True,
      "num_attention_heads": 4, "num_experts": 8, "num_experts_per_tok": 3,
      "num_hidden_layers": 2, "num_key_value_heads": 2,
      "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
      "sliding_window": None, "tie_word_embeddings": False,
      "use_sliding_window": False, "vocab_size": 256,
      "block_length": 4, "mask_token_id": 255}
HF8 = dict(HF, block_length=8)
# float32 on both sides, logits up to ~2: the system reassociates (the
# paged walk's running softmax, the expert scan's running sum), which
# moves a logit by ~1e-5 (measured here 4e-6). Every control moves one by
# 0.05 and more: three orders of magnitude above the limit.
ATOL = 2e-4
ENGINE = dict(max_seq_len=256, kv_block_size=16, num_kv_blocks=64,
              max_batch_size=48, min_prefill_bucket=16)
FAMILY = F.Family(
    hf=HF, ref=ref, atol=ATOL, engine=ENGINE, far=100,
    training_refuses="block_length",
    cut=F.BENCH / "configs/sdar-30b-a3b-chat-serve-l6.json",
    reduced=("num_hidden_layers",),
    assumed=("block_length", "denoising_steps", "reveal_rule",
             "mask_token_id", "unshifted_logits", "layer", "noise_schedule",
             "weights", "kv_pool", "max_batch_size"),
    unservable=(
        ("a dense MLP in some layer", dict(HF, mlp_only_layers=[1]),
         "dense MLP"),
        ("a dense MLP every second layer", dict(HF, decoder_sparse_step=2),
         "dense MLP"),
        ("a sliding window", dict(HF, use_sliding_window=True),
         "use_sliding_window"),
        ("a mask id outside the vocabulary", dict(HF, mask_token_id=256),
         "mask_token_id"),
        ("no block", dict(HF, block_length=0), "at least one position"),
        ("another architecture's block key", dict(F.MISTRAL,
                                                 moe_intermediate_size=32),
         "moe_intermediate_size")))
# the check's shape at this size: two prompts of whole blocks, a chunk of
# two blocks, three blocks of four passes and a commit
CHK = {"prompt_lens": [40, 72], "chunk": 8, "blocks": 3}


def make_model(hf):
    mcfg = config_from_hf(hf, use_flash=False)

    def make():
        params = T.init(mcfg, jax.random.PRNGKey(1))
        # spread the logits (the 0.02 init gives nearly flat ones) and
        # make the per-head QK-norm scales matter (init gives ones)
        params = jax.tree.map(lambda x: x * 4 if x.ndim > 1 else x, params)
        for i, name in enumerate(("q_norm_scale", "k_norm_scale")):
            params["layers"][name] = 1 + 0.3 * jax.random.normal(
                jax.random.PRNGKey(2 + i), params["layers"][name].shape)
        return params

    return mcfg, jax.jit(make)()


@pytest.fixture(scope="module")
def model():
    return make_model(HF)


@pytest.fixture(scope="module")
def model8():
    return make_model(HF8)


@pytest.fixture(scope="module")
def engine8(engines, model8):
    """B 8 serves another model: ONE engine of its own for its cases."""
    return engines.fresh(model=model8, max_batch_size=32)


def cell_of(hf, chk=CHK, steps=0):
    """What runners/serve_blocks.py reads of a cell, at this size."""
    return types.SimpleNamespace(
        config=dict(hf, reference="sdar_moe", serve={
            "scheduler": {"denoising_steps": steps}}),
        traffic={"logits_check": chk}, bench_dir=F.BENCH)


def errors(eng, model, hf=HF, chk=CHK, seed=0, steps=0):
    mcfg, params = model
    host = dict(params)  # reference_inputs takes the stacked tree
    return SB.block_errors(cell_of(hf, chk, steps), eng, mcfg, host, seed)


@pytest.fixture(scope="module")
def served(engines, model):
    """The benchmark's block check at this size on the module's engine:
    prefill, a chunk, three blocks of passes and commits."""
    return errors(engines(), model)


def serve(eng, asked, ahead=True, eos=None, **sched):
    s = ServingScheduler(eng, ServingSchedulerConfig(
        **dict(dict(max_num_batched_tokens=16, prefill_chunk=8,
                    warmup=False), **sched)))
    rids = [s.submit(p, max_new_tokens=n, eos_token_id=eos) for p, n in asked]
    if ahead:
        s.run()
    else:
        while s.step():
            pass
    return s, [s.finished[r].output for r in rids]


def by_the_reference(model, hf, asked, steps=0, eos=None):
    _, params = model
    return [ref.generate(F.top(params), F.layer_fn(params), p, n, hf,
                         denoising_steps=steps or None, eos_token_id=eos)
            for p, n in asked]


def prompts(B, seed=5):
    """Prompts whose length leaves every kind of remainder (0, 1, B - 1
    of a block, and less than one block), answers that are no whole
    blocks."""
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, 255, n).tolist(), m) for n, m in (
        (5 * B, 2 * B + 1), (3 * B + 1, B + 3), (7 * B - 1, 3 * B - 2),
        (B - 1, B + 1))]


# -- the configuration ---------------------------------------------------

def test_the_published_config_builds_the_model_with_max_seq_alone():
    hf = {k: v for k, v in json.loads(PUBLISHED.read_text()).items()
          if not k.startswith("_")}
    assert "architectures" not in hf and hf["model_type"] == "sdar_moe"
    cfg = config_from_hf(hf, max_seq=4096)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.kv_heads,
            cfg.head_dim, cfg.ff_dim) == (48, 2048, 32, 4, 128, 768)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.vocab_size) == (128, 8, 151936)
    assert cfg.qk_norm and cfg.qk_norm_per_head and cfg.moe_norm_topk_prob
    assert cfg.moe_dropless and not cfg.tie_embeddings
    assert (cfg.rope_theta, cfg.norm_eps) == (1e6, 1e-6)
    # what the row does not give is the family's
    assert cfg.block_length == hf_checkpoint.SDAR_BLOCK_LENGTH == 4
    assert cfg.mask_token_id == hf_checkpoint.SDAR_MASK_TOKEN_ID == 151669
    assert (ref.BLOCK_LENGTH, ref.MASK_TOKEN_ID) == (4, 151669)
    assert cfg.serving_only == ("block_length",)
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    assert shapes["layers"]["q_norm_scale"].shape == (48, 128)
    assert shapes["layers"]["w_in"].shape == (48, 128, 2048, 768)


def test_the_cut_builds_with_max_seq_alone_and_counts_what_the_issue_counts():
    hf, cfg = F.cut_of(FAMILY)
    assert hf["serve"]["model_overrides"] == {"max_seq": 4096}
    assert (cfg.n_layers, cfg.block_length, cfg.mask_token_id) == (
        6, 4, 151669)
    shapes = jax.eval_shape(lambda k: T.init(cfg, k), jax.random.PRNGKey(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n == 4_361_055_744
    assert "8 pipeline stages of 6 layers" in hf["stands_for"]
    eng, sched = hf["serve"]["engine"], hf["serve"]["scheduler"]
    assert eng["kv_block_size"] % cfg.block_length == 0
    assert sched["prefill_chunk"] % cfg.block_length == 0
    assert eng["max_batch_size"] == 256 and sched["denoising_steps"] == 4


def test_the_weights_do_not_import_yet(tmp_path):
    (tmp_path / "config.json").write_text(json.dumps(HF))
    with pytest.raises(NotImplementedError, match="configuration alone"):
        hf_checkpoint.import_external(str(tmp_path))


@pytest.mark.parametrize("kwargs", [
    dict(layer_types=("attention",) * 2), dict(sliding_window=8),
    dict(block_length=-4)])
def test_a_block_length_on_another_kind_of_layer_is_refused(kwargs):
    base = dict(vocab_size=64, n_layers=2, n_heads=2, d_model=32,
                variant="llama", block_length=4, mask_token_id=3)
    with pytest.raises(ValueError, match="block_length"):
        T.TransformerConfig(**dict(base, **kwargs))


# -- engine against reference --------------------------------------------

def test_prefill_chunks_passes_and_commits_match_the_reference(served):
    """Every row of the prefill's last block, of the chunk's, and of
    every pass and commit of three blocks: 68 positions a prompt."""
    e = served
    assert e["finite"] and e["err"].shape == (2, 4 * (2 + 3 * 5))
    assert e["ref_max"] > 1.0  # the logits are not flat
    assert e["err"].max() < ATOL, e["err"].max(-1)


@pytest.mark.parametrize("lens,chunk", [([36, 48], 4), ([44, 40], 20)])
def test_a_chunk_boundary_at_every_block_offset(engines, model, lens, chunk):
    """A continuation that starts at every offset a diffusion block has
    in a KV block of 16 (4 and 0, 12 and 8), of one block and of five
    (the second crosses a KV block's edge), then a block through its
    passes."""
    e = errors(engines(), model, seed=chunk,
               chk=dict(prompt_lens=lens, chunk=chunk, blocks=1))
    assert e["err"].max() < ATOL, e["err"].max(-1)


@pytest.mark.parametrize("control", (
    "float8_weights", "bf16_weights") + SB.PROTOCOL_CONTROLS + ref.MUTANTS)
def test_a_wrong_model_fails_the_written_tolerance(model, served, control):
    """Each control put in the engine's place against the same
    reference: the largest position is over 100 x the tolerance."""
    _, params = model
    if control == "bf16_weights":
        # the dtype below the float32 this module states
        low = jax.jit(lambda p: jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p))(params)
        got = SB.reference_logits(ref, F.top(low), F.layer_fn(low),
                                  cell_of(HF).config, served["proto"])
        err = np.abs(got - served["want"]).max()
    else:
        err = SB.control_errors(cell_of(HF), dict(params), served,
                                names=(control,))[control].max()
    assert err > FAMILY.far * ATOL, (control, err)


def test_the_rule_names_the_worst_row_and_both_limits(served):
    ok = SB.verdict({"rtol": 1e-3, "typical_rtol": 1e-4}, served)
    assert ok["ok"] and ok["positions"] == 136
    tight = SB.verdict({"rtol": 1e-9, "typical_rtol": 1e-10}, served)
    assert not tight["ok"] and len(tight["broken"]) == 2
    assert tight["worst"].startswith("prompt ") and "row" in tight["worst"]


@pytest.mark.usefixtures("pallas_interpret")
def test_the_engine_with_kernels_matches_the_reference(engines, model):
    """decode_impl 'auto' under the interpreter resolves the kernels:
    paged_kv_write at the rows' POSITIONS, then paged_decode_grid over
    rows that see through their block's end."""
    eng = engines()
    assert eng.resolved_impl == "pallas"
    e = errors(eng, model, chk=dict(CHK, prompt_lens=[32, 36], blocks=2),
               seed=4)
    assert e["err"].max() < ATOL, e["err"].max(-1)


def test_a_step_takes_positions_apart_from_what_its_rows_see(model, engines):
    """model.decode_step: rows of one block at four positions all see
    the same 4 x (p // 4 + 1) tokens; the fused single-token program
    refuses them."""
    mcfg, params = model
    eng = engines()
    assert list(eng.block_ctx(np.arange(9))) == [4] * 4 + [8] * 4 + [12]
    cache = M.init_cache(mcfg, 5, 16, jnp.float32)
    toks = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError, match="unique_rows"):
        M.decode_step(params, cache, toks, jnp.zeros((8, 4), jnp.int32),
                      jnp.full((8,), 4, jnp.int32), mcfg, use_kernel=False,
                      unique_rows=True, positions=jnp.arange(8))
    # the engine has ONE step program a width, whatever it is asked
    assert eng._decode_fn(8, True) is eng._decode_fn(8, False)


# -- the scheduler ---------------------------------------------------------

@pytest.mark.parametrize("B,steps", [(4, 0), (8, 4), (8, 3)])
def test_generation_is_the_references_token_for_token(engines, model, model8,
                                                      engine8, B, steps):
    """Greedy, every kind of prompt remainder, answers that are no whole
    blocks, four requests of different lengths through 16 rows (B 4) or
    32 (B 8): two run side by side while another is still in prefill.
    steps 4 of B 8 reveals two positions a pass, 3 reveals 3, 3, 2."""
    hf, mdl = (HF, model) if B == 4 else (HF8, model8)
    eng = engines() if B == 4 else engine8
    asked = prompts(B)
    s, got = serve(eng, asked, denoising_steps=steps,
                   max_num_batched_tokens=4 * B)
    assert got == by_the_reference(mdl, hf, asked, steps)
    assert [len(o) for o in got] == [n for _, n in asked]
    c = s.counters
    assert c["block_rows"] == B * (c["block_passes"] + c["block_commits"])
    assert c["block_tokens"] == c["output_tokens"] == sum(map(len, got))
    # every prompt's whole blocks, less what the prefix index credited
    # (the second case of B 8 meets the first one's prompts there)
    credited = sum(r.n_cached for r in s.finished.values())
    assert credited % B == 0
    assert c["batched_tokens"] == c["block_rows"] - credited + sum(
        len(p) - len(p) % B for p, _ in asked)
    assert c["lookahead_steps"] > 0.8 * c["steps"]
    if B == 4:  # 4 passes + a commit a whole block: 5 rows a token, but
        # for first blocks' remainders and last blocks' cuts
        assert 5.0 <= c["block_rows"] / c["block_tokens"] < 7.5
        assert c["block_masked_rows"] < c["block_rows"] / 2


def test_the_look_ahead_on_and_off_give_the_same_tokens(engines):
    asked = prompts(4, seed=9)
    _, first = serve(engines(), asked)  # leaves the prompts in the index:
    # the two runs compared are credited alike
    ahead, a = serve(engines(), asked)
    plain, b = serve(engines(), asked, ahead=False)
    assert first == a == b
    assert ahead.counters["lookahead_steps"] and not \
        plain.counters["lookahead_steps"]
    for key in ("block_passes", "block_commits", "block_rows",
                "block_masked_rows", "block_tokens", "steps"):
        assert ahead.counters[key] == plain.counters[key], key


def test_an_eos_inside_a_block_ends_the_request_there(engines, model):
    asked = prompts(4, seed=11)[:2]
    _, free = serve(engines(), asked)
    eos = free[0][5]  # a token the first request generates in its 2nd block
    s, got = serve(engines(), asked, eos=eos)
    assert got == by_the_reference(model, HF, asked, eos=eos)
    assert got[0] == free[0][:free[0].index(eos) + 1]
    assert s.finished[0].finish_reason == "eos"


def test_preemption_mid_block_resumes_at_the_last_committed_block(engines):
    """A pool too small for the batch: the youngest sequence is flushed
    with a block in flight and recomputed to identical tokens."""
    rng = np.random.default_rng(7)
    asked = [(rng.integers(0, 255, 18).tolist(), 40) for _ in range(4)]
    _, roomy = serve(engines(), asked)
    s, tight = serve(engines(num_kv_blocks=9), asked)
    assert s.counters["preemptions"] > 0
    assert s.counters["block_restarts"] > 0
    assert tight == roomy


def test_the_prefix_index_credits_whole_blocks_and_pages_travel(engines,
                                                                model):
    """Pages move and are shared in KV blocks of 16 tokens = 4 diffusion
    blocks, so COW, the index and export / import need nothing of their
    own: a second request with the same prompt is credited a whole
    number of blocks and generates the same tokens; an exported sequence
    imported under another uid gives the same logits."""
    eng = engines()
    asked = prompts(4, seed=13)[:1] * 2  # 20 tokens: one whole KV block
    s, (a, b) = serve(eng, asked, max_num_batched_tokens=8)
    assert a == b
    stats = eng.prefix_cache_stats()
    assert stats["cached_tokens"] >= 12 and stats["cached_tokens"] % 4 == 0
    toks = np.random.default_rng(3).integers(0, 255, 44).astype(np.int32)
    eng.put([900], [toks[:40]])
    payload = eng.export_kv(900)
    want = eng.put([900], [toks[40:44]], commit=False)
    eng.flush(900)
    eng.import_kv(901, payload)
    got = eng.put([901], [toks[40:44]], commit=False)
    eng.flush(901)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert want.shape == (1, 4, 256)


# -- the refusals ----------------------------------------------------------

@pytest.mark.parametrize("what,sched,kwargs", [
    ("speculation", {}, dict(speculative={"ngram": 2, "draft_len": 3})),
    ("decode_multi", dict(decode_chunk=4), {}),
    ("wave", dict(prefill_mode="wave"), {}),
    ("presence", {}, dict(sampling=dict(repetition_penalty=1.2))),
])
def test_the_scheduler_refuses_at_build(engines, what, sched, kwargs):
    with pytest.raises(NotImplementedError, match=what):
        ServingScheduler(engines(), ServingSchedulerConfig(
            warmup=False, **sched), **kwargs)


def test_what_else_is_refused_and_why(engines, model):
    eng = engines()
    with pytest.raises(ValueError, match="prefill_chunk"):
        ServingScheduler(eng, ServingSchedulerConfig(
            warmup=False, prefill_chunk=6))
    with pytest.raises(ValueError, match="hold no block"):
        ServingScheduler(eng, ServingSchedulerConfig(
            warmup=False, max_num_batched_tokens=3))
    s = ServingScheduler(eng, ServingSchedulerConfig(warmup=False))
    with pytest.raises(NotImplementedError, match="handoff"):
        s.submit([1, 2, 3], handoff=True)
    with pytest.raises(NotImplementedError, match="decode_multi"):
        eng.decode_multi_fn(8, 4)
    with pytest.raises(ValueError, match="kv_block_size"):
        engines.fresh(kv_block_size=6, min_prefill_bucket=16)
    with pytest.raises(NotImplementedError, match="one device"):
        engines.fresh(tp_size=2)
    # put()'s contract: whole blocks, logits and no tokens, a denoising
    # pass of a sequence that is there
    with pytest.raises(ValueError, match="whole blocks"):
        eng.put([5], [np.zeros((6,), np.int32)])
    with pytest.raises(ValueError, match="returns logits"):
        eng.put([5], [np.zeros((8,), np.int32)], return_tokens=True)
    with pytest.raises(ValueError, match="already in the cache"):
        eng.put([5], [np.zeros((8,), np.int32)], commit=False)


def test_a_causal_models_counters_read_what_they_read():
    """Rows as rows and tokens as tokens, for every other family as
    before: `batched_tokens` is the rows fed (prompt + one a generated
    token but the last), `output_tokens` what entered the outputs, no
    block counter moves, and commit=False is refused."""
    from deepspeed_tpu.inference import init_inference

    mcfg = T.TransformerConfig(vocab_size=64, n_layers=1, n_heads=2,
                               d_model=32, max_seq=64, variant="llama",
                               use_flash=False)
    eng = init_inference(T.init(mcfg, jax.random.PRNGKey(0)), mcfg,
                         dict(ENGINE, max_seq_len=64), dtype=jnp.float32)
    asked = [(list(range(1, 10)), 5), (list(range(3, 20)), 3)]
    s, got = serve(eng, asked)
    c = s.counters
    assert [len(o) for o in got] == [5, 3]
    assert c["output_tokens"] == 8
    assert c["batched_tokens"] == (9 + 4) + (17 + 2)
    assert not any(c[k] for k in c if k.startswith("block_"))
    assert s.metrics()["batched_tokens_per_step"] == pytest.approx(
        c["batched_tokens"] / c["steps"])
    with pytest.raises(ValueError, match="causal"):
        eng.put([1], [np.zeros((4,), np.int32)], commit=False)
    assert S._Block(tokens=[1], n_fixed=0, masked=0) != S._Block(
        tokens=[1], n_fixed=0, masked=0)  # a block is itself, not its value
