"""The FLOPs / bytes / parameter arithmetic against hand-computed
Mistral-7B numbers."""

import json
import pathlib

import pytest

from benchmarks import harness
from benchmarks.kernels import shapes

BENCH = pathlib.Path(__file__).resolve().parents[1]
HF = json.loads((BENCH / "configs" / "mistral-7b-serve-l16.json").read_text())


def test_parameter_counts():
    # q 4096*4096 + k, v 2*4096*1024 + o 4096*4096 + mlp 3*4096*14336
    assert shapes.layer_matmul_params(HF) == 218_103_808
    assert shapes.layer_params(HF) == 218_112_000
    assert shapes.model_params(HF, 32) == 7_241_732_096
    assert shapes.model_params(HF, 2) == 2 * 218_112_000 + 262_144_000 + 4096
    # what a token is multiplied by at two layers: not the embedding
    assert shapes.matmul_params(HF, 2) == 2 * 218_103_808 + 131_072_000


# the catalog row's `config` (model-configs guide, architectures.jsonl:
# OLMoE-1B-7B-0125-Instruct), the keys this arithmetic reads
OLMOE = {"attention_bias": False, "hidden_size": 2048, "intermediate_size": 1024,
         "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
         "num_experts_per_tok": 8, "num_hidden_layers": 16,
         "num_key_value_heads": 16, "tie_word_embeddings": False,
         "vocab_size": 50304}


def test_a_routed_block_holds_more_than_a_token_is_multiplied_by():
    attention, router, expert = 4 * 2048 * 2048, 2048 * 64, 3 * 2048 * 1024
    head = 2048 * 50304
    assert (attention, router, expert, head) == (16_777_216, 131_072, 6_291_456, 103_022_592)
    assert shapes.layer_matmul_params(OLMOE) == attention + router + 64 * expert
    assert shapes.layer_matmul_params(OLMOE, active=True) == attention + router + 8 * expert
    held = 16 * (attention + router + 64 * expert) + 2 * head
    assert held == 6_919_028_736                        # 6.92 B, norms apart
    assert shapes.model_params(OLMOE) == held + 16 * 2 * 2048 + 2048
    assert shapes.matmul_params(OLMOE) == 1_178_861_568  # 1.18 B
    assert shapes.train_flops_per_token(OLMOE, 4096) == \
        6 * 1_178_861_568 + 6 * 16 * 4096 * 2048
    # Mixtral's key for the same count
    mixtral = dict(HF, num_local_experts=8, num_experts_per_tok=2)
    dense = shapes.layer_matmul_params(HF) - 3 * 4096 * 14336
    assert shapes.layer_matmul_params(mixtral) == dense + 4096 * 8 + 8 * 3 * 4096 * 14336
    assert shapes.layer_matmul_params(mixtral, active=True) == \
        dense + 4096 * 8 + 2 * 3 * 4096 * 14336


@pytest.mark.parametrize("keys", [
    {"moe_intermediate_size": 1408}, {"n_shared_experts": 2},
    {"n_routed_experts": 64}, {"kv_lora_rank": 512}, {"qk_rope_head_dim": 64},
    {"first_k_dense_replace": 1}, {"mlp_bias": True}, {"ssm_state_size": 16},
    {"num_experts_per_tok": 2},                      # without a count of experts
    {"num_experts": 8},                              # without experts per token
    {"num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 2}])
def test_a_block_this_file_cannot_count_is_an_error_never_a_dense_count(keys):
    with pytest.raises(ValueError):
        shapes.layer_matmul_params(dict(HF, **keys))
    assert shapes.layer_matmul_params(dict(HF, attention_bias=False, mlp_bias=False)) \
        == 218_103_808


def test_train_flops_per_token():
    got = shapes.train_flops_per_token(HF, 4096, 2)
    assert got == 6 * 567_279_616 + 6 * 2 * 4096 * 4096
    assert 3.59e9 < got < 3.62e9
    # the window caps the attention term
    assert shapes.train_flops_per_token(HF, 8192, 2) == got


def test_flash_and_kv_arithmetic():
    need = shapes.flash_flops_and_bytes(HF, batch=1, seq_len=4096)
    assert need["flops"] == 7.0 * 32 * 4096 * 4096 * 128
    q, kv = 4096 * 32 * 128 * 2, 4096 * 8 * 128 * 2
    assert need["bytes"] == 6 * q + 6 * kv
    assert shapes.kv_bytes_per_token(HF, 16) == 65_536


def test_peaks_table_refuses_an_unknown_device():
    v5e = harness.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
