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
