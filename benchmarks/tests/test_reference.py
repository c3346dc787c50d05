"""The plain float32 Mistral reference against the system at a tiny
size on the CPU, in float32 so the tolerances are tight: logits for
serving (prefill, a continuation chunk, decode through the cache) and
loss plus gradients for training. The window (48) is shorter than the
sequences, so a wrong window rule fails here."""

import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mistral as ref
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.pallas import interpret_kernels
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

DATA = pathlib.Path(__file__).resolve().parent / "data"
HF = dict(json.loads((DATA / "configs" / "tiny-mistral.json").read_text()),
          sliding_window=48)

# float32 on both sides; the system reassociates (fused QKV, flash
# blocks, chunked CE), which moves a float32 logit of order 1 by ~1e-5
LOGITS_ATOL = 2e-4
LOSS_ATOL = 2e-5
GRAD_RTOL = 2e-3   # of the largest |gradient| in each leaf


@pytest.fixture(scope="module")
def model():
    mcfg = config_from_hf(HF, max_seq=256, use_flash=True,
                          flash_block_q=128, flash_block_k=128)
    params = T.init(mcfg, jax.random.PRNGKey(4))
    # spread the logits: the default 0.02 init gives nearly flat ones
    params = jax.tree.map(lambda x: x * 4 if x.ndim > 1 else x, params)
    return mcfg, params


def _layer_fn(params):
    return lambda l: jax.tree.map(lambda a: a[l], params["layers"])


def test_training_loss_and_gradients(model):
    mcfg, params = model
    tokens = np.random.default_rng(0).integers(0, mcfg.vocab_size, (2, 129))
    loss_fn = T.make_loss_fn(mcfg, loss_chunks=2)
    with interpret_kernels():
        got_loss, got_grads = jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": jnp.asarray(tokens)}, None))(params)
    want_loss, want_grads = ref.loss_and_grads(params, tokens, HF)
    assert abs(float(got_loss) - float(want_loss)) < LOSS_ATOL
    assert ref.loss({k: v for k, v in params.items() if k != "layers"},
                    _layer_fn(params), tokens, HF) == pytest.approx(
                        float(want_loss), abs=1e-6)
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_grads))
    for path, g in flat_got:
        w = np.asarray(flat_want[path])
        assert np.abs(np.asarray(g) - w).max() <= GRAD_RTOL * np.abs(w).max(), \
            jax.tree_util.keystr(path)


def test_serving_logits_through_the_cache(model):
    from deepspeed_tpu.inference import init_inference

    mcfg, params = model
    rng = np.random.default_rng(1)
    lens, k, n_dec = [70, 101], 3, 3
    full = [rng.integers(0, mcfg.vocab_size, n + n_dec).astype(np.int32)
            for n in lens]
    with interpret_kernels():
        eng = init_inference(
            params, mcfg,
            dict(max_seq_len=256, kv_block_size=32, num_kv_blocks=32,
                 max_batch_size=8, min_prefill_bucket=32),
            dtype=jnp.float32)
        assert eng.resolved_impl == "pallas"
        uids = [0, 1]
        got = [eng.put(uids, [f[:n - k] for f, n in zip(full, lens)]),
               eng.put(uids, [f[n - k:n] for f, n in zip(full, lens)])]
        for j in range(n_dec):
            got.append(eng.put(uids, [f[n + j:n + j + 1]
                                      for f, n in zip(full, lens)]))
    top = {k2: v for k2, v in params.items() if k2 != "layers"}
    for i, (f, n) in enumerate(zip(full, lens)):
        want = np.asarray(ref.forward_logits(top, _layer_fn(params),
                                             f[None], HF))[0]
        pos = [n - k - 1, n - 1] + [n + j for j in range(n_dec)]
        for step, p in enumerate(pos):
            err = np.abs(np.asarray(got[step][i]) - want[p]).max()
            assert err < LOGITS_ATOL, (i, step, err)
    assert np.abs(want).max() > 0.5   # the logits are not flat
