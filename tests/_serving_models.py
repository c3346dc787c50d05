"""Tiny models and engines the serving tests share
(tests/test_inference*.py)."""

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.models import transformer as T


def small_model(variant="llama", **kw):
    base = dict(vocab_size=128, n_layers=2, n_heads=4, d_model=64, max_seq=128,
                variant=variant, use_flash=False)
    base.update(kw)
    cfg = T.TransformerConfig(**base)
    params = T.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def engine_for(cfg, params, **ckw):
    base = dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                min_prefill_bucket=8, max_batch_size=8)
    base.update(ckw)
    return init_inference(params, cfg, base, dtype=jnp.float32)


def oracle_next_logits(params, cfg, context):
    """Training-model full-context forward → last-token logits."""
    logits = T.forward(params, jnp.asarray([context], jnp.int32), cfg)
    return np.asarray(logits[0, -1], np.float32)
