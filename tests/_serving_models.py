"""Tiny models and engines the serving tests share
(tests/test_inference*.py)."""

import functools

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
    return cfg, _init(cfg)(jax.random.PRNGKey(0))


@functools.lru_cache(maxsize=None)
def _init(cfg):
    # ONE program a configuration (leaf by leaf, forty small compiles)
    return jax.jit(lambda key: T.init(cfg, key))


def engine_for(cfg, params, **ckw):
    base = dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                min_prefill_bucket=8, max_batch_size=8)
    base.update(ckw)
    return init_inference(params, cfg, base, dtype=jnp.float32)


@functools.lru_cache(maxsize=None)
def _forward(cfg):
    return jax.jit(lambda params, toks: T.forward(params, toks, cfg))


def oracle_next_logits(params, cfg, context):
    """Training-model full-context forward → last-token logits. ONE
    program a configuration: the context padded to max_seq (causal: what
    follows the last token cannot reach it). Op by op at every length a
    decode loop passes through, the forward was sixty small compiles a
    length."""
    toks = np.zeros((1, cfg.max_seq), np.int32)
    toks[0, :len(context)] = context
    logits = _forward(cfg)(params, toks)
    return np.asarray(logits[0, len(context) - 1], np.float32)
