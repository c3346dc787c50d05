"""The StableHLO of a tiny serving configuration's 8-row decode step,
lowered for the TPU with no chip: what tests/test_mellum2.py hashes for
the older families and tests/test_kernels_lower_once.py counts kernels
in."""

import json
import pathlib

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

CONFIGS = (pathlib.Path(__file__).resolve().parents[1]
           / "benchmarks/tests/data/configs")
ROWS, BLOCK = 8, 128


def tiny(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())


def step_program(name: str, kernels: bool = True, unique: bool = False):
    """(cfg, text): the configuration's 8-row decode step, single-token
    (`unique`) or shared-table, with the Pallas kernels (their Mosaic
    modules are in the text) or as the XLA oracle, without source
    locations."""
    cfg = config_from_hf(tiny(name), max_seq=512)
    params = jax.eval_shape(lambda: M.prepare(jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        T.init(cfg, jax.random.PRNGKey(0))), cfg))
    rings = {"ring_pool_blocks": ROWS * M.ring_blocks(cfg, BLOCK, 4) + 1
             } if cfg.mixed_windows else {}
    cache = jax.eval_shape(lambda: M.init_cache(
        cfg, 17, BLOCK, jnp.bfloat16, state_slots=ROWS, **rings))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    named = (["slots"] if cache.state else []) + (
        ["rings"] if cfg.mixed_windows else [])

    def step(p, c, tok, tab, ctx, *rows):
        return M.decode_step(p, c, tok, tab, ctx, cfg, use_kernel=kernels,
                             unique_rows=unique, **dict(zip(named, rows)))

    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        traced = jax.jit(step, donate_argnums=(1,)).trace(
            params, cache, i32(ROWS), i32(ROWS, 4), i32(ROWS),
            *(i32(ROWS) for _ in named))
        return cfg, traced.lower(
            lowering_platforms=("tpu",) if kernels else None).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
