"""A serving step program holds each Pallas kernel ONCE, whatever the
layers that call it (ops/pallas `kernel_jit`): the 8-row step of every
family's tiny serving configuration, lowered for the TPU with no chip
(its Mosaic modules are in the StableHLO), carries one module a distinct
signature of a kernel and one call of the function that holds it a layer.
Un-jitted, a kernel is traced, built and serialized once a LAYER, which
is what a replica's start waited on (PERF.md section 6, PR 49).
"""

import collections
import re

import jax
import jax.numpy as jnp
import pytest

from _step_program import step_program
from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops import pallas
from deepspeed_tpu.utils import profiler

# the kernels a family's step holds, single-token (unique_rows) and
# shared-table: names as the trace has them (the latent walk and the
# (S, NB) grid carry the shared-table attention's, `paged_decode_grid`)
WRITE_WALK = {"paged_kv_write", "paged_decode_grid"}
KERNELS = {
    "tiny-mistral": ({"paged_decode_fused"}, WRITE_WALK),
    "tiny-olmoe": ({"paged_decode_fused", "expert_stream"},
                   WRITE_WALK | {"expert_stream"}),
    "tiny-pangu": ({"paged_latent_write", "paged_decode_grid"},) * 2,
    # two K/V heads of 64 packed in ONE head of 128: no whole tile of a
    # 16-bit pool (Mosaic refuses its row's DMA), the fused write on the grid
    "tiny-lfm2": ({"paged_decode_grid", "expert_stream", "conv_carry"},
                  WRITE_WALK | {"expert_stream", "conv_carry"}),
    "tiny-qwen3next": (
        {"paged_decode_fused", "expert_stream", "conv_carry", "gdn_state"},
        WRITE_WALK | {"expert_stream", "conv_carry", "gdn_state"}),
    # head dim 64 over one K/V head: the fused write stays on the grid
    "tiny-granite4h": ({"paged_decode_grid", "conv_carry", "ssm_state"},
                       WRITE_WALK | {"conv_carry", "ssm_state"}),
    "tiny-mellum2": ({"paged_decode_fused", "expert_stream"},
                     WRITE_WALK | {"expert_stream"}),
    # a dense FFN: the delta rule over heads in pairs in 4 of 6 layers
    "tiny-olmohybrid": (
        {"paged_decode_fused", "conv_carry", "gdn_state"},
        WRITE_WALK | {"conv_carry", "gdn_state"}),
    # layers of one mixer each: 4 hold a slot, 2 K/V, 2 experts (ungated)
    "tiny-nemotron3": (
        {"paged_decode_grid", "conv_carry", "ssm_state",
         "expert_stream_ungated"},
        WRITE_WALK | {"conv_carry", "ssm_state", "expert_stream_ungated"}),
    # 3 scans, 2 rings and the paged layer, which a cross layer walks
    # again (a pair of 64 in one K/V head: the fused write on the grid)
    "tiny-phi4flash": ({"paged_decode_grid", "conv_carry", "sscan_state"},
                       WRITE_WALK | {"conv_carry", "sscan_state"}),
}
KV_KERNELS = {"paged_decode_fused", "paged_kv_write", "paged_decode_grid",
              "paged_latent_write"}


def kernel_census(text: str):
    """(modules, sites): by kernel name, the Mosaic modules the text
    holds and the calls of the functions that hold them."""
    modules, sites = collections.Counter(), collections.Counter()
    for fn in re.split(r"\n  func\.func ", text)[1:]:
        held = re.findall(r'kernel_name = "(\w+)"', fn)
        fname = re.match(r"(?:private |public )?@(\w+)", fn).group(1)
        calls = len(re.findall(rf"call @{fname}\(", text))
        for kernel in held:
            modules[kernel] += 1
            sites[kernel] += calls if fname != "main" else 1
    return dict(modules), dict(sites)


@pytest.mark.parametrize("unique", [True, False],
                         ids=["single_token", "shared_table"])
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_a_step_holds_each_kernel_once_and_calls_it_a_layer(name, unique):
    cfg, text = step_program(name, unique=unique)
    modules, sites = kernel_census(text)
    assert set(modules) == KERNELS[name][not unique]
    # a model of mixed windows has two signatures of each K/V kernel:
    # the full layers' pool and window 0, the rings' pool and the window
    routed = (cfg.layer_types.count("experts") if cfg.mixer_only
              else cfg.n_layers)
    layers = {k: cfg.n_kv_layers if k in KV_KERNELS
              else routed if k.startswith("expert_stream")
              else cfg.n_state_layers for k in modules}
    signatures = {k: 1 + (cfg.mixed_windows and k in KV_KERNELS)
                  for k in modules}
    # a layer that reads ANOTHER layer's pool walks it once more and
    # writes nothing: one more site of the walk, and one more signature
    # where the owners' walk carries their write under the same name
    readers = cfg.n_kv_reader_layers
    if readers:
        layers["paged_decode_grid"] += readers
        signatures["paged_decode_grid"] += (
            unique and "paged_decode_fused" not in modules)
    assert modules == signatures
    assert sites == layers
    assert all(n > 1 for n in layers.values())


def test_a_programs_kernel_traces_are_on_its_span_and_in_per_program():
    """`warmup.program` carries `kernel_traces`, the kernel bodies the
    program traced: 1 for a four-layer model's single-token program
    (un-jitted it would be 4), 2 for its shared-table program (the write
    and the walk)."""
    cfg = T.TransformerConfig(
        vocab_size=128, max_seq=512, n_layers=4, n_heads=4, n_kv_heads=2,
        d_model=512, d_ff=256, use_flash=False)
    with pallas.interpret_kernels():
        eng = init_inference(T.init(cfg, jax.random.PRNGKey(0)), cfg, dict(
            max_seq_len=256, kv_block_size=128, num_kv_blocks=9,
            max_batch_size=8, decode_impl="pallas"), dtype=jnp.float32)
        for body in ("_decode_fused", "_kv_write", "_attend_live_blocks"):
            getattr(pallas.paged_attention, body).clear_cache()
        profiler.clear()
        out = eng.warmup(widths=[8], footprint=False)
    decode = [p for p in out["per_program"] if p["kind"] == "decode"]
    assert [(p["unique"], p["kernel_traces"]) for p in decode] == [
        (1, 1), (0, 2)]
    spans = [s for s in profiler.spans() if s.name == "warmup.program"
             and s.ids["kind"] == "decode"]
    assert [s.ids["kernel_traces"] for s in spans] == [1, 2]
    # a program that calls no kernel traced none
    assert all(p["kernel_traces"] == 0 for p in out["per_program"]
               if p["kind"] != "decode")
