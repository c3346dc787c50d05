"""Share of the HBM roofline a HELD share of ungated experts reaches:
the bytes the held experts NEED to stream once a routed layer (two
matrices each at the PUBLISHED widths, whatever the program pads them
to, plus the tokens in and out) over the HBM peak, over the device time
of the kernel `expert_stream_ungated` per shared-table program. By NEED
the pass is bound by its bytes: a held expert has 256 x 6 / 128 = 12
real rows an iteration, 12 x 4 E F operations beside 2 E F x 2 bytes.
The pass AS WRITTEN multiplies every row by every held expert, 0.83 ms
of MXU a layer at 256 rows beside 0.78 ms of stream at the published
widths, and streams F padded from 1,856 to 1,920 (3.4% more bytes than
counted here): so this share cannot pass ~94% while the pass is written
so, and what it leaves is the room a pass that multiplies an expert's
own pairs alone would have. Tokens an iteration are the scheduler's
count (`moe_token_expert_pairs`); the held pairs are the EXPECTATION
`moe_held_pairs_expected_per_step` reads (the operations are printed,
not taken into the share). None for a configuration without
`hybrid_override_pattern` or a program without the kernel."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_ms = harness.load_module(_here.with_name("ungated_experts_ms_per_step.py"))
_pairs = harness.load_module(
    _here.with_name("moe_held_pairs_expected_per_step.py"))
_shapes = harness.load_module(_here.parents[1] / "kernels" / "nemotron_h.py")


def read(obs):
    ms = _ms.kernel_ms(obs)
    d = obs.get("counters_delta") or {}
    hf = obs.get("hf") or {}
    pairs = _pairs.expected(obs)
    if ms is None or pairs is None or not obs.get("peaks") \
            or "hybrid_override_pattern" not in hf:
        return None
    tokens = d["moe_token_expert_pairs"] / d["steps"] / hf["num_experts_per_tok"]
    need = _shapes.ungated_held_experts_flops_and_bytes(hf, tokens, pairs)
    layers = _shapes.layer_counts(hf)["routed"]
    by_bytes = 1e3 * layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    by_flops = 1e3 * layers * need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    written = 1e3 * layers * 4.0 * hf["hidden_size"] * hf[
        "moe_intermediate_size"] * _shapes.held(hf) * tokens / obs["peaks"][
            "bf16_flops_per_s"]
    print(f"[bench] ungated held experts: {tokens:.1f} tokens an iteration "
          f"in {layers} layers, {by_bytes:.3f} ms of stream needed "
          f"(operations needed {by_flops:.3f} ms for {pairs:.0f} expected "
          f"pairs; the pass as written multiplies every row by every held "
          f"expert, {written:.3f} ms of MXU) vs {ms:.3f} ms taken",
          flush=True)
    return 100.0 * by_bytes / ms
