"""Share of its roofline the K/V walk reaches at 30 KV heads of 128 with
ONE query head a KV head (an Olmo-Hybrid model's full-attention layers:
15,360 B of K/V a token a layer): the larger of the K/V bytes the
running contexts NEED (the mean over the traced window's ticks of the
running sequences' summed context x the bytes a token a layer x the
attention layers: each sequence's cache read once) over the HBM peak
and the rows' score and value operations (the scheduler's
`kv_live_blocks` x block size bounds the cached tokens summed over rows
from above) over the bf16 peak, over the device time of the
shared-table attention kernel (`paged_decode_grid`) per shared-table
program. None unless the configuration is of the family
(`gdn_state_roofline.of_family`). Sequences still in prefill are left
out of the bytes, so a memory-bound reading is a little low; it prints
which bound and the kernel's time."""

import pathlib

from benchmarks import harness
from benchmarks.trace import reduce as R

_here = pathlib.Path(__file__).resolve()
_shapes = harness.load_module(_here.parents[1] / "kernels" / "olmo_hybrid.py")
_family = harness.load_module(_here.with_name("gdn_state_roofline.py"))
KERNEL = "paged_decode_grid"


def read(obs):
    td = obs.get("trace")
    hf = obs.get("hf") or {}
    d = obs.get("counters_delta") or {}
    ticks = obs.get("ticks") or []
    if td is None or not _family.of_family(hf) or not obs.get("peaks") \
            or not ticks or not d.get("steps"):
        return None
    s = R.kernel_seconds(td, (KERNEL,))
    n = len(R.modules_with(td, KERNEL))
    if s is None or not n:
        return None
    ms = 1e3 * s / n
    table_tokens = sum(t[1] for t in ticks) / len(ticks)
    block = hf["serve"]["engine"]["kv_block_size"]
    row_tokens = d.get("kv_live_blocks", 0) / d["steps"] * block
    need = _shapes.attention_flops_and_bytes(hf, table_tokens, row_tokens)
    layers = _shapes.layer_counts(hf)["attention"]
    by_bytes = 1e3 * layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    by_flops = 1e3 * layers * need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    print(f"[bench] walk at {hf['num_key_value_heads']} KV heads: "
          f"{'memory' if by_bytes >= by_flops else 'compute'}-bound; "
          f"{table_tokens:.0f} cached tokens a table-read in {layers} layers, "
          f"{max(by_bytes, by_flops):.3f} ms needed (bytes {by_bytes:.3f}, "
          f"operations at most {by_flops:.3f}) vs {ms:.3f} ms taken",
          flush=True)
    return 100.0 * max(by_bytes, by_flops) / ms
