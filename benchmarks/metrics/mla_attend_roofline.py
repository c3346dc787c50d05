"""Share of its roofline the latent walk reaches: the larger of the
bytes the cached rows NEED (every live row once a table: the mean over
the traced window's ticks of the running sequences' summed context x
1,152 B) over the HBM peak and the operations its rows need (the
scheduler's `mla_cache_tokens` / steps, cached tokens summed over ROWS,
x heads x (576 + 512) x 2) over the bf16 peak, over all latent layers,
over the device time of scope `mla_attend` per shared-table program. It
prints which bound. Sequences still in prefill are left out of the
bytes (a few per cent of the context), so a memory-bound reading is a
little low; `kv_bytes_per_token` of the runner is another family's
arithmetic and is not read."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_shapes = harness.load_module(_here.parents[1] / "kernels" / "mla.py")


def read(obs):
    ms = _moe.per_program_ms(obs, ("mla_attend",))
    d = obs.get("counters_delta") or {}
    ticks = obs.get("ticks") or []
    if ms is None or not obs.get("peaks") or not ticks or not d.get("steps") \
            or not d.get("mla_cache_tokens"):
        return None
    hf = obs["hf"]
    table_tokens = sum(t[1] for t in ticks) / len(ticks)
    row_tokens = d["mla_cache_tokens"] / d["steps"]
    need = _shapes.latent_walk_flops_and_bytes(hf, table_tokens, row_tokens)
    layers = hf["num_hidden_layers"]      # every layer attends
    by_bytes = 1e3 * layers * need["bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    by_flops = 1e3 * layers * need["flops"] / obs["peaks"]["bf16_flops_per_s"]
    print(f"[bench] mla_attend: {'memory' if by_bytes >= by_flops else 'compute'}"
          f"-bound; {table_tokens:.0f} cached tokens a table-read, "
          f"{row_tokens:.0f} summed over rows; {max(by_bytes, by_flops):.3f} ms "
          f"needed (bytes {by_bytes:.3f}, operations {by_flops:.3f}) vs "
          f"{ms:.3f} ms taken", flush=True)
    return 100.0 * max(by_bytes, by_flops) / ms
