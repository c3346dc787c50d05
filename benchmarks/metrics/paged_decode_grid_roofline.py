"""Share of its (memory-bound) roofline the shared-table attention
kernel reaches: the KV bytes the contexts NEED per iteration (mean over
the traced window's ticks of the running sequences' summed context, x K
and V bytes per token over all layers: each sequence's cache read once,
however many rows it has) over HBM bandwidth, over the kernel's device
time per iteration. Compute is far below the memory bound at a few
query tokens per sequence. Sequences still in prefill are left out of
the need (under 2% of the context in the chat mix), so the share reads
a little low."""

from benchmarks.trace import reduce as R

KERNEL = "paged_decode_grid"


def read(obs):
    td = obs.get("trace")
    ticks = obs.get("ticks") or []
    if td is None or not ticks or not obs.get("peaks"):
        return None
    s = R.kernel_seconds(td, (KERNEL,))
    n = len(R.modules_with(td, KERNEL))
    if s is None or not n:
        return None
    mean_ctx = sum(t[1] for t in ticks) / len(ticks)
    need_s = mean_ctx * obs["kv_bytes_per_token"] / obs["peaks"]["hbm_bytes_per_s"]
    print(f"[bench] {KERNEL}: memory-bound; mean summed context "
          f"{mean_ctx:.0f} tokens, {need_s * 1e3:.3f} ms needed vs "
          f"{s / n * 1e3:.3f} ms taken per iteration", flush=True)
    return 100.0 * need_s / (s / n)
