"""Device time of `paged_decode_grid` + `paged_kv_write` (all layers)
per shared-table (chunked-prefill) iteration of the traced window."""

from benchmarks.trace import reduce as R


def read(obs):
    td = obs.get("trace")
    if td is None:
        return None
    n = len(R.modules_with(td, "paged_decode_grid"))
    if not n:
        return None
    grid = R.kernel_seconds(td, ("paged_decode_grid",)) or 0.0
    # kv_write also runs in programs without the grid kernel (none in
    # chunked mode); count only what ran inside shared-table programs
    mods = R.modules_with(td, "paged_decode_grid")
    write = sum(e.dur for e in R.in_window(td.ops.get(0, []), td.window)
                if "paged_kv_write" in e.name
                and any(m.start <= e.start < m.end for m in mods))
    return 1e3 * (grid + write) / n
