"""Share of the traced window's decode-program executions that ran
the shared-table program (`paged_decode_grid`: an iteration with at
least one multi-token prefill chunk) and not the single-token one
(`paged_decode_fused`)."""

from benchmarks.trace import reduce as R


def read(obs):
    td = obs.get("trace")
    if td is None:
        return None
    mixed = len(R.modules_with(td, "paged_decode_grid"))
    single = len(R.modules_with(td, "paged_decode_fused"))
    if mixed + single == 0:
        return None
    return 100.0 * mixed / (mixed + single)
