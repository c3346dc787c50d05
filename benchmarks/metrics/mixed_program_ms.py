"""Median device duration of the shared-table program that chunked
prefill runs: the XLA Modules events of the traced window that ran
`paged_decode_grid`."""

from benchmarks.trace import reduce as R


def read(obs):
    td = obs.get("trace")
    if td is None:
        return None
    m = R.median([e.dur for e in R.modules_with(td, "paged_decode_grid")])
    return None if m is None else 1e3 * m
