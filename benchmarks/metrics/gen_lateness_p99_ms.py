"""99th percentile of (actual submit time - due time) over the
window's requests: a starved generator must not read as a fast
server."""

import numpy as np


def read(obs):
    xs = obs.get("lateness_s") or []
    return 1e3 * float(np.percentile(xs, 99)) if len(xs) else None
