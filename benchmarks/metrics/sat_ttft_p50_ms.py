"""Median time to first token from the due time ABOVE the knee: it
grows with the queue all through the run. Recorded, never judged."""

import numpy as np


def read(obs):
    xs = obs.get("ttft_s") or []
    return 1e3 * float(np.percentile(xs, 50)) if xs else None
