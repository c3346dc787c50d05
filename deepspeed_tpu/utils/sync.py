"""The one named host-sync choke point.

Benchmarks and profiling scripts must synchronize with the device at
end-of-run/per-trial boundaries; hot paths must not. ds-lint rule R002
flags raw `jax.block_until_ready`/`jax.device_get` in the engine
step/decode paths — deliberate measurement syncs route through
`host_sync` instead, so every blocking point in the tree is greppable by
one name and auditable in one place.
"""

from typing import Any

import jax
import numpy as np

__all__ = ["host_sync", "serving_readback"]


def host_sync(tree: Any) -> Any:
    """Block until every leaf of `tree` has materialized on device, then
    return it. The allowlisted R002 helper: use at trial/run boundaries
    (comm/bench.py, scripts/profile_*.py), never inside a step loop."""
    return jax.block_until_ready(tree)  # ds-lint: ok R002 the choke point


def serving_readback(x: Any) -> np.ndarray:
    """The serving scheduler's ONE per-iteration host readback: sampled
    token ids ([bucket] or [chunk, bucket] int32) of an in-flight
    dispatch (inference/scheduler.py). R002-allowlisted because the
    loop looks ahead: ServingScheduler.run() issues the readback of
    step N AFTER it has launched step N+1 on N's device-resident
    tokens, in every iteration whose composition allows it (not under
    speculation, the presence bitmap, a mesh, wave or fused parts, a
    reservation that must preempt; step() always reads back first), so
    the device does not idle on it — and what crosses the link is
    token ids, never [batch, vocab] logits. The scheduler's
    `readback_wait_s` is the time spent in here."""
    return np.asarray(jax.device_get(x))  # ds-lint: ok R002 the serving choke point
