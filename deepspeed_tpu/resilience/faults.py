"""Deterministic fault injection: the chaos half of the self-healing
serving fleet (docs/fault_tolerance.md).

Faults in production arrive from the environment — a preempted VM, a
flaky NIC, a crashed writer — which makes every recovery path the least
tested code in the system. This module inverts that: recovery paths are
driven by a seeded, REPLAYABLE `FaultPlan` injected at named **fault
points** compiled into the real code paths (router dispatch, KV
handoff, checkpoint commit, offload I/O, heartbeats), so CI exercises
replica death, handoff failure, stragglers, and crash-consistent
checkpoint recovery deterministically (scripts/ds_gate.py chaos; the
Varuna/Bamboo-class preemption-tolerance posture, PAPERS).

Design constraints:

- **zero overhead disarmed**: a fault point is one module-global
  ``None`` check when no plan is armed — safe to leave in per-step hot
  paths forever.
- **deterministic**: a spec fires on the Nth *matching* invocation of
  its point (`at`), for `times` consecutive matches (-1 = forever).
  No wall clocks, no RNG in the trigger path; the plan's `seed` only
  drives payload choices (which byte to corrupt). Same plan + same
  workload = same failure schedule, replica for replica.
- **typed failures**: injected errors subclass `InjectedFault` so
  recovery code can assert it healed an *injected* fault, and so a
  stray injection outside a chaos lane is attributable in one grep.

The registry of fault points compiled into the tree lives in the
module constant ``FAULT_POINTS`` below — one entry per point with its
ctx keys, source site, and failure meaning. That constant is the
SINGLE authority: ``registered_points()`` exposes the names, the
lifecycle analyzer (analysis/lifecycle.py, L003) audits committed
chaos plans against it, and docs/fault_tolerance.md renders its
registry table from ``registry_markdown_table()`` (a docs-drift test
pins the rendered table to the file).

kind='corrupt' payloads: `corrupt_file` flips raw bytes of a file on
disk (checkpoint bitrot); the three in-memory points above flip bits
of the leaf's ACTUAL dtype via resilience/integrity.py, keyed on
(plan seed, matching invocation, leaf path) — same plan + same
workload = same flips (the FaultAction carries `seed` and
`invocation` for exactly this).
"""

import contextlib
import dataclasses
import json
import os
import threading
from typing import Any, Dict, List, Optional, Union

__all__ = [
    "FaultPlan", "FaultSpec", "FaultAction", "fault_point", "arm",
    "disarm", "armed", "active_plan", "corrupt_file",
    "FAULT_POINTS", "registered_points", "registry_markdown_table",
    "InjectedFault", "ReplicaDeadError", "HandoffError",
    "InjectedIOError", "CheckpointCrashError", "RankPreemptedError",
]


class InjectedFault(RuntimeError):
    """Base of every injected failure (grep-able provenance)."""


class ReplicaDeadError(InjectedFault):
    """A serving replica died mid-step (device gone)."""


class HandoffError(InjectedFault):
    """A KV block transfer (export/import) failed."""


class InjectedIOError(InjectedFault, OSError):
    """A transient storage-layer I/O failure (retry-able)."""


class CheckpointCrashError(InjectedFault):
    """Process crash inside the checkpoint commit window."""


class RankPreemptedError(InjectedFault):
    """A training rank's host was preempted mid-run (the VM is gone;
    its HBM-resident shards with it). The spec's `value` names the
    preempted logical rank — read it off the raised error's `.spec`."""


_ERRORS = {
    "replica_dead": ReplicaDeadError,
    "handoff": HandoffError,
    "io": InjectedIOError,
    "ckpt_crash": CheckpointCrashError,
    "preempted": RankPreemptedError,
    "generic": InjectedFault,
}

_KINDS = ("raise", "delay", "skip", "corrupt")

#: The fault-point registry: every point name fault_point() is called
#: with anywhere in the tree, mapped to the ctx keys its call site
#: passes, the source site, and the failure meaning. Kept a PURE dict
#: literal so static passes (analysis/lifecycle.py L003) can read it
#: with ast.literal_eval without importing this module; registering a
#: new point here without a committed chaos lane that fires it — or
#: calling fault_point() with a name missing here — is an L003 red.
FAULT_POINTS = {
    "scheduler.step": {
        "ctx": ("replica",),
        "site": "inference/scheduler.py `step()`",
        "meaning": ("raise = replica death mid-decode (before "
                    "dispatch, so requeue is safe); delay = straggler "
                    "(accrues to `scheduler.fault_delay_s`)"),
    },
    "engine.step": {
        "ctx": ("rank", "step"),
        "site": "runtime/engine.py `_dispatch_step` entry",
        "meaning": ("raise `preempted` (spec `value` = the lost "
                    "logical rank) = host preempted mid-run, BEFORE "
                    "any state mutates — the elastic trainer "
                    "reconstructs from peer shards; delay = training "
                    "straggler (accrues to `engine.fault_delay_s`, "
                    "flags in the monitor feed)"),
    },
    "comm.collective": {
        "ctx": ("op", "group"),
        "site": "comm/comm.py guarded barrier / broadcast_host",
        "meaning": ("raise `io` = transient control-plane failure "
                    "(bounded retry heals); delay >= the "
                    "`DS_COMM_TIMEOUT_S` deadline = deterministic "
                    "`CollectiveTimeoutError` verdict without a real "
                    "hang"),
    },
    "pipe.permute": {
        "ctx": ("stage", "step"),
        "site": ("comm/comm.py `pipe_permute_tick`, once per stage "
                 "before every pipelined dispatch"),
        "meaning": ("the host-side representative of the step's "
                    "stage-boundary collective-permute ring "
                    "(docs/pipeline.md): raise `io` = transient "
                    "boundary-link failure (bounded retry heals); "
                    "delay < the deadline = a slow stage link charged "
                    "to that stage's skew counter "
                    "(`engine.pipe_stage_delay_s`, surfaced by "
                    "`monitor.training_events`); delay >= the "
                    "deadline = a wedged stage peer (deterministic "
                    "`CollectiveTimeoutError`)"),
    },
    "dataloader.fetch": {
        "ctx": ("epoch", "index"),
        "site": "runtime/dataloader.py, before the position advances",
        "meaning": ("raise `io` = transient batch-fetch failure (a "
                    "retry re-fetches the SAME batch — loader state "
                    "stays clean)"),
    },
    "elastic.launch": {
        "ctx": ("generation", "world"),
        "site": "elasticity/agent.py `_launch_generation`",
        "meaning": ("raise `io` = the relaunch itself fails; the "
                    "supervisor counts the burned generation and "
                    "keeps shrinking"),
    },
    "elastic.generation": {
        "ctx": ("generation", "world"),
        "site": "elasticity/trainer.py engine rebuild",
        "meaning": "raise = an in-process generation bump fails",
    },
    "engine.export_kv": {
        "ctx": ("uid",),
        "site": "inference/engine.py",
        "meaning": ("raise = handoff export failure; delay = hung "
                    "transfer (sleeps, trips `handoff_timeout_s`)"),
    },
    "engine.import_kv": {
        "ctx": ("uid",),
        "site": "inference/engine.py",
        "meaning": ("raise = handoff import failure (adopt cleans up "
                    "+ falls back)"),
    },
    "router.probe": {
        "ctx": ("replica",),
        "site": "inference/router.py `_probe_replica`",
        "meaning": ("raise = half-open probe fails (replica still "
                    "bad)"),
    },
    "checkpoint.save": {
        "ctx": ("tag",),
        "site": "runtime/checkpoint.py orbax write",
        "meaning": ("raise `io` = transient storage error (save retry "
                    "heals)"),
    },
    "checkpoint.commit": {
        "ctx": ("tag",),
        "site": "runtime/checkpoint.py commit window",
        "meaning": ("raise `ckpt_crash` = crash with state durable "
                    "but unmarked"),
    },
    "checkpoint.corrupt": {
        "ctx": ("tag", "dir"),
        "site": "runtime/checkpoint.py post-commit",
        "meaning": "`corrupt` = bitrot in the largest state file",
    },
    "offload.io": {
        "ctx": ("what",),
        "site": "inference/offload_store.py `_io_retry`",
        "meaning": ("raise `io` = transient NVMe error (bounded retry "
                    "heals; persistent surfaces)"),
    },
    "spill.io": {
        "ctx": ("op", "key"),
        "site": "inference/offload_store.py `HostKvSpillStore.put/get`",
        "meaning": ("raise `io` on `op='put'` = the spill export is "
                    "lost (the victim falls back to "
                    "flush-and-recompute); on `op='get'` = the resume "
                    "readback dies (same fallback — the entry is "
                    "dropped first so the byte budget never wedges)"),
    },
    "heartbeat.beat": {
        "ctx": ("rank",),
        "site": "elasticity/agent.py",
        "meaning": ("`skip` = alive-but-wedged controller (staleness "
                    "detection fires)"),
    },
    "engine.grads": {
        "ctx": ("rank", "step"),
        "site": ("runtime/engine.py `_dispatch_step` exit (post-step, "
                 "pre-commit)"),
        "meaning": ("`corrupt` = a silent bit flip in the gradient "
                    "path: exponent bits flip in the step's "
                    "loss/grad-norm readout AND one just-updated "
                    "state leaf; the guardian's anomaly window must "
                    "veto before commit"),
    },
    "mirror.payload": {
        "ctx": ("step", "holder", "owner"),
        "site": ("resilience/redundancy.py `snapshot`, once per "
                 "mirror entry"),
        "meaning": ("`corrupt` = a DRAM flip in that holder's copy of "
                    "the owner's shard slice; the digest envelope "
                    "catches it at `reconstruct` and falls over to "
                    "the next holder"),
    },
    "handoff.payload": {
        "ctx": ("uid",),
        "site": "inference/engine.py `import_kv`, pre-verification",
        "meaning": ("`corrupt` = an in-transit flip in the K/V page "
                    "stacks; digest verification raises "
                    "`HandoffIntegrityError` and the router "
                    "recomputes token-identically (spill resumes ride "
                    "the same import path, so this point also models "
                    "a flip while a spilled payload sat in host "
                    "DRAM)"),
    },
    "replica.spinup": {
        "ctx": ("replica", "phase"),
        "site": ("inference/router.py `add_replica` (phase 'build' "
                 "before scheduler construction, 'join' after warmup "
                 "+ warm boot)"),
        "meaning": ("raise = the replica died mid-scale-up: the "
                    "attempt is BURNED (counter, no id consumed) and "
                    "the autoscaler retries with exponential "
                    "backoff"),
    },
    "replica.drain": {
        "ctx": ("replica",),
        "site": ("inference/router.py `drain_replica`, BEFORE any "
                 "state mutates"),
        "meaning": ("raise = the drain rejected at entry; the replica "
                    "keeps serving untouched"),
    },
}


def registered_points() -> tuple:
    """Sorted names of every registered fault point — the coverage
    universe the L003 audit (analysis/lifecycle.py) checks committed
    chaos lanes against."""
    return tuple(sorted(FAULT_POINTS))


def registry_markdown_table() -> str:
    """The docs/fault_tolerance.md fault-point registry table,
    rendered from FAULT_POINTS so the docs cannot drift from the code
    (tests/test_lifecycle.py pins the doc to this output)."""
    lines = ["| point | ctx | site | meaning |", "|---|---|---|---|"]
    for name, info in FAULT_POINTS.items():
        ctx = ", ".join(f"`{k}`" for k in info["ctx"])
        lines.append(
            f"| `{name}` | {ctx} | {info['site']} | {info['meaning']} |")
    return "\n".join(lines)


@dataclasses.dataclass
class FaultSpec:
    """One deterministic failure rule.

    point: fault-point name (registry in the module docstring).
    kind:  'raise' (throw `error`), 'delay' (hand `value` seconds to
           the call site), 'skip' (suppress the guarded action),
           'corrupt' (call site mutates bytes via corrupt_file).
    where: ctx filters — every key must equal the call site's ctx for
           the invocation to count as a match.
    at:    fire from the at-th matching invocation (1-based).
    times: for how many consecutive matches (-1 = forever)."""

    point: str
    kind: str = "raise"
    error: str = "generic"
    value: float = 0.0
    where: Dict[str, Any] = dataclasses.field(default_factory=dict)
    at: int = 1
    times: int = 1
    note: str = ""

    def __post_init__(self):
        if self.kind not in _KINDS:
            raise ValueError(f"unknown fault kind '{self.kind}' "
                             f"(expected one of {_KINDS})")
        if self.kind == "raise" and self.error not in _ERRORS:
            raise ValueError(f"unknown error '{self.error}' "
                             f"(expected one of {sorted(_ERRORS)})")
        if self.at < 1:
            raise ValueError("at is 1-based and must be >= 1")


class FaultAction:
    """Non-raising verdict of a fault point: kind + value + the spec,
    plus the plan `seed` and the 1-based matching `invocation` count —
    the (seed, invocation) pair keys kind='corrupt' call sites'
    deterministic bit flips (resilience/integrity.py)."""

    __slots__ = ("kind", "value", "spec", "seed", "invocation")

    def __init__(self, kind: str, value: float, spec: FaultSpec,
                 seed: int = 0, invocation: int = 1):
        self.kind = kind
        self.value = value
        self.spec = spec
        self.seed = int(seed)
        self.invocation = int(invocation)

    def __repr__(self):  # pragma: no cover - debug aid
        return f"FaultAction({self.kind}, {self.value})"


class FaultPlan:
    """A seeded, ordered set of FaultSpecs plus the chaos lane's pass
    budget. Counters live here (not in the specs), so one plan object
    can be reset and replayed."""

    def __init__(self, faults: List[Union[FaultSpec, Dict[str, Any]]],
                 seed: int = 0, budget: Optional[Dict[str, float]] = None,
                 name: str = "chaos"):
        self.name = name
        self.seed = int(seed)
        # chaos-gate budget: min_goodput_ratio (chaos/clean goodput),
        # max_recovery_s (virtual failover->drained), max_token_loss
        self.budget: Dict[str, float] = dict(budget or {})
        self.faults: List[FaultSpec] = [
            f if isinstance(f, FaultSpec) else FaultSpec(**f)
            for f in faults]
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> "FaultPlan":
        # counters + fire-log swap under the lock: reset() races
        # in-flight _hit()s arriving on io_callback threads (a reset
        # between _hit's read-modify-write would resurrect the old
        # counter list; C001, docs/concurrency.md)
        with self._lock:
            self._matched = [0] * len(self.faults)
            self.fired: List[str] = []   # human-readable injection log
        return self

    # -- construction -----------------------------------------------------
    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "FaultPlan":
        return cls(d.get("faults", []), seed=d.get("seed", 0),
                   budget=d.get("budget"), name=d.get("name", "chaos"))

    @classmethod
    def from_json(cls, path_or_text: str) -> "FaultPlan":
        if os.path.exists(path_or_text):
            with open(path_or_text) as f:
                d = json.load(f)
            d.setdefault("name", os.path.basename(path_or_text))
        else:
            d = json.loads(path_or_text)
        return cls.from_dict(d)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name, "seed": self.seed, "budget": self.budget,
            "faults": [dataclasses.asdict(f) for f in self.faults],
        }

    # -- the trigger path -------------------------------------------------
    def _hit(self, point: str, ctx: Dict[str, Any]):
        """One fault-point invocation: count matches, fire what is due.
        A 'raise' spec throws immediately; other kinds return the last
        due FaultAction (None when nothing fires)."""
        act: Optional[FaultAction] = None
        for k, spec in enumerate(self.faults):
            if spec.point != point:
                continue
            if any(ctx.get(key) != want for key, want in spec.where.items()):
                continue
            # count + fire-log under the lock: fault points sit in
            # io_callback paths, so invocations arrive from unordered
            # threads (the offload.io point)
            with self._lock:
                self._matched[k] += 1
                n = self._matched[k]
                due = n >= spec.at and (
                    spec.times < 0 or n < spec.at + spec.times)
                if due:
                    detail = (spec.error if spec.kind == "raise"
                              else f"{spec.value}" if spec.kind == "delay"
                              else spec.kind)
                    self.fired.append(f"{point}#{n}:{spec.kind}:{detail}")
            if not due:
                continue
            if spec.kind == "raise":
                err = _ERRORS[spec.error](
                    f"injected {spec.error} at {point} "
                    f"(matching invocation {n}, plan '{self.name}')")
                # recovery code keys off the spec (e.g. value = the
                # preempted rank for error='preempted')
                err.spec = spec
                raise err
            act = FaultAction(spec.kind, spec.value, spec,
                              seed=self.seed, invocation=n)
        return act


# -- the armed-plan singleton ---------------------------------------------
# One process-global plan: fault points are sprinkled across modules
# that must not know about each other, and chaos runs arm exactly one
# plan at a time (the lane's determinism depends on it).
_ACTIVE: Optional[FaultPlan] = None


def arm(plan: Union[FaultPlan, Dict[str, Any], str]) -> FaultPlan:
    """Arm a plan (FaultPlan | dict | JSON path/text). Returns it."""
    global _ACTIVE
    if isinstance(plan, str):
        plan = FaultPlan.from_json(plan)
    elif isinstance(plan, dict):
        plan = FaultPlan.from_dict(plan)
    _ACTIVE = plan
    return plan


def disarm() -> None:
    global _ACTIVE
    _ACTIVE = None


def active_plan() -> Optional[FaultPlan]:
    return _ACTIVE


@contextlib.contextmanager
def armed(plan: Union[FaultPlan, Dict[str, Any], str]):
    """Scope-bound arming: ``with armed(plan) as p: ...`` — disarms on
    exit even when the injected fault propagates."""
    p = arm(plan)
    try:
        yield p
    finally:
        disarm()


def fault_point(point: str, **ctx) -> Optional[FaultAction]:
    """The injection site. Disarmed: one global read + None check.
    Armed: may raise an InjectedFault subclass, or return a FaultAction
    ('delay'/'skip'/'corrupt') for the call site to interpret."""
    plan = _ACTIVE
    if plan is None:
        return None
    return plan._hit(point, ctx)


def corrupt_file(path: str, seed: int = 0) -> int:
    """Deterministically flip one byte per KiB (min 1) in the middle
    half of a file — the injected-bitrot payload behind
    kind='corrupt'. Returns the number of bytes flipped."""
    import numpy as np

    size = os.path.getsize(path)
    if size == 0:
        return 0
    rng = np.random.default_rng(
        seed ^ int.from_bytes(os.path.basename(path).encode()[:8].ljust(8, b"\0"), "little"))
    n = max(1, size // 1024)
    lo, hi = size // 4, max(size // 4 + 1, 3 * size // 4)
    offsets = sorted(set(int(x) for x in rng.integers(lo, hi, n)))
    with open(path, "r+b") as f:
        for off in offsets:
            f.seek(off)
            b = f.read(1)
            if not b:
                continue
            f.seek(off)
            f.write(bytes([b[0] ^ 0xFF]))
    return len(offsets)
