"""Schedule-aware static analysis: comm/compute overlap, hierarchy
placement, and critical-path step-time projection (S007-S009).

The cost model (costmodel.py S004-S006) treats a compiled program as
three independent totals — flops, HBM bytes, collective bytes — so it
cannot see the two effects that dominate step time at pod scale: a
collective that serializes against compute it could have overlapped
with, and a replica group that straddles the slow DCN tier when a
two-stage hierarchical decomposition would keep the bulk on ICI. Both
are SCHEDULE properties of the compiled artifact: post-scheduling HLO
text order is the schedule (`is_scheduled=true`), async collectives
carry explicit `-start`/`-done` windows, and def-use edges say where a
synchronous collective's first consumer actually lands. This module
parses that structure (profiling/hlo.py parse_hlo_computations) once
per program and derives three checks, in the same
findings-ride-the-sanitizer-report discipline as the rest of
`analysis/`:

  S007  check_exposed_comm        — exposed-collective time: comm on
        the schedule that independent compute could hide (an async
        window too small, or a synchronous collective whose first
        consumer is scheduled far later) exceeds the reporting floor;
        regression form vs a captured baseline.
  S008  check_hierarchy_placement — a collective's replica groups
        straddle slice boundaries of a pod topology while keeping
        >= min_slice_degree members per slice: a
        reduce-scatter-within-slice + all-reduce-across-slices
        decomposition would cut DCN bytes by the slice degree.
  S009  check_step_time           — the critical-path step-time
        projection (serial roofline compute/HBM leg + exposed comm,
        replacing the three-leg SUM) is comm-dominated, or drifted
        beyond tolerance against a captured baseline. The projection
        itself is the AOT score autotuning/autotuner.py ranks candidate
        configs with before any trial execution.

Baselines persist to SCHEDULE.json (scripts/ds_gate.py schedule --capture /
--check, the tier-1 pre-test gate next to ds_lint/ds_budget/
ds_numerics). All bandwidth constants come from the single authority
platform/accelerator.LINKS.
"""

import dataclasses
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..platform.accelerator import LINKS
from ..profiling.hlo import (
    parse_hlo_computations,
    parse_replica_groups,
    parse_source_target_pairs,
)
from .report import Finding, SanitizerReport

__all__ = [
    "PodTopology",
    "CollectiveNode",
    "ScheduleAnalysis",
    "analyze_schedule",
    "analyze_compiled",
    "check_exposed_comm",
    "check_hierarchy_placement",
    "check_step_time",
]

# collective base kinds the DAG tracks (the -start/-done async forms
# pair up; `async-start` is the generic wrapper whose payload lives in
# its called computation)
_COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)
# bytes each device moves per payload byte over a ring of g members:
# all-reduce = reduce-scatter + all-gather (2 passes); pt2pt ops move
# the payload once regardless of group size
_RING_FACTORS = {
    "all-reduce": lambda g: 2.0 * (g - 1) / g if g > 1 else 0.0,
    "all-gather": lambda g: (g - 1) / g if g > 1 else 0.0,
    "reduce-scatter": lambda g: (g - 1) / g if g > 1 else 0.0,
    "all-to-all": lambda g: (g - 1) / g if g > 1 else 0.0,
    "collective-permute": lambda g: 1.0,
    "collective-broadcast": lambda g: 1.0,
}
# ops that carry no execution cost of their own: control/bookkeeping,
# plus call sites whose cost lives in their called computation's body
# (fusion/while/call bodies are weighed once, like collective counts)
_ZERO_COST_OPS = frozenset((
    "parameter", "constant", "get-tuple-element", "tuple", "bitcast",
    "after-all", "partition-id", "replica-id", "domain",
    "opt-barrier", "optimization-barrier",
    "fusion", "while", "call", "conditional", "custom-call-start",
    "async-start", "async-update", "async-done",
))


@dataclasses.dataclass(frozen=True)
class PodTopology:
    """A candidate pod layout for hierarchy classification: devices
    [0, slice_devices) form slice 0, the next slice_devices slice 1,
    ... (flat device ids in device-assignment order — jax lays the
    DCN-spanning mesh axis outermost, so contiguous blocks ARE
    slices). num_slices=0 derives the slice count from the program's
    device count."""

    slice_devices: int
    num_slices: int = 0
    ici_bandwidth: float = LINKS["ici_bytes_per_s"]
    dcn_bandwidth: float = LINKS["dcn_bytes_per_s"]
    # reporting floor: a straddling collective only surfaces when the
    # hierarchical decomposition would save at least this much DCN time
    # per step — the scalar loss/grad-norm all-reduces every step
    # carries are world-spanning by design and cost nanoseconds
    min_saving_us: float = 50.0

    def slice_of(self, device_id: int) -> int:
        return device_id // max(1, self.slice_devices)


@dataclasses.dataclass
class CollectiveNode:
    """One collective in the schedule, with its overlap accounting."""

    name: str
    op: str                       # base kind (start/done collapsed)
    computation: str
    payload_bytes: int
    group_size: int               # 0 = flat world group
    groups: List[List[int]] = dataclasses.field(default_factory=list)
    pairs: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    is_async: bool = False
    t_comm_s: float = 0.0         # ring-model wire time (ICI)
    overlap_s: float = 0.0        # compute inside the async window
    exposed_s: float = 0.0        # max(0, t_comm - overlap)
    slack_s: float = 0.0          # compute between issue and first
                                  # consumer — what a serialized
                                  # collective COULD have hidden behind

    def effective_group(self, n_devices: int) -> int:
        """Ring size the wire-time model uses: the stated group size
        (1-member identity groups carry no payload — shard_map's
        manual-axis machinery emits them), or the flat world when the
        group is unstated."""
        if self.group_size >= 1:
            return self.group_size
        return max(2, n_devices)


@dataclasses.dataclass
class ScheduleAnalysis:
    """Schedule profile of ONE compiled program (per-device view)."""

    label: str
    n_devices: int = 1
    t_compute_s: float = 0.0      # max(flops/peak, bytes/hbm_bw)
    t_comm_s: float = 0.0         # sum of ring-model wire times
    exposed_s: float = 0.0        # schedule-aware exposed comm
    slack_s: float = 0.0          # hideable-but-serialized total
    n_async: int = 0
    n_sync: int = 0
    collectives: List[CollectiveNode] = dataclasses.field(
        default_factory=list)

    @property
    def step_time_s(self) -> float:
        """The S009 critical-path projection: the serial roofline leg
        (compute and HBM overlap on-chip — max, not sum) plus only the
        comm the schedule EXPOSES. Replaces summing all three legs."""
        return self.t_compute_s + self.exposed_s

    @property
    def n_collectives(self) -> int:
        return len(self.collectives)

    @property
    def exposed_comm_fraction(self) -> float:
        """Exposed share of total wire time, in [0, 1] — the quantity
        the overlap gate budgets (SCHEDULE.json `overlap` pins): 0
        means the schedule hides every collective, 1 means fully
        serialized comm."""
        return self.exposed_s / self.t_comm_s if self.t_comm_s > 0 else 0.0

    @property
    def n_hidden_sync(self) -> int:
        """Sync collectives the slack credit fully hides (wire time
        > 0, zero exposure) — the overlap layer's scoreboard."""
        return sum(1 for c in self.collectives
                   if not c.is_async and c.t_comm_s > 0
                   and c.exposed_s == 0.0)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "n_devices": self.n_devices,
            "n_collectives": self.n_collectives,
            "n_async": self.n_async,
            "n_sync": self.n_sync,
            "n_hidden_sync": self.n_hidden_sync,
            "compute_us": self.t_compute_s * 1e6,
            "comm_us": self.t_comm_s * 1e6,
            "exposed_us": self.exposed_s * 1e6,
            "slack_us": self.slack_s * 1e6,
            "step_time_us": self.step_time_s * 1e6,
            "exposed_comm_fraction": self.exposed_comm_fraction,
        }


def _base_op(op: str) -> Optional[str]:
    for base in _COLLECTIVE_OPS:
        if op == base or op == base + "-start":
            return base
    return None


def _window_cost(weights: List[float], prefix: List[float],
                 lo: int, hi: int) -> float:
    """Sum of instruction weights at positions [lo, hi) (clamped)."""
    lo = max(0, min(lo, len(weights)))
    hi = max(0, min(hi, len(weights)))
    if hi <= lo:
        return 0.0
    return prefix[hi] - prefix[lo]


# ops that FORWARD a value without executing on it: a consumer of this
# kind does not end a collective's slack window — the window runs on to
# the first consumer that does real work. optimization_barrier is the
# load-bearing member: the overlap layer (runtime/overlap.py
# bucketed_apply, runtime/pipe.py) orders a collective against compute
# with a barrier, and the barrier must not read as the collective's
# "consumer" or every such collective would measure zero slack. (A
# projection: on the chip a barrier's outputs wait for ALL its inputs,
# so the slack credited across one is not paid out — docs/overlap.md.)
_TUPLING_OPS = frozenset(("tuple", "opt-barrier", "optimization-barrier"))

# layout/dtype packaging: ops (and all-packaging fusions) that XLA's
# TPU pipeline fuses into the eventual consumer — a convert or copy
# sitting right after an all-gather does not anchor the gather's
# schedule position, so consumer search traces through them
_PACKAGING_OPS = frozenset((
    "parameter", "constant", "iota", "convert", "copy", "bitcast",
    "reshape", "transpose", "slice", "dynamic-slice", "broadcast",
    "tuple", "get-tuple-element", "pad", "reverse",
))

_GTE_INDEX_RE = re.compile(r"index=(\d+)")


def _gte_index(ins: Dict[str, Any]) -> Optional[int]:
    m = _GTE_INDEX_RE.search(ins.get("attrs") or "")
    return int(m.group(1)) if m else None


def _first_real_consumer(instrs: List[Dict[str, Any]], pos: int,
                         passthru=None) -> int:
    """Schedule position of the first instruction after `pos` that
    consumes instrs[pos]'s value and is not a zero-cost forwarder.
    Forwarding is traced with tuple-position awareness: a barrier/tuple
    packing the value tracks WHICH elements hold it, and a
    get-tuple-element extracting a different element is neither a
    consumer nor a forwarder — so a pinned gather's window is not
    cut short by the sibling value its barrier orders it against.
    Returns len(instrs) when the value is only carried out of the
    computation (root tuple) — the window then spans the rest of the
    schedule."""
    # tracked name -> None (whole value) | set of tuple indices holding it
    tracked: Dict[str, Optional[set]] = {instrs[pos]["name"]: None}
    for p in range(pos + 1, len(instrs)):
        ins = instrs[p]
        ops = ins["operands"]
        hits = [o for o in ops if o in tracked]
        if not hits:
            continue
        op = ins["op"]
        if op in _TUPLING_OPS:
            idxs = {i for i, o in enumerate(ops) if o in tracked}
            prev = tracked.get(ins["name"])
            tracked[ins["name"]] = (None if prev is None
                                    and ins["name"] in tracked
                                    else idxs | (prev or set()))
            continue
        if op == "get-tuple-element":
            src_idx = tracked[hits[0]]
            k = _gte_index(ins)
            if src_idx is None or k is None or k in src_idx:
                tracked[ins["name"]] = None
            continue
        if op == "bitcast":
            tracked[ins["name"]] = tracked[hits[0]]
            continue
        if passthru is not None and passthru(ins):
            tracked[ins["name"]] = None
            continue
        return p
    return len(instrs)


def analyze_schedule(
    hlo_text: str,
    flops: float = 0.0,
    bytes_accessed: float = 0.0,
    peak_flops: float = 1.0,
    hbm_bandwidth: float = 1.0,
    ici_bandwidth: Optional[float] = None,
    n_devices: int = 1,
    label: str = "program",
    hide_sync_slack: bool = True,
) -> ScheduleAnalysis:
    """Parse one compiled module's schedule into a ScheduleAnalysis.

    Per-instruction compute cost is the program's roofline node time
    max(flops/peak, bytes_accessed/hbm_bw) distributed over instruction
    result bytes (per-instruction flop counts are not in the artifact;
    byte weight is the stable proxy, and only RATIOS inside a window
    matter for overlap accounting). Collective wire time is the ring
    model over the replica-group size at `ici_bandwidth` (the LINKS
    authority). Async `-start`/`-done` pairs get their achieved overlap
    from the compute scheduled inside the window; a synchronous
    collective's `slack` — compute between it and its first real
    consumer (forwarding tuples/GTEs/barriers traced through) — is
    what S007 reports as hideable.

    hide_sync_slack=True (the default) additionally CREDITS that slack
    as achieved overlap, min(slack, wire time) per sync collective: the
    static projection of XLA's TPU latency-hiding scheduler, which
    converts a sync collective into an async start/done pair spanning
    to its first consumer. The CPU test backend compiles every
    collective synchronous, so without this credit no source-level
    scheduling change is measurable. hide_sync_slack=False models
    serialized execution (every sync collective fully exposed) — the
    engine maps `zero_optimization.overlap_comm: false` onto it, and
    ds_schedule commits the pair as the overlap-on/overlap-off twin
    pins (docs/overlap.md)."""
    ici_bw = (LINKS["ici_bytes_per_s"] if ici_bandwidth is None
              else float(ici_bandwidth))
    comps, _entry = parse_hlo_computations(hlo_text)
    out = ScheduleAnalysis(label=label, n_devices=max(1, int(n_devices)))
    out.t_compute_s = max(flops / max(peak_flops, 1.0),
                          bytes_accessed / max(hbm_bandwidth, 1.0))

    # one weight list per computation (each body counted once — while
    # trip counts are not static). A fusion's cost is charged to its
    # CALL SITE rather than its body: fused bodies cannot contain
    # collectives, and a heavily-fused while body would otherwise
    # present zero-weight slack windows to the collectives scheduled
    # between its fusion calls. Fusion-body computations are excluded
    # from the normalization total so the cost is not double-counted.
    raw_weight: Dict[str, float] = {}
    fusion_bodies: set = set()
    for cname, instrs in comps.items():
        raw_weight[cname] = sum(
            0.0 if (i["op"] in _ZERO_COST_OPS
                    or _base_op(i["op"]) is not None
                    or i["op"].endswith("-done"))
            else float(i["nbytes"])
            for i in instrs)
        for i in instrs:
            if i["op"] == "fusion":
                fusion_bodies.update(i["called"])
    weight_total = 0.0
    comp_weights: Dict[str, List[float]] = {}
    comp_prefix: Dict[str, List[float]] = {}
    for cname, instrs in comps.items():
        ws = []
        for i in instrs:
            if i["op"] == "fusion":
                ws.append(sum(raw_weight.get(c, 0.0) for c in i["called"]))
            elif (i["op"] in _ZERO_COST_OPS
                  or _base_op(i["op"]) is not None
                  or i["op"].endswith("-done")):
                ws.append(0.0)
            else:
                ws.append(float(i["nbytes"]))
        comp_weights[cname] = ws
        pre = [0.0]
        for w in ws:
            pre.append(pre[-1] + w)
        comp_prefix[cname] = pre
        if cname not in fusion_bodies:
            weight_total += pre[-1]
    unit = (out.t_compute_s / weight_total) if weight_total > 0 else 0.0

    # while-loop bodies: a collective here whose only consumer is the
    # root carry is consumed NEXT iteration — the window XLA's
    # collective pipeliner rotates it across (one full body)
    loop_bodies: set = set()
    for instrs in comps.values():
        for i in instrs:
            if i["op"] == "while":
                loop_bodies.update(i["called"])

    def _packaging(ins: Dict[str, Any]) -> bool:
        op = ins["op"]
        if op in ("convert", "copy"):
            return True
        if op == "fusion":
            return all(j["op"] in _PACKAGING_OPS
                       for c in ins["called"] for j in comps.get(c, ()))
        return False

    for cname, instrs in comps.items():
        ws, pre = comp_weights[cname], comp_prefix[cname]
        for pos, ins in enumerate(instrs):
            base = _base_op(ins["op"])
            if base is None:
                continue
            is_start = ins["op"].endswith("-start")
            payload = int(ins["nbytes"])
            groups = parse_replica_groups(ins["attrs"])
            pairs = parse_source_target_pairs(ins["attrs"])
            g = len(groups[0]) if groups else 0
            node = CollectiveNode(
                name=ins["name"], op=base, computation=cname,
                payload_bytes=payload, group_size=g, groups=groups,
                pairs=pairs, is_async=is_start)
            geff = node.effective_group(out.n_devices)
            node.t_comm_s = (payload * _RING_FACTORS[base](geff)
                             / max(ici_bw, 1.0))
            if is_start:
                # achieved overlap: compute scheduled inside the
                # start..done window
                done = next(
                    (p for p in range(pos + 1, len(instrs))
                     if instrs[p]["op"] in (base + "-done", "async-done")
                     and ins["name"] in instrs[p]["operands"]),
                    len(instrs))
                node.overlap_s = _window_cost(ws, pre, pos + 1,
                                              done) * unit
            else:
                # serialized in the artifact: measure the compute
                # between this collective and its first real consumer —
                # the window the latency-hiding scheduler spans with an
                # async rewrite. hide_sync_slack credits it as achieved
                # overlap; serialized-execution mode leaves it exposed
                cons = _first_real_consumer(instrs, pos, _packaging)
                if cons >= len(instrs) and cname in loop_bodies:
                    # loop-carried (prefetch discipline): spans the
                    # rest of this body plus the next iteration up to
                    # the same slot
                    node.slack_s = (
                        _window_cost(ws, pre, pos + 1, len(instrs))
                        + _window_cost(ws, pre, 0, pos)) * unit
                else:
                    node.slack_s = _window_cost(ws, pre, pos + 1,
                                                cons) * unit
                if hide_sync_slack:
                    node.overlap_s = min(node.slack_s, node.t_comm_s)
            node.exposed_s = max(0.0, node.t_comm_s - node.overlap_s)
            out.collectives.append(node)
            out.t_comm_s += node.t_comm_s
            out.exposed_s += node.exposed_s
            out.slack_s += node.slack_s
            if is_start:
                out.n_async += 1
            else:
                out.n_sync += 1
    return out


def analyze_compiled(compiled: Any, label: str = "program",
                     hide_sync_slack: bool = True,
                     ) -> Optional[ScheduleAnalysis]:
    """ScheduleAnalysis for a compiled executable (rates from the
    running accelerator), or None when even the HLO text is
    unavailable."""
    import re as _re

    from ..platform.accelerator import get_accelerator
    from ..profiling.hlo import compiled_cost_stats

    try:
        text = compiled.as_text()
    except Exception:
        return None
    cost = compiled_cost_stats(compiled) or {}
    m = _re.search(r"num_partitions=(\d+)", text[: text.find("\n")])
    try:
        acc = get_accelerator()
        peak, hbm = acc.peak_flops(), acc.hbm_bandwidth()
    except Exception:  # no backend: keep ratios finite
        peak, hbm = 1.0, 1.0
    return analyze_schedule(
        text,
        flops=float(cost.get("flops", 0.0)),
        bytes_accessed=float(cost.get("bytes_accessed", 0.0)),
        peak_flops=peak, hbm_bandwidth=hbm,
        n_devices=int(m.group(1)) if m else 1,
        label=label, hide_sync_slack=hide_sync_slack)


# ----------------------------------------------------------------------
# check S007: exposed-collective time
# ----------------------------------------------------------------------

def check_exposed_comm(
    analysis: ScheduleAnalysis,
    baseline: Optional[Dict[str, Any]] = None,
    min_exposed_us: float = 50.0,
    overlap_frac: float = 0.5,
    tolerance: float = 0.10,
    label: Optional[str] = None,
) -> SanitizerReport:
    """S007: (a) a collective exposed >= min_exposed_us on the schedule
    while enough independent compute (>= overlap_frac x its wire time)
    is scheduled where it could hide — serialized comm that an async
    window / schedule move would overlap; (b) regression form — total
    exposed microseconds grew past the captured baseline entry
    ({"exposed_us": E}) by more than `tolerance` plus the reporting
    floor."""
    label = label or analysis.label
    out = SanitizerReport(label=f"{label}/exposed_comm")
    floor_s = min_exposed_us * 1e-6
    for c in analysis.collectives:
        hideable = c.overlap_s + c.slack_s
        if c.exposed_s >= floor_s and hideable >= overlap_frac * c.t_comm_s:
            mb = 1 / 2**20
            out.findings.append(Finding(
                rule="S007", path=label, line=0, severity="error",
                message=(
                    f"{c.op} '{c.name}' ({c.computation}) moves "
                    f"{c.payload_bytes * mb:.1f} MiB over a "
                    f"{c.effective_group(analysis.n_devices)}-way group "
                    f"but is exposed {c.exposed_s * 1e6:.0f}us on the "
                    f"schedule while {hideable * 1e6:.0f}us of "
                    "independent compute sits between it and its first "
                    "consumer — serialized comm that could overlap"),
                fix_hint=(
                    "let the collective run async across the gap "
                    "(schedule its consumer later / enable async "
                    "collectives), or restructure so dependent compute "
                    "does not immediately consume the result"),
            ))
    if baseline:
        base_us = float(baseline.get("exposed_us", 0.0))
        cur_us = analysis.exposed_s * 1e6
        if cur_us > base_us * (1.0 + tolerance) + min_exposed_us:
            out.findings.append(Finding(
                rule="S007", path=label, line=0, severity="error",
                message=(
                    f"exposed-collective time regressed: {cur_us:.0f}us "
                    f"vs baseline {base_us:.0f}us (tolerance "
                    f"{100 * tolerance:.0f}% + {min_exposed_us:.0f}us "
                    "floor)"),
                fix_hint=(
                    "inspect the per-collective exposure ledger "
                    "(ScheduleAnalysis.collectives); re-capture with "
                    "scripts/ds_gate.py schedule --capture only if the new "
                    "exposure is intended"),
            ))
    return out


# ----------------------------------------------------------------------
# check S008: hierarchy-aware placement
# ----------------------------------------------------------------------

def _permute_cut_stats(node: CollectiveNode, topology: PodTopology
                       ) -> Tuple[int, int, int]:
    """(total pairs, DCN-straddling pairs, minimum achievable cuts) for
    a collective-permute's source-target pairs under `topology`. A
    pipeline ring whose stages sit in CONTIGUOUS slice blocks (mesh.py
    lays 'pipe' outermost exactly for this) crosses the DCN boundary
    once per slice it touches — that ring-wraparound count is the
    placement lower bound; every cut beyond it is a stage->slice
    placement that interleaves slices and pays DCN on steady-state hops
    ICI could carry."""
    cuts = sum(1 for a, b in node.pairs
               if topology.slice_of(a) != topology.slice_of(b))
    touched = len({topology.slice_of(d) for p in node.pairs for d in p})
    min_cuts = touched if touched > 1 else 0
    return len(node.pairs), cuts, min_cuts


def _group_slice_stats(node: CollectiveNode, topology: PodTopology,
                       n_devices: int) -> Tuple[int, int]:
    """(group size, max slices one group spans) for a collective under
    `topology`. Flat/unstated groups span the whole projected world."""
    groups = node.groups
    if not groups:
        world = (topology.num_slices or 1) * topology.slice_devices \
            if topology.num_slices else max(n_devices,
                                            topology.slice_devices)
        groups = [list(range(world))]
    g = max(len(grp) for grp in groups)
    spans = max(len({topology.slice_of(d) for d in grp})
                for grp in groups)
    return g, spans


def check_hierarchy_placement(
    analysis: ScheduleAnalysis,
    topology: Optional[PodTopology],
    target_devices: Optional[Sequence[int]] = None,
    min_slice_degree: float = 2.0,
    label: Optional[str] = None,
) -> SanitizerReport:
    """S008: a collective's replica groups straddle the topology's
    slice boundaries with >= min_slice_degree members per slice — a
    two-stage decomposition (reduce-scatter within the slice on ICI,
    all-reduce across slices on DCN over 1/degree of the payload,
    all-gather back within the slice) cuts DCN bytes by the slice
    degree. The penalty is projected per candidate pod size in
    `target_devices` (the S004 projection discipline: per-device ring
    payload is ~constant in world size, so the flat-vs-hierarchical gap
    survives scale)."""
    label = label or analysis.label
    out = SanitizerReport(label=f"{label}/hierarchy")
    if topology is None or topology.slice_devices <= 0:
        return out
    targets = [int(t) for t in (target_devices or [])
               if int(t) > topology.slice_devices]
    for c in analysis.collectives:
        if c.pairs:
            # collective-permute (the pipeline rotate / ring-attention
            # hop): hierarchy here is stage->slice PLACEMENT, not group
            # decomposition — flag when the permute crosses the DCN
            # boundary more often than a contiguous stage layout would
            # (docs/pipeline.md; mesh.py lays 'pipe' outermost so
            # steady-state hops stay on ICI)
            n_pairs, cuts, min_cuts = _permute_cut_stats(c, topology)
            if n_pairs == 0 or cuts <= min_cuts:
                continue
            per_pair = c.payload_bytes  # each pair moves the payload once
            t_now = per_pair * cuts / max(topology.dcn_bandwidth, 1.0)
            t_min = per_pair * min_cuts / max(topology.dcn_bandwidth, 1.0)
            if (t_now - t_min) * 1e6 < topology.min_saving_us:
                continue
            out.findings.append(Finding(
                rule="S008", path=label, line=0, severity="error",
                message=(
                    f"collective-permute '{c.name}' crosses the DCN "
                    f"boundary on {cuts} of {n_pairs} source-target "
                    f"pairs where a contiguous stage->slice placement "
                    f"needs only {min_cuts} ring-wraparound cut(s) — "
                    f"{(cuts - min_cuts) * per_pair / 2**20:.1f} MiB of "
                    "steady-state stage-boundary traffic pays the DCN "
                    f"tier per step ({t_now * 1e6:.0f}us vs "
                    f"{t_min * 1e6:.0f}us contiguous)"),
                fix_hint=(
                    "keep the 'pipe' mesh axis outermost (contiguous "
                    "device block per stage, platform/mesh.MESH_AXES "
                    "order) and size slices to a multiple of the "
                    "per-stage device count so consecutive stages "
                    "share a slice"),
            ))
            continue
        g, spans = _group_slice_stats(c, topology, analysis.n_devices)
        if spans <= 1:
            continue  # whole group on ICI: nothing to decompose
        degree = g / spans
        if degree < min_slice_degree:
            continue  # one member per slice(-ish): already hierarchical
        ring = _RING_FACTORS[c.op](max(2, g))
        flat_dcn = c.payload_bytes * ring
        hier_dcn = flat_dcn / degree
        t_flat = flat_dcn / max(topology.dcn_bandwidth, 1.0)
        t_hier = (c.payload_bytes * ring / max(topology.ici_bandwidth, 1.0)
                  + hier_dcn / max(topology.dcn_bandwidth, 1.0))
        if (t_flat - t_hier) * 1e6 < topology.min_saving_us:
            continue  # scalar/tiny payloads: straddling by design
        proj = "; ".join(
            f"{t}dev: {flat_dcn / 2**20:.1f}->"
            f"{hier_dcn / 2**20:.1f} MiB DCN/step"
            for t in targets) or (
            f"{flat_dcn / 2**20:.1f}->{hier_dcn / 2**20:.1f} MiB "
            "DCN/step")
        out.findings.append(Finding(
            rule="S008", path=label, line=0, severity="error",
            message=(
                f"{c.op} '{c.name}' replica groups straddle "
                f"{spans} slice(s) of {topology.slice_devices} devices "
                f"with {degree:.0f} members per slice — the whole "
                f"{c.payload_bytes / 2**20:.1f} MiB payload pays the "
                f"DCN tier ({t_flat * 1e6:.0f}us vs {t_hier * 1e6:.0f}"
                "us hierarchical); decomposing within-slice would cut "
                f"DCN bytes {degree:.0f}x ({proj})"),
            fix_hint=(
                "lay the DCN-spanning mesh axis outermost and decompose "
                "the collective hierarchically: reduce-scatter within "
                "the slice (ICI), all-reduce across slices on 1/degree "
                "of the payload (DCN), all-gather within the slice"),
        ))
    return out


# ----------------------------------------------------------------------
# check S009: critical-path step-time projection
# ----------------------------------------------------------------------

def check_step_time(
    analysis: ScheduleAnalysis,
    baseline: Optional[Dict[str, Any]] = None,
    comm_frac: float = 0.5,
    min_exposed_us: float = 50.0,
    tolerance: float = 0.10,
    label: Optional[str] = None,
) -> SanitizerReport:
    """S009: (a) the critical path is comm-dominated — exposed
    collective time is more than `comm_frac` of the projected step time
    (and above the reporting floor): the step spends the majority of
    its critical path waiting on serialized wires, the schedule-aware
    form of S006's comm-bound verdict; (b) drift form — the step-time
    projection moved beyond `tolerance` against the captured baseline
    entry ({"step_time_us": T}): growth is an error, shrink a warning
    (stale baseline — re-capture)."""
    label = label or analysis.label
    out = SanitizerReport(label=f"{label}/step_time")
    step = analysis.step_time_s
    if (analysis.exposed_s * 1e6 >= min_exposed_us
            and step > 0 and analysis.exposed_s > comm_frac * step):
        out.findings.append(Finding(
            rule="S009", path=label, line=0, severity="error",
            message=(
                f"comm-dominated critical path: exposed collective time "
                f"{analysis.exposed_s * 1e6:.0f}us is "
                f"{100 * analysis.exposed_s / step:.0f}% of the "
                f"projected step time {step * 1e6:.0f}us (compute+HBM "
                f"leg {analysis.t_compute_s * 1e6:.0f}us, "
                f"{analysis.n_sync} sync / {analysis.n_async} async "
                "collectives)"),
            fix_hint=(
                "overlap the exposed collectives (S007 lists them), cut "
                "their volume (S005), or re-shard so the per-step "
                "gather set shrinks"),
        ))
    if baseline:
        base_us = float(baseline.get("step_time_us", 0.0))
        cur_us = step * 1e6
        if base_us > 0 and abs(cur_us - base_us) > \
                base_us * tolerance + 1.0:
            grew = cur_us > base_us
            out.findings.append(Finding(
                rule="S009", path=label, line=0,
                severity="error" if grew else "warning",
                message=(
                    f"step-time projection drifted: {cur_us:.1f}us vs "
                    f"baseline {base_us:.1f}us "
                    f"({'+' if grew else ''}"
                    f"{100 * (cur_us / base_us - 1):.1f}% > "
                    f"{100 * tolerance:.0f}% tolerance)"),
                fix_hint=(
                    "diff the schedule ledger (exposed/compute legs) "
                    "against the baseline; re-capture with "
                    "scripts/ds_gate.py schedule --capture only if the new "
                    "projection is intended"),
            ))
    return out
