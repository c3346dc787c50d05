"""Compile-time cost model: does this program FIT, and what does it move?

The sanitizer (sanitizer.py) verifies *properties* of a compiled program
— donation honored, specs survived, signatures stable. This module
predicts its *costs* before a single step runs on real hardware: peak
HBM per device (args + outputs + temps, donation-credited), collective
byte volume per step, and the roofline balance between flops, HBM
traffic and ICI traffic. All three are static properties of the
compiled artifact (`compiled.memory_analysis()` / `cost_analysis()` +
the profiling/hlo.py HLO parsers) — ground truth, not invocation-side
bookkeeping, in the same discipline as the rest of `analysis/`.

Three checks (findings ride the sanitizer report machinery):

  S004  check_hbm_budget       — peak program HBM exceeds the
        per-device budget of the target topology (chip capacity from
        platform/accelerator.py; sharded entry parameters project to
        meshes larger than the compiling host via their `sharding=`
        annotations).
  S005  check_collective_volume — all-gather bytes exceed k x the live
        sharded-param bytes (the "accidental replication" class: a
        sharded table materialized whole), or per-step comm bytes
        regressed beyond tolerance against a captured baseline.
  S006  check_roofline         — a program the spec declares
        compute-bound compiles comm- or memory-bound (flops vs
        bytes-accessed vs ICI bytes against the chip's peak rates).

Baselines persist to MEMBUDGET.json (scripts/ds_gate.py budget --capture /
--check, the tier-1 pre-test gate next to ds-lint).
"""

import dataclasses
import re
from typing import Any, Dict, Optional

from ..profiling.hlo import (
    compiled_cost_stats,
    compiled_memory_stats,
    parse_entry_parameters,
    parse_hlo_collectives,
)
from .report import Finding, SanitizerReport

__all__ = [
    "ICI_GBPS",
    "CostReport",
    "build_cost_report",
    "check_hbm_budget",
    "check_collective_volume",
    "check_roofline",
    "check_against_baseline",
    "roofline",
    "baseline_doc",
]

# Effective per-chip ICI bandwidth (bytes/s) for the ring-collective
# projection — re-exported from the single link-table authority
# (platform/accelerator.LINKS, shared with scripts/ici_projection.py
# and analysis/schedule.py; tests assert no local re-declaration).
from ..platform.accelerator import LINKS as _LINKS

ICI_GBPS = _LINKS["ici_bytes_per_s"]

_NUM_PARTITIONS_RE = re.compile(r"num_partitions=(\d+)")


@dataclasses.dataclass
class CostReport:
    """Static cost profile of ONE compiled program (per-device view:
    every byte count is what a single device holds or moves)."""

    label: str
    arg_bytes: int = 0
    out_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0          # donated args whose storage outputs reuse
    sharded_arg_bytes: int = 0    # entry params carrying a devices=[...] tile
    replicated_arg_bytes: int = 0
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collectives: Dict[str, Dict[str, float]] = dataclasses.field(
        default_factory=dict)  # {op: {count, bytes}}
    n_devices: int = 1
    estimated: bool = False       # memory_analysis unavailable: args only
    # schedule-aware projection (analysis/schedule.py S007-S009): the
    # critical-path step time — serial roofline leg + EXPOSED comm only
    # — and its summary ledger. The autotuner's AOT score reads
    # step_time_s; the full ScheduleAnalysis rides the non-field
    # `_schedule` attribute for the checks.
    step_time_s: float = 0.0
    exposed_comm_s: float = 0.0
    schedule: Optional[Dict[str, Any]] = None

    @property
    def peak_hbm_bytes(self) -> int:
        """Resident bytes while the program runs: arguments + outputs +
        scratch, minus the donated storage outputs alias in place."""
        return max(
            0, self.arg_bytes + self.out_bytes + self.temp_bytes
            - self.alias_bytes)

    @property
    def comm_bytes(self) -> int:
        return int(sum(v["bytes"] for v in self.collectives.values()))

    @property
    def all_gather_bytes(self) -> int:
        return int(self.collectives.get("all-gather", {}).get("bytes", 0))

    def projected_arg_bytes(self, target_devices: int) -> int:
        """Per-device argument bytes at a LARGER topology: sharded entry
        parameters keep shrinking with the mesh (per-shard dims scale by
        compiled/target device ratio), replicated parameters do not."""
        scale = self.n_devices / max(1, int(target_devices))
        return int(self.sharded_arg_bytes * scale) + self.replicated_arg_bytes

    def projected_peak_hbm(self, target_devices: int) -> int:
        """Peak HBM projected to `target_devices`. Outputs/temps follow
        the sharded-argument scaling fraction (they are dominated by the
        same tensors); replicated residency is held constant."""
        if self.arg_bytes <= 0:
            return self.peak_hbm_bytes
        frac = self.sharded_arg_bytes / self.arg_bytes
        scale = self.n_devices / max(1, int(target_devices))
        scaled = 1.0 - frac + frac * scale
        rest = self.out_bytes + self.temp_bytes - self.alias_bytes
        return max(0, int(self.projected_arg_bytes(target_devices)
                          + rest * scaled))

    def to_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["peak_hbm_bytes"] = self.peak_hbm_bytes
        d["comm_bytes"] = self.comm_bytes
        return d

    def render(self) -> str:
        mb = 1 / 2**20
        return (
            f"cost[{self.label}]: peak {self.peak_hbm_bytes * mb:.1f} MiB "
            f"(args {self.arg_bytes * mb:.1f} | out {self.out_bytes * mb:.1f}"
            f" | temp {self.temp_bytes * mb:.1f} | aliased "
            f"-{self.alias_bytes * mb:.1f}), comm "
            f"{self.comm_bytes * mb:.1f} MiB/step, "
            f"{self.flops / 1e9:.2f} GFLOP"
            + (" [estimated]" if self.estimated else "")
        )


def _is_sharded(sharding: Optional[str]) -> bool:
    """Does a `sharding=` annotation actually tile the value? A bare
    `replicated`/`maximal` (or `devices=[1,1,...]`) holds a full copy."""
    if not sharding or "devices" not in sharding:
        return False
    m = re.search(r"devices=\[([\d,]+)\]", sharding)
    if not m:
        return False
    tile = [int(x) for x in m.group(1).split(",") if x]
    if "last_tile_dim_replicate" in sharding and len(tile) > 1:
        tile = tile[:-1]
    n = 1
    for t in tile:
        n *= t
    return n > 1


def build_cost_report(compiled: Any, label: str = "program",
                      hide_sync_slack: bool = True,
                      ) -> Optional[CostReport]:
    """Cost profile of one compiled program, or None when even the HLO
    text is unavailable. Degrades gracefully: without memory_analysis()
    (some backends) the argument footprint is rebuilt from the entry
    parameters and `estimated` is set.

    hide_sync_slack feeds the schedule analyzer's latency-hiding
    credit (analysis/schedule.py): the engine passes
    `zero_optimization.overlap_comm` here, so an overlap-off engine's
    S009 projection models serialized execution — the overlap-off twin
    ds_schedule commits."""
    try:
        text = compiled.as_text()
    except Exception:
        return None
    params = parse_entry_parameters(text)
    sharded = sum(p["nbytes"] for p in params if _is_sharded(p["sharding"]))
    replicated = sum(
        p["nbytes"] for p in params if not _is_sharded(p["sharding"]))
    m = _NUM_PARTITIONS_RE.search(text[: text.find("\n")])
    n_devices = int(m.group(1)) if m else 1

    rep = CostReport(label=label, n_devices=n_devices,
                     sharded_arg_bytes=int(sharded),
                     replicated_arg_bytes=int(replicated))
    mem = compiled_memory_stats(compiled)
    if mem is not None:
        rep.arg_bytes = mem["argument_bytes"]
        rep.out_bytes = mem["output_bytes"]
        rep.temp_bytes = mem["temp_bytes"]
        rep.alias_bytes = mem["alias_bytes"]
        # keep the sharded/replicated split consistent with the backend's
        # total (layout padding makes the parsed sum a slight undercount)
        parsed = sharded + replicated
        if parsed > 0 and rep.arg_bytes > 0:
            ratio = rep.arg_bytes / parsed
            rep.sharded_arg_bytes = int(sharded * ratio)
            rep.replicated_arg_bytes = rep.arg_bytes - rep.sharded_arg_bytes
    else:
        rep.arg_bytes = int(sharded + replicated)
        rep.estimated = True
    cost = compiled_cost_stats(compiled)
    if cost is not None:
        rep.flops = cost["flops"]
        rep.bytes_accessed = cost["bytes_accessed"]
    agg: Dict[str, Dict[str, float]] = {}
    for c in parse_hlo_collectives(text):
        slot = agg.setdefault(c["op"], {"count": 0, "bytes": 0})
        slot["count"] += 1
        slot["bytes"] += c["bytes"]
    rep.collectives = agg
    # schedule-aware step-time projection (S007-S009 input + the
    # autotuner's AOT score); never fatal — a backend without
    # cost_analysis still gets the comm-only schedule ledger
    try:
        from ..platform.accelerator import get_accelerator
        from .schedule import analyze_schedule

        try:
            acc = get_accelerator()
            peak, hbm = acc.peak_flops(), acc.hbm_bandwidth()
        except Exception:
            peak, hbm = 1.0, 1.0
        sched = analyze_schedule(
            text, flops=rep.flops, bytes_accessed=rep.bytes_accessed,
            peak_flops=peak, hbm_bandwidth=hbm, n_devices=n_devices,
            label=label, hide_sync_slack=hide_sync_slack)
    except Exception:
        sched = None
    if sched is not None:
        rep.step_time_s = sched.step_time_s
        rep.exposed_comm_s = sched.exposed_s
        rep.schedule = sched.to_dict()
        rep._schedule = sched
    return rep


# ----------------------------------------------------------------------
# check S004: per-device HBM budget
# ----------------------------------------------------------------------

def check_hbm_budget(
    report: CostReport,
    budget_bytes: Optional[int] = None,
    target_devices: Optional[int] = None,
    label: Optional[str] = None,
) -> SanitizerReport:
    """One S004 error when the program's peak HBM footprint exceeds the
    per-device budget. budget_bytes defaults to the running chip's HBM
    capacity (platform/accelerator.py). target_devices projects the
    footprint to a mesh larger than the compiling host: sharded entry
    parameters keep shrinking, replicated residency does not — exactly
    the term that OOMs a "it fit on 8 devices" program at scale."""
    label = label or report.label
    out = SanitizerReport(label=f"{label}/hbm_budget")
    if budget_bytes is None:
        from ..platform.accelerator import get_accelerator

        budget_bytes = get_accelerator().hbm_per_device()
    if target_devices is None or target_devices == report.n_devices:
        peak, where = report.peak_hbm_bytes, f"{report.n_devices} device(s)"
    else:
        peak = report.projected_peak_hbm(target_devices)
        where = (f"projected {target_devices} devices "
                 f"(compiled on {report.n_devices})")
    if peak > budget_bytes:
        gib = 1 / 2**30
        out.findings.append(Finding(
            rule="S004", path=label, line=0, severity="error",
            message=(
                f"peak HBM {peak * gib:.2f} GiB at {where} exceeds the "
                f"per-device budget {budget_bytes * gib:.2f} GiB "
                f"(args {report.arg_bytes * gib:.2f} + out "
                f"{report.out_bytes * gib:.2f} + temp "
                f"{report.temp_bytes * gib:.2f} - aliased "
                f"{report.alias_bytes * gib:.2f}; replicated residency "
                f"{report.replicated_arg_bytes * gib:.2f} GiB does not "
                "shrink with the mesh)"),
            fix_hint=(
                "shard the replicated state (zero stage / TP specs), "
                "donate large buffers so outputs alias, or lower the "
                "batch/sequence buckets"),
        ))
    return out


# ----------------------------------------------------------------------
# check S005: collective-volume blowups
# ----------------------------------------------------------------------

def check_collective_volume(
    report: CostReport,
    live_sharded_bytes: Optional[int] = None,
    k: float = 4.0,
    baseline: Optional[Dict[str, Any]] = None,
    tolerance: float = 0.10,
    label: Optional[str] = None,
) -> SanitizerReport:
    """S005: (a) accidental replication — the program's all-gather bytes
    exceed k x the live sharded-param bytes it could legitimately need
    to materialize per step (a sharded table gathered whole, or gathered
    once per consumer instead of once); (b) comm regression — per-step
    collective bytes grew more than `tolerance` over a captured baseline
    entry ({"comm_bytes": N}, see save_baseline)."""
    label = label or report.label
    out = SanitizerReport(label=f"{label}/collective_volume")
    ag = report.all_gather_bytes
    if live_sharded_bytes and ag > k * live_sharded_bytes:
        mb = 1 / 2**20
        out.findings.append(Finding(
            rule="S005", path=label, line=0, severity="error",
            message=(
                f"all-gather moves {ag * mb:.1f} MiB/step — "
                f"{ag / live_sharded_bytes:.1f}x the {live_sharded_bytes * mb:.1f} "
                f"MiB of live sharded params (allowed {k:.1f}x): a sharded "
                "value is being materialized replicated (accidental "
                "full-gather)"),
            fix_hint=(
                "keep the consumer sharded (with_sharding_constraint per "
                "parallel/sharding.py), or gather once and reuse — diff "
                "collective_volumes() against the expected gather set"),
        ))
    if baseline:
        base = float(baseline.get("comm_bytes", 0))
        if base > 0 and report.comm_bytes > base * (1.0 + tolerance):
            out.findings.append(Finding(
                rule="S005", path=label, line=0, severity="error",
                message=(
                    f"per-step collective volume regressed: "
                    f"{report.comm_bytes / 2**20:.1f} MiB vs baseline "
                    f"{base / 2**20:.1f} MiB "
                    f"(+{100 * (report.comm_bytes / base - 1):.1f}% > "
                    f"{100 * tolerance:.0f}% tolerance)"),
                fix_hint=(
                    "inspect collective_volumes() per op kind; re-capture "
                    "the baseline (scripts/ds_gate.py budget --capture) only if "
                    "the growth is intended"),
            ))
    return out


# ----------------------------------------------------------------------
# check S006: roofline balance
# ----------------------------------------------------------------------

def roofline(
    report: CostReport,
    peak_flops: float,
    hbm_bandwidth: float,
    ici_bandwidth: float = ICI_GBPS,
) -> Dict[str, float]:
    """Per-leg lower-bound times for one program and its binding leg.

    t_flops = flops / peak, t_hbm = bytes_accessed / HBM bandwidth,
    t_ici = collective bytes / ICI bandwidth. `bound` is the largest
    leg; `intensity` is flops per HBM byte (classic roofline x-axis)."""
    t_flops = report.flops / max(peak_flops, 1.0)
    t_hbm = report.bytes_accessed / max(hbm_bandwidth, 1.0)
    t_ici = report.comm_bytes / max(ici_bandwidth, 1.0)
    legs = {"compute": t_flops, "memory": t_hbm, "comm": t_ici}
    bound = max(legs, key=legs.get)
    return {
        "t_flops": t_flops, "t_hbm": t_hbm, "t_ici": t_ici,
        "bound": bound,
        "intensity": report.flops / max(report.bytes_accessed, 1.0),
    }


def check_roofline(
    report: CostReport,
    peak_flops: Optional[float] = None,
    hbm_bandwidth: Optional[float] = None,
    ici_bandwidth: float = ICI_GBPS,
    expect: str = "compute",
    comm_only: bool = False,
    label: Optional[str] = None,
) -> SanitizerReport:
    """S006: the program compiles with a different binding leg than the
    spec declares (`expect`: compute|memory|comm). comm_only=True flags
    only the comm-bound case — the right setting for small verification
    slices, which are legitimately memory-bound at toy sizes but should
    NEVER be dominated by collective traffic."""
    label = label or report.label
    out = SanitizerReport(label=f"{label}/roofline")
    if peak_flops is None or hbm_bandwidth is None:
        from ..platform.accelerator import get_accelerator

        acc = get_accelerator()
        peak_flops = peak_flops or acc.peak_flops()
        hbm_bandwidth = hbm_bandwidth or acc.hbm_bandwidth()
    if report.flops <= 0 and report.bytes_accessed <= 0:
        return out  # no cost_analysis on this backend: nothing to judge
    r = roofline(report, peak_flops, hbm_bandwidth, ici_bandwidth)
    if r["bound"] == expect or (comm_only and r["bound"] != "comm"):
        return out
    out.findings.append(Finding(
        rule="S006", path=label, line=0, severity="warning",
        message=(
            f"program compiles {r['bound']}-bound but is declared "
            f"{expect}-bound: t_flops {r['t_flops']:.2e}s, t_hbm "
            f"{r['t_hbm']:.2e}s, t_ici {r['t_ici']:.2e}s (arithmetic "
            f"intensity {r['intensity']:.1f} flop/byte)"),
        fix_hint=(
            "comm-bound: cut collective volume (S005 diagnoses which op); "
            "memory-bound: raise arithmetic intensity (fuse, batch, "
            "larger tiles) or accept and re-declare the spec"),
    ))
    return out


# ----------------------------------------------------------------------
# the baseline document (MEMBUDGET.json; scripts/ds_gate.py budget
# reads and writes the file)
# ----------------------------------------------------------------------

def baseline_doc(
    programs: Dict[str, CostReport],
    budgets: Optional[Dict[str, Any]] = None,
    meta: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """The MEMBUDGET.json document: one entry per program with the
    regression-gated scalars, plus the budget block --check enforces."""
    return {
        "schema": 1,
        **(meta or {}),
        "budgets": {"hbm_regression_tolerance": 0.10, **(budgets or {})},
        "programs": {
            name: {
                "peak_hbm_bytes": rep.peak_hbm_bytes,
                "arg_bytes": rep.arg_bytes,
                "out_bytes": rep.out_bytes,
                "temp_bytes": rep.temp_bytes,
                "alias_bytes": rep.alias_bytes,
                "comm_bytes": rep.comm_bytes,
                "flops": rep.flops,
                "n_devices": rep.n_devices,
            }
            for name, rep in programs.items()
        },
    }


def check_against_baseline(
    report: CostReport,
    baseline_entry: Dict[str, Any],
    tolerance: float = 0.10,
    label: Optional[str] = None,
) -> SanitizerReport:
    """S004 regression form: peak HBM grew more than `tolerance` over
    the captured baseline entry (the scripts/ds_gate.py budget --check gate — a PR
    that quietly inflates a step's footprint fails like a lint
    finding). Comm regressions ride check_collective_volume."""
    label = label or report.label
    out = SanitizerReport(label=f"{label}/baseline")
    base = float(baseline_entry.get("peak_hbm_bytes", 0))
    if base > 0 and report.peak_hbm_bytes > base * (1.0 + tolerance):
        out.findings.append(Finding(
            rule="S004", path=label, line=0, severity="error",
            message=(
                f"peak HBM regressed: {report.peak_hbm_bytes / 2**20:.1f} "
                f"MiB vs baseline {base / 2**20:.1f} MiB "
                f"(+{100 * (report.peak_hbm_bytes / base - 1):.1f}% > "
                f"{100 * tolerance:.0f}% tolerance)"),
            fix_hint=(
                "find the new residency (args/out/temp breakdown in the "
                "cost report); re-capture with scripts/ds_gate.py budget "
                "--capture only if the growth is intended"),
        ))
    return out
