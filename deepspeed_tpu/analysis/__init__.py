"""Static analysis for compiled TPU programs and the codebase itself.

Seven prongs (see docs/static_analysis.md):

  sanitizer — ground-truth checks on compiled/lowered artifacts:
              donation aliasing (S001), PartitionSpec survival (S002),
              recompilation-hazard classification (S003). Run against a
              live engine with `engine.sanitize(batch)`.
  costmodel — compile-time cost predictions over the same artifacts:
              per-device HBM budget (S004), collective-volume blowups
              and baseline regressions (S005), roofline balance (S006).
              Baselines persist to MEMBUDGET.json
              (`python scripts/ds_gate.py budget --capture / --check`).
  schedule  — schedule-aware analysis over the same artifacts:
              exposed-collective time (S007), hierarchy-aware replica-
              group placement (S008), critical-path step-time
              projection (S009) — the autotuner's AOT score. Baselines
              persist to SCHEDULE.json
              (`python scripts/ds_gate.py schedule --capture / --check`).
  numerics  — precision-flow analysis over the same artifacts: low-
              precision accumulation (N001), fp32 master-weight
              integrity (N002), loss-scale coverage (N003),
              quantized-collective sanity (N004). Dtype ledgers
              persist to NUMERICS.json
              (`python scripts/ds_gate.py numerics --capture / --check`).
  lint      — `ds-lint`, an AST pass with project rules R001-R008
              (`python scripts/ds_gate.py lint --strict`).
  concurrency — interprocedural lockset race detection (C001),
              lock-order deadlock cycles (C002), and callback-thread
              escape analysis (C003) over the whole tree at once; the
              lock ledger persists to CONCURRENCY.json
              (`python scripts/ds_gate.py race --capture / --check`). R003
              is a per-file shim over C001.
  determinism — RNG-discipline and bitwise-reproducibility analysis:
              layout-dependent PRNG draws (D001), reassociation hazards
              on bitwise-pinned programs (D002), host-side ordering
              nondeterminism (D003), serving draw-key discipline
              (D004); the rng-op/reduce-class ledger persists to
              DETERMINISM.json
              (`python scripts/ds_gate.py determinism --capture / --check`).
              R008 is the per-file lint shim over D001.
"""

from .report import Finding, LintReport, SanitizerReport, merge_reports
from .sanitizer import (
    RecompileTracker,
    abstract_signature,
    check_donation,
    check_sharding,
)
from .costmodel import (
    ICI_GBPS,
    CostReport,
    baseline_doc,
    build_cost_report,
    check_against_baseline,
    check_collective_volume,
    check_hbm_budget,
    check_roofline,
    roofline,
)
from .schedule import (
    PodTopology,
    ScheduleAnalysis,
    analyze_compiled,
    analyze_schedule,
    check_exposed_comm,
    check_hierarchy_placement,
    check_step_time,
)
from .numerics import (
    check_accumulation_dtypes,
    check_loss_scale,
    check_master_integrity,
    check_program_numerics,
    check_quantized_groups,
    diff_ledgers,
    dtype_ledger,
    grad_elem_counts,
)
from .lint import lint_paths, lint_source, RULES
from .concurrency import (
    C_RULES,
    ConcurrencyReport,
    analyze_paths,
    analyze_sources,
)
from .determinism import (
    BITWISE_PINS,
    BitwisePin,
    D_RULES,
    check_draw_keys,
    check_host_ordering,
    check_reassociation,
    check_rng_discipline,
    pin_for,
    program_determinism,
)

__all__ = [
    "Finding",
    "LintReport",
    "SanitizerReport",
    "merge_reports",
    "RecompileTracker",
    "abstract_signature",
    "check_donation",
    "check_sharding",
    "ICI_GBPS",
    "CostReport",
    "build_cost_report",
    "check_against_baseline",
    "check_collective_volume",
    "check_hbm_budget",
    "check_roofline",
    "baseline_doc",
    "roofline",
    "PodTopology",
    "ScheduleAnalysis",
    "analyze_compiled",
    "analyze_schedule",
    "check_exposed_comm",
    "check_hierarchy_placement",
    "check_step_time",
    "check_accumulation_dtypes",
    "check_loss_scale",
    "check_master_integrity",
    "check_program_numerics",
    "check_quantized_groups",
    "diff_ledgers",
    "dtype_ledger",
    "grad_elem_counts",
    "lint_paths",
    "lint_source",
    "RULES",
    "C_RULES",
    "ConcurrencyReport",
    "analyze_paths",
    "analyze_sources",
    "BITWISE_PINS",
    "BitwisePin",
    "D_RULES",
    "check_draw_keys",
    "check_host_ordering",
    "check_reassociation",
    "check_rng_discipline",
    "pin_for",
    "program_determinism",
]
