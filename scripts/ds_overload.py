#!/usr/bin/env python
"""ds-overload CLI — deterministic overload-resilience gate: the
pressure governor, KV spill-to-host preemption, and SLO-aware
admission under a 4x-capacity burst (docs/fault_tolerance.md pressure
section).

Usage:
    python scripts/ds_overload.py                  # check vs committed OVERLOAD.json
    python scripts/ds_overload.py --check --strict # identical; gate-CLI symmetry
    python scripts/ds_overload.py --capture        # (re)write OVERLOAD.json
    python scripts/ds_overload.py --plan my.json   # custom plan

The eighth tier-1 pre-test gate next to ds_lint / ds_budget /
ds_numerics / the serving-fleet smoke / ds_chaos / ds_elastic / ds_sdc
(.claude/skills/verify/SKILL.md): runs `bench.py --overload-sim` — a
burst trace at ~4x single-replica capacity served against an
unpressured reference, with the governor + spill tier on and then with
armed 'spill.io' faults — and fails unless every gate holds:

  no_livelock_every_admitted_request_finishes
                                     sustained pressure never wedges
                                     the scheduler; every admitted
                                     request reaches a finish_reason
  spill_path_exercised_under_red     the governor climbed to RED and
                                     answered preemption with
                                     export-to-host + import-resume
  spill_resume_token_identical       spilled/resumed outputs equal the
                                     unpressured run token for token
  spill_fault_falls_back_to_recompute injected spill put/get failures
                                     fell back to flush-and-recompute
                                     with zero token loss
  deadline_rejects_consume_no_blocks unservable SLO deadlines rejected
                                     at submit (finish_reason
                                     'deadline'), zero KV blocks
                                     touched, nothing leaked
  deterministic_rerun                same plan + same trace = same
                                     spills, fallbacks, and tokens,
                                     byte for byte
  ledger_matches_baseline            spill/rejection counts equal the
                                     committed OVERLOAD.json

A legitimate change to the lane's geometry re-captures the baseline in
the same PR: `python scripts/ds_overload.py --capture` and commit
OVERLOAD.json. Everything is virtual-time and seeded: a red gate is a
pressure-governor regression, never flake.
"""

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--plan", default="default",
                    help="'default' (the committed OVERLOAD.json) or a "
                         "FaultPlan JSON path with workload/expect "
                         "blocks")
    ap.add_argument("--capture", action="store_true",
                    help="run the lane and (re)write OVERLOAD.json "
                         "with the plan + measured pressure ledger")
    ap.add_argument("--check", action="store_true",
                    help="explicit check mode (the default)")
    ap.add_argument("--strict", action="store_true",
                    help="accepted for symmetry with the other gates "
                         "(every overload gate is already hard)")
    args = ap.parse_args(argv)

    import bench

    capture = os.path.join(_REPO, "OVERLOAD.json") if args.capture \
        else None
    rc = bench._overload_sim(args.plan, capture=capture)
    print(json.dumps({"ok": rc == 0, "gate": "ds_overload",
                      "plan": args.plan,
                      "mode": "capture" if args.capture else "check"}),
          file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
