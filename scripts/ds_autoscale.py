#!/usr/bin/env python
"""ds-autoscale CLI — deterministic elastic-autoscaling gate: replica
lifecycle (cache-warm spin-up / graceful drain), the SLO-class
autoscaler, and the diurnal/burst resilience trace
(docs/autoscaling.md).

Usage:
    python scripts/ds_autoscale.py                  # check vs committed AUTOSCALE.json
    python scripts/ds_autoscale.py --check --strict # identical; gate-CLI symmetry
    python scripts/ds_autoscale.py --capture        # (re)write AUTOSCALE.json
    python scripts/ds_autoscale.py --plan my.json   # custom plan

The tenth tier-1 pre-test gate next to ds_lint / ds_budget /
ds_numerics / ds_schedule / the serving-fleet smoke / ds_chaos /
ds_elastic / ds_sdc / ds_overload (.claude/skills/verify/SKILL.md):
runs `bench.py --autoscale-sim` — a macro multi-hour virtual-clock
diurnal/burst lane (millions of fluid-modeled sessions driven through
the REAL Autoscaler policy loop) plus a micro real-fleet lane (real
engine replicas scaling up cache-warm and draining by page-move
migration under the virtual clock, clean and under an armed
'replica.spinup' kill) — and fails unless every gate holds:

  macro_million_sessions             the diurnal trace integrates >= 1M
                                     simulated sessions
  macro_premium_slo_held_zero_sheds  the autoscaler holds premium-class
                                     p95 TTFT within its SLO with ZERO
                                     premium sheds
  macro_hours_materially_below_static_peak
                                     replica-hours <= max_hours_ratio x
                                     static peak provisioning (which
                                     also holds the SLO — a fair
                                     comparison)
  macro_valley_static_violates_slo   a fleet frozen at the valley size
                                     must BLOW the premium SLO — the
                                     trace has teeth
  macro_autoscaler_exercised         >= 2 scale-ups and >= 1 scale-down
  macro_deterministic                a macro rerun is value-identical
  micro_all_finish_no_livelock       every request reaches a finish
                                     reason in every fleet mode
  micro_token_identical_vs_static    autoscaled outputs == the static
                                     max-fleet reference, token for
                                     token (scale-up, rebalance, drain,
                                     and chaos never show in outputs)
  micro_autoscaler_exercised         the real fleet grew from 1 replica
                                     and drained back down
  micro_warm_boot_exercised          a joining replica imported the
                                     donor's parked prefix chains
  micro_drain_migrates_zero_tokens   a drain moved RUNNING sequences by
                                     page transfer with zero token
                                     change
  micro_elastic_saves_replica_hours  dynamic replica-hours < the static
                                     fleet's over the same trace
  micro_zero_recompiles              zero S003 recompile findings on
                                     every replica of every lane —
                                     joins keep the steady state
  chaos_spinup_burned_and_retried    the armed replica.spinup kill
                                     burned exactly one spin-up and the
                                     autoscaler retried with backoff
  chaos_recovers_token_identical     the chaos pass serves the full
                                     trace token-identically, no disk
  deterministic_rerun                same plan + same trace = the same
                                     ledger and tokens, byte for byte
  ledger_matches_baseline            measured macro/micro ledgers equal
                                     the committed AUTOSCALE.json

A legitimate change to the lane's geometry re-captures the baseline in
the same PR: `python scripts/ds_autoscale.py --capture` and commit
AUTOSCALE.json. Everything is virtual-time and seeded: a red gate is an
autoscaler/lifecycle regression, never flake.
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
                    help="'default' (the committed AUTOSCALE.json) or "
                         "a FaultPlan JSON path with workload/expect "
                         "blocks")
    ap.add_argument("--capture", action="store_true",
                    help="run the lanes and (re)write AUTOSCALE.json "
                         "with the plan + measured ledgers")
    ap.add_argument("--check", action="store_true",
                    help="explicit check mode (the default)")
    ap.add_argument("--strict", action="store_true",
                    help="accepted for symmetry with the other gates "
                         "(every autoscale gate is already hard)")
    args = ap.parse_args(argv)

    import bench

    capture = os.path.join(_REPO, "AUTOSCALE.json") if args.capture \
        else None
    rc = bench._autoscale_sim(args.plan, capture=capture)
    print(json.dumps({"ok": rc == 0, "gate": "ds_autoscale",
                      "plan": args.plan,
                      "mode": "capture" if args.capture else "check"}),
          file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
