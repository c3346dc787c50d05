#!/usr/bin/env python
"""ds-moe CLI — deterministic dropless-MoE gate: capacity-free routing
quality/zero-drop pinning, EP-layout invariance, and dropless MoE
serving decode (docs/moe.md).

Usage:
    python scripts/ds_moe.py                  # check vs committed MOE.json
    python scripts/ds_moe.py --check --strict # identical; gate-CLI symmetry
    python scripts/ds_moe.py --capture        # (re)write MOE.json
    python scripts/ds_moe.py --plan my.json   # custom plan

The eleventh tier-1 pre-test gate next to ds_lint / ds_budget /
ds_numerics / ds_schedule / the serving-fleet smoke / ds_chaos /
ds_elastic / ds_sdc / ds_overload / ds_autoscale
(.claude/skills/verify/SKILL.md): runs `bench.py --moe-sim` — dropless
vs capacity-factor routing trained on identical seeds/batches on the
virtual 8-device mesh, plus dropless MoE decode through the
ServingScheduler — and fails unless every gate holds:

  dropless_zero_drops                every top-k assignment routed;
                                     none lost (the dropless contract,
                                     counts sum == T*k exactly)
  capacity_path_drops_on_skew        the capacity-factor reference
                                     measurably drops on the skewed
                                     router distribution (the tradeoff
                                     the lane documents)
  dropless_quality_no_worse          no dropped information -> at
                                     least loss parity on the same
                                     seeds/batches
  ep_layout_training_invariant       EP=1 == EP=N training losses
                                     (expert parallelism is a layout,
                                     never the math)
  ep_layout_serving_token_identical  the same weights served EP=1 and
                                     expert-sharded produce identical
                                     greedy tokens
  zero_recompiles_after_warmup       steady-state dropless serving
                                     compiles nothing (S003 clean)
  expert_census_counted              per-expert utilization counters
                                     reach scheduler.metrics()
  deterministic_rerun                same seeds = same tokens and
                                     census, byte for byte
  ledger_matches_baseline            losses/routing counts equal the
                                     committed MOE.json

A legitimate change to the lane's geometry re-captures the baseline in
the same PR: `python scripts/ds_moe.py --capture` and commit MOE.json.
Everything is seeded and compiled on CPU: a red gate is a routing/
serving regression, never flake.
"""

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--plan", default="default",
                    help="'default' (the committed MOE.json) or a plan "
                         "JSON path with a workload block")
    ap.add_argument("--capture", action="store_true",
                    help="run the lane and (re)write MOE.json with the "
                         "plan + measured quality/routing ledger")
    ap.add_argument("--check", action="store_true",
                    help="explicit check mode (the default)")
    ap.add_argument("--strict", action="store_true",
                    help="accepted for symmetry with the other gates "
                         "(every MoE gate is already hard)")
    args = ap.parse_args(argv)

    import bench

    capture = os.path.join(_REPO, "MOE.json") if args.capture else None
    rc = bench._moe_sim(args.plan, capture=capture)
    print(json.dumps({"ok": rc == 0, "gate": "ds_moe",
                      "plan": args.plan,
                      "mode": "capture" if args.capture else "check"}),
          file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
