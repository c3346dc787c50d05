#!/usr/bin/env python
"""ds-sdc CLI — deterministic silent-data-corruption gate: runtime
integrity checks + anomaly-triggered rollback (docs/fault_tolerance.md
SDC section).

Usage:
    python scripts/ds_sdc.py                  # check vs committed SDCCHAOS.json
    python scripts/ds_sdc.py --check --strict # identical; gate-CLI symmetry
    python scripts/ds_sdc.py --capture        # (re)write SDCCHAOS.json
    python scripts/ds_sdc.py --plan my.json   # custom plan

The seventh tier-1 pre-test gate next to ds_lint / ds_budget /
ds_numerics / the serving-fleet smoke / ds_chaos / ds_elastic
(.claude/skills/verify/SKILL.md): runs `bench.py --sdc-chaos` — the
elastic-training and disaggregated-serving lanes executed clean and
then under injected in-memory BIT FLIPS (seeded, dtype-aware,
replayable: resilience/integrity.py) — and fails unless every gate
holds:

  grad_flip_detected_before_commit   a flipped gradient readout/update
                                     tripped the EMA z-score guardian
                                     and was answered by a rollback to
                                     the last digest-VERIFIED peer
                                     mirror — never committed
  mirror_flip_detected_with_fallover a bit-flipped mirror copy failed
                                     its blake2b envelope at
                                     reconstruct and recovery fell
                                     over to the next holder
  handoff_flip_detected              a flipped KV handoff payload was
                                     discarded at import and the
                                     request recomputed
  zero_poisoned_updates_committed    loss prefix bitwise-identical to
                                     the clean run THROUGH the
                                     corrupted-then-replayed steps;
                                     (step -> sample ids) ledger
                                     byte-exact
  zero_corrupted_tokens_served       serving outputs token-identical
                                     to the clean pass
  recovered_without_disk             peer-shard recovery, zero disk
                                     restores
  loss_trajectory_within_budget      within the TRAINCHAOS-class
                                     reassociation tolerance
  deterministic_rerun                same plan = same flips = same
                                     detections, byte for byte
  detection_ledger_matches_baseline  injected/detected counts equal
                                     the committed SDCCHAOS.json

A legitimate change to the lane's geometry re-captures the baseline in
the same PR: `python scripts/ds_sdc.py --capture` and commit
SDCCHAOS.json. Everything is seeded and fires on exact step counts: a
red gate is an integrity-guardian regression, never flake.
"""

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--plan", default="default",
                    help="'default' (the committed SDCCHAOS.json) or "
                         "a FaultPlan JSON path with workload/expect "
                         "blocks")
    ap.add_argument("--capture", action="store_true",
                    help="run the lane and (re)write SDCCHAOS.json "
                         "with the plan + measured detection ledger")
    ap.add_argument("--check", action="store_true",
                    help="explicit check mode (the default)")
    ap.add_argument("--strict", action="store_true",
                    help="accepted for symmetry with the other gates "
                         "(every SDC gate is already hard)")
    args = ap.parse_args(argv)

    import bench

    capture = os.path.join(_REPO, "SDCCHAOS.json") if args.capture \
        else None
    rc = bench._sdc_chaos(args.plan, capture=capture)
    print(json.dumps({"ok": rc == 0, "gate": "ds_sdc",
                      "plan": args.plan,
                      "mode": "capture" if args.capture else "check"}),
          file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
