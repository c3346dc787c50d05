#!/usr/bin/env python3
"""Read, once, on the chip, what a serving cell's logits check reads
over many seeds, and what the same check reads of models that are wrong:
the data a family's `logits_check` limits are set from. One process; a
fresh engine and fresh weights per seed, through the runner's own
`build_engine` and `logits_errors` (no warm-up of the serving widths, no
window):

    python3 benchmarks/logits_audit.py --workload <cell> \
        --seeds 1,2,3000000001 --controls 8 --decode-steps 10

Per seed it prints the error at every checked position (prompts x
steps) as a share of the largest |reference logit|, and, where the
reference knows its router (`router_margins`), the smallest margin over
the layers at each of those positions: an isolated large error beside a
margin under bf16's resolution is a flipped near-tied expert, a large
error at every position is a wrong model. For the first `--controls`
seeds it also reads the CONTROLS, each put in the program's place and
compared with the reference: the reference in the nearest precision
below the configuration's (every weight rounded to float8_e4m3), and
every wrong model the reference can compute (`MUTANTS`). It ends with
the distribution (quantiles, the largest three) of the largest and of
the median error over the sound seeds, and each control's smallest.
This is a tool for the PR that sets or re-sets a family's limits; the
limits it finds are written into the mix's file as numbers with their
reason, and no check runs this.
"""

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

QUANTILES = (0.5, 0.9, 0.99, 1.0)


def to_float8(a):
    """A leaf rounded to float8_e4m3 and back: the precision below the
    bf16 the serving configurations state."""
    import jax.numpy as jnp

    return a.astype(jnp.float8_e4m3fn).astype(a.dtype)


def control_errors(runner, ref, e, cell, host_params):
    """{control: [prompts, steps] max |control - reference|} for the
    reference on float8 weights and each of the reference's mutants."""
    import numpy as np

    hf = cell.config

    def against_reference(logits):
        at = np.stack([np.asarray(logits[i])[p] for i, p in enumerate(e["pos"])])
        return np.abs(at - e["want"]).max(axis=-1)

    top, layer = runner.reference_inputs(host_params, cast=to_float8)
    out = {"float8": against_reference(
        ref.forward_logits(top, layer, e["tokens"], hf))}
    top, layer = runner.reference_inputs(host_params)
    for name in getattr(ref, "MUTANTS", ()):
        out[name] = against_reference(
            ref.forward_logits(top, layer, e["tokens"], hf, mutate=name))
    return out


def summary(rows, chk):
    """The distribution over the sound seeds of the two statistics a
    rule can limit, and the smallest each control reads."""
    import numpy as np

    def dist(xs):
        xs = np.sort(np.asarray(xs))
        return {"n": len(xs), "largest_three": xs[-3:][::-1].tolist(),
                **{f"q{q}": float(np.quantile(xs, q)) for q in QUANTILES}}

    out = {"sound": {"max_share": dist([r["max_share"] for r in rows]),
                     "median_share": dist([r["median_share"] for r in rows])},
           "rule": {k: v for k, v in chk.items() if not k.endswith("_why")},
           "sound_not_ok": [r["seed"] for r in rows if not r["ok"]]}
    for name in sorted({c for r in rows for c in r.get("controls", {})}):
        got = [r["controls"][name] for r in rows if name in r.get("controls", {})]
        out[name] = {"n": len(got),
                     "smallest_max_share": min(c["max_share"] for c in got),
                     "smallest_median_share": min(c["median_share"] for c in got),
                     "passed_on_seeds": [r["seed"] for r in rows
                                         if r.get("controls", {}).get(name, {}).get("ok")]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--controls", type=int, default=0,
                    help="read the controls on the first N seeds")
    ap.add_argument("--decode-steps", type=int, default=None,
                    help="single-token steps per prompt (the mix's own if absent)")
    args = ap.parse_args(argv)

    import numpy as np

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    try:
        harness.require_tpu(cell.chips)
    except harness.NoAcceleratorError as e:
        print(f"benchmarks/logits_audit.py: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    runner = harness.load_module(cell.bench_dir / "runners" / "serve.py")
    ref = harness.load_module(
        cell.bench_dir / "reference" / f"{cell.config['reference']}.py")
    chk = cell.traffic["logits_check"]
    out_dir = ROOT / "chiprun_out" / "logits_audit"
    out_dir.mkdir(parents=True, exist_ok=True)
    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t = harness.now()
        eng, mcfg, host_params, _ = runner.build_engine(cell, seed)
        e = runner.logits_errors(cell, eng, mcfg, host_params, seed,
                                 args.decode_steps)
        v = runner.logits_verdict(chk, e["err"], e["ref_max"], e["finite"])
        row = {"seed": seed, "ok": v["ok"], "broken": v["broken"],
               "ref_max": e["ref_max"], "finite": e["finite"],
               "max_share": v["max_share"], "median_share": v["median_share"],
               "share": (e["err"] / e["ref_max"]).round(6).tolist(),
               # the contract's statistic, for a later PR: how far the
               # served argmax lies below the reference's best
               "top1_gap": (e["want"].max(-1) - np.take_along_axis(
                   e["want"], e["got"].argmax(-1)[..., None], -1)[..., 0]
               ).round(5).tolist()}
        del eng
        gc.collect()
        if hasattr(ref, "router_margins"):
            top, layer = runner.reference_inputs(host_params)
            m = np.asarray(ref.router_margins(
                top, layer, e["tokens"], cell.config))
            # the smallest margin over the layers AND over every token
            # the position attends to would be ~0 everywhere; a flip
            # moves the token it happens at most, so: that token's own
            row["router_margin_min"] = np.stack(
                [m[:, i, p].min(axis=0) for i, p in enumerate(e["pos"])]
            ).round(6).tolist()
        if n < args.controls:
            row["controls"] = {}
            for name, err in control_errors(runner, ref, e, cell, host_params).items():
                c = runner.logits_verdict(chk, err, e["ref_max"])
                row["controls"][name] = {
                    "ok": c["ok"], "max_share": c["max_share"],
                    "median_share": c["median_share"],
                    "min_share": float((err / e["ref_max"]).min())}
        del host_params, e
        gc.collect()
        row["seconds"] = round(harness.now() - t, 1)
        rows.append(row)
        print("[audit] " + json.dumps(row), flush=True)
        with open(out_dir / f"{cell.name}.json", "w") as f:
            json.dump({"decode_steps": args.decode_steps, "rows": rows}, f)
    s = summary(rows, chk)
    print("[audit] summary " + json.dumps(s), flush=True)
    with open(out_dir / f"{cell.name}.json", "w") as f:
        json.dump({"decode_steps": args.decode_steps, "rows": rows,
                   "summary": s}, f)
    print(f"[audit] {len(rows)} seeds in {harness.now() - _T0:.0f}s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
