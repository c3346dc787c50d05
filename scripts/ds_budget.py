#!/usr/bin/env python
"""ds-budget CLI — compile-time memory/comm budget gate (MEMBUDGET.json).

Usage:
    python scripts/ds_budget.py --capture          # write the baseline
    python scripts/ds_budget.py --check            # exit 1 on regression
    python scripts/ds_budget.py --check --strict   # warnings also fail

The tier-1 pre-test companion to `ds_lint.py --strict` (see
.claude/skills/verify/SKILL.md): a PR that inflates a canonical
program's peak HBM footprint beyond the baseline tolerance, pushes it
past the per-device budget (S004), or regresses its per-step collective
volume (S005) fails here before pytest ever runs. Canonical programs —
compiled on the virtual 8-device CPU mesh, no step executed:

  train_step        the zero-3 + TP fused training step
                    (engine.sanitize's compiled artifact)
  train_step_moe    the dropless MoE zero-3 + EP + TP training step
                    (moe/dropless.py, docs/moe.md): expert weights
                    sharded over their own 'expert' mesh axis, the
                    dispatch/combine all-to-all pair over the expert
                    groups in this entry's collective ledger
  train_step_pipe3d the interleaved-pipeline 3D training step
                    (runtime/pipe.py, docs/pipeline.md): zero-3 +
                    {data, pipe, model} mesh, circular V=2 schedule —
                    the stage collective-permute ring rides this
                    entry's ledger, and its SCHEDULE.json entry
                    additionally pins the V=2-beats-V=1 step-time
                    projection (the interleave bubble saving)
  serving_decode_w8 the width-8 paged-KV decode program
                    (the serving warmup footprint unit)
  serving_decode_w8_int8
                    the width-8 FUSED decode program over the int8
                    per-block-quantized KV pool (decode_impl='pallas':
                    the Pallas kernel in interpret mode — in-place
                    paged indexing, no block-table gather). Also
                    carries the kv_bytes_per_token capacity ratio the
                    budgets section pins at >= 1.8x.

Everything is compile-time static analysis: byte counts come from
compiled.memory_analysis() and the HLO text, so the gate runs anywhere
(CI, laptops) without an accelerator.
"""

import argparse
import json
import os
import sys

# the virtual 8-device CPU mesh must exist BEFORE jax initializes
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

DEFAULT_PATH = os.path.join(_REPO, "MEMBUDGET.json")


def _attach_overlap_pin(on_san, off_san):
    """Attach the `_overlap` rider to an overlap-on report: measured
    exposure, the budget ceiling (25% headroom + 2pt floor over the
    measured fraction, frozen at capture), and the serialized twin's
    step-time/exposure — ds_schedule serializes and enforces these."""
    if on_san.cost is None or off_san.cost is None:
        return
    s_on = getattr(on_san.cost, "_schedule", None)
    s_off = getattr(off_san.cost, "_schedule", None)
    if s_on is None or s_off is None:
        return
    frac = s_on.exposed_comm_fraction
    on_san.cost._overlap = {
        "exposed_comm_fraction": round(frac, 6),
        "budget": round(min(1.0, frac * 1.25 + 0.02), 6),
        "overlap_off_step_time_us": round(s_off.step_time_s * 1e6, 3),
        "overlap_off_exposed_us": round(s_off.exposed_s * 1e6, 3),
    }


def build_reports():
    """{name: CostReport} for the canonical programs + the live sharded
    param bytes of the train engine (the S005 denominator)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.analysis.costmodel import build_cost_report
    from deepspeed_tpu.models import transformer as T

    mcfg = T.TransformerConfig(
        vocab_size=128, n_layers=2, n_heads=4, d_model=64, max_seq=32,
        variant="llama", use_flash=False)

    def _train_engine(overlap=True):
        return ds.initialize(
            {"train_micro_batch_size_per_gpu": 1,
             "gradient_accumulation_steps": 2,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 3,
                                   "param_persistence_threshold": 64,
                                   "overlap_comm": overlap},
             "bf16": {"enabled": True},
             "mesh": {"data": 4, "model": 2},
             "steps_per_print": 10**9},
            loss_fn=T.make_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg))

    engine = _train_engine()
    batch = {"tokens": np.zeros(
        (engine.config.train_batch_size, 33), np.int32)}
    san = engine.sanitize(batch)
    # the serialized twin: same program, overlap_comm: false — no
    # prefetch/bucket restructure and every sync collective scored
    # fully exposed. The pair is SCHEDULE.json's S007/S009 exposure
    # pin: overlap-on fraction <= budget AND overlap-on step time
    # strictly under the twin's (docs/overlap.md)
    off_san = _train_engine(overlap=False).sanitize(batch)
    _attach_overlap_pin(san, off_san)
    tree = engine.state.master if engine._use_master else engine.state.params
    live = int(sum(x.nbytes for x in jax.tree.leaves(tree)))

    # dropless MoE zero-3 + EP + TP train step: the expert-parallel
    # canonical program — S005/S007/S009 must keep attributing its
    # dispatch/combine all-to-all pair with 'expert' replica groups
    moe_cfg = T.TransformerConfig(
        vocab_size=128, n_layers=2, n_heads=4, d_model=64, max_seq=32,
        variant="llama", use_flash=False, n_experts=4, moe_top_k=2,
        moe_dropless=True, moe_z_loss_coef=1e-3)
    moe_engine = ds.initialize(
        {"train_micro_batch_size_per_gpu": 1,
         "gradient_accumulation_steps": 2,
         "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
         "zero_optimization": {"stage": 3, "param_persistence_threshold": 64},
         "bf16": {"enabled": True},
         "mesh": {"data": 2, "expert": 2, "model": 2},
         "steps_per_print": 10**9},
        loss_fn=T.make_loss_fn(moe_cfg),
        param_init_fn=lambda k: T.init(moe_cfg, k),
        param_logical_specs=T.logical_specs(moe_cfg))
    moe_batch = {"tokens": np.zeros(
        (moe_engine.config.train_batch_size, 33), np.int32)}
    moe_san = moe_engine.sanitize(moe_batch)

    # interleaved-pipeline 3D train step (docs/pipeline.md): zero-3 x
    # pipeline x TP on one mesh, circular V=2 schedule at seq 128 (the
    # flops/bytes regime where the interleave's wasted-work division
    # is visible — the V=1 twin is compiled alongside and the pair's
    # S009 projections ride SCHEDULE.json as the committed
    # interleave-wins pin)
    def _pipe_engine(v, overlap=True):
        pcfg = T.TransformerConfig(
            vocab_size=128, n_layers=4, n_heads=4, d_model=64,
            max_seq=128, variant="llama", use_flash=False,
            pipeline_stages=2, pipeline_virtual_stages=v)
        eng_p = ds.initialize(
            {"train_micro_batch_size_per_gpu": 2,
             "gradient_accumulation_steps": 8,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 3,
                                   "param_persistence_threshold": 64,
                                   "overlap_comm": overlap},
             "bf16": {"enabled": True},
             "mesh": {"pipe": 2, "data": 2, "model": 2},
             "steps_per_print": 10**9},
            loss_fn=T.make_pipelined_loss_fn(pcfg),
            param_init_fn=lambda k: T.init(pcfg, k),
            param_logical_specs=T.logical_specs(pcfg),
            pipelined=True, pipeline_virtual_stages=v)
        batch_p = {"tokens": np.zeros(
            (eng_p.config.train_batch_size, 129), np.int32)}
        return eng_p.sanitize(batch_p)

    pipe_san = _pipe_engine(2)
    pipe_v1_san = _pipe_engine(1)
    _attach_overlap_pin(pipe_san, _pipe_engine(2, overlap=False))
    if pipe_san.cost is not None and pipe_v1_san.cost is not None:
        s2 = getattr(pipe_san.cost, "_schedule", None)
        s1 = getattr(pipe_v1_san.cost, "_schedule", None)
        if s1 is not None and s2 is not None:
            pipe_san.cost._pipe_projection = {
                "v1_step_time_us": round(s1.step_time_s * 1e6, 3),
                "v2_step_time_us": round(s2.step_time_s * 1e6, 3),
            }

    from deepspeed_tpu.inference import init_inference
    import jax.numpy as jnp

    params = T.init(mcfg, jax.random.PRNGKey(0))
    icfg = dict(max_seq_len=32, kv_block_size=8, num_kv_blocks=32,
                min_prefill_bucket=8, max_batch_size=8)
    eng = init_inference(params, mcfg, dict(icfg), dtype=jnp.float32)
    decode_cost = build_cost_report(eng.compiled_decode(8),
                                    label="serving_decode[w8]")

    # the int8-quantized FUSED decode program (kv_cache_dtype='int8',
    # decode_impl='pallas' — the Pallas kernel in interpret mode, so
    # the canonical artifact is the in-place paged indexing path, not
    # the gather oracle). Three committed verdicts ride this program:
    # the KV capacity ratio (budgets, >= 1.8x), the S006 roofline
    # bound, and the max-gather probe (SCHEDULE.json — a regression
    # back to the block-table gather materialization fails ds_schedule)
    from deepspeed_tpu.analysis.costmodel import roofline
    from deepspeed_tpu.platform.accelerator import chip_roofline
    from deepspeed_tpu.profiling.hlo import max_gather_bytes

    from deepspeed_tpu.ops.pallas import interpret_kernels

    eng_q = init_inference(
        params, mcfg, dict(icfg, kv_cache_dtype="int8",
                           decode_impl="pallas"),
        dtype=jnp.float32)
    with interpret_kernels():  # a Pallas program named on the CPU
        compiled_q = eng_q.compiled_decode(8)
    quant_cost = build_cost_report(compiled_q,
                                   label="serving_decode[w8,int8kv]")
    if quant_cost is not None:
        # the verdict projects the SERVING chip's balance point (v5e
        # flagship profile from the chip-table authority) — the CPU
        # host's degenerate 1:1 flops:bytes profile would call any
        # program with intensity > 1 compute-bound
        peak, hbm_bw = chip_roofline("v5e")
        quant_cost._s006_bound = roofline(
            quant_cost, peak, hbm_bw)["bound"]
        quant_cost._max_gather_bytes = max_gather_bytes(
            compiled_q.as_text())
        quant_cost._kv_bytes_per_token = {
            "ref": eng.kv_bytes_per_token(),
            "int8": eng_q.kv_bytes_per_token(),
        }

    reports = {}
    if san.cost is not None:
        reports["train_step"] = san.cost
    if moe_san.cost is not None:
        reports["train_step_moe"] = moe_san.cost
    if pipe_san.cost is not None:
        reports["train_step_pipe3d"] = pipe_san.cost
    if decode_cost is not None:
        reports["serving_decode_w8"] = decode_cost
    if quant_cost is not None:
        reports["serving_decode_w8_int8"] = quant_cost
    return reports, live


def capture(path: str) -> int:
    import jax

    from deepspeed_tpu.analysis.costmodel import save_baseline
    from deepspeed_tpu.platform.accelerator import get_accelerator

    reports, live = build_reports()
    if not reports:
        print(json.dumps({"error": "no cost artifacts available on this "
                                   "backend; baseline not written"}))
        return 1
    kv = getattr(reports.get("serving_decode_w8_int8"),
                 "_kv_bytes_per_token", None)
    doc = save_baseline(
        path, reports,
        budgets={
            "hbm_per_device_bytes": get_accelerator().hbm_per_device(),
            "hbm_regression_tolerance": 0.10,
            "collective_k": 6.0,  # 2*gas+2 of the canonical train engine
            "live_sharded_bytes": live,
            # int8 per-block KV quantization capacity win: resident
            # bytes/token of the reference pool vs the quantized pool
            # (engine.kv_bytes_per_token — codes + scale tiles), and
            # the floor --check enforces
            "kv_bytes_per_token_ref": int(kv["ref"]) if kv else 0,
            "kv_bytes_per_token_int8": int(kv["int8"]) if kv else 0,
            "kv_capacity_ratio_min": 1.8,
        },
        meta={"platform": jax.default_backend(),
              "device_count": jax.device_count(),
              "jax_version": jax.__version__},
    )
    print(json.dumps({
        "captured": path,
        "programs": {n: p["peak_hbm_bytes"]
                     for n, p in doc["programs"].items()},
    }))
    return 0


def check(path: str, strict: bool) -> int:
    from deepspeed_tpu.analysis.costmodel import (
        check_against_baseline,
        check_collective_volume,
        check_hbm_budget,
        load_baseline,
    )

    base = load_baseline(path)
    if base is None:
        print(json.dumps({
            "error": f"no baseline at {path}; run --capture first"}))
        return 1
    budgets = base.get("budgets", {})
    tol = float(budgets.get("hbm_regression_tolerance", 0.10))
    k = float(budgets.get("collective_k", 6.0))
    live = int(budgets.get("live_sharded_bytes", 0))
    hbm_budget = int(budgets.get("hbm_per_device_bytes", 0)) or None

    reports, _ = build_reports()
    findings = []
    summary = {}
    # int8-KV capacity floor: the quantized pool must keep >= the
    # committed ratio more resident tokens per byte than the reference
    # pool — a scale-tensor widening (or a quiet dequant-at-rest
    # regression) fails here before pytest ever runs
    kv = getattr(reports.get("serving_decode_w8_int8"),
                 "_kv_bytes_per_token", None)
    if kv:
        ratio_min = float(budgets.get("kv_capacity_ratio_min", 1.8))
        ratio = kv["ref"] / max(1, kv["int8"])
        summary["kv_bytes_per_token"] = {
            "ref": int(kv["ref"]), "int8": int(kv["int8"]),
            "ratio": round(ratio, 2), "min": ratio_min}
        if ratio < ratio_min:
            findings.append({
                "rule": "S004", "severity": "error",
                "program": "serving_decode_w8_int8",
                "message": (
                    f"int8 KV pool holds only {ratio:.2f}x more tokens "
                    f"per byte than the reference pool (floor "
                    f"{ratio_min}x): {kv['int8']} vs {kv['ref']} "
                    "bytes/token — scale tensors grew or codes widened")})
    for name, rep in reports.items():
        entry = base.get("programs", {}).get(name)
        if entry is None:
            findings.append({
                "rule": "S004", "severity": "warning", "program": name,
                "message": f"no baseline entry for {name}; re-capture"})
            continue
        checks = [
            check_against_baseline(rep, entry, tolerance=tol, label=name),
            check_hbm_budget(rep, budget_bytes=hbm_budget, label=name),
            check_collective_volume(
                rep, live_sharded_bytes=(live or None) if
                name == "train_step" else None,
                k=k, baseline=entry, tolerance=tol, label=name),
        ]
        for c in checks:
            findings.extend(
                {"rule": f.rule, "severity": f.severity, "program": name,
                 "message": f.message}
                for f in c.findings)
        summary[name] = {
            "peak_hbm_bytes": rep.peak_hbm_bytes,
            "baseline_peak_hbm_bytes": entry.get("peak_hbm_bytes"),
            "comm_bytes": rep.comm_bytes,
            "baseline_comm_bytes": entry.get("comm_bytes"),
        }
    for name in base.get("programs", {}):
        if name not in reports:
            findings.append({
                "rule": "S004", "severity": "warning", "program": name,
                "message": f"baseline program {name} was not rebuilt "
                           "(backend without cost artifacts?)"})
    errors = [f for f in findings if f["severity"] == "error"]
    failed = bool(errors) or (strict and bool(findings))
    print(json.dumps({"ok": not failed, "findings": findings,
                      "programs": summary}))
    return 1 if failed else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--capture", action="store_true",
                    help="compile the canonical programs and write the "
                         "baseline")
    ap.add_argument("--check", action="store_true",
                    help="recompile and compare against the baseline; "
                         "exit 1 on any error-severity finding")
    ap.add_argument("--strict", action="store_true",
                    help="with --check: warnings also fail")
    ap.add_argument("--baseline", default=DEFAULT_PATH,
                    help=f"baseline path (default {DEFAULT_PATH})")
    args = ap.parse_args(argv)
    if args.capture == args.check:
        ap.error("pass exactly one of --capture / --check")
    if args.capture:
        return capture(args.baseline)
    return check(args.baseline, strict=args.strict)


if __name__ == "__main__":
    sys.exit(main())
