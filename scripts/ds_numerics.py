#!/usr/bin/env python
"""ds-numerics CLI — compile-time precision-flow gate (NUMERICS.json).

Usage:
    python scripts/ds_numerics.py --capture          # write the ledger
    python scripts/ds_numerics.py --check            # exit 1 on regression
    python scripts/ds_numerics.py --check --strict   # warnings also fail

The third tier-1 pre-test gate next to `ds_lint.py --strict` and
`ds_budget.py --check --strict` (see .claude/skills/verify/SKILL.md):
a PR that sneaks a dtype downcast into a canonical program — a bf16
accumulation where the policy declares fp32, a master-weight leaf that
stops aliasing, a dropped loss-scale inf-check, fp32 leaking onto the
compressed wire — fails here before pytest ever runs. Canonical
programs, compiled on the virtual 8-device CPU mesh, no step executed:

  train_step         the zero-3 + TP bf16 fused training step
  train_step_moe     the dropless MoE zero-3 + EP + TP bf16 step — the
                     ledger pins the fp32 gate chain (router dot,
                     softmax, z-loss logsumexp) against the bf16
                     compute dtype, and the all-to-all payload dtype
  train_step_pipe3d  the interleaved-pipeline 3D bf16 step (zero-3 +
                     {data,pipe,model}, circular V=2 —
                     docs/pipeline.md): pins the stage register's
                     dtype flow through the collective-permute ring
  train_step_fp16    the fp16 dynamic-loss-scaled training step
  train_step_onebit  the 1-bit Adam compressed-momentum step
  serving_decode_w8  the width-8 paged-KV decode program
  serving_decode_w8_int8
                     the width-8 FUSED Pallas decode program over the
                     int8 per-block-quantized KV pool (pins the
                     codes -> f32-scale dequant chain)

Per program the committed NUMERICS.json records a dtype LEDGER —
additive-reduce / dot dtype histograms and convert chains from the
pre-optimization HLO (the declared precision; deterministic for a
fixed trace) plus collective payload dtypes from the compiled text —
and requires zero N-series findings. On --check a dtype key absent
from the baseline is an error; count drift on an existing key is a
warning (re-capture with --capture when the change is intended).
"""

import argparse
import json
import os
import sys
import warnings

# the virtual 8-device CPU mesh must exist BEFORE jax initializes
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

DEFAULT_PATH = os.path.join(_REPO, "NUMERICS.json")


def _model_cfg():
    from deepspeed_tpu.models import transformer as T

    return T.TransformerConfig(
        vocab_size=128, n_layers=2, n_heads=4, d_model=64, max_seq=32,
        variant="llama", use_flash=False)


def _engine(mcfg, **overrides):
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import transformer as T

    base = {"train_micro_batch_size_per_gpu": 1,
            "gradient_accumulation_steps": 2,
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "steps_per_print": 10**9}
    base.update(overrides)
    return ds.initialize(
        base, loss_fn=T.make_loss_fn(mcfg),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg))


def _train_artifacts(engine, batch, fn=None):
    """(compiled, lowered, sharded_batch) of one train-step program."""
    batch = engine._reshape_gas(batch)
    batch = engine.shard_batch(batch, leading_accum_dim=True)
    if fn is None:
        if engine._train_step_fn is None:
            engine._train_step_fn = engine._build_train_step()
        fn = engine._train_step_fn
    with warnings.catch_warnings(), engine.mesh:
        warnings.simplefilter("ignore")
        lowered = fn.lower(engine.state, batch)
        compiled = lowered.compile()
    return compiled, lowered, batch


ALL_PROGRAMS = ("train_step", "train_step_moe", "train_step_pipe3d",
                "train_step_fp16", "train_step_onebit",
                "serving_decode_w8", "serving_decode_w8_int8")


def build_programs(only=None):
    """{name: (ledger, n_error_findings, error_renders)} for the
    canonical programs (`only` filters by name — each program is an
    independent engine build, so a filtered check is proportionally
    cheaper)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.analysis.numerics import dtype_ledger
    from deepspeed_tpu.models import transformer as T

    only = set(only) if only else set(ALL_PROGRAMS)
    mcfg = _model_cfg()
    out = {}

    def record(name, compiled, lowered, report):
        errors = [f for f in report.findings if f.severity == "error"]
        out[name] = (dtype_ledger(compiled, lowered), len(errors),
                     [f.render() for f in errors[:5]])

    # zero-3 + TP bf16 fused step (the ds_budget canonical program)
    if "train_step" in only:
        eng = _engine(mcfg,
                      zero_optimization={"stage": 3,
                                         "param_persistence_threshold": 64},
                      bf16={"enabled": True}, mesh={"data": 4, "model": 2})
        batch = {"tokens": np.zeros(
            (eng.config.train_batch_size, 33), np.int32)}
        compiled, lowered, _ = _train_artifacts(eng, batch)
        record("train_step", compiled, lowered,
               eng._numerics_checks(compiled, lowered, "train_step",
                                    master=eng.state.master,
                                    opt=eng.state.opt))

    # dropless MoE zero-3 + EP + TP bf16 step (docs/moe.md): fp32 gate
    # math under a bf16 compute dtype, expert a2a payloads on the wire
    if "train_step_moe" in only:
        moe_cfg = T.TransformerConfig(
            vocab_size=128, n_layers=2, n_heads=4, d_model=64,
            max_seq=32, variant="llama", use_flash=False, n_experts=4,
            moe_top_k=2, moe_dropless=True, moe_z_loss_coef=1e-3)
        engm = _engine(moe_cfg,
                       zero_optimization={"stage": 3,
                                          "param_persistence_threshold": 64},
                       bf16={"enabled": True},
                       mesh={"data": 2, "expert": 2, "model": 2})
        batchm = {"tokens": np.zeros(
            (engm.config.train_batch_size, 33), np.int32)}
        cm, lm, _ = _train_artifacts(engm, batchm)
        record("train_step_moe", cm, lm,
               engm._numerics_checks(cm, lm, "train_step_moe",
                                     master=engm.state.master,
                                     opt=engm.state.opt))

    # interleaved-pipeline 3D bf16 step (docs/pipeline.md): zero-3 x
    # pipeline x TP, circular V=2 schedule — the ledger pins the stage
    # register's dtype flow (bf16 activations through the
    # collective-permute ring, fp32 grad accumulation under the
    # declared policy) so a precision leak into the rotate shows as a
    # new dtype key
    if "train_step_pipe3d" in only:
        import deepspeed_tpu as ds

        pcfg = T.TransformerConfig(
            vocab_size=128, n_layers=4, n_heads=4, d_model=64,
            max_seq=32, variant="llama", use_flash=False,
            pipeline_stages=2, pipeline_virtual_stages=2)
        engp = ds.initialize(
            {"train_micro_batch_size_per_gpu": 1,
             "gradient_accumulation_steps": 4,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 3,
                                   "param_persistence_threshold": 64},
             "bf16": {"enabled": True},
             "mesh": {"pipe": 2, "data": 2, "model": 2},
             "steps_per_print": 10**9},
            loss_fn=T.make_pipelined_loss_fn(pcfg),
            param_init_fn=lambda k: T.init(pcfg, k),
            param_logical_specs=T.logical_specs(pcfg),
            pipelined=True, pipeline_virtual_stages=2)
        batchp = {"tokens": np.zeros(
            (engp.config.train_batch_size, 33), np.int32)}
        cp, lp, _ = _train_artifacts(engp, batchp)
        record("train_step_pipe3d", cp, lp,
               engp._numerics_checks(cp, lp, "train_step_pipe3d",
                                     master=engp.state.master,
                                     opt=engp.state.opt))

    # fp16 dynamic-loss-scaled step
    if "train_step_fp16" in only:
        eng16 = _engine(mcfg, fp16={"enabled": True}, mesh={"data": 8})
        batch16 = {"tokens": np.zeros(
            (eng16.config.train_batch_size, 33), np.int32)}
        c16, l16, _ = _train_artifacts(eng16, batch16)
        record("train_step_fp16", c16, l16,
               eng16._numerics_checks(c16, l16, "train_step_fp16",
                                      master=eng16.state.master,
                                      opt=eng16.state.opt))

    # 1-bit Adam compressed-momentum step (+ N004 group geometry)
    if "train_step_onebit" in only:
        engob = _engine(
            mcfg,
            optimizer={"type": "onebit_adam",
                       "params": {"lr": 1e-3, "freeze_step": 2}},
            bf16={"enabled": True}, mesh={"data": 8})
        batchob = {"tokens": np.zeros(
            (engob.config.train_batch_size, 33), np.int32)}
        from deepspeed_tpu.analysis.numerics import check_quantized_groups
        from deepspeed_tpu.analysis.report import merge_reports

        cob, lob, _ = _train_artifacts(engob, batchob,
                                       fn=engob._build_onebit_step())
        rep_ob = merge_reports(
            "train_step_onebit",
            engob._numerics_checks(cob, lob, "train_step_onebit",
                                   master=engob.state.master,
                                   opt=engob.state.opt),
            check_quantized_groups(engob.state.params, dp=8,
                                   compiled_text=cob.as_text(),
                                   label="train_step_onebit"))
        record("train_step_onebit", cob, lob, rep_ob)

    # width-8 serving decode (the ds_budget serving program)
    if "serving_decode_w8" in only:
        from deepspeed_tpu.inference import init_inference

        params = T.init(mcfg, jax.random.PRNGKey(0))
        ieng = init_inference(
            params, mcfg,
            dict(max_seq_len=32, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32)
        toks = np.zeros((8,), np.int32)
        ctx = np.zeros((8,), np.int32)
        tables = np.full((8, ieng.config.blocks_per_seq), ieng.pad_block,
                         np.int32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            ld = ieng._decode_fn(8, True).lower(
                ieng.params, ieng.cache, ieng._dev(toks),
                ieng._dev(tables), ieng._dev(ctx))
            cd = ld.compile()
        record("serving_decode_w8", cd, ld,
               ieng.sanitize_numerics(widths=[8]))

    # width-8 FUSED decode over the int8 per-block-quantized KV pool
    # (kv_cache_dtype='int8', decode_impl='pallas'): the committed
    # ledger pins the dequant dtype chain — int8 codes -> f32 scale
    # multiply -> compute dtype — so a quiet downcast of the scales or
    # an integer dot sneaking in shows as a new/absent dtype key
    if "serving_decode_w8_int8" in only:
        from deepspeed_tpu.inference import init_inference

        params = T.init(mcfg, jax.random.PRNGKey(0))
        qeng = init_inference(
            params, mcfg,
            dict(max_seq_len=32, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8,
                 kv_cache_dtype="int8", decode_impl="pallas"),
            dtype=jnp.float32)
        toks = np.zeros((8,), np.int32)
        ctx = np.zeros((8,), np.int32)
        tables = np.full((8, qeng.config.blocks_per_seq), qeng.pad_block,
                         np.int32)
        from deepspeed_tpu.ops.pallas import interpret_kernels

        # a Pallas program named on the CPU: interpret mode by request
        with warnings.catch_warnings(), interpret_kernels():
            warnings.simplefilter("ignore")
            ldq = qeng._decode_fn(8, True).lower(
                qeng.params, qeng.cache, qeng._dev(toks),
                qeng._dev(tables), qeng._dev(ctx))
            cdq = ldq.compile()
            findings = qeng.sanitize_numerics(widths=[8])
        record("serving_decode_w8_int8", cdq, ldq, findings)
    return out


def capture(path: str) -> int:
    import jax

    programs = build_programs()
    dirty = {n: msgs for n, (_, errs, msgs) in programs.items() if errs}
    if dirty:
        print(json.dumps({"error": "N-series findings on the canonical "
                                   "programs; fix before capturing",
                          "findings": dirty}))
        return 1
    doc = {
        "schema": 1,
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "programs": {n: ledger for n, (ledger, _, _) in programs.items()},
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    print(json.dumps({
        "captured": path,
        "programs": {
            n: {k: sum(v.values()) if isinstance(v, dict) and
                all(not isinstance(x, dict) for x in v.values())
                else len(v)
                for k, v in ledger.items()}
            for n, (ledger, _, _) in programs.items()},
    }))
    return 0


def check(path: str, strict: bool, only=None) -> int:
    from deepspeed_tpu.analysis.numerics import diff_ledgers

    if not os.path.exists(path):
        print(json.dumps({
            "error": f"no baseline at {path}; run --capture first"}))
        return 1
    with open(path, "r", encoding="utf-8") as fh:
        base = json.load(fh)
    programs = build_programs(only=only)
    findings = []
    for name, (ledger, errs, msgs) in programs.items():
        for msg in msgs:
            findings.append({"rule": "N-series", "severity": "error",
                             "program": name, "message": msg})
        if errs and not msgs:
            findings.append({"rule": "N-series", "severity": "error",
                             "program": name,
                             "message": f"{errs} numerics finding(s)"})
        entry = base.get("programs", {}).get(name)
        if entry is None:
            findings.append({
                "rule": "N001", "severity": "warning", "program": name,
                "message": f"no baseline entry for {name}; re-capture"})
            continue
        findings.extend(
            {"rule": f.rule, "severity": f.severity, "program": name,
             "message": f.message}
            for f in diff_ledgers(ledger, entry, name))
    for name in base.get("programs", {}):
        if name not in programs and not only:
            findings.append({
                "rule": "N001", "severity": "warning", "program": name,
                "message": f"baseline program {name} was not rebuilt"})
    errors = [f for f in findings if f["severity"] == "error"]
    failed = bool(errors) or (strict and bool(findings))
    print(json.dumps({"ok": not failed, "findings": findings}))
    return 1 if failed else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--capture", action="store_true",
                    help="compile the canonical programs and write the "
                         "dtype ledger baseline")
    ap.add_argument("--check", action="store_true",
                    help="recompile and compare against the baseline; "
                         "exit 1 on any error-severity finding")
    ap.add_argument("--strict", action="store_true",
                    help="with --check: warnings also fail")
    ap.add_argument("--baseline", default=DEFAULT_PATH,
                    help=f"baseline path (default {DEFAULT_PATH})")
    ap.add_argument("--programs", nargs="*", choices=ALL_PROGRAMS,
                    help="with --check: rebuild only these programs "
                         "(each is an independent engine build)")
    args = ap.parse_args(argv)
    if args.capture == args.check:
        ap.error("pass exactly one of --capture / --check")
    if args.capture:
        if args.programs:
            ap.error("--programs only filters --check; --capture "
                     "always writes the full ledger")
        return capture(args.baseline)
    return check(args.baseline, strict=args.strict, only=args.programs)


if __name__ == "__main__":
    sys.exit(main())
