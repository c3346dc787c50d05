#!/usr/bin/env python
"""ds-gate: the development gates, one driver (docs/static_analysis.md).

Usage:
    python scripts/ds_gate.py all --check --strict    # before tier-1
    python scripts/ds_gate.py <gate> [--check|--capture] [--strict]
                                     [--baseline PATH] [--json]

A gate builds something on the CPU (an AST pass, the canonical programs
compiled for the virtual 8-device mesh, a `bench.py` lane under its
virtual clock), holds it to its named conditions and, where it has a
baseline, to that file. Nothing here executes on an accelerator and no
number here is a device metric. The table `GATES` at the end of this
file is the list; docs/static_analysis.md says what each pins.

  --check     compare against the baseline (the default)
  --capture   rebuild and write the baseline; refused while a finding
              stands, and for a subset run
  --strict    warnings fail too (drift of waivers alone, a count that
              moved inside its tolerance class)
  --baseline  another file than the gate's committed one
  --json      also print what was measured (what --capture would write)

An error-severity finding is red in every mode. Baselines hold findings,
waivers and pinned numbers, never line numbers or inventories: re-capture
when a finding changes, in the PR that changes it.

Gate-specific flags: `--programs a,b` (numerics, determinism: a subset
of the canonical programs), `--rules L003,...` (lifecycle),
`--static-only` (race), `--plan P` / `--replicas N` (the `bench.py`
lanes), `--show-suppressed` / `--rules` / paths (lint).
"""

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import tempfile
import traceback
import warnings
from typing import Any, Callable, Dict, List, Optional

# the virtual 8-device CPU mesh must exist BEFORE jax initializes
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


# ----------------------------------------------------------------------
# plumbing, written once
# ----------------------------------------------------------------------

@dataclasses.dataclass
class Built:
    """What a gate's build hands the driver.

    findings: dicts {rule, severity, where, message[, hint]}
    measured: the document --capture writes and --json prints (None: the
              gate writes its own baseline, or has none)
    data:     whatever the gate's compare needs beyond `measured`
    view:     committed -> the part of it this (subset) run measured
    partial:  why this run may not be captured (a subset flag)
    compare:  False: this run is not held to the baseline at all
    summary:  extra keys of the status line
    """

    findings: List[dict] = dataclasses.field(default_factory=list)
    measured: Any = None
    data: Any = None
    view: Optional[Callable[[dict], dict]] = None
    partial: str = ""
    compare: bool = True
    summary: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _finding(rule, where, message, severity="error", hint=""):
    f = {"rule": rule, "severity": severity, "where": where,
         "message": message}
    if hint:
        f["hint"] = hint
    return f


def _from_reports(where, *reports):
    """Finding dicts of analysis reports (SanitizerReport & co)."""
    return [_finding(f.rule, where or (f"{f.path}:{f.line}" if f.line
                                       else f.path),
                     f.message, f.severity, f.fix_hint)
            for r in reports for f in r.findings]


def _say(name, msg):
    print(f"[ds-{name}] {msg}", file=sys.stderr)


def _load(path):
    """(document, None) or (None, why not)."""
    if not os.path.exists(path):
        return None, f"no baseline at {path} — run --capture first"
    try:
        with open(path, "r", encoding="utf-8") as fh:
            return json.load(fh), None
    except (OSError, ValueError) as e:
        return None, f"unreadable baseline {path}: {e}"


def _write(path, doc):
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")


def _jax_meta():
    import jax

    return {"platform": jax.default_backend(),
            "device_count": jax.device_count(),
            "jax_version": jax.__version__}


def _subset(arg, known, what, error):
    """A comma-separated flag against the known names (None: all);
    `error` is the parser's."""
    if not arg:
        return None
    names = [n.strip() for n in arg.split(",") if n.strip()]
    unknown = [n for n in names if n not in known]
    if unknown:
        error(f"unknown {what}(s) {unknown}; choose from {list(known)}")
    return names


def _at(doc, path):
    for k in path:
        doc = (doc or {}).get(k)
    return doc


def _strip_suppressions(ledger, patterns):
    """A deep copy without the waiver lists `patterns` name (tuples of
    keys, "*" for every key): what non-strict mode compares."""
    out = json.loads(json.dumps(ledger))

    def drop(node, pat):
        if not isinstance(node, dict):
            return
        if len(pat) == 1:
            node.pop(pat[0], None)
            return
        for k in (list(node) if pat[0] == "*" else [pat[0]]):
            drop(node.get(k), pat[1:])

    for pat in patterns:
        drop(out, pat)
    return out


def _diff(name, committed, measured, sections):
    """Print where two ledgers differ. `sections`: (key path, label,
    one line a key?)."""
    for path, label, per_key in sections:
        c, m = _at(committed, path), _at(measured, path)
        if c == m:
            continue
        keys = sorted(set(c or {}) | set(m or {})) if per_key else [None]
        for k in keys:
            ck, mk = ((c or {}).get(k), (m or {}).get(k)) if per_key \
                else (c, m)
            if ck == mk:
                continue
            _say(name, f"{label} drift" + (f": {k}" if per_key else ":"))
            _say(name, f"    committed: {json.dumps(ck, sort_keys=True)}")
            _say(name, f"    measured:  {json.dumps(mk, sort_keys=True)}")


def _ledger_compare(name, sections, suppressions):
    """compare() of the exact-ledger gates (race, determinism,
    lifecycle): findings have no baseline; the ledger is pinned byte
    for byte, and drift of the waiver lists alone is a warning."""
    def compare(built, committed):
        measured = built.measured
        if built.view is not None:
            committed = built.view(committed)
        if committed == measured:
            return []
        _diff(name, committed, measured, sections)
        if _strip_suppressions(committed, suppressions) == \
                _strip_suppressions(measured, suppressions):
            return [_finding("ledger", name, "suppression drift "
                             "(non-strict: warning only)", "warning")]
        return [_finding(
            "ledger", name,
            "ledger drift: rerun with --capture after review (findings "
            "never have a baseline; the ledger and the waivers do)")]
    return compare


def _programs_compare(rule, check_program, check_doc=None):
    """compare() of the tolerance gates (budget, schedule, numerics):
    each rebuilt program against its baseline entry; a program on one
    side only is a warning."""
    def compare(built, committed):
        programs = built.data
        entries = committed.get("programs", {})
        findings = list(check_doc(built, committed)) if check_doc else []
        for pname, prog in programs.items():
            if pname not in entries:
                findings.append(_finding(
                    rule, pname, f"no baseline entry for {pname}; "
                    "re-capture", "warning"))
                continue
            findings.extend(check_program(pname, prog, entries[pname],
                                          committed, built))
        if not built.partial:
            findings.extend(
                _finding(rule, pname, f"baseline program {pname} was "
                         "not rebuilt", "warning")
                for pname in entries if pname not in programs)
        return findings
    return compare


# ----------------------------------------------------------------------
# the canonical programs, built once a process (budget, schedule,
# numerics and determinism read the same engines)
# ----------------------------------------------------------------------

_ADAMW = {"type": "adamw", "params": {"lr": 1e-3}}
_BF16 = {"enabled": True}


def _mcfg(**kw):
    from deepspeed_tpu.models import transformer as T

    base = dict(vocab_size=128, n_layers=2, n_heads=4, d_model=64,
                max_seq=32, variant="llama", use_flash=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def _zero3(overlap=True):
    return {"stage": 3, "param_persistence_threshold": 64,
            "overlap_comm": overlap}


def _train_engine(mcfg, micro=1, gas=2, optimizer=_ADAMW, **cfg):
    """(engine, host batch of zeros) of one training configuration."""
    import jax
    import numpy as np

    jax.config.update("jax_platforms", "cpu")
    import deepspeed_tpu as ds
    from deepspeed_tpu.models import transformer as T

    pipelined = mcfg.pipeline_stages > 1
    kw = dict(pipelined=True,
              pipeline_virtual_stages=mcfg.pipeline_virtual_stages) \
        if pipelined else {}
    eng = ds.initialize(
        {"train_micro_batch_size_per_gpu": micro,
         "gradient_accumulation_steps": gas, "optimizer": optimizer,
         "steps_per_print": 10**9, **cfg},
        loss_fn=(T.make_pipelined_loss_fn(mcfg) if pipelined
                 else T.make_loss_fn(mcfg)),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg), **kw)
    batch = {"tokens": np.zeros(
        (eng.config.train_batch_size, mcfg.max_seq + 1), np.int32)}
    return eng, batch


@functools.lru_cache(maxsize=None)
def _canonical(name, overlap=True, v=2):
    """The canonical training engines (docs/static_analysis.md):
    train_step        zero-3 + TP, bf16, mesh {data 4, model 2}
    train_step_moe    dropless MoE, zero-3 + EP + TP (docs/moe.md)
    train_step_pipe3d zero-3 x pipeline x TP, circular V schedule at
                      seq 128, where the interleave's saving is visible
                      (docs/pipeline.md)"""
    if name == "train_step":
        return _train_engine(_mcfg(), zero_optimization=_zero3(overlap),
                             bf16=_BF16, mesh={"data": 4, "model": 2})
    if name == "train_step_moe":
        return _train_engine(
            _mcfg(n_experts=4, moe_top_k=2, moe_dropless=True,
                  moe_z_loss_coef=1e-3),
            zero_optimization=_zero3(overlap), bf16=_BF16,
            mesh={"data": 2, "expert": 2, "model": 2})
    assert name == "train_step_pipe3d", name
    return _train_engine(
        _mcfg(n_layers=4, max_seq=128, pipeline_stages=2,
              pipeline_virtual_stages=v),
        micro=2, gas=8, zero_optimization=_zero3(overlap), bf16=_BF16,
        mesh={"pipe": 2, "data": 2, "model": 2})


@functools.lru_cache(maxsize=None)
def _serving_engine(int8=False):
    """The width-8 paged-KV decode engine; `int8`: the FUSED Pallas
    decode over the per-block-quantized pool (interpret mode here)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import init_inference
    from deepspeed_tpu.models import transformer as T

    mcfg = _mcfg()
    icfg = dict(max_seq_len=32, kv_block_size=8, num_kv_blocks=32,
                min_prefill_bucket=8, max_batch_size=8)
    if int8:
        icfg.update(kv_cache_dtype="int8", decode_impl="pallas")
    return init_inference(T.init(mcfg, jax.random.PRNGKey(0)), mcfg,
                          icfg, dtype=jnp.float32)


def _lower_train(eng, batch, fn=None):
    """(compiled, lowered) of an engine's train step."""
    batch = eng.shard_batch(eng._reshape_gas(batch),
                            leading_accum_dim=True)
    if fn is None:
        if eng._train_step_fn is None:
            eng._train_step_fn = eng._build_train_step()
        fn = eng._train_step_fn
    with warnings.catch_warnings(), eng.mesh:
        warnings.simplefilter("ignore")
        lowered = fn.lower(eng.state, batch)
        return lowered.compile(), lowered


def _interpret(eng):
    """A Pallas program named on the CPU runs in interpret mode, by
    request; the reference program must not see that request."""
    from deepspeed_tpu.ops.pallas import interpret_kernels

    return interpret_kernels() \
        if eng.config.decode_impl == "pallas" else contextlib.nullcontext()


def _lower_decode(eng):
    """(compiled, lowered) of a serving engine's width-8 decode."""
    import numpy as np

    toks = np.zeros((8,), np.int32)
    tables = np.full((8, eng.config.blocks_per_seq), eng.pad_block,
                     np.int32)
    with warnings.catch_warnings(), _interpret(eng):
        warnings.simplefilter("ignore")
        lowered = eng._decode_fn(8, True).lower(
            eng.params, eng.cache, eng._dev(toks), eng._dev(tables),
            eng._dev(toks))
        return lowered.compile(), lowered


@functools.lru_cache(maxsize=None)
def _artifacts(name):
    """(compiled, lowered) of a canonical program, by its ledger name."""
    if name == "serving_decode_w8":
        return _lower_decode(_serving_engine())
    if name == "serving_decode_w8_int8":
        return _lower_decode(_serving_engine(int8=True))
    return _lower_train(*_canonical(name))


# ----------------------------------------------------------------------
# lint: R-series over the package (analysis/lint.py); no baseline
# ----------------------------------------------------------------------

def build_lint(opts):
    from deepspeed_tpu.analysis.lint import RULES, lint_paths

    if opts.rules is not None and not opts.paths:
        for rule, desc in sorted(RULES.items()):
            print(f"{rule}  {desc}")
        return Built()
    report = lint_paths(
        opts.paths or [os.path.join(_REPO, "deepspeed_tpu")], base=_REPO)
    if opts.show_suppressed and report.suppressed:
        print("-- suppressed by pragma --")
        for f in report.suppressed:
            print(f.render())
    print(report.summary())
    # a lint finding fails under --strict alone, as it always has
    return Built(
        findings=[dict(d, severity="warning")
                  for d in _from_reports(None, report)],
        measured={
            "findings": [dataclasses.asdict(f) for f in report.findings],
            "suppressed": [dataclasses.asdict(f)
                           for f in report.suppressed],
            "files_checked": report.files_checked,
            "by_rule": report.by_rule()})


# ----------------------------------------------------------------------
# budget (MEMBUDGET.json) and schedule (SCHEDULE.json): the five
# canonical programs' static cost and schedule, compiled, never run
# ----------------------------------------------------------------------

STEP_TIME_TOLERANCE = 0.10   # relative drift that fails schedule
MIN_EXPOSED_US = 50.0        # reporting floor for exposure findings


def _sched(san):
    return getattr(san.cost, "_schedule", None) \
        if san.cost is not None else None


def _overlap_pin(on_san, off_san):
    """The `overlap` rider of an overlap-on report: its exposed-comm
    fraction, that fraction's ceiling (25% headroom + 2 points, frozen
    at capture) and the serialized twin's projection. Each is a pin of
    a number against its own baseline, not a comparison of the two
    (docs/overlap.md)."""
    s_on, s_off = _sched(on_san), _sched(off_san)
    if s_on is None or s_off is None:
        return
    frac = s_on.exposed_comm_fraction
    on_san.cost._overlap = {
        "exposed_comm_fraction": round(frac, 6),
        "budget": round(min(1.0, frac * 1.25 + 0.02), 6),
        "overlap_off_step_time_us": round(s_off.step_time_s * 1e6, 3),
        "overlap_off_exposed_us": round(s_off.exposed_s * 1e6, 3),
    }


@functools.lru_cache(maxsize=None)
def build_reports():
    """({name: CostReport}, live sharded param bytes of the train
    engine: the S005 denominator). One builder for budget and schedule,
    so the two baselines can never describe different programs."""
    import jax

    from deepspeed_tpu.analysis.costmodel import build_cost_report, roofline
    from deepspeed_tpu.platform.accelerator import chip_roofline
    from deepspeed_tpu.profiling.hlo import max_gather_bytes

    def sanitize(name, **kw):
        eng, batch = _canonical(name, **kw)
        return eng.sanitize(batch)

    san = sanitize("train_step")
    _overlap_pin(san, sanitize("train_step", overlap=False))
    engine = _canonical("train_step")[0]
    tree = engine.state.master if engine._use_master \
        else engine.state.params
    live = int(sum(x.nbytes for x in jax.tree.leaves(tree)))

    moe_san = sanitize("train_step_moe")

    # the V=1 twin is compiled alongside: the pair's S009 projections
    # ride SCHEDULE.json as the interleave-wins pin
    pipe_san = sanitize("train_step_pipe3d")
    pipe_v1_san = sanitize("train_step_pipe3d", v=1)
    _overlap_pin(pipe_san, sanitize("train_step_pipe3d", overlap=False))
    s2, s1 = _sched(pipe_san), _sched(pipe_v1_san)
    if s1 is not None and s2 is not None:
        pipe_san.cost._pipe_projection = {
            "v1_step_time_us": round(s1.step_time_s * 1e6, 3),
            "v2_step_time_us": round(s2.step_time_s * 1e6, 3),
        }

    eng, eng_q = _serving_engine(), _serving_engine(int8=True)
    decode_cost = build_cost_report(eng.compiled_decode(8),
                                    label="serving_decode[w8]")
    # three verdicts ride the int8 program: the KV capacity ratio
    # (budget, >= 1.8x), the S006 roofline bound and the max-gather
    # probe (schedule: a regression back to the block-table gather
    # materialization fails there)
    with _interpret(eng_q):
        compiled_q = eng_q.compiled_decode(8)
    quant_cost = build_cost_report(compiled_q,
                                   label="serving_decode[w8,int8kv]")
    if quant_cost is not None:
        # projected for the SERVING chip's balance point (v5e): the CPU
        # host's 1:1 flops:bytes profile would call any program with
        # intensity > 1 compute-bound
        peak, hbm_bw = chip_roofline("v5e")
        quant_cost._s006_bound = roofline(
            quant_cost, peak, hbm_bw)["bound"]
        quant_cost._max_gather_bytes = max_gather_bytes(
            compiled_q.as_text())
        quant_cost._kv_bytes_per_token = {
            "ref": eng.kv_bytes_per_token(),
            "int8": eng_q.kv_bytes_per_token(),
        }

    reports = {
        "train_step": san.cost, "train_step_moe": moe_san.cost,
        "train_step_pipe3d": pipe_san.cost,
        "serving_decode_w8": decode_cost,
        "serving_decode_w8_int8": quant_cost}
    return {n: r for n, r in reports.items() if r is not None}, live


def build_budget(opts):
    from deepspeed_tpu.analysis.costmodel import baseline_doc
    from deepspeed_tpu.platform.accelerator import get_accelerator

    reports, live = build_reports()
    if not reports:
        return Built(findings=[_finding(
            "S004", "budget", "no cost artifacts available on this "
            "backend")])
    kv = getattr(reports.get("serving_decode_w8_int8"),
                 "_kv_bytes_per_token", None)
    doc = baseline_doc(
        reports,
        budgets={
            "hbm_per_device_bytes": get_accelerator().hbm_per_device(),
            "hbm_regression_tolerance": 0.10,
            "collective_k": 6.0,  # 2*gas+2 of the canonical train engine
            "live_sharded_bytes": live,
            # resident bytes/token of the reference pool vs the int8
            # pool (engine.kv_bytes_per_token: codes + scale tiles), and
            # the floor --check enforces
            "kv_bytes_per_token_ref": int(kv["ref"]) if kv else 0,
            "kv_bytes_per_token_int8": int(kv["int8"]) if kv else 0,
            "kv_capacity_ratio_min": 1.8,
        },
        meta=_jax_meta())
    return Built(measured=doc, data=reports, summary={"programs": {
        n: {"peak_hbm_bytes": r.peak_hbm_bytes, "comm_bytes": r.comm_bytes}
        for n, r in reports.items()}})


def _budget_doc(built, base):
    """The int8-KV capacity floor: the quantized pool must keep >= the
    committed ratio more resident tokens per byte than the reference
    pool (a scale-tensor widening fails here)."""
    kv = getattr(built.data.get("serving_decode_w8_int8"),
                 "_kv_bytes_per_token", None)
    if not kv:
        return
    ratio_min = float(base.get("budgets", {}).get(
        "kv_capacity_ratio_min", 1.8))
    ratio = kv["ref"] / max(1, kv["int8"])
    built.summary["programs"]["kv_bytes_per_token"] = {
        "ref": int(kv["ref"]), "int8": int(kv["int8"]),
        "ratio": round(ratio, 2), "min": ratio_min}
    if ratio < ratio_min:
        yield _finding(
            "S004", "serving_decode_w8_int8",
            f"int8 KV pool holds only {ratio:.2f}x more tokens per byte "
            f"than the reference pool (floor {ratio_min}x): "
            f"{kv['int8']} vs {kv['ref']} bytes/token — scale tensors "
            "grew or codes widened")


def _budget_program(name, rep, entry, base, built):
    from deepspeed_tpu.analysis.costmodel import (
        check_against_baseline, check_collective_volume, check_hbm_budget)

    budgets = base.get("budgets", {})
    tol = float(budgets.get("hbm_regression_tolerance", 0.10))
    live = int(budgets.get("live_sharded_bytes", 0))
    built.summary["programs"][name].update(
        baseline_peak_hbm_bytes=entry.get("peak_hbm_bytes"),
        baseline_comm_bytes=entry.get("comm_bytes"))
    return _from_reports(
        name,
        check_against_baseline(rep, entry, tolerance=tol, label=name),
        check_hbm_budget(
            rep, label=name,
            budget_bytes=int(budgets.get("hbm_per_device_bytes", 0))
            or None),
        check_collective_volume(
            rep, live_sharded_bytes=(live or None)
            if name == "train_step" else None,
            k=float(budgets.get("collective_k", 6.0)), baseline=entry,
            tolerance=tol, label=name))


def _schedule_entry(rep, sched):
    d = sched.to_dict()
    e = {k: round(d[k], 3) for k in ("step_time_us", "exposed_us",
                                     "compute_us", "comm_us")}
    e.update({k: d[k] for k in ("n_collectives", "n_async", "n_sync")})
    # the riders build_reports attached: the interleave-wins pin
    # (docs/pipeline.md), the overlap exposure pins (docs/overlap.md)
    for key, attr in (("pipe_projection", "_pipe_projection"),
                      ("overlap", "_overlap")):
        if getattr(rep, attr, None) is not None:
            e[key] = getattr(rep, attr)
    bound = getattr(rep, "_s006_bound", None)
    if bound is not None:
        # the fused int8-KV decode program's S006 verdict (must be
        # memory- i.e. bandwidth-bound) and the max-gather probe: the
        # limit passes table and embedding lookups and fails ANY
        # [S, NB*bs, ...] block-table materialization
        gb = int(getattr(rep, "_max_gather_bytes", 0))
        e.update(s006_bound=bound, max_gather_bytes=gb,
                 gather_bytes_limit=max(4096, 2 * gb))
    return e


def build_schedule(opts):
    reports, _live = build_reports()
    scheds = {n: (r, r._schedule) for n, r in reports.items()
              if getattr(r, "_schedule", None) is not None}
    if not scheds:
        return Built(findings=[_finding(
            "S009", "schedule", "no schedule artifacts available on "
            "this backend")])
    doc = {
        "schema": 1, **_jax_meta(),
        "tolerances": {
            # relative step-time drift that fails --check; exposure
            # regressions get an absolute floor as well, so near-zero
            # baselines do not amplify noise
            "step_time_tolerance": STEP_TIME_TOLERANCE,
            "min_exposed_us": MIN_EXPOSED_US,
        },
        "programs": {n: _schedule_entry(r, s)
                     for n, (r, s) in scheds.items()},
    }
    return Built(measured=doc, data=scheds, summary={"programs": {
        n: {"step_time_us": round(s.step_time_s * 1e6, 3),
            "exposed_us": round(s.exposed_s * 1e6, 3),
            "n_collectives": s.n_collectives}
        for n, (_r, s) in scheds.items()}})


def _schedule_program(name, pair, entry, base, built):
    from deepspeed_tpu.analysis.schedule import (check_exposed_comm,
                                                 check_step_time)

    rep, sched = pair
    tols = base.get("tolerances", {})
    tol = float(tols.get("step_time_tolerance", STEP_TIME_TOLERANCE))
    floor = float(tols.get("min_exposed_us", MIN_EXPOSED_US))
    out = []
    if "s006_bound" in entry:
        bound = getattr(rep, "_s006_bound", None)
        if bound is not None and bound != entry["s006_bound"]:
            out.append(_finding(
                "S006", name,
                f"fused decode program compiles {bound}-bound but the "
                f"committed verdict is {entry['s006_bound']}-bound — "
                "re-capture only if the balance change is intended"))
        gb = int(getattr(rep, "_max_gather_bytes", 0))
        limit = int(entry.get("gather_bytes_limit", 0))
        if limit and gb > limit:
            out.append(_finding(
                "S006", name,
                f"fused decode program materializes a {gb}-byte gather "
                f"(limit {limit}) — the per-step block-table gather is "
                "back; decode must index paged KV blocks in place"))
    if "overlap" in entry:
        base_ov, cur_ov = entry["overlap"], getattr(rep, "_overlap", None)
        if cur_ov is None:
            out.append(_finding("S007", name, "overlap twin pair was "
                                "not rebuilt; re-capture", "warning"))
        else:
            # two regression pins, each of one number against its own
            # captured value; neither says overlap is faster (the chip
            # decides that: PERF.md)
            budget = float(base_ov.get("budget", 1.0))
            frac = float(cur_ov["exposed_comm_fraction"])
            if frac > budget:
                out.append(_finding(
                    "S007", name,
                    f"overlap-on exposed-comm fraction {frac:.3f} is "
                    f"over its captured ceiling {budget:.3f} — a "
                    "collective lost its slack window in the projection "
                    "(docs/overlap.md)"))
            off_us = float(base_ov.get("overlap_off_step_time_us", 0.0))
            on_us = sched.step_time_s * 1e6
            if off_us and on_us >= off_us:
                out.append(_finding(
                    "S009", name,
                    f"overlap-on step-time projection {on_us:.1f}us "
                    f"reached the captured ceiling {off_us:.1f}us (the "
                    "serialized twin's projection at capture) "
                    "(docs/overlap.md)"))
    if "pipe_projection" in entry:
        proj = getattr(rep, "_pipe_projection", None)
        if proj is None:
            out.append(_finding("S009", name, "pipe projection pair "
                                "was not rebuilt; re-capture", "warning"))
        elif proj["v2_step_time_us"] >= proj["v1_step_time_us"]:
            out.append(_finding(
                "S009", name,
                f"interleaved (V=2) step-time projection "
                f"{proj['v2_step_time_us']:.1f}us no longer beats the "
                f"V=1 schedule ({proj['v1_step_time_us']:.1f}us) — the "
                "circular schedule's bubble saving regressed "
                "(docs/pipeline.md)"))
    out.extend(_from_reports(
        name,
        check_exposed_comm(sched, baseline=entry, min_exposed_us=floor,
                           tolerance=tol, label=name),
        check_step_time(sched, baseline=entry, tolerance=tol,
                        min_exposed_us=floor, label=name)))
    if sched.n_collectives != entry.get("n_collectives",
                                        sched.n_collectives):
        out.append(_finding(
            "S007", name,
            f"collective count changed: {sched.n_collectives} vs "
            f"baseline {entry.get('n_collectives')} — the schedule "
            "ledger is stale; re-capture if intended", "warning"))
    built.summary["programs"][name].update(
        baseline_step_time_us=entry.get("step_time_us"),
        baseline_exposed_us=entry.get("exposed_us"))
    return out


# ----------------------------------------------------------------------
# numerics (NUMERICS.json): N001-N004 over seven programs, and each
# program's dtype ledger (analysis/numerics.py)
# ----------------------------------------------------------------------

NUMERICS_PROGRAMS = ("train_step", "train_step_moe", "train_step_pipe3d",
                     "train_step_fp16", "train_step_onebit",
                     "serving_decode_w8", "serving_decode_w8_int8")


def _numerics_program(name):
    """(compiled, lowered, N-series report) of one program."""
    if name.startswith("serving"):
        eng = _serving_engine(int8=name.endswith("int8"))
        with _interpret(eng):
            return (*_artifacts(name), eng.sanitize_numerics(widths=[8]))
    if name in ("train_step", "train_step_moe"):
        eng, fn = _canonical(name)[0], None
        compiled, lowered = _artifacts(name)
    else:
        if name == "train_step_pipe3d":
            # the stage register's dtype flow through the
            # collective-permute ring, at the short sequence
            eng, batch = _train_engine(
                _mcfg(n_layers=4, pipeline_stages=2,
                      pipeline_virtual_stages=2),
                gas=4, zero_optimization=_zero3(), bf16=_BF16,
                mesh={"pipe": 2, "data": 2, "model": 2})
        elif name == "train_step_fp16":
            eng, batch = _train_engine(_mcfg(), fp16={"enabled": True},
                                       mesh={"data": 8})
        else:  # 1-bit Adam compressed-momentum step
            eng, batch = _train_engine(
                _mcfg(), optimizer={"type": "onebit_adam", "params": {
                    "lr": 1e-3, "freeze_step": 2}},
                bf16=_BF16, mesh={"data": 8})
        fn = eng._build_onebit_step() \
            if name == "train_step_onebit" else None
        compiled, lowered = _lower_train(eng, batch, fn)
    report = eng._numerics_checks(compiled, lowered, name,
                                  master=eng.state.master,
                                  opt=eng.state.opt)
    if name == "train_step_onebit":  # + N004 group geometry
        from deepspeed_tpu.analysis.numerics import check_quantized_groups
        from deepspeed_tpu.analysis.report import merge_reports

        report = merge_reports(name, report, check_quantized_groups(
            eng.state.params, dp=8, compiled_text=compiled.as_text(),
            label=name))
    return compiled, lowered, report


def waived(finding, waivers):
    """The waiver that covers an N-finding, or None. A waiver names a
    program, a rule and the finding's signature (the count, op and
    dtype its message starts with): one more such reduce, another
    dtype or another program is not covered."""
    for w in waivers:
        if (finding["where"], finding["rule"]) == (w["program"], w["rule"]) \
                and finding["message"].startswith(w["signature"]):
            return w
    return None


def build_numerics(opts):
    from deepspeed_tpu.analysis.numerics import dtype_ledger

    # a waiver is hand-written: read from the baseline in use, else
    # from the committed one, and carried by --capture as it stands
    default = os.path.join(_REPO, "NUMERICS.json")
    committed = _load(opts.baseline or default)[0] or _load(default)[0]
    waivers = (committed or {}).get("waived", [])
    only = opts.programs
    findings, ledgers = [], {}
    for name in only or NUMERICS_PROGRAMS:
        compiled, lowered, report = _numerics_program(name)
        ledgers[name] = dtype_ledger(compiled, lowered)
        for f in _from_reports(name, report):
            if f["severity"] != "error":
                continue
            w = waived(f, waivers)
            if w is None:
                findings.append(f)
            else:
                _say("numerics", f"waived: {name} {f['rule']} "
                                 f"{w['signature']}")
    doc = {"schema": 1, **_jax_meta(), "waived": waivers,
           "programs": ledgers}
    return Built(findings=findings, measured=doc, data=ledgers,
                 partial="--programs" if only else "")


def _numerics_ledger(name, ledger, entry, base, built):
    from deepspeed_tpu.analysis.numerics import diff_ledgers

    return [_finding(f.rule, name, f.message, f.severity)
            for f in diff_ledgers(ledger, entry, name)]


# ----------------------------------------------------------------------
# the bench.py lanes: each prints one JSON line whose "gates" are its
# named conditions and holds itself to its plan file (the baseline)
# ----------------------------------------------------------------------

def _lane_gate(name, call, by_hand=""):
    """A gate that is `call(bench, plan, opts) -> exit code`; `by_hand`
    says why --capture writes nothing for it."""
    def build(opts):
        import bench

        plan = opts.plan if opts.plan != "default" \
            else (opts.baseline or "default")
        rc = call(bench, plan, opts)
        return Built(partial=by_hand, findings=[] if rc == 0 else [_finding(
            name, plan, f"lane exited {rc}: a condition of its 'gates' "
            "line is false")])
    return build


def _fleet_size(opts):
    if opts.replicas < 2:
        opts.error("--replicas must be >= 2 (a fleet to route and fail "
                   "over inside)")
    return opts.replicas


def _with_capture(lane):
    return lambda bench, plan, opts: getattr(bench, lane)(
        plan, capture=opts.capture_path)


# ----------------------------------------------------------------------
# race (CONCURRENCY.json): the lockset analyzer over the package
# (analysis/concurrency.py: C001-C003, no baseline for a finding) and
# four lanes of the interleaving harness (resilience/interleave.py),
# two seeds each. Each lane returns (trace digest, outcome); outcomes
# must agree across seeds, digests must differ and match the ledger
# ----------------------------------------------------------------------

SEEDS = (11, 23)


def _lane_spill_store(seed: int):
    import numpy as np
    from deepspeed_tpu.inference.offload_store import HostKvSpillStore
    from deepspeed_tpu.resilience.interleave import CooperativeScheduler

    sched = CooperativeScheduler(seed=seed)
    store = HostKvSpillStore(capacity_bytes=1 << 16)
    sched.instrument(store, ["_lock"])
    payload = {"k": np.zeros(512, np.uint8)}  # 512 B/entry, cap = 128

    def producer(base):
        def fn():
            for i in range(8):
                store.put((base, i), dict(payload))
                sched.yield_point(f"put:{base}")
        return fn

    def consumer():
        got = 0
        while got < 8:
            for i in range(8):
                if store.get(("a", i)) is not None:
                    got += 1
            sched.yield_point("sweep")

    def discarder():
        for i in range(8):
            store.discard(("b", i))
            sched.yield_point("discard")

    sched.spawn("prod_a", producer("a"))
    sched.spawn("prod_b", producer("b"))
    sched.spawn("cons", consumer)
    sched.spawn("disc", discarder)
    sched.run()
    # coherence: whatever survived must account for every byte, and
    # every admitted entry must be consumed, discarded, or resident
    resident = len(store._entries)
    assert store.used_bytes == sum(store._bytes.values()), \
        (store.used_bytes, store._bytes)
    c = store.counters
    assert c["puts"] == c["gets"] + c["discards"] + resident, c
    assert store.peak_bytes >= store.used_bytes
    return sched.trace_digest(), {
        "puts": c["puts"], "gets": c["gets"],
        "rejects": c["rejects"],
        "final_used_plus_discarded_bytes":
            store.used_bytes + 512 * c["discards"],
    }


def _lane_fault_plan(seed: int):
    from deepspeed_tpu.resilience import FaultPlan, armed, fault_point
    from deepspeed_tpu.resilience.interleave import CooperativeScheduler

    n = 12
    plan = FaultPlan([{"point": "race.lane", "kind": "skip",
                       "at": 1, "times": -1}], seed=0)
    sched = CooperativeScheduler(seed=seed)
    sched.instrument(plan, ["_lock"])
    skips = {"x": 0, "y": 0}

    def hitter(name):
        def fn():
            for _ in range(n):
                act = fault_point("race.lane", lane=name)
                if act is not None and act.kind == "skip":
                    skips[name] += 1
                sched.yield_point(f"hit:{name}")
        return fn

    def resetter():
        for _ in range(3):
            plan.reset()
            sched.yield_point("reset")

    with armed(plan):
        sched.spawn("hit_x", hitter("x"))
        sched.spawn("hit_y", hitter("y"))
        sched.spawn("reset", resetter)
        sched.run()
    # coherence: a times=-1 skip spec fires on EVERY match no matter
    # how reset() interleaves — a lost increment would break this
    assert skips["x"] == n and skips["y"] == n, skips
    assert plan._matched[0] + 3 * 0 <= 2 * n  # resets only shrink
    return sched.trace_digest(), {"skips_per_hitter": n,
                                  "resets": 3}


def _lane_aio_inflight(seed: int):
    import numpy as np
    from deepspeed_tpu.ops.aio import AsyncIOHandle
    from deepspeed_tpu.resilience.interleave import CooperativeScheduler

    with tempfile.TemporaryDirectory(prefix="ds_race_aio_") as d:
        h = AsyncIOHandle(n_threads=2)
        sched = CooperativeScheduler(seed=seed)
        sched.instrument(h, ["_lock"])
        rng = np.random.default_rng(0)
        bufs = {i: rng.integers(0, 256, 4096).astype(np.uint8)
                for i in range(4)}
        outs = {i: np.empty(4096, np.uint8) for i in range(4)}

        # completion signaling stays INSIDE the harness (baton-
        # serialized set) rather than polling the filesystem: the
        # native pool's file visibility lags ds_aio_wait by a beat,
        # which would make the poll count — and the trace — racy
        written = set()

        def writer():
            for i in range(4):
                h.pwrite(bufs[i], os.path.join(d, f"{i}.bin"))
                written.add(i)
                sched.yield_point(f"pwrite:{i}")

        def reader(ids):
            def fn():
                for i in ids:
                    while i not in written:
                        sched.yield_point(f"wait:{i}")
                    h.pread(outs[i], os.path.join(d, f"{i}.bin"))
                    sched.yield_point(f"pread:{i}")
            return fn

        sched.spawn("writer", writer)
        sched.spawn("read02", reader((0, 2)))
        sched.spawn("read13", reader((1, 3)))
        sched.run()
        identical = all(bool(np.array_equal(bufs[i], outs[i]))
                        for i in range(4))
        assert identical, "aio round-trip corrupted a payload"
        assert not h._inflight, f"leaked pins: {list(h._inflight)}"
        return sched.trace_digest(), {"payloads": 4,
                                      "round_trip_identical": True,
                                      "native": bool(h.native)}


def _serving_fixture():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference import init_inference
    from deepspeed_tpu.models import transformer as T

    mcfg = T.TransformerConfig(
        vocab_size=128, n_layers=2, n_heads=4, d_model=64,
        max_seq=64, variant="llama", use_flash=False)
    params = T.init(mcfg, jax.random.PRNGKey(0))

    def build_engine():
        return init_inference(
            params, mcfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32)

    rng = np.random.default_rng(7)
    reqs = [(list(rng.integers(1, 128, int(rng.integers(4, 12)))),
             int(rng.integers(3, 8))) for _ in range(6)]
    return build_engine, reqs


def _serve(build_engine, reqs, seed=None):
    """Serve `reqs` on a 2-replica router. seed=None: single-threaded
    oracle. Otherwise: scheduler/pump/autoscaler/spill tasks permuted
    under the harness at that seed. Returns (tokens, digest|None)."""
    import numpy as np
    from deepspeed_tpu.inference import (Autoscaler, RouterFleetAdapter,
                                         ServingRouter)
    from deepspeed_tpu.inference.offload_store import HostKvSpillStore
    from deepspeed_tpu.resilience.interleave import CooperativeScheduler

    router = ServingRouter([build_engine(), build_engine()],
                           {"mode": "colocated"}, seed=0)
    gids = [router.submit(p, m) for p, m in reqs]

    def done():
        return all(router.result(g).done for g in gids)

    if seed is None:
        while not done():
            for sj in router.schedulers:
                if sj.has_work:
                    sj.step()
            router.pump()
        return [list(router.result(g).output) for g in gids], None

    sched = CooperativeScheduler(seed=seed, max_switches=500_000)

    def stepper(j):
        sj = router.schedulers[j]

        def fn():
            while not done():
                if sj.has_work:
                    sj.step()
                sched.yield_point(f"step{j}")
        return fn

    def pump():
        while not done():
            router.pump()
            sched.yield_point("pump")

    def ticker():
        adapter = RouterFleetAdapter(router, build_engine, join=False)
        asc = Autoscaler(adapter, dict(
            enabled=True, min_replicas=2, max_replicas=2,
            evaluation_interval_s=1.0), clock=lambda: 0.0)
        t = 0.0
        while not done():
            t += 1.0
            asc.tick(now=t)
            sched.yield_point("tick")
        # a min==max fleet must never change size under any schedule
        assert asc.counters["scale_ups"] == 0
        assert asc.counters["scale_downs"] == 0

    def spiller():
        store = HostKvSpillStore(capacity_bytes=1 << 14)
        sched.instrument(store, ["_lock"])
        pay = {"k": np.zeros(256, np.uint8)}
        i = 0
        while not done():
            store.put(("s", i), dict(pay))
            sched.yield_point("spill.put")
            assert store.get(("s", i)) is not None
            i += 1
            sched.yield_point("spill.get")
        assert store.used_bytes == 0

    sched.spawn("sched0", stepper(0))
    sched.spawn("sched1", stepper(1))
    sched.spawn("pump", pump)
    sched.spawn("autoscaler", ticker)
    sched.spawn("spill", spiller)
    sched.run()
    return [list(router.result(g).output) for g in gids], \
        sched.trace_digest()


def _lane_serving_plane(seed: int, _cache={}):
    import hashlib
    if "fixture" not in _cache:
        _cache["fixture"] = _serving_fixture()
        build_engine, reqs = _cache["fixture"]
        _cache["oracle"], _ = _serve(build_engine, reqs, seed=None)
    build_engine, reqs = _cache["fixture"]
    tokens, digest = _serve(build_engine, reqs, seed=seed)
    assert tokens == _cache["oracle"], (
        "token identity broken: interleaved control plane emitted "
        "different tokens than the single-threaded oracle")
    tok_h = hashlib.blake2b(
        json.dumps(tokens).encode(), digest_size=16).hexdigest()
    return digest, {"requests": len(reqs),
                    "tokens_equal_oracle": True,
                    "token_digest": tok_h}


RACE_LANES = {
    "spill_store": _lane_spill_store,
    "fault_plan": _lane_fault_plan,
    "aio_inflight": _lane_aio_inflight,
    "serving_plane": _lane_serving_plane,
}


def build_race(opts):
    from deepspeed_tpu.analysis.concurrency import analyze_paths

    rep = analyze_paths([os.path.join(_REPO, "deepspeed_tpu")],
                        base=_REPO)
    _say("race", rep.summary())
    measured = {"version": 1, "lanes": {},
                "static": {"suppressed": rep.suppressed_sites,
                           "classes": rep.ledger}}
    for name, fn in ({} if opts.static_only else RACE_LANES).items():
        digests, outcome = {}, None
        for seed in SEEDS:
            digests[str(seed)], out = fn(seed)
            if outcome is None:
                outcome = out
            elif outcome != out:
                raise AssertionError(
                    f"lane {name}: outcome differs across seeds "
                    f"{SEEDS}: {outcome} != {out}")
        assert len(set(digests.values())) == len(SEEDS), \
            f"lane {name}: seeds {SEEDS} produced identical " \
            "schedules — the harness is not permuting"
        measured["lanes"][name] = {"trace_digests": digests,
                                   "outcome": outcome}
        _say("race", f"lane {name}: ok ({', '.join(digests.values())})")
    return Built(
        findings=_from_reports(None, rep), measured=measured,
        partial="--static-only" if opts.static_only else "",
        view=(lambda c: dict(c, lanes={})) if opts.static_only else None)


# ----------------------------------------------------------------------
# determinism (DETERMINISM.json): D001 layout-dependent draws and D002
# reassociation hazards over five canonical programs, D003 host
# ordering and D004 draw keys over the sources
# (analysis/determinism.py); the selftest seeds one violation a rule
# and each must fire exactly once
# ----------------------------------------------------------------------

def _prog_serving_sample_w8():
    # the sampled-decode draw path: gumbel-max over the candidate pool,
    # keys per stream, position folded in — the D004 reference shape,
    # and the one canonical program whose rng ledger carries real draws
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference.sampling import (SamplingConfig,
                                                  sample_tokens)
    from deepspeed_tpu.profiling.hlo import preopt_hlo_text

    scfg = SamplingConfig(do_sample=True, temperature=0.8, top_k=8)

    def fn(logits, keys, step):
        return sample_tokens(logits, scfg, keys=keys, step=step)

    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.PRNGKey(0), jnp.arange(8, dtype=jnp.uint32))
    lowered = jax.jit(fn).lower(
        jnp.zeros((8, 128), jnp.float32), keys,
        jnp.zeros((8,), jnp.int32))
    compiled = lowered.compile()
    return preopt_hlo_text(lowered), compiled.as_text()


_D003_FIXTURE = '''
import json
import os


def emit(d, out):
    tags = [t for t in os.listdir(d)]
    with open(out, "w") as f:
        json.dump({"tags": tags}, f, sort_keys=True)
'''


_D004_FIXTURE = '''
import jax


def sample(key, logits):
    return jax.random.categorical(key, logits)
'''


def _determinism_selftest():
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deepspeed_tpu.analysis.determinism import (
        BitwisePin, check_draw_keys, check_host_ordering,
        check_reassociation, check_rng_discipline)
    from deepspeed_tpu.profiling.hlo import preopt_hlo_text

    counts = {}
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("expert", "model"))

    # D001: a draw deliberately pinned to a mesh-TILED sharding
    @jax.jit
    def sharded_draw(key):
        x = jax.random.uniform(key, (8, 8))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P("expert", "model")))

    pre = preopt_hlo_text(sharded_draw.lower(jax.random.PRNGKey(0)))
    counts["D001"] = 0 if pre is None else len(
        check_rng_discipline(pre, label="selftest_d001").findings)

    # ... and the pinned twin stays silent (the _replicated_draw idiom)
    @jax.jit
    def pinned_draw(key):
        x = jax.random.uniform(key, (8, 8))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P()))

    pre_ok = preopt_hlo_text(pinned_draw.lower(jax.random.PRNGKey(0)))
    counts["D001_pinned"] = 0 if pre_ok is None else len(
        check_rng_discipline(pre_ok, label="selftest_d001_ok").findings)

    # D002: a real fp additive psum over an axis the pin declares
    # layout-varying, no waiver
    import jax.numpy as jnp

    def body(x):
        return jax.lax.psum(x, "expert")

    reduced = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P("expert", None),
        out_specs=P(None, None)))
    txt = reduced.lower(jnp.ones((8, 8), jnp.float32)).compile().as_text()
    pin = BitwisePin(
        program="selftest_d002",
        mesh_axes=(("expert", 2), ("model", 2)),
        varying_axes=("expert",))
    counts["D002"] = len(
        check_reassociation(txt, pin, label="selftest_d002").findings)

    # D003 / D004: source fixtures through the real AST drivers
    counts["D003"] = len(check_host_ordering(
        _REPO, sources=[("scripts/selftest_d003.py",
                         _D003_FIXTURE)]).findings)
    counts["D004"] = len(check_draw_keys(
        _REPO, sources=[("deepspeed_tpu/inference/selftest_d004.py",
                         _D004_FIXTURE)]).findings)
    return counts


DETERMINISM_PROGRAMS = ("train_step", "train_step_moe",
                        "train_step_pipe3d", "serving_decode_w8",
                        "serving_sample_w8")


def _determinism_texts(name):
    from deepspeed_tpu.profiling.hlo import preopt_hlo_text

    if name == "serving_sample_w8":
        return _prog_serving_sample_w8()
    compiled, lowered = _artifacts(name)
    return preopt_hlo_text(lowered), compiled.as_text()


def _teeth(name, selftest, expected):
    if selftest == expected:
        return []
    return [_finding("selftest", name, f"expected {expected}, got "
                     f"{selftest} — a check lost its teeth")]


def build_determinism(opts):
    from deepspeed_tpu.analysis.determinism import (
        check_draw_keys, check_host_ordering, pin_for,
        program_determinism)

    names = opts.programs or list(DETERMINISM_PROGRAMS)
    reports, programs = [], {}
    for name in names:
        rep, programs[name] = program_determinism(
            *_determinism_texts(name), label=name, pin=pin_for(name))
        reports.append(rep)
        _say("determinism",
             f"{name}: {sum(programs[name].get('rng_ops', {}).values())} "
             f"rng op(s), "
             f"{sum(programs[name].get('reduce_classes', {}).values())} "
             f"fp additive reduce(s), {len(rep.findings)} finding(s)")
    ordering, draws = check_host_ordering(_REPO), check_draw_keys(_REPO)
    _say("determinism",
         f"host ordering: {ordering.files_checked} files, "
         f"{len(ordering.findings)} finding(s); draw keys: "
         f"{draws.files_checked} files, {len(draws.findings)} finding(s)")
    selftest = _determinism_selftest()
    measured = {
        "version": 1, "programs": programs, "selftest": selftest,
        "host": {"ordering": {"suppressed": ordering.suppressed_sites},
                 "draw_keys": {"suppressed": draws.suppressed_sites}}}
    return Built(
        findings=_from_reports(None, *reports, ordering, draws) + _teeth(
            "determinism", selftest,
            {"D001": 1, "D001_pinned": 0, "D002": 1, "D003": 1,
             "D004": 1}),
        measured=measured, partial="--programs" if opts.programs else "",
        view=lambda c: dict(c, programs={
            k: v for k, v in (c.get("programs") or {}).items()
            if k in names}))


# ----------------------------------------------------------------------
# lifecycle (LIFECYCLE.json): L001 exception-path leaks, L002 pool
# accounting, L003 fault coverage, L004 swallowed typed failures over
# the pool-owning roots (analysis/lifecycle.py), with its selftest
# ----------------------------------------------------------------------

LIFECYCLE_RULES = ("L001", "L002", "L003", "L004")


_L001_FIXTURE = '''
class Sched:
    def grab(self, uid):
        blk = self.allocator.allocate()
        self.state.extend(uid, 1)
        self.table[uid] = blk
'''


_L001_PROTECTED = '''
class Sched:
    def grab(self, uid):
        blk = self.allocator.allocate()
        try:
            self.state.extend(uid, 1)
        finally:
            self.allocator.free(blk)
        self.table[uid] = blk
'''


_L002_FIXTURE = '''
class Sched:
    def __init__(self):
        self.counters = {"hits": 0}

    def poke(self):
        self.counters["oops"] += 1
'''


_L004_FIXTURE = '''
class Sched:
    def pull(self, uid):
        try:
            self.engine.import_kv(uid, None)
        except Exception:
            return None
'''


_L004_COUNTED = '''
class Sched:
    def pull(self, uid):
        try:
            self.engine.import_kv(uid, None)
        except Exception:
            self.counters["import_failures"] += 1
            return None
'''


def _lifecycle_selftest():
    from deepspeed_tpu.analysis.lifecycle import (
        l001_findings, l002_findings, l003_findings, l004_findings)

    counts = {}
    f, _ = l001_findings([("selftest_l001.py", _L001_FIXTURE)])
    counts["L001"] = len(f)
    # ... and the try/finally twin stays silent (the protected idiom)
    f, _ = l001_findings([("selftest_l001_ok.py", _L001_PROTECTED)])
    counts["L001_protected"] = len(f)
    f, _ = l002_findings([("selftest_l002.py", _L002_FIXTURE)])
    counts["L002"] = len(f)
    # a registered point with a call site but ZERO committed lanes
    f, _ = l003_findings({"self.test": {}}, {},
                         {"self.test": [("selftest.py", 1)]})
    counts["L003"] = len(f)
    counts["L004"] = len(
        l004_findings([("selftest_l004.py", _L004_FIXTURE)]))
    # ... and the counted twin stays silent (observe-then-absorb is ok)
    counts["L004_counted"] = len(
        l004_findings([("selftest_l004_ok.py", _L004_COUNTED)]))
    return counts


def build_lifecycle(opts):
    from deepspeed_tpu.analysis.lifecycle import analyze_tree

    rules = _subset(opts.rules, LIFECYCLE_RULES, "rule", opts.error) \
        or LIFECYCLE_RULES
    rep = analyze_tree(_REPO)
    rep.findings = [f for f in rep.findings if f.rule in rules]
    uncovered = [p for p, lanes in rep.coverage.items() if not lanes]
    _say("lifecycle",
         f"{rep.summary()}; {len(uncovered)} uncovered point(s)")
    selftest = _lifecycle_selftest()
    return Built(
        findings=_from_reports(None, rep) + _teeth(
            "lifecycle", selftest,
            {"L001": 1, "L001_protected": 0, "L002": 1, "L003": 1,
             "L004": 1, "L004_counted": 0}),
        measured={"version": 1, "ledger": rep.ledger,
                  "coverage": rep.coverage, "selftest": selftest},
        # a subset of the rules measures the whole ledger all the same,
        # but is neither captured nor diffed
        partial="--rules" if opts.rules else "", compare=not opts.rules)


# ----------------------------------------------------------------------
# the table and the driver
# ----------------------------------------------------------------------

#: gate -> (build, baseline file at the repo root or None, compare or
#: None). A lane gate's baseline is its plan, which the lane compares
#: itself against (and writes, on --capture); `elastic`'s is edited by
#: hand; lint, chaos and fleet have none
GATES = {
    "lint": (build_lint, None, None),
    "budget": (build_budget, "MEMBUDGET.json",
               _programs_compare("S004", _budget_program, _budget_doc)),
    "numerics": (build_numerics, "NUMERICS.json",
                 _programs_compare("N001", _numerics_ledger)),
    "schedule": (build_schedule, "SCHEDULE.json",
                 _programs_compare("S009", _schedule_program)),
    "fleet": (_lane_gate("fleet", lambda b, plan, o: b._router_sim(
        _fleet_size(o))), None, None),
    "chaos": (_lane_gate("chaos", lambda b, plan, o: b._chaos_sim(
        _fleet_size(o), plan)), None, None),
    "elastic": (_lane_gate(
        "elastic", lambda b, plan, o: b._train_chaos(plan),
        by_hand="TRAINCHAOS.json is the lane's plan, edited by hand"),
        "TRAINCHAOS.json", None),
    "sdc": (_lane_gate("sdc", _with_capture("_sdc_chaos")),
            "SDCCHAOS.json", None),
    "overload": (_lane_gate("overload", _with_capture("_overload_sim")),
                 "OVERLOAD.json", None),
    "autoscale": (_lane_gate("autoscale",
                             _with_capture("_autoscale_sim")),
                  "AUTOSCALE.json", None),
    "moe": (_lane_gate("moe", _with_capture("_moe_sim")), "MOE.json",
            None),
    "pipe": (_lane_gate("pipe", _with_capture("_pipe_sim")), "PIPE.json",
             None),
    "race": (build_race, "CONCURRENCY.json", _ledger_compare(
        "race",
        [(("static", "suppressed"), "suppression", False),
         (("static", "classes"), "class ledger", True),
         (("lanes",), "lane", True)],
        [("static", "suppressed"),
         ("static", "classes", "*", "suppressed")])),
    "determinism": (build_determinism, "DETERMINISM.json", _ledger_compare(
        "determinism",
        [(("programs",), "program ledger", True),
         (("host",), "host ledger", False),
         (("selftest",), "selftest", False)],
        [("host", "*", "suppressed")])),
    "lifecycle": (build_lifecycle, "LIFECYCLE.json", _ledger_compare(
        "lifecycle",
        [(("ledger",), "ledger", False), (("coverage",), "coverage", True),
         (("selftest",), "selftest", False)],
        [("ledger", "suppressions")])),
}


def run_gate(name, opts) -> bool:
    """Build one gate, capture or compare, print its findings and its
    status; True when it holds."""
    build, baseline, compare = GATES[name]
    path = opts.baseline or (baseline and os.path.join(_REPO, baseline))
    opts.capture_path = path if opts.capture else None
    built = build(opts)
    findings = list(built.findings)
    errors = [f for f in findings if f["severity"] == "error"]
    if opts.capture:
        refused = (
            "nothing to capture: this gate has no baseline"
            if not baseline else
            "findings on the tree; fix them before capturing"
            if errors else
            f"refusing to capture a partial ledger ({built.partial}); "
            "run a full --capture" if built.partial else "")
        if refused:
            findings.append(_finding(name, name, refused))
        elif compare is not None:  # a lane has written its own plan file
            _write(path, built.measured)
            _say(name, f"wrote {path}")
    elif compare is not None and built.compare:
        committed, why = _load(path)
        findings.extend([_finding(name, path, why)] if committed is None
                        else compare(built, committed))
    for f in findings:
        _say(name, f"{f['rule']} {f['where']} [{f['severity']}] "
                   f"{f['message']}")
        if f.get("hint"):
            _say(name, f"    hint: {f['hint']}")
    ok = not any(f["severity"] == "error" for f in findings) and \
        not (opts.strict and findings)
    if opts.json and built.measured is not None:
        print(json.dumps(built.measured, indent=1, sort_keys=True))
    status = {"ok": ok, "gate": f"ds_{name}", "strict": bool(opts.strict)}
    print(json.dumps({**status, "findings": findings, **built.summary}))
    print(json.dumps(status), file=sys.stderr)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        epilog="gates: " + ", ".join(GATES) + "; docs/static_analysis.md "
               "says what each builds, pins and when to re-capture")
    ap.add_argument("gate", choices=[*GATES, "all"])
    ap.add_argument("paths", nargs="*",
                    help="lint: files or directories (default: the "
                         "package)")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true",
                      help="compare against the baseline (the default)")
    mode.add_argument("--capture", action="store_true",
                      help="rebuild and write the gate's baseline")
    ap.add_argument("--strict", action="store_true",
                    help="warnings fail as well")
    ap.add_argument("--baseline", default=None,
                    help="baseline path (default: the gate's file at the "
                         "repo root)")
    ap.add_argument("--json", action="store_true",
                    help="also print what was measured")
    ap.add_argument("--programs", nargs="+", default=None,
                    help="numerics, determinism: only these canonical "
                         "programs (the diff is restricted to them)")
    ap.add_argument("--rules", nargs="?", const="", default=None,
                    help="lifecycle: comma-separated L-rules (skips the "
                         "ledger diff); lint: print the rule catalog")
    ap.add_argument("--static-only", action="store_true",
                    help="race: the analyzer and its ledger, no lanes")
    ap.add_argument("--show-suppressed", action="store_true",
                    help="lint: list pragma-suppressed findings too")
    ap.add_argument("--plan", default="default",
                    help="lanes: 'default' (the committed plan) or a "
                         "FaultPlan JSON path")
    ap.add_argument("--replicas", type=int, default=4,
                    help="fleet, chaos: fleet size (>= 2; default 4)")
    opts = ap.parse_args(argv)
    opts.error = ap.error
    if opts.gate == "all" and (opts.programs or opts.capture):
        ap.error("--programs and --capture go with one gate")
    if opts.programs:
        known = NUMERICS_PROGRAMS if opts.gate == "numerics" \
            else DETERMINISM_PROGRAMS
        opts.programs = _subset(",".join(opts.programs), known, "program",
                                ap.error)
    if opts.gate != "all":
        return 0 if run_gate(opts.gate, opts) else 1
    held = {}
    for name in GATES:
        try:
            held[name] = run_gate(name, opts)
        except Exception:  # one gate's crash must not hide the others
            traceback.print_exc()
            held[name] = False
    print(json.dumps({"ok": all(held.values()), "gate": "all",
                      "gates": held}))
    return 0 if all(held.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
