#!/usr/bin/env python
"""ds-determinism CLI — determinism gate (DETERMINISM.json).

Usage:
    python scripts/ds_determinism.py                  # check vs the ledger
    python scripts/ds_determinism.py --capture        # rerun + write ledger
    python scripts/ds_determinism.py --check --strict # CI spelling
    python scripts/ds_determinism.py --programs train_step  # subset (fast)

The fourteenth tier-1 pre-test gate (.claude/skills/verify/SKILL.md).
Four checks (analysis/determinism.py), all compile-time/AST static —
no step executes, everything runs on the virtual 8-device CPU mesh:

  D001  layout-dependent PRNG: every canonical program's PRE-OPT HLO
        is scanned for draws whose result/seed carries a mesh-tiled
        sharding or sits in a shard_map manual context without a
        replicated pin (the PR-14 EP=1 != EP=N router-noise class).
  D002  reassociation hazards: each program's COMPILED text is checked
        for fp additive reduce collectives spanning a mesh axis its
        bitwise pin declares layout-varying, minus the committed
        waivers in analysis.determinism.BITWISE_PINS.
  D003  host-side ordering: AST pass over every committed-artifact
        emitter (scripts/, analysis/, runtime/checkpoint.py,
        profiling/latency.py) — unsorted enumeration, mtime-only
        sorts, json.dump without sort_keys, set iteration, wall-clock
        entropy in capture paths.
  D004  serving draw-key discipline: AST pass over the serving paths —
        every sampled draw keys on (seed, stream, position) via
        fold_in, never process-global or wall-clock entropy.

D findings have NO baseline — any active finding is red in every mode;
only the per-program rng-op/reduce-class ledger (and the pragma
suppression lists) is pinned in DETERMINISM.json. A SELFTEST section
seeds one deliberate violation per check (a sharded-threefry program,
a layout-dependent reduce on a pinned program, an unsorted-listdir
emitter, a position-independent draw) and requires each to fire
EXACTLY once — the gate proves its own teeth every run.
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

DEFAULT_PATH = os.path.join(_REPO, "DETERMINISM.json")


# ----------------------------------------------------------------------
# canonical programs — (preopt_text, compiled_text) per label; configs
# mirror scripts/ds_budget.py so the two gates pin the SAME artifacts
# ----------------------------------------------------------------------

def _mcfg(**kw):
    from deepspeed_tpu.models import transformer as T

    base = dict(vocab_size=128, n_layers=2, n_heads=4, d_model=64,
                max_seq=32, variant="llama", use_flash=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def _train_texts(ds_cfg, mcfg, batch_cols):
    import warnings

    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.profiling.hlo import preopt_hlo_text

    pipelined = getattr(mcfg, "pipeline_stages", 1) > 1
    kw = {}
    if pipelined:
        kw = dict(pipelined=True,
                  pipeline_virtual_stages=mcfg.pipeline_virtual_stages)
    eng = ds.initialize(
        ds_cfg,
        loss_fn=(T.make_pipelined_loss_fn(mcfg) if pipelined
                 else T.make_loss_fn(mcfg)),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg), **kw)
    batch = {"tokens": np.zeros(
        (eng.config.train_batch_size, batch_cols), np.int32)}
    batch = eng._reshape_gas(batch)
    batch = eng.shard_batch(batch, leading_accum_dim=True)
    if eng._train_step_fn is None:
        eng._train_step_fn = eng._build_train_step()
    with warnings.catch_warnings(), eng.mesh:
        warnings.simplefilter("ignore")
        lowered = eng._train_step_fn.lower(eng.state, batch)
        compiled = lowered.compile()
    return preopt_hlo_text(lowered), compiled.as_text()


def _prog_train_step():
    return _train_texts(
        {"train_micro_batch_size_per_gpu": 1,
         "gradient_accumulation_steps": 2,
         "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
         "zero_optimization": {"stage": 3,
                               "param_persistence_threshold": 64},
         "bf16": {"enabled": True},
         "mesh": {"data": 4, "model": 2},
         "steps_per_print": 10**9},
        _mcfg(), 33)


def _prog_train_step_moe():
    return _train_texts(
        {"train_micro_batch_size_per_gpu": 1,
         "gradient_accumulation_steps": 2,
         "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
         "zero_optimization": {"stage": 3,
                               "param_persistence_threshold": 64},
         "bf16": {"enabled": True},
         "mesh": {"data": 2, "expert": 2, "model": 2},
         "steps_per_print": 10**9},
        _mcfg(n_experts=4, moe_top_k=2, moe_dropless=True,
              moe_z_loss_coef=1e-3), 33)


def _prog_train_step_pipe3d():
    return _train_texts(
        {"train_micro_batch_size_per_gpu": 2,
         "gradient_accumulation_steps": 8,
         "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
         "zero_optimization": {"stage": 3,
                               "param_persistence_threshold": 64},
         "bf16": {"enabled": True},
         "mesh": {"pipe": 2, "data": 2, "model": 2},
         "steps_per_print": 10**9},
        _mcfg(n_layers=4, max_seq=128, pipeline_stages=2,
              pipeline_virtual_stages=2), 129)


def _prog_serving_decode_w8():
    import warnings

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import init_inference
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.profiling.hlo import preopt_hlo_text

    mcfg = _mcfg()
    params = T.init(mcfg, jax.random.PRNGKey(0))
    eng = init_inference(
        params, mcfg,
        dict(max_seq_len=32, kv_block_size=8, num_kv_blocks=32,
             min_prefill_bucket=8, max_batch_size=8),
        dtype=jnp.float32)
    toks = np.zeros((8,), np.int32)
    ctx = np.zeros((8,), np.int32)
    tables = np.full((8, eng.config.blocks_per_seq), eng.pad_block,
                     np.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lowered = eng._decode_fn(8, True).lower(
            eng.params, eng.cache, eng._dev(toks), eng._dev(tables),
            eng._dev(ctx))
        compiled = lowered.compile()
    return preopt_hlo_text(lowered), compiled.as_text()


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


PROGRAMS = {
    "train_step": _prog_train_step,
    "train_step_moe": _prog_train_step_moe,
    "train_step_pipe3d": _prog_train_step_pipe3d,
    "serving_decode_w8": _prog_serving_decode_w8,
    "serving_sample_w8": _prog_serving_sample_w8,
}


# ----------------------------------------------------------------------
# selftest — one seeded violation per check; each must fire EXACTLY once
# ----------------------------------------------------------------------

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


def _selftest():
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


# ----------------------------------------------------------------------
# driver
# ----------------------------------------------------------------------

def _run_all(program_names):
    from deepspeed_tpu.analysis.determinism import (
        check_draw_keys, check_host_ordering, pin_for,
        program_determinism)

    findings = []
    measured = {"version": 1, "programs": {}, "host": {},
                "selftest": {}}

    for name in program_names:
        pre, post = PROGRAMS[name]()
        rep, entry = program_determinism(
            pre, post, label=name, pin=pin_for(name))
        findings.extend(rep.findings)
        measured["programs"][name] = entry
        n_rng = sum((entry.get("rng_ops") or {}).values())
        n_red = sum((entry.get("reduce_classes") or {}).values())
        print(f"[ds-determinism] {name}: {n_rng} rng op(s), {n_red} fp "
              f"additive reduce(s), {len(rep.findings)} finding(s)",
              file=sys.stderr)

    ordering = check_host_ordering(_REPO)
    draws = check_draw_keys(_REPO)
    findings.extend(ordering.findings)
    findings.extend(draws.findings)
    measured["host"] = {
        "ordering": {
            "files": ordering.files_checked,
            "suppressed": sorted(
                f"{f.path}:{f.line} {f.rule}"
                for f in ordering.suppressed),
        },
        "draw_keys": {
            "files": draws.files_checked,
            "suppressed": sorted(
                f"{f.path}:{f.line} {f.rule}"
                for f in draws.suppressed),
        },
    }
    print(f"[ds-determinism] host ordering: {ordering.files_checked} "
          f"files, {len(ordering.findings)} finding(s); draw keys: "
          f"{draws.files_checked} files, {len(draws.findings)} "
          "finding(s)", file=sys.stderr)

    selftest = _selftest()
    measured["selftest"] = selftest
    expected = {"D001": 1, "D001_pinned": 0, "D002": 1, "D003": 1,
                "D004": 1}
    teeth_ok = selftest == expected
    if not teeth_ok:
        print(f"[ds-determinism] SELFTEST FAILED: expected {expected}, "
              f"got {selftest} — a check lost its teeth",
              file=sys.stderr)
    return findings, measured, teeth_ok


def _strip_suppressions(ledger):
    out = json.loads(json.dumps(ledger))
    for half in (out.get("host") or {}).values():
        half.pop("suppressed", None)
    return out


def _diff(committed, measured):
    cp = committed.get("programs") or {}
    mp = measured["programs"]
    for k in sorted(set(cp) | set(mp)):
        if cp.get(k) != mp.get(k):
            print(f"[ds-determinism] program ledger drift: {k}",
                  file=sys.stderr)
            print(f"    committed: {json.dumps(cp.get(k), sort_keys=True)}",
                  file=sys.stderr)
            print(f"    measured:  {json.dumps(mp.get(k), sort_keys=True)}",
                  file=sys.stderr)
    ch = committed.get("host") or {}
    if ch != measured["host"]:
        print(f"[ds-determinism] host ledger drift: committed "
              f"{json.dumps(ch, sort_keys=True)} -> measured "
              f"{json.dumps(measured['host'], sort_keys=True)}",
              file=sys.stderr)
    print("[ds-determinism] ledger drift: rerun with --capture after "
          "review (D findings never have a baseline; only the rng-op/"
          "reduce-class ledger and suppression lists do)",
          file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--capture", action="store_true",
                    help="run all checks and write the ledger into "
                         f"{DEFAULT_PATH}")
    ap.add_argument("--check", action="store_true",
                    help="explicit check mode (the default)")
    ap.add_argument("--strict", action="store_true",
                    help="also fail on suppression drift vs the "
                         "committed ledger (findings always fail)")
    ap.add_argument("--programs", default=None,
                    help="comma-separated canonical-program subset "
                         "(default: all; the ledger diff is restricted "
                         "to the subset)")
    ap.add_argument("--baseline", default=DEFAULT_PATH,
                    help=f"ledger path (default {DEFAULT_PATH})")
    ap.add_argument("--json", action="store_true",
                    help="print the measured ledger to stdout")
    args = ap.parse_args(argv)

    names = list(PROGRAMS)
    if args.programs:
        names = [n.strip() for n in args.programs.split(",") if n.strip()]
        unknown = [n for n in names if n not in PROGRAMS]
        if unknown:
            ap.error(f"unknown program(s) {unknown}; "
                     f"choose from {list(PROGRAMS)}")

    findings, measured, teeth_ok = _run_all(names)
    rc = 0
    if not teeth_ok:
        rc = 1

    # determinism findings have no baseline: any active finding is red
    if findings:
        for f in findings:
            print(f"[ds-determinism] {f.rule} {f.path}:{f.line} "
                  f"{f.message}", file=sys.stderr)
            if f.fix_hint:
                print(f"    hint: {f.fix_hint}", file=sys.stderr)
        rc = 1

    if args.capture:
        if rc == 0:
            if args.programs:
                print("[ds-determinism] refusing to capture a partial "
                      "ledger (--programs); run a full --capture",
                      file=sys.stderr)
                rc = 1
            else:
                with open(args.baseline, "w") as fh:
                    json.dump(measured, fh, indent=1, sort_keys=True)
                    fh.write("\n")
                print(f"[ds-determinism] wrote {args.baseline}",
                      file=sys.stderr)
    else:
        if not os.path.exists(args.baseline):
            print(f"[ds-determinism] no committed ledger at "
                  f"{args.baseline} — run --capture first",
                  file=sys.stderr)
            rc = 1
        else:
            with open(args.baseline) as fh:
                committed = json.load(fh)
            committed = {
                "version": committed.get("version"),
                "programs": {k: v for k, v in
                             (committed.get("programs") or {}).items()
                             if k in names},
                "host": committed.get("host"),
                "selftest": committed.get("selftest"),
            }
            if committed != measured:
                if not args.strict and \
                        _strip_suppressions(committed) == \
                        _strip_suppressions(measured):
                    print("[ds-determinism] suppression drift "
                          "(non-strict: warning only)", file=sys.stderr)
                else:
                    _diff(committed, measured)
                    rc = 1

    if args.json:
        print(json.dumps(measured, indent=1, sort_keys=True))
    print(json.dumps({"ok": rc == 0, "gate": "ds_determinism",
                      "strict": bool(args.strict)}), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
