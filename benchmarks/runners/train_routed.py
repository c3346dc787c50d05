"""Runner kind `train_routed`: runners/train.py's protocol (set-up,
warm-up, window, trace, the loss checks, the kernels in the compiled
step, the loss against the plain reference) for a ROUTED model whose
step carries state of its own: the engine is built with `has_aux` and
the model's step-state rule (models/transformer.step_state_rule), the
needed operations and held parameters come from the family's own
arithmetic (`kernels/<config.reference>.py`; kernels/shapes.py refuses
a routed block's keys), and three checks are added:

  - every step's census sums to tokens x k x routed layers, and no
    held pair was dropped (the counters the step reads back with the
    loss);
  - `matches_reference` also holds, on the engine's own compute-dtype
    parameters AS INITIALISED (where every token reaches a held
    expert; a host copy taken before the warm-up) and as the window
    left them, over the timed length (`reference_numbers`): the
    per-position logits of `transformer.forward` to the reference's by
    the serving cells' rule (runners/serve.logits_verdict: no
    position's largest error above `logits_check.rtol` of the largest
    reference logit, judged on the parameters as initialised alone; the
    median position under `typical_rtol` in both states), and the
    training step's own router (`transformer.route_tokens`) to the
    reference's on the reference's own layer inputs (`router_verdict`:
    the share of tokens whose chosen experts differ, and the largest
    difference of a weight where they agree: what a router's PRECISION
    moves, which the logits cannot tell from a near-tie);
  - the compared numbers are printed on the run's last lines.

The two runners differ in arithmetic and aux alone (ROADMAP.md C: a
`benchmark` PR folds this file into runners/train.py).
"""

import math

import numpy as np

from benchmarks import harness
from benchmarks.runners.serve import logits_verdict
from benchmarks.runners.train import (FLASH_KERNELS, REF_LOSS_ATOL,
                                      TRACE_STEPS, mosaic_kernels,
                                      reference_loss)
from benchmarks.traffic import generate


def build_engine(cell, devices, seed):
    import jax

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.platform.mesh import build_mesh
    from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

    tr = cell.config["train"]
    mcfg = config_from_hf(cell.config, **tr["model_overrides"])
    engine = ds.initialize(
        dict(tr["ds_config"], steps_per_print=10**9),
        loss_fn=T.make_loss_fn(mcfg, loss_chunks=tr["loss_chunks"],
                               has_aux=True),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
        mesh=build_mesh(tr["mesh"], devices=list(devices)),
        init_rng=jax.random.PRNGKey(seed),
        has_aux=True, state_rule=T.step_state_rule(mcfg),
    )
    return engine, mcfg


def combine_matrix(idx, weights, width: int):
    """[T, width]: a token's weight on each expert, 0 where not chosen."""
    import jax.numpy as jnp

    rows = jnp.arange(idx.shape[0])[:, None]
    return jnp.zeros((idx.shape[0], width), jnp.float32).at[rows, idx].add(
        weights.astype(jnp.float32))


def routing_errors(got, want):
    """Two combine matrices [T, X] -> (tokens whose chosen experts
    differ, the largest |weight difference| among the others)."""
    import jax.numpy as jnp

    flipped = jnp.any((got > 0) != (want > 0), axis=-1)
    d = jnp.max(jnp.abs(got - want), axis=-1)
    return int(jnp.sum(flipped)), float(jnp.max(jnp.where(flipped, 0.0, d)))


def router_verdict(chk, flipped_share: float, weight_err: float):
    """The traffic file's `router_check` on the router's agreement."""
    broken = []
    if not flipped_share <= chk["flipped_share"]:
        broken.append(f"the router chose other experts for {flipped_share:.5f}"
                      f" of the tokens, limit {chk['flipped_share']}")
    if not weight_err <= chk["weight_atol"]:
        broken.append(f"a router weight is {weight_err:.2e} off, limit "
                      f"{chk['weight_atol']}")
    return {"flipped_share": flipped_share, "weight_err": weight_err,
            "ok": not broken, "broken": broken}


def reference_numbers(cell, mcfg, params, tokens, mesh, stand_ins=None):
    """`params` (a compute-dtype tree of the engine's) against the plain
    reference on the same values, one sequence of `tokens` at a time
    (8,192 x 25,024 float32 logits are 0.8 GB a side): per-position
    largest |logit difference| [sequences, positions] of
    `transformer.forward` and the largest |reference logit|; and, on
    the reference's own input to each routed layer's MLP rounded to the
    compute dtype (so both routers read the same values), the tokens
    whose experts `transformer.route_tokens` chose differ from the
    reference's and the largest weight difference among the rest.
    Returns {"system": numbers}; `stand_ins(ref, top, layer)` -> {name:
    (logits(row), routing(lw, h))} adds the numbers of whatever else is
    put in the system's place against the same reference
    (benchmarks/afmoe_audit.py: the controls)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import transformer as T

    hf = cell.config
    ref = harness.load_module(
        cell.bench_dir / "reference" / f"{hf['reference']}.py")
    top = {k: v for k, v in params.items() if k != "layers"}

    def layer(l):
        return {k: v[l] for k, v in params["layers"].items()}

    X = mcfg.n_experts  # the router's whole width
    fwd = jax.jit(lambda p, t: T.forward(p, t, mcfg))
    routed = jax.jit(lambda lw, h: combine_matrix(
        *T.route_tokens(mcfg, lw, h), X))

    def logits(row):
        with jax.sharding.set_mesh(mesh):
            return fwd(params, row[None])

    def routing(lw, h):
        return routed({k: lw[k] for k in ("w_router", "expert_bias")}, h)

    # one fused pass: no float32 copy of the logits, no difference kept
    errors = jax.jit(lambda got, want: (
        jnp.max(jnp.abs(got.astype(jnp.float32) - want), axis=-1),
        jnp.max(jnp.abs(want)), jnp.all(jnp.isfinite(got))))
    held = {"system": (logits, routing),
            **(stand_ins(ref, top, layer) if stand_ins else {})}
    out = {name: {"err": [], "ref_max": 0.0, "finite": True, "flipped": 0,
                  "weight_err": 0.0} for name in held}
    routed_tokens = 0
    for row in np.asarray(tokens):
        hs = []
        want = ref.forward_logits(top, layer, row[None], hf, routed_inputs=hs)
        for name, (logits_of, _) in held.items():
            err, top_, fin = errors(logits_of(row), want)
            o = out[name]
            o["err"].append(np.asarray(err)[0])
            o["ref_max"] = max(o["ref_max"], float(top_))
            o["finite"] = o["finite"] and bool(fin)
        del want
        for l, h in enumerate(hs):
            lw = layer(l)
            h = h[0].astype(lw["w_router"].dtype)
            routed_tokens += h.shape[0]
            with jax.default_matmul_precision("highest"):
                want_r = combine_matrix(*ref.route(h, lw, hf), X)
            for name, (_, routing_of) in held.items():
                n, w = routing_errors(routing_of(lw, h), want_r)
                out[name]["flipped"] += n
                out[name]["weight_err"] = max(out[name]["weight_err"], w)
    return {name: {"err": np.stack(o["err"]), "ref_max": o["ref_max"],
                   "finite": o["finite"],
                   "flipped_share": o["flipped"] / routed_tokens,
                   "weight_err": o["weight_err"]}
            for name, o in out.items()}


def reference_verdict(mix, numbers, state: str, ceiling: bool = True):
    """(ok, the compared line, the numbers kept) of `reference_numbers`
    under the traffic file's two rules. `ceiling` False: the largest
    position is reported and not judged (a state in which ONE flipped
    near-tied expert decides it: the traffic file says which and why)."""
    v = logits_verdict(
        mix["logits_check"] if ceiling
        else dict(mix["logits_check"], rtol=math.inf),
        numbers["err"], numbers["ref_max"], numbers["finite"])
    r = router_verdict(mix["router_check"], numbers["flipped_share"],
                       numbers["weight_err"])
    line = (
        f"[bench] compared ({state} parameters): logits over "
        f"{numbers['err'].size} positions, as shares of the largest "
        f"|reference logit| {numbers['ref_max']:.3f}: largest position "
        f"{v['max_share']:.5f}, limit {v['rtol'] if ceiling else 'none'}; "
        f"median position "
        f"{v['median_share']:.5f}, limit {v.get('typical_rtol', 'none')}; "
        f"the router on the reference's layer inputs: experts differ for "
        f"{r['flipped_share']:.6f} of the tokens, limit "
        f"{mix['router_check']['flipped_share']}; weights within "
        f"{r['weight_err']:.2e}, limit {mix['router_check']['weight_atol']}"
        + "".join(f"; BROKEN: {b}" for b in v["broken"] + r["broken"]))
    numbers = {"max_share": v["max_share"], "median_share": v["median_share"],
               "ref_max_abs": v["ref_max_abs"],
               "flipped_share": r["flipped_share"],
               "weight_err": r["weight_err"]}
    return v["ok"] and r["ok"], line, numbers


def run(ctx: harness.RunContext) -> harness.Outcome:
    import jax

    from benchmarks.trace.capture import Capture

    cell, mix, log = ctx.cell, ctx.cell.traffic, ctx.log
    arith = harness.load_module(
        cell.bench_dir / "kernels" / f"{cell.config['reference']}.py")
    chips = len(ctx.devices)
    engine, mcfg = build_engine(cell, ctx.devices, ctx.seed)
    bs = engine.config.train_batch_size
    seq_len = int(mix["seq_len"])
    tokens_per_step = bs * seq_len
    batches = generate.token_batches(mix, ctx.seed, mcfg.vocab_size, bs)
    # the parameters as initialised, for the comparison after the window
    fresh = jax.device_get(engine.state.params)
    log(f"[bench] engine built {harness.now() - ctx.t_process_start:.1f}s "
        f"after start; {bs} x {seq_len} tokens a step on {chips} chip(s)")

    steps = []  # every step's host metrics, warm-up included
    for _ in range(int(mix["warmup_steps"])):
        steps.append(engine.train_batch(next(batches)))
    n_warm = len(steps)
    counters0 = dict(engine.counters)

    # ---- the measured window --------------------------------------------
    compiles0 = ctx.compiles.n
    t0 = harness.now()
    setup_s = t0 - ctx.t_process_start
    step_s, gen_s = [], []
    traced_steps = None
    capture = Capture(ctx.out_dir) if ctx.trace else None
    t_end = t0 + ctx.seconds
    td = None
    while harness.now() < t_end:
        if capture is not None and len(step_s) >= 3 \
                and harness.now() - t0 >= 0.3 * ctx.seconds:
            capture.start()
            traced0 = len(steps)
            for _ in range(TRACE_STEPS):
                with jax.profiler.TraceAnnotation("bench.batch_gen"):
                    batch = next(batches)
                with jax.profiler.TraceAnnotation("bench.train_batch"):
                    steps.append(engine.train_batch(batch))
            td = capture.stop()
            traced_steps = TRACE_STEPS
            break  # a traced run reports per-layer metrics only
        ta = harness.now()
        batch = next(batches)
        tb = harness.now()
        steps.append(engine.train_batch(batch))
        tc = harness.now()
        gen_s.append(tb - ta)
        step_s.append(tc - ta)
    elapsed = harness.now() - t0 if td is None else float(sum(step_s))
    compiles_in_window = ctx.compiles.n - compiles0
    hbm_in_use = harness.hbm_in_use_bytes(ctx.devices)
    recompiles = len(engine._recompile_tracker.findings)
    n_steps = len(step_s)
    tps_chip = n_steps * tokens_per_step / elapsed / chips
    # sums and extremes over the window's steps (the warm-up's taken off
    # the sums; an extreme or a last value is the engine's as it stands)
    counters_delta = {
        k: engine.counters[k] - counters0[k] if how == "sum"
        else engine.counters[k]
        for k, how in engine.state_rule.counters.items()}
    counters_delta["steps"] = len(steps) - n_warm

    # ---- correctness, outside the window ----------------------------------
    losses = [m["loss"] for m in steps]
    checks, compared = {}, []
    checks["finite"] = all(math.isfinite(x) for x in losses)
    # a fresh model's logits are N(0, s^2) with s = 0.02 * sqrt(d_model)
    # (unit-RMS hidden state x the head's 0.02 init), so its loss is
    # ln V + s^2 / 2
    first_want = math.log(mcfg.vocab_size) + 0.5 * 0.02 ** 2 * mcfg.d_model
    checks["first_loss_as_a_fresh_model"] = abs(losses[0] - first_want) <= 0.5
    k = min(5, len(losses) // 2)
    checks["loss_fell"] = bool(np.mean(losses[-k:]) < np.mean(losses[:k])) \
        if k else False
    checks["no_compile_in_window"] = compiles_in_window == 0 and recompiles == 0
    if ctx.devices[0].platform == "tpu":
        got = mosaic_kernels(engine._train_compiled.as_text())
        checks["flash_kernels_compiled"] = got == set(FLASH_KERNELS)
    # every token chose k experts in every routed layer, every step, and
    # the grouped products that ran covered every pair on a held expert
    want_pairs = tokens_per_step * mcfg.moe_top_k * mcfg.n_layers
    checks["census_sums_to_every_pair"] = all(
        m["moe_pairs_routed"] == want_pairs
        and int(m["moe_census"].sum()) == want_pairs for m in steps)
    checks["no_held_pair_dropped"] = all(
        m["moe_pairs_dropped"] == 0 for m in steps)
    compared.append(
        f"[bench] compared: pairs routed a step "
        f"{sorted({int(m['moe_pairs_routed']) for m in steps})} against "
        f"{want_pairs}; held pairs dropped "
        f"{int(sum(m['moe_pairs_dropped'] for m in steps))} against 0; held pairs "
        f"in the first and the last step {int(steps[0]['moe_pairs_held'])}, "
        f"{int(steps[-1]['moe_pairs_held'])}")

    ev = mix["reference_check"]
    eval_tokens = next(generate.token_batches(
        dict(mix, seq_len=ev["seq_len"]), ctx.seed + 1, mcfg.vocab_size,
        chips * int(ev["sequences_per_chip"])))["tokens"]
    got_loss = float(engine.eval_batch({"tokens": eval_tokens}))
    want_loss = reference_loss(cell, engine, eval_tokens)
    matches = abs(got_loss - want_loss) <= REF_LOSS_ATOL
    compared.append(
        f"[bench] compared: eval loss {got_loss:.5f} against the float32 "
        f"reference's {want_loss:.5f}: |d| {abs(got_loss - want_loss):.5f}, "
        f"limit {REF_LOSS_ATOL}")
    reference = {}
    for state, params in (("initial", fresh),
                          ("trained", engine.state.params)):
        ok, line, reference[state] = reference_verdict(
            mix, reference_numbers(cell, mcfg, params, eval_tokens[:, :-1],
                                   engine.mesh)["system"], state,
            ceiling=state == "initial")
        matches = matches and ok
        compared.append(line)
    del fresh
    checks["matches_reference"] = bool(matches)
    log(f"[bench] losses first {losses[:3]} last {losses[-3:]}; compiles in "
        f"window {compiles_in_window}, recompiles {recompiles}")
    for line in compared:
        log(line)
    log(f"[bench] checks {checks}")

    obs = {
        "chips": chips, "hf": cell.config, "n_layers": mcfg.depth,
        "seq_len": seq_len, "tokens_per_step": tokens_per_step,
        "micro_batch_per_chip": bs // chips,
        "step_s": step_s, "batch_gen_s": gen_s,
        "tokens_per_s_per_chip": tps_chip,
        "flops_per_token": arith.train_flops_per_token(cell.config, seq_len),
        "trace": td, "traced_steps": traced_steps,
        "hbm_in_use_bytes": hbm_in_use,
        "counters_delta": counters_delta,
        # the traced steps' own held pairs (the rooflines' operations)
        "traced_pairs_held": (sum(m["moe_pairs_held"]
                                  for m in steps[traced0:])
                              if td is not None else None),
    }
    return harness.Outcome(
        correct=all(checks.values()),
        attempted=len(losses) - n_warm,
        failed=sum(1 for x in losses[n_warm:] if not math.isfinite(x)),
        end_to_end={"train_tokens_per_s_per_chip": tps_chip,
                    "setup_s": setup_s},
        obs=obs,
        notes={"checks": checks, "steps": n_steps,
               "step_ms_median": 1e3 * float(np.median(step_s)) if step_s else None,
               # how uneven the routed block's data-dependent groups make
               # the steps
               "step_ms_spread": (1e3 * float(np.max(step_s) - np.min(step_s))
                                  if step_s else None),
               "batch_gen_ms_median": 1e3 * float(np.median(gen_s)) if gen_s else None,
               "loss_first": losses[0], "loss_last": losses[-1],
               "eval_loss": got_loss, "reference_loss": want_loss,
               "reference": reference,
               "compile_s_total": ctx.compiles.seconds,
               "programs_compiled": ctx.compiles.n,
               "bytes_in_use_after": hbm_in_use,
               "counters_delta": counters_delta,
               "compared": compared,
               "params": arith.model_params(cell.config)})
