"""Runner kind `train`: ds.initialize + engine.train_batch on a stream
of seeded batches, through the entry points a user calls.

Set-up: build the engine (weights and optimizer state made on the
device inside the engine's own jitted init), run the warm-up steps (the
first compiles or loads the step program). Window: whole steps, each
ending in the loss readback train_batch does, started while the window
is open; throughput is their tokens over the time they took. After the
window: the loss checks, the kernels in the compiled step, and the
engine's forward loss against the plain float32 reference.
"""

import math

import numpy as np

from benchmarks import harness
from benchmarks.traffic import generate

FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")

# |engine.eval_batch loss - float32 reference loss| on the same tokens
# and the same (bf16-rounded) weights. The engine computes in bf16 with
# float32 accumulation: per-logit rounding noise of order 2^-8 relative
# averages out over the token mean, leaving a bias of order 1e-3 on a
# loss near ln(32000) = 10.37 (measured on the chip: see PERF.md §6).
# 0.02 is ~10x that, and far under what a wrong mask, rope pairing or
# head mapping does (those move the loss of a fresh model by > 0.1
# only after training, so the test at tiny size pins them exactly).
REF_LOSS_ATOL = 0.02
TRACE_STEPS = 3


def mosaic_kernels(compiled_text: str, names=FLASH_KERNELS) -> set:
    """Names of the Pallas kernels that compiled to Mosaic custom calls
    (chip_smoke._mosaic_kernels's rule)."""
    found = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        for name in names:
            if f"/{name}/" in line or f"%{name}" in line:
                found.add(name)
    return found


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
        loss_fn=T.make_loss_fn(mcfg, loss_chunks=tr["loss_chunks"]),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
        mesh=build_mesh(tr["mesh"], devices=list(devices)),
        init_rng=jax.random.PRNGKey(seed),
    )
    return engine, mcfg


def reference_loss(cell, engine, tokens) -> float:
    """The plain reference on the engine's own compute-dtype weights,
    fetched one layer at a time."""
    import jax

    ref = harness.load_module(
        cell.bench_dir / "reference" / f"{cell.config['reference']}.py")
    params = engine.state.params
    top = {k: np.asarray(jax.device_get(v))
           for k, v in params.items() if k != "layers"}

    def layer(l):
        return {k: np.asarray(jax.device_get(v[l]))
                for k, v in params["layers"].items()}

    return ref.loss(top, layer, tokens, cell.config)


def run(ctx: harness.RunContext) -> harness.Outcome:
    import jax

    from benchmarks.kernels import shapes
    from benchmarks.trace.capture import Capture

    cell, mix, log = ctx.cell, ctx.cell.traffic, ctx.log
    chips = len(ctx.devices)
    engine, mcfg = build_engine(cell, ctx.devices, ctx.seed)
    bs = engine.config.train_batch_size
    tokens_per_step = bs * int(mix["seq_len"])
    batches = generate.token_batches(mix, ctx.seed, mcfg.vocab_size, bs)
    log(f"[bench] engine built {harness.now() - ctx.t_process_start:.1f}s "
        f"after start; {bs} x {mix['seq_len']} tokens a step on {chips} chip(s)")

    losses = []
    for _ in range(int(mix["warmup_steps"])):
        losses.append(engine.train_batch(next(batches))["loss"])
    n_warm = len(losses)

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
            for _ in range(TRACE_STEPS):
                with jax.profiler.TraceAnnotation("bench.batch_gen"):
                    batch = next(batches)
                with jax.profiler.TraceAnnotation("bench.train_batch"):
                    losses.append(engine.train_batch(batch)["loss"])
            td = capture.stop()
            traced_steps = TRACE_STEPS
            break  # a traced run reports per-layer metrics only
        ta = harness.now()
        batch = next(batches)
        tb = harness.now()
        losses.append(engine.train_batch(batch)["loss"])
        tc = harness.now()
        gen_s.append(tb - ta)
        step_s.append(tc - ta)
    elapsed = harness.now() - t0 if td is None else float(sum(step_s))
    compiles_in_window = ctx.compiles.n - compiles0
    hbm_in_use = harness.hbm_in_use_bytes(ctx.devices)
    recompiles = len(engine._recompile_tracker.findings)
    n_steps = len(step_s)
    tps_chip = n_steps * tokens_per_step / elapsed / chips

    # ---- correctness, outside the window ----------------------------------
    checks = {}
    checks["finite"] = all(math.isfinite(x) for x in losses)
    # a fresh model's logits are N(0, s^2) with s = 0.02 * sqrt(d_model)
    # (unit-RMS hidden state x the head's 0.02 init), so its loss is
    # ln V + s^2 / 2: 10.37 + 0.82 at d 4096
    first_want = math.log(mcfg.vocab_size) + 0.5 * 0.02 ** 2 * mcfg.d_model
    checks["first_loss_as_a_fresh_model"] = abs(losses[0] - first_want) <= 0.5
    k = min(5, len(losses) // 2)
    checks["loss_fell"] = (np.mean(losses[-k:]) < np.mean(losses[:k])) if k else False
    checks["no_compile_in_window"] = compiles_in_window == 0 and recompiles == 0
    on_tpu = ctx.devices[0].platform == "tpu"
    if on_tpu:
        got = mosaic_kernels(engine._train_compiled.as_text())
        checks["flash_kernels_compiled"] = got == set(FLASH_KERNELS)
    ev = mix["reference_check"]
    eval_tokens = next(generate.token_batches(
        dict(mix, seq_len=ev["seq_len"]), ctx.seed + 1, mcfg.vocab_size,
        chips * int(ev["sequences_per_chip"])))["tokens"]
    got_loss = float(engine.eval_batch({"tokens": eval_tokens}))
    want_loss = reference_loss(cell, engine, eval_tokens)
    checks["matches_reference"] = abs(got_loss - want_loss) <= REF_LOSS_ATOL
    log(f"[bench] losses first {losses[:3]} last {losses[-3:]}; eval "
        f"{got_loss:.5f} vs reference {want_loss:.5f} "
        f"(|d| {abs(got_loss - want_loss):.5f}, atol {REF_LOSS_ATOL}); "
        f"compiles in window {compiles_in_window}, recompiles {recompiles}")
    log(f"[bench] checks {checks}")

    n_layers = mcfg.n_layers
    obs = {
        "chips": chips, "hf": cell.config, "n_layers": n_layers,
        "seq_len": int(mix["seq_len"]), "tokens_per_step": tokens_per_step,
        "micro_batch_per_chip": bs // chips,
        "step_s": step_s, "batch_gen_s": gen_s,
        "tokens_per_s_per_chip": tps_chip,
        "flops_per_token": shapes.train_flops_per_token(
            cell.config, int(mix["seq_len"]), n_layers),
        "trace": td, "traced_steps": traced_steps,
        "hbm_in_use_bytes": hbm_in_use,
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
               "batch_gen_ms_median": 1e3 * float(np.median(gen_s)) if gen_s else None,
               "loss_first": losses[0], "loss_last": losses[-1],
               "eval_loss": got_loss, "reference_loss": want_loss,
               "compile_s_total": ctx.compiles.seconds,
               "programs_compiled": ctx.compiles.n,
               "bytes_in_use_after": hbm_in_use,
               "params": shapes.model_params(cell.config, n_layers)})
