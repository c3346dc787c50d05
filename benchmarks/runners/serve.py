"""Runner kind `serve`: init_inference + ServingScheduler under an
open-loop schedule, through the entry points a user calls.

Set-up: seeded bf16 weights made on the device, the engine, the warm-up
of every decode bucket the scheduler can dispatch, and the ramp (the
schedule starts `ramp_s` before the window, so the window opens on a
loaded system). Window: `--seconds` of `ServingScheduler.run(tick=...)`
(the loop of `bench.py::_serving_sim`, with the scheduler's own
double-buffered run()), the tick submitting what is due and never
more. Then the drain, and, outside every timed span, the correctness
checks: request accounting and teacher-forced logits against the plain
float32 reference.

Latencies are computed here from Request.arrival / first_token_t /
finish_t and the schedule, from when a request was DUE, over the
window only (ServingScheduler.metrics() times from submit() over every
request since construction).
"""

import dataclasses
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks import harness
from benchmarks.traffic import generate

TRACE_SECONDS = 2.0

# Max |logit difference| of the served bf16 engine vs the plain float32
# reference on the engine's own weights, teacher-forced, as a share of
# the largest |reference logit|. chip_smoke's LOGITS_ATOL argument
# redone for this model: there two bf16 implementations (same rounding
# points) over 24 layers at d 1024 differed by 1% of the largest logit;
# here one side is exact and the other rounds activations to bf16
# (2^-8 relative) at each of ~7 matmul boundaries in each of 16 layers,
# which random-walks to sqrt(112) x 0.4% ~ 4%. Measured on the chip
# (PERF.md §6): 3.4-4.2% of max |logit| ~ 6.4 over five seeds, the same
# at the prefill, chunk and decode steps. 8% is ~2x that noise, wide
# enough that no seed of a later check fails on rounding alone. A wrong
# block, mask, slot or rope offset replaces a logit by an unrelated one:
# an error of the order of the logits' own spread (25% of the maximum
# and more). It does NOT tell bf16 from a coarser KV type: PR 21 saw
# int8 KV (0.035) about level with bf16 (0.032) on the smaller model.
# That argument is this family's sixteen dense layers: a traffic file's
# `logits_check` may state its own `rtol` with `rtol_why` (a routed
# model can flip a near-tied expert under bf16).
LOGITS_RTOL = 0.08


class _Stop(Exception):
    """Raised from the tick to leave ServingScheduler.run() when the
    run is over (run() itself returns only when the queue is empty)."""


@dataclasses.dataclass
class _Snap:
    t: float
    out_tokens: int
    counters: Dict[str, int]


def _snap(sched) -> _Snap:
    n = sum(len(r.output) for r in sched.active) \
        + sum(len(r.output) for r in sched.finished.values())
    return _Snap(harness.now(), n, dict(sched.counters))


def build_engine(cell, seed):
    import jax

    from benchmarks import weights
    from deepspeed_tpu.inference import init_inference
    from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

    sv = cell.config["serve"]
    mcfg = config_from_hf(cell.config, **sv["model_overrides"])
    # init_inference keeps its input alive while it builds the serving
    # layout AND the KV pool, so 7.5 GB of input + 7.5 GB of prepared
    # weights + the pool do not fit one chip: the input goes through the
    # host (made on the device, fetched, freed), and the engine's own
    # jitted transform takes it from there.
    t = [harness.now()]
    dev = weights.make_params(mcfg, seed)
    jax.block_until_ready(dev)
    t.append(harness.now())
    host = jax.device_get(dev)
    del dev
    t.append(harness.now())
    eng = init_inference(host, mcfg, sv["engine"])
    jax.block_until_ready(eng.params)
    t.append(harness.now())
    phases = dict(zip(("weights_on_device_s", "weights_to_host_s",
                       "init_inference_s"), np.diff(t).round(2).tolist()))
    return eng, mcfg, host, phases


def percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else None


def latency_stats(reqs: List[Any], due_abs: np.ndarray, w0: float, w1: float,
                  t_stop: float) -> Dict[str, Any]:
    """TTFT (from due time) over requests DUE inside [w0, w1); a request
    with no first token by t_stop counts as the largest value. TPOT over
    requests FINISHED inside the window with more than one token."""
    ttft, missing_due = [], []
    for r in reqs:
        due = due_abs[r.rid]
        if not (w0 <= due < w1):
            continue
        if r.first_token_t is None:
            missing_due.append(due)
        else:
            ttft.append(r.first_token_t - due)
    if missing_due:
        # no shorter than the longest of them has waited already
        ttft += [max(ttft + [t_stop - min(missing_due)])] * len(missing_due)
    tpot = [(r.finish_t - r.first_token_t) / (len(r.output) - 1)
            for r in reqs
            if r.finish_t is not None and w0 <= r.finish_t < w1
            and r.first_token_t is not None and len(r.output) > 1]
    return {"ttft_s": ttft, "ttft_missing": len(missing_due), "tpot_s": tpot}


def reference_inputs(host_params, cast=None):
    """What a reference's `forward_logits` takes: the top-level leaves
    and a function handing out one layer's weights at a time (so the
    float32 model never sits on the device whole). `cast` is applied to
    every leaf on its way (the audit's lower-precision control)."""
    import jax.numpy as jnp

    cast = cast or (lambda a: a)
    top = {k: cast(jnp.asarray(v)) for k, v in host_params.items()
           if k != "layers"}

    def layer(l):
        return {k: cast(jnp.asarray(v[l]))
                for k, v in host_params["layers"].items()}

    return top, layer


def logits_errors(cell, eng, mcfg, host_params, seed,
                  decode_steps=None) -> Dict[str, Any]:
    """Teacher-forced engine.put() logits (whole-prompt prefill, a
    continuation chunk, single-token decode steps through the cache)
    against the reference's full forward pass on the same tokens:
    max |difference| at every checked position, [prompts, steps]."""
    ref = harness.load_module(
        cell.bench_dir / "reference" / f"{cell.config['reference']}.py")
    chk = cell.traffic["logits_check"]
    rng = np.random.default_rng([seed, 0xC4EC])
    n_dec = int(chk["decode_steps"] if decode_steps is None else decode_steps)
    k = int(chk["chunk"])
    lens = [int(n) for n in chk["prompt_lens"]]
    full = [rng.integers(0, mcfg.vocab_size, n + n_dec).astype(np.int32)
            for n in lens]
    uids = [10_000_000 + i for i in range(len(lens))]
    feeds = [[f[:n - k] for f, n in zip(full, lens)],
             [f[n - k:n] for f, n in zip(full, lens)]]
    for j in range(n_dec):
        feeds.append([f[n + j:n + j + 1] for f, n in zip(full, lens)])
    got = [np.asarray(eng.put(uids, toks), np.float32) for toks in feeds]
    for u in uids:
        eng.flush(u)
    # one pass over both prompts, padded at the END to one length: under
    # a causal mask the padding cannot reach the positions compared
    padded = np.zeros((len(full), max(len(f) for f in full)), np.int32)
    for i, f in enumerate(full):
        padded[i, :len(f)] = f
    top, layer = reference_inputs(host_params)
    want_all = np.asarray(ref.forward_logits(top, layer, padded, cell.config))
    # positions whose next-token logits put() returned: the last fed
    # token of each call
    pos = np.asarray([[n - k - 1, n - 1] + [n + j for j in range(n_dec)]
                      for n in lens])
    want = np.stack([want_all[i, p] for i, p in enumerate(pos)])
    got = np.stack(got, axis=1)                      # [prompts, steps, V]
    return {"err": np.abs(got - want).max(axis=-1),
            "ref_max": float(np.abs(want).max()),
            "finite": bool(np.isfinite(got).all()),
            "got": got, "want": want, "tokens": padded, "pos": pos}


def step_name(s: int) -> str:
    return ("prefill", "chunk")[s] if s < 2 else f"decode {s - 2}"


def logits_verdict(chk: Dict[str, Any], err, ref_max: float,
                   finite: bool = True) -> Dict[str, Any]:
    """The traffic file's rule on the per-position errors `err`
    [prompts, steps], each taken as a share of the largest |reference
    logit| `ref_max`. `rtol` (absent: LOGITS_RTOL) is the CEILING: no
    position may read above it. `typical_rtol`, where the file states
    it, is a limit on the MEDIAN over the positions: a model that is
    wrong everywhere moves every position, one flipped near-tied expert
    moves one. A file that states neither gets the rule the benchmark
    started with: the largest error against LOGITS_RTOL."""
    share = np.asarray(err, np.float64) / ref_max
    rtol = float(chk.get("rtol", LOGITS_RTOL))
    i, s = np.unravel_index(int(np.argmax(share)), share.shape)
    broken = []
    if not finite:
        broken.append("a logit is not finite")
    if not share.max() <= rtol:
        broken.append(
            f"prompt {i} {step_name(s)}: max |err| {share.max():.5f} of the "
            f"largest |reference logit| {ref_max:.3f} is above rtol {rtol}")
    out = {"max_abs_err": float(np.max(err)), "ref_max_abs": ref_max,
           "rtol": rtol, "max_share": float(share.max()),
           "median_share": float(np.median(share))}
    if "typical_rtol" in chk:
        out["typical_rtol"] = typical = float(chk["typical_rtol"])
        if not out["median_share"] <= typical:
            broken.append(
                f"the median over {share.size} positions, "
                f"{out['median_share']:.5f} of {ref_max:.3f}, is above "
                f"typical_rtol {typical}")
    return dict(out, ok=not broken, broken=broken)


def logits_check(cell, eng, mcfg, host_params, seed, log) -> Dict[str, Any]:
    """`logits_errors` held to the traffic file's `logits_check` rule."""
    e = logits_errors(cell, eng, mcfg, host_params, seed)
    v = logits_verdict(cell.traffic["logits_check"], e["err"], e["ref_max"],
                       e["finite"])
    log(f"[bench] logits vs float32 reference: max |err| by step "
        f"{e['err'].max(axis=0).round(5).tolist()} on logits of max "
        f"|{e['ref_max']:.3f}|: largest {v['max_share']:.5f} of that "
        f"(allowed {v['rtol']}), median over the positions "
        f"{v['median_share']:.5f} (allowed {v.get('typical_rtol', 'any')})")
    return v


def report_false_checks(checks, readings, lc, log):
    """One line naming the checks that are false and what each read, so
    that a refused run can be understood from its output alone."""
    false = [k for k, ok in checks.items() if not ok]
    if not false:
        return
    why = dict(readings, matches_reference="; ".join(lc["broken"]))
    log("[bench] FALSE: " + " | ".join(f"{k}: {why[k]}" for k in false))


def setup(ctx: harness.RunContext):
    """Engine, weights and the warm-up of every bucket: paid once."""
    eng, mcfg, host_params, phases = build_engine(ctx.cell, ctx.seed)
    ctx.log(f"[bench] engine built {harness.now() - ctx.t_process_start:.1f}s "
            f"after start {phases}")
    t = harness.now()
    # the buckets THIS cell's traffic uses and no others: warm-up is
    # minutes of host work that no compile cache saves (PERF.md §5)
    eng.warmup(widths=ctx.cell.traffic["warmup_widths"], footprint=False)
    phases["warmup_s"] = round(harness.now() - t, 2)
    ctx.log(f"[bench] warm-up done {harness.now() - ctx.t_process_start:.1f}s "
            f"after start ({ctx.compiles.n} programs compiled, "
            f"{ctx.compiles.seconds:.1f}s)")
    return eng, mcfg, host_params, phases


def run(ctx: harness.RunContext) -> harness.Outcome:
    eng, mcfg, host_params, phases = setup(ctx)
    m = measure(ctx, eng, mcfg, ctx.seed)
    compared = []           # every number compared, beside its limit

    def say(msg):
        compared.append(msg)
        ctx.log(msg)

    lc = logits_check(ctx.cell, eng, mcfg, host_params, ctx.seed, say)
    m["checks"]["matches_reference"] = lc["ok"]
    say("[bench] compared: " + "; ".join(m["readings"].values()))
    say(f"[bench] checks {m['checks']}")
    report_false_checks(m["checks"], m["readings"], lc, say)
    m["notes"].update(logits=lc, setup_phases=phases, checks=m["checks"],
                      compared=compared,
                      compile_s_total=ctx.compiles.seconds,
                      programs_compiled=ctx.compiles.n)
    return harness.Outcome(
        correct=all(m["checks"].values()), attempted=m["attempted"],
        failed=m["failed"], end_to_end=m["end_to_end"], obs=m["obs"],
        notes=m["notes"])


def measure(ctx: harness.RunContext, eng, mcfg, seed: int,
            rate_rps: float = None) -> Dict[str, Any]:
    """Ramp, window and drain of one schedule on a warmed engine with a
    fresh scheduler; leaves the engine's pool empty."""
    import jax

    from benchmarks.kernels import shapes
    from benchmarks.trace.capture import Capture
    from deepspeed_tpu.inference import ServingScheduler, ServingSchedulerConfig

    cell, mix, log = ctx.cell, ctx.cell.traffic, ctx.log
    sv = cell.config["serve"]
    # the deployment's scheduler block: the configuration alone sets it
    sched = ServingScheduler(
        eng, ServingSchedulerConfig(**sv["scheduler"], warmup=False),
        seed=seed)

    ramp_s, drain_s = float(mix["ramp_s"]), float(mix["drain_s"])
    plan = generate.serve_schedule(
        mix, seed, ramp_s + ctx.seconds, mcfg.vocab_size, rate_rps)
    n_req = len(plan.due_s)

    # ---- ramp, window, drain --------------------------------------------
    t_ramp0 = harness.now()
    w0, w1 = t_ramp0 + ramp_s, t_ramp0 + ramp_s + ctx.seconds
    t_stop = w1 + drain_s
    due_abs = t_ramp0 + plan.due_s
    st = {"next": 0, "snap0": None, "snap1": None, "compiles0": None,
          "compiles1": None, "td": None, "capture": None, "ticks": [],
          "iteration": None}
    lateness = np.zeros((n_req,))
    trace_at = w0 + 0.3 * ctx.seconds if ctx.trace else None

    def close_iteration():
        if st["iteration"] is not None:
            st["iteration"].__exit__(None, None, None)
            st["iteration"] = None

    def tick(s):
        # one span per scheduler iteration, from the end of this tick to
        # the start of the next: the idle gaps inside it are the
        # scheduler's host work between two device programs
        close_iteration()
        now = harness.now()
        if st["snap0"] is None and now >= w0:
            st["snap0"], st["compiles0"] = _snap(s), ctx.compiles.n
        if st["snap1"] is None and now >= w1:
            st["snap1"], st["compiles1"] = _snap(s), ctx.compiles.n
        if trace_at is not None and st["td"] is None:
            cap = st["capture"]
            if cap is None and now >= trace_at:
                st["capture"] = cap = Capture(ctx.out_dir)
                cap.start()
                cap.t_started = harness.now()
            elif cap is not None and now >= cap.t_started + TRACE_SECONDS:
                st["td"] = cap.stop()
        if now >= t_stop or (now >= w1 and drain_s == 0):
            raise _Stop
        with jax.profiler.TraceAnnotation("bench.submit"):
            i = st["next"]
            while i < n_req and due_abs[i] <= now:
                s.submit(plan.prompts[i], max_new_tokens=int(plan.answer_len[i]))
                lateness[i] = harness.now() - due_abs[i]
                i += 1
            st["next"] = i
        if st["capture"] is not None and st["td"] is None:
            # what the iteration about to run will read: every active
            # sequence's context so far (for the KV bytes the decode
            # kernel needs)
            st["ticks"].append((now, sum(
                len(r.prompt) + len(r.output) for r in s.active
                if r.state == "running"), len(s.active), len(s.waiting)))
        st["iteration"] = jax.profiler.TraceAnnotation("bench.sched_iteration")
        st["iteration"].__enter__()

    try:
        while True:
            tick(sched)
            if sched.has_work:
                with jax.profiler.TraceAnnotation("bench.sched_run"):
                    try:
                        sched.run(tick=tick)
                    finally:
                        close_iteration()
            elif st["next"] < n_req:
                time.sleep(min(0.002, max(0.0, due_abs[st["next"]] - harness.now())))
            elif harness.now() >= w1:
                break
            else:
                time.sleep(0.002)
    except _Stop:
        pass
    t_done = harness.now()
    if st["snap1"] is None:  # the queue emptied before the window's end
        st["snap1"], st["compiles1"] = _snap(sched), ctx.compiles.n
    if st["capture"] is not None and st["td"] is None:
        st["td"] = st["capture"].stop()
    snap0, snap1 = st["snap0"], st["snap1"]
    window_s = snap1.t - snap0.t
    compiles_in_window = st["compiles1"] - st["compiles0"]
    recompiles = len(eng.recompile_tracker.findings)

    # ---- accounting (nothing below is timed) --------------------------------
    reqs = list(sched.finished.values()) + list(sched.active) + list(sched.waiting)
    by_rid = {r.rid: r for r in reqs}
    lat = latency_stats(reqs, due_abs, w0, w1, t_done)
    finished_in = [r for r in sched.finished.values()
                   if w0 <= r.finish_t < w1]
    bad_finish = {r.rid for r in sched.finished.values()
                  if r.finish_reason != "length"
                  or len(r.output) != int(plan.answer_len[r.rid])
                  or not all(0 <= t < mcfg.vocab_size for t in r.output)}
    due_in = [i for i in range(n_req) if w0 <= due_abs[i] < w1]
    if mix["count"] == "due_in_window":
        attempted = len(due_in)
        failed = sum(1 for i in due_in
                     if i not in by_rid or by_rid[i].finish_t is None
                     or i in bad_finish)
    elif mix["count"] == "finished_in_window":
        attempted = len(finished_in)
        failed = sum(1 for r in finished_in if r.rid in bad_finish)
    else:
        raise ValueError(f"unknown count rule {mix['count']!r}")
    unfinished = sum(1 for i in due_in
                     if i not in by_rid or by_rid[i].finish_t is None)
    no_first = lat["ttft_missing"]
    hbm_in_use = harness.hbm_in_use_bytes(ctx.devices)
    for r in list(sched.active):  # free the pool for the logits check
        if r.uid is not None:
            eng.flush(r.uid)

    checks = {
        "pallas": eng.resolved_impl == "pallas"
        or ctx.devices[0].platform != "tpu",
        "no_compile_in_window": compiles_in_window == 0 and recompiles == 0,
        "finished_as_asked": not bad_finish,
        "some_finished": len(finished_in) > 0,
    }
    log(f"[bench] compiles in window {compiles_in_window}, recompiles {recompiles}")
    # what each check read, beside what it allows
    readings = {
        "pallas": f"decode_impl resolved {eng.resolved_impl!r} "
        f"(pallas on a TPU)",
        "no_compile_in_window": f"{compiles_in_window} programs compiled in "
        f"the window, {recompiles} recompile findings (allowed 0)",
        "finished_as_asked": f"{len(bad_finish)} finished with another reason "
        f"than `length`, another count than asked or a token outside the "
        f"vocabulary (allowed 0){sorted(bad_finish)[:8] or ''}",
        "some_finished": f"{len(finished_in)} finished inside the window "
        f"(at least 1)",
    }

    d = {k: snap1.counters[k] - snap0.counters[k] for k in snap1.counters}
    e2e = {
        "serve_tokens_per_s": (snap1.out_tokens - snap0.out_tokens) / window_s,
        "setup_s": w0 - ctx.t_process_start,
    }
    if lat["ttft_s"]:
        e2e["ttft_p50_ms"] = 1e3 * percentile(lat["ttft_s"], 50)
    if lat["tpot_s"]:
        e2e["tpot_p50_ms"] = 1e3 * percentile(lat["tpot_s"], 50)
    late_in = [lateness[i] for i in due_in if i < st["next"]]
    obs = {
        "hf": cell.config, "n_layers": mcfg.n_layers,
        "counters_delta": d, "window_s": window_s,
        "ttft_s": lat["ttft_s"], "tpot_s": lat["tpot_s"],
        "lateness_s": late_in, "ticks": st["ticks"],
        "kv_bytes_per_token": shapes.kv_bytes_per_token(
            cell.config, mcfg.n_layers, 2),
        "trace": st["td"], "hbm_in_use_bytes": hbm_in_use,
    }
    notes = {
        "rate_rps": float(mix["rate_rps"] if rate_rps is None else rate_rps),
        "requests_scheduled": n_req, "submitted": st["next"],
        "due_in_window": len(due_in), "finished_in_window": len(finished_in),
        "unfinished_after_drain": unfinished, "no_first_token": no_first,
        "waiting_at_end": len(sched.waiting), "active_at_end": len(sched.active),
        "counters_delta": d, "window_s": window_s,
        "ttft_ms": {q: (1e3 * percentile(lat["ttft_s"], q) if lat["ttft_s"] else None)
                    for q in (50, 90, 99)},
        "ttft_samples": len(lat["ttft_s"]),
        "tpot_ms": {q: (1e3 * percentile(lat["tpot_s"], q) if lat["tpot_s"] else None)
                    for q in (50, 90, 99)},
        "tpot_samples": len(lat["tpot_s"]),
        "gen_lateness_ms_p99": 1e3 * percentile(late_in, 99) if late_in else None,
        "bytes_in_use_after": hbm_in_use,
    }
    return {"checks": checks, "readings": readings,
            "attempted": attempted, "failed": failed,
            "end_to_end": e2e, "obs": obs, "notes": notes}
