#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a
user calls, at the full width and depth of the flagship model, with
seeded random weights:

  train      ds.initialize + engine.train_batch: a few steps on a fixed
             batch; loss starts near ln(vocab), ends strictly lower,
             all finite; the compiled step holds the flash forward AND
             backward Mosaic kernels.
  serve      init_inference (decode_impl='auto', kv_cache_dtype='auto')
             under ServingScheduler (warm-up on, chunked prefill): every
             request finishes with the asked number of tokens; then the
             same prompts through engine.put() against a second engine
             with decode_impl='xla' (the jnp oracle), logits compared;
             the compiled prefill / chunked / decode programs hold the
             Mosaic kernels they should.
  serve_int8 the same with kv_cache_dtype='int8' (the fused int8-KV grid
             kernel).
  train_4chip / serve_4chip   only when jax.device_count() >= 4:
             ZeRO-3 x {data: 2, model: 2} training with the state spread
             over all four devices, and tp_size=4 serving against the
             same oracle.

`python3 chip_smoke.py` FAILS (non-zero, no result line) unless JAX's
backend is a TPU; no option changes that. A failed phase prints its name
and traceback and the run exits non-zero. The last line of stdout is one
JSON object with exactly these keys, the device as JAX reports it:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}.
The line before it, "[chip_smoke] report: {...}", is the full report:
versions, compile cache, and per phase pass/fail, compile seconds, peak
memory, kernels found, logits error. Times in it are information printed
beside the device, not a record.

`run(tiny=True, require_tpu=False)` walks the same phases at a tiny size
with the kernels in interpret mode — the CPU rehearsal and the tier-1
test (tests/test_chip_smoke.py).
"""

import contextlib
import gc
import json
import math
import os
import sys
import time


def sizes(tiny: bool) -> dict:
    """Every size of the run, in one place. The full row is the
    flagship: vocab 32000, 24 layers, d_model 1024, 8 heads x 128,
    seq 2048, micro-batch 8."""
    if tiny:
        # the least that still walks every phase and every kernel: the
        # interpreter pays per traced kernel, so one layer and few steps
        return dict(
            # 4 heads so tp_size=4 divides; head_dim stays 128, the
            # width the manual-DMA decode kernel needs
            model=dict(vocab_size=256, n_layers=1, n_heads=4, d_model=256,
                       head_dim_override=128, max_seq=256,
                       flash_block_q=128, flash_block_k=128),
            micro_bs=2, train_steps=2, loss_chunks=2,
            serve=dict(max_seq_len=288, kv_block_size=32, num_kv_blocks=24,
                       max_batch_size=8, min_prefill_bucket=32),
            # scheduler requests (prompt length, new tokens): one short,
            # one long, one that crosses several KV blocks
            requests=((5, 2), (70, 2), (40, 2)),
            prefill_chunk=8,
            # engine.put() prompts for the logits comparison; the last
            # put_chunk tokens of each ride the continuation path
            put_prompts=(258,), put_chunk=2, put_decode=1,
        )
    return dict(
        model=dict(vocab_size=32000, n_layers=24, n_heads=8, d_model=1024,
                   max_seq=2048, flash_block_q=1024, flash_block_k=1024),
        micro_bs=8, train_steps=4, loss_chunks=16,
        serve=dict(max_seq_len=1024, kv_block_size=128, num_kv_blocks=64,
                   max_batch_size=8, min_prefill_bucket=64),
        requests=((24, 16), (600, 8), (300, 12)),
        prefill_chunk=32,
        put_prompts=(24, 300, 500), put_chunk=2, put_decode=4,
    )


# Max |logit difference| of a served engine vs the decode_impl='xla'
# oracle. The tests pin 2e-4 (kernel engine vs XLA engine) and 2e-2
# (engine vs training forward) for FLOAT32 engines; this run serves the
# default bf16, where the two implementations differ by the
# reassociation of 24 layers of bf16 rounding — measured 0.032-0.035 on
# logits of max |3.29| on a v5e (PR 21's chip run), flat across the
# prefill, chunk and decode steps. 0.08 is the loosest logits tolerance
# the tests pin for this family (tests/test_paged_quant.py
# INT8_VS_FP_ATOL): ~2.3x that noise, and an order of magnitude under
# what a wrong block, mask or slot does to a logit.
LOGITS_ATOL = 0.08
LOSS_4CHIP_TOL = 0.05  # bf16: same batch, same seed, another layout


class _Report:
    """Phase bookkeeping: pass/fail, wall seconds, compile seconds (from
    jax.monitoring's backend-compile events), peak device memory."""

    def __init__(self):
        import jax

        self.phases = {}
        self._compile_s = 0.0
        self._n_compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compile_s += duration
            self._n_compiles += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        import jax

        print(f"[chip_smoke] {name} ...", flush=True)
        c0, n0, t0 = self._compile_s, self._n_compiles, time.perf_counter()
        info = {}
        ok = False
        try:
            yield info
            ok = True
        finally:
            # no except: a failure keeps its traceback and ends the run
            info.update(
                ok=ok, seconds=round(time.perf_counter() - t0, 2),
                compile_seconds=round(self._compile_s - c0, 2),
                programs_compiled=self._n_compiles - n0,
                peak_bytes_in_use=[
                    (d.memory_stats() or {}).get("peak_bytes_in_use")
                    for d in jax.local_devices()],
            )
            self.phases[name] = info
            print(f"[chip_smoke] {name} {'passed' if ok else 'FAILED'}: "
                  f"{json.dumps(info)}", flush=True)
            gc.collect()  # the phase's engines free their HBM here


def _mosaic_kernels(compiled_text: str) -> set:
    """Names of the Pallas kernels that compiled to Mosaic custom calls
    in one program (each pallas_call in ops/pallas carries name=)."""
    found = set()
    for line in compiled_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                     "paged_decode_fused", "paged_decode_grid",
                     "paged_kv_write"):
            if f"/{name}/" in line or f"%{name}" in line:
                found.add(name)
    return found


def _expect_kernels(compile_fn, want: set, program: str, on_tpu: bool) -> list:
    """On the chip: the compiled program must hold exactly these Mosaic
    kernels (an interpreted or XLA-substituted kernel leaves no
    tpu_custom_call). Off the chip (interpret rehearsal) nothing
    compiles to Mosaic, so there is nothing to compile or look for."""
    if not on_tpu:
        return []
    got = _mosaic_kernels(compile_fn().as_text())
    if got != want:
        raise AssertionError(
            f"{program}: Mosaic kernels compiled {sorted(got)}, "
            f"expected {sorted(want)}")
    return sorted(got)


def _model_config(sz):
    from deepspeed_tpu.models import transformer as T

    return T.TransformerConfig(
        variant="llama", remat="save_attn_qkv", use_flash=True, **sz["model"])


def _train(sz, mcfg, mesh_axes, zero_stage, micro_bs, devices, on_tpu, info):
    """ds.initialize + train_batch on a fixed batch. Returns (engine,
    first-step loss)."""
    import numpy as np

    import deepspeed_tpu as ds
    from deepspeed_tpu.models import transformer as T
    from deepspeed_tpu.platform.mesh import build_mesh

    engine = ds.initialize(
        {
            "train_micro_batch_size_per_gpu": micro_bs,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "adamw",
                          "params": {"lr": 1e-4, "weight_decay": 0.1}},
            "zero_optimization": {"stage": zero_stage},
            "bf16": {"enabled": True},
            "gradient_clipping": 1.0,
            "steps_per_print": 10**9,
        },
        loss_fn=T.make_loss_fn(mcfg, loss_chunks=sz["loss_chunks"]),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
        mesh=build_mesh(mesh_axes, devices=devices),
    )
    batch = {"tokens": np.random.default_rng(0).integers(
        0, mcfg.vocab_size,
        (engine.config.train_batch_size, mcfg.max_seq + 1)).astype(np.int32)}
    losses, step_s = [], []
    for _ in range(sz["train_steps"]):
        t0 = time.perf_counter()
        losses.append(engine.train_batch(batch)["loss"])
        step_s.append(round(time.perf_counter() - t0, 3))
    info.update(losses=[round(x, 4) for x in losses], step_seconds=step_s)
    if not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"non-finite loss: {losses}")
    if abs(losses[0] - math.log(mcfg.vocab_size)) > 0.5:
        raise AssertionError(
            f"first loss {losses[0]} is not near ln(vocab) "
            f"{math.log(mcfg.vocab_size):.3f}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall on a fixed batch: {losses}")
    info["kernels"] = _expect_kernels(
        lambda: engine._train_compiled,
        {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}, "train_step", on_tpu)
    return engine, losses[0]


def _prompts(lengths, vocab, seed):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lengths]


def _oracle(sz, mcfg, params):
    """decode_impl='xla': the jnp oracle (paged_decode_attention_xla,
    ops/attention._xla_attention, the jnp scatter) — no Pallas program
    anywhere in this engine. Greedy-decodes the put() prompts; returns
    its logits and the exact token feed, so every served engine is
    teacher-forced with the SAME tokens and contexts never diverge on a
    near-tie of a barely-trained model."""
    import numpy as np

    from deepspeed_tpu.inference import init_inference

    eng = init_inference(params, mcfg, dict(sz["serve"], decode_impl="xla"))
    prompts = _prompts(sz["put_prompts"], mcfg.vocab_size, seed=2)
    uids = list(range(len(prompts)))
    k = sz["put_chunk"]
    head, tail = [p[:-k] for p in prompts], [p[-k:] for p in prompts]
    out = [eng.put(uids, head), eng.put(uids, tail)]
    fed = []
    for _ in range(sz["put_decode"]):
        fed.append([np.asarray([int(np.argmax(row))], np.int32)
                    for row in out[-1]])
        out.append(eng.put(uids, fed[-1]))
    return np.stack(out), [head, tail] + fed


def _forced_logits(eng, feed):
    """The oracle's token feed through engine.put(): a whole-prompt
    prefill wave, a multi-token continuation chunk (the shared-table
    program chunked prefill runs), then single-token decode steps (the
    fused write+attend program). Every step's logits, stacked."""
    import numpy as np

    uids = list(range(len(feed[0])))
    out = np.stack([eng.put(uids, toks) for toks in feed])
    for u in uids:
        eng.flush(u)
    return out


def _serve(sz, mcfg, params, oracle, feed, on_tpu, info, **engine_kw):
    """One serving engine under ServingScheduler, then its logits
    against the oracle's."""
    import numpy as np

    from deepspeed_tpu.inference import (
        ServingScheduler,
        ServingSchedulerConfig,
        init_inference,
    )

    eng = init_inference(params, mcfg, dict(sz["serve"], **engine_kw))
    info["resolved_impl"] = eng.resolved_impl
    if eng.resolved_impl != "pallas":
        raise AssertionError(
            f"decode_impl='auto' resolved to {eng.resolved_impl!r}: the "
            "Pallas kernels are not running")
    t0 = time.perf_counter()
    sched = ServingScheduler(eng, ServingSchedulerConfig(
        prefill_chunk=sz["prefill_chunk"], warmup=True))
    info["warmup_seconds"] = round(time.perf_counter() - t0, 2)
    prompts = _prompts([n for n, _ in sz["requests"]], mcfg.vocab_size, seed=1)
    rids = [sched.submit(p, max_new_tokens=n)
            for p, (_, n) in zip(prompts, sz["requests"])]
    t0 = time.perf_counter()
    sched.run()
    info["run_seconds"] = round(time.perf_counter() - t0, 2)
    for rid, (_, n) in zip(rids, sz["requests"]):
        req = sched.finished[rid]
        if req.finish_reason != "length" or len(req.output) != n:
            raise AssertionError(
                f"request {rid}: finish_reason {req.finish_reason!r}, "
                f"{len(req.output)} of {n} tokens")
        if not all(0 <= t < mcfg.vocab_size for t in req.output):
            raise AssertionError(f"request {rid}: token out of range")
    m = sched.metrics()
    if m["recompiles"] or m["decode_kernel"] != 1.0:
        raise AssertionError(f"scheduler metrics: {m}")
    info.update(scheduler_steps=int(m["steps"]),
                ttft_p50_ms=round(m["ttft_p50_ms"], 1),
                tpot_p50_ms=round(m["tpot_p50_ms"], 1))

    got = _forced_logits(eng, feed)
    if not np.isfinite(got).all():
        raise AssertionError("non-finite logits")
    err = np.abs(got - oracle)
    # per put() step: prefill wave, continuation chunk, decode steps
    info["logits_max_abs_err_by_step"] = [
        round(float(e.max()), 5) for e in err]
    info["logits_ref_max_abs"] = round(float(np.abs(oracle).max()), 4)
    info["logits_mean_abs_err"] = round(float(err.mean()), 5)
    if err.max() > LOGITS_ATOL:
        raise AssertionError(
            f"logits differ from the decode_impl='xla' oracle by "
            f"{err.max():.4f} > {LOGITS_ATOL} "
            f"({int((err > LOGITS_ATOL).sum())} of {err.size} elements)")

    # which kernels each compiled serving program holds: single-token
    # decode is the manual-DMA fused kernel, except int8 KV and TP
    # shards, which run the grid kernel (inference/model._decode_attention)
    width = sz["serve"]["max_batch_size"]
    if "tp_size" in engine_kw:   # per-shard grid kernel, separate write
        decode_kernels = {"paged_decode_grid", "paged_kv_write"}
    elif "kv_cache_dtype" in engine_kw:      # fused int8 grid kernel
        decode_kernels = {"paged_decode_grid"}
    else:
        decode_kernels = {"paged_decode_fused"}
    tp_bucket = max(tp for _, tp in eng._prefill_batch_fns)
    info["kernels"] = {
        "prefill": _expect_kernels(
            lambda: eng.compiled_prefill(1, tp_bucket),
            {"flash_fwd", "paged_kv_write"}, "prefill", on_tpu),
        "chunked_prefill": _expect_kernels(
            lambda: eng.compiled_decode(width, False),
            {"paged_decode_grid", "paged_kv_write"}, "chunked_prefill",
            on_tpu),
        "decode": _expect_kernels(
            lambda: eng.compiled_decode(width, True), decode_kernels,
            "decode", on_tpu),
    }


def run(tiny: bool = False, require_tpu: bool = True) -> dict:
    """All phases; returns the full report (the CLI prints it, then
    result_line() of it as the last line). Raises on the first failed
    phase."""
    import jax

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    if require_tpu and not on_tpu:
        raise RuntimeError(
            f"chip_smoke needs a TPU; JAX's backend is {platform!r}")
    if on_tpu:
        from deepspeed_tpu.platform.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        return _run(sizes(tiny), on_tpu, {
            "dir": cache_dir,
            "populated_at_start": os.path.isdir(cache_dir)
            and bool(os.listdir(cache_dir))})
    # the rehearsal's explicit request for interpreted kernels; no
    # compile cache is written from off the chip
    from deepspeed_tpu.ops.pallas import interpret_kernels

    with interpret_kernels():
        return _run(sizes(tiny), on_tpu, None)


def _run(sz, on_tpu, cache) -> dict:
    import jax
    import jaxlib
    import libtpu

    from deepspeed_tpu.inference import init_inference

    devs = jax.devices()
    rep = _Report()
    mcfg = _model_config(sz)

    with rep.phase("train") as info:
        engine, loss0 = _train(sz, mcfg, {}, 1, sz["micro_bs"], devs[:1],
                               on_tpu, info)
        params = engine.state.params
        del engine

    with rep.phase("oracle") as info:
        oracle, feed = _oracle(sz, mcfg, params)

    with rep.phase("serve") as info:
        _serve(sz, mcfg, params, oracle, feed, on_tpu, info)

    with rep.phase("serve_int8") as info:
        # kernel vs oracle on the SAME int8 pools: the difference is
        # reassociation, not the quantization error
        oracle8 = _forced_logits(
            init_inference(params, mcfg, dict(
                sz["serve"], decode_impl="xla", kv_cache_dtype="int8")),
            feed)
        _serve(sz, mcfg, params, oracle8, feed, on_tpu, info,
               kv_cache_dtype="int8")

    if len(devs) >= 4:
        with rep.phase("train_4chip") as info:
            engine, loss4 = _train(
                sz, mcfg, {"data": 2, "model": 2}, 3, sz["micro_bs"] // 2,
                devs[:4], on_tpu, info)
            info["loss_vs_1chip"] = round(abs(loss4 - loss0), 4)
            if abs(loss4 - loss0) > LOSS_4CHIP_TOL:
                raise AssertionError(
                    f"first-step loss {loss4} vs one chip {loss0}")
            info["layout"] = _check_layout(engine, devs[:4])
            del engine
        with rep.phase("serve_4chip") as info:
            _serve(sz, mcfg, params, oracle, feed, on_tpu, info, tp_size=4)

    return {
        "ok": all(p["ok"] for p in rep.phases.values()),
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs)},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu.__version__},
        "compile_cache": cache,
        "phases": rep.phases,
    }


def _check_layout(engine, devs) -> dict:
    """ZeRO-3 x {data, model}: the fp32 master shards over 'data', the
    compute params over 'model', every device holds a part of the state
    and none holds all of it."""
    import jax

    def spec_axes(tree):
        axes = set()
        for leaf in jax.tree.leaves(tree):
            for entry in leaf.sharding.spec:
                if entry is not None:
                    axes.update((entry,) if isinstance(entry, str) else entry)
        return axes

    master_axes = spec_axes(engine.state.master)
    param_axes = spec_axes(engine.state.params)
    if "data" not in master_axes or "model" not in param_axes:
        raise AssertionError(
            f"master spec axes {master_axes}, params spec axes {param_axes}")
    leaves = jax.tree.leaves((engine.state.master, engine.state.opt))
    total = sum(x.nbytes for x in leaves)
    per_dev = {d.id: 0 for d in devs}
    for x in leaves:
        for sh in x.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    if not all(0 < b < total for b in per_dev.values()):
        raise AssertionError(
            f"state bytes per device {per_dev} of {total} total")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devs]
    if any(b == 0 for b in in_use):
        raise AssertionError(f"a device reports no bytes in use: {in_use}")
    return {"master_axes": sorted(master_axes),
            "param_axes": sorted(param_axes),
            "state_bytes_total": total,
            "state_bytes_per_device": list(per_dev.values()),
            "bytes_in_use": in_use}


def result_line(report: dict) -> str:
    """The last line of stdout: exactly `ok` and `device`, nothing
    else — the driver's check reads this line and refuses extra keys.
    Everything else run() reports goes on the line before it."""
    dev = report["device"]
    return json.dumps({
        "ok": bool(report["ok"]),
        "device": {"platform": str(dev["platform"]), "kind": str(dev["kind"]),
                   "count": int(dev["count"])}})


def main() -> int:
    if len(sys.argv) > 1:
        print("usage: python3 chip_smoke.py   (no options; needs a TPU)",
              file=sys.stderr)
        return 2
    report = run(tiny=False, require_tpu=True)
    print(f"[chip_smoke] report: {json.dumps(report)}", flush=True)
    print(result_line(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
