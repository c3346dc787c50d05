#!/usr/bin/env python3
"""Where does a slow step's LAUNCH go? Some processes of the routed
training cell run every step ~14 ms slow, and the 11 of them sit in
`train_batch`'s `launch` phase (the call of the compiled step: 3 ms in
most processes, 14 in some), with the device, its clock, its memory,
a scalar's round trip and numpy as fast as anywhere (PERF.md §6, PR 55;
`runners/train_routed.py` `notes.phases_ms_median`). This builds the
cell's engine (or, named second, another train cell's) as its runner
does, takes a few steps and prints the
phases' medians, then the same under what tells a WAIT from a COST:
a pause before each step (something asynchronous the launch waits for
would have finished), the state blocked on, a collection, and beside
them the launch of a program of no work over as many small buffers as
the step takes, and over eight, a host array's way to the device at
three sizes, what the device says of its memory, and how many of the
calls of the compiled step left jax's C++ fast path for the Python one
(`slow_path_calls`: 1, the first, where the fast path holds), and what
THIS process gets of the machine (`machine_probe`). A third argument
`quick` stops after the plain steps; `trajectory` instead prints the
run's first 90 steps one by one (loss, held pairs, the fullest routed
layer, the phases): how a router as seeded moves under the cell's
recipe. One process a seed; a mode is a process's, so run it over
several (PYTHONPATH=. through chiprun):

    for s in 1 2 3 4 5 6 7 8; do python3 scripts/step_launch_probe.py $s; done
    python3 scripts/step_launch_probe.py 1 train-seq4k
    python3 scripts/step_launch_probe.py 1 train-trinity-seq8k trajectory
"""

import gc
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def machine_probe():
    """Three programs that own the chip: the round trip of a scalar (the
    host's launch and its sight of the result), a chain of bf16 matrix
    products (the clock) and a pass over 1 GiB (the memory). A slow
    process is slow in one of them or in none (then it is the step
    program's own). Not the host's load: the guest reads 0.0 whatever
    runs beside it."""
    def timed(f, x, n):
        f(x).block_until_ready()
        ts = []
        for _ in range(n):
            t = time.perf_counter()
            f(x).block_until_ready()
            ts.append(time.perf_counter() - t)
        return ts

    def chain(a):
        for _ in range(16):
            a = (a @ a) * jnp.bfloat16(1 / 64)
        return a

    m = jnp.full((4096, 4096), 1 / 64, jnp.bfloat16)
    trip = timed(jax.jit(lambda x: x + 1), jnp.float32(0), 200)
    mm = min(timed(jax.jit(chain), m, 5))
    big = jnp.zeros((1 << 28,), jnp.float32)
    cp = min(timed(jax.jit(lambda x: x + 1), big, 5))
    return {"round_trip_us_median": 1e6 * float(np.median(trip)),
            "round_trip_us_max": 1e6 * max(trip),
            "matmul_tflops": 16 * 2 * 4096 ** 3 / mm / 1e12,
            "pass_gb_per_s": 2 * big.nbytes / cp / 1e9}


def trajectory(engine, batches, n, held):
    """One line a step from the engine's first: what the run's notes do
    not keep."""
    h0, hn = held
    for i in range(n):
        t = time.perf_counter_ns()
        m = engine.train_batch(next(batches))
        ms = (time.perf_counter_ns() - t) * 1e-6
        row = {"step": i + 1, "loss": round(m["loss"], 4), "ms": round(ms, 1),
               **{k: round(v * 1e-6, 2) for k, v in engine._phases.ns.items()}}
        if "moe_census" in m:
            row.update(
                pairs_held=int(m["moe_pairs_held"]),
                held_layer_max=int(
                    m["moe_census"][:, h0:h0 + hn].sum(axis=1).max()),
                bias_abs_max=round(m["expert_bias_abs_max"], 4))
        print(json.dumps(row), flush=True)


def main():
    seed = int(sys.argv[1])
    from benchmarks import harness
    from benchmarks.traffic import generate

    cell = harness.load_cell(
        sys.argv[2] if len(sys.argv) > 2 else "train-trinity-seq8k")
    runner = harness.load_module(
        cell.bench_dir / "runners" / f"{cell.traffic['runner']}.py")
    engine, mcfg = runner.build_engine(cell, jax.devices()[:1], seed)
    batches = generate.token_batches(cell.traffic, seed, mcfg.vocab_size,
                                     engine.config.train_batch_size)

    if sys.argv[3:] == ["trajectory"]:
        trajectory(engine, batches, 90, mcfg.experts_held or (0, 0))
        return

    def steps(n, before=None):
        rows = []
        for _ in range(n):
            batch = next(batches)
            if before is not None:
                before()
            t = time.perf_counter_ns()
            engine.train_batch(batch)
            rows.append(dict(engine._phases.ns,
                             step=time.perf_counter_ns() - t))
        return {k: round(statistics.median(r[k] for r in rows) * 1e-6, 2)
                for k in rows[0]}

    def idle_launch(n_buffers):
        """ms to LAUNCH (not to finish) a donating program of no work
        over n small buffers, the device idle."""
        xs = [jnp.zeros((8, 128), jnp.float32) + i for i in range(n_buffers)]
        f = jax.jit(lambda xs: [x + 1 for x in xs], donate_argnums=0)
        ts = []
        for _ in range(20):
            jax.block_until_ready(xs)
            t = time.perf_counter_ns()
            xs = f(xs)
            ts.append(time.perf_counter_ns() - t)
        return round(statistics.median(ts[2:]) * 1e-6, 3)

    def h2d(n_bytes):
        """ms for a host array of n_bytes to be ON the device."""
        x = np.ones((n_bytes // 4,), np.float32)
        ts = []
        for _ in range(12):
            t = time.perf_counter_ns()
            jax.device_put(x).block_until_ready()
            ts.append(time.perf_counter_ns() - t)
        return round(statistics.median(ts[2:]) * 1e-6, 3)

    # calls that miss the C++ fast path come through here (pxla
    # MeshExecutable.create_cpp_call's aot_cache_miss)
    from jax._src import stages

    slow_path, through = [0], stages.Compiled.call

    def counted(*args, **kwargs):
        slow_path[0] += 1
        return through(*args, **kwargs)

    stages.Compiled.call = staticmethod(counted)

    steps(3)  # the compile, and the second step's new signature
    n_buffers = len(jax.tree.leaves(engine.state))
    out = {"seed": seed, "cell": cell.name, "buffers": n_buffers,
           "plain": steps(8)}
    if sys.argv[3:] == ["quick"]:
        print(json.dumps(dict(out, slow_path_calls=slow_path[0])), flush=True)
        return
    out["pause_20ms"] = steps(6, lambda: time.sleep(0.02))
    out["pause_200ms"] = steps(4, lambda: time.sleep(0.2))
    out["state_blocked_on"] = steps(
        6, lambda: jax.block_until_ready(engine.state))
    out["collected"] = steps(4, gc.collect)
    out["plain_again"] = steps(6)
    out["idle_launch_ms"] = {n: idle_launch(n) for n in (8, n_buffers)}
    out["h2d_ms"] = {n: h2d(n) for n in (1 << 16, 1 << 24, 1 << 28)}
    out["memory_stats"] = jax.devices()[0].memory_stats()
    out["machine_probe"] = machine_probe()
    out["slow_path_calls"] = slow_path[0]
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
