"""Standalone chip timing of the held routed wire's row movers at the
training cell's shape ([32,768 x 2,048] bf16 rows of a chunk <-> [16,384 x
2,048] tokens): the table of PERF.md section 6, PR 61 (ISSUE 61's Step 0),
kept so that the next change to the wire can re-run it (flash_bench.py's
sibling for moe/dropless.py and ops/pallas/token_sum.py).

  chiprun -- python scripts/wire_bench.py                 # --kernel sum
  chiprun -- python scripts/wire_bench.py --kernel gather
  python scripts/wire_bench.py --aot                      # compile only, no chip
  ... --real-ids 6100000001 6100000002   on the ids the CELL's own router
        draws (its engine built as its runner builds it; before the first
        step and after --steps): the kernel against the XLA segment sum,
        and the held wire's gradients against the scatter form's; nothing
        is timed

--kernel sum times three forms of `sum_to_tokens` (rows of a chunk summed to
their tokens, dead rows left out):
  segment_sum     `jax.ops.segment_sum(where(live, rows, 0), src, T)`: a row
                  scatter-add, what the wire held before PR 61
  sorted_scatter  one sort of the ids by token (dead last), one row gather
                  into token order, `segment_sum(indices_are_sorted=True)`
  banded_kernel   the same sort and gather, then the banded one-hot product
                  (ops/pallas/token_sum.py `token_tile_sum`, Pallas)
--kernel gather times the parts: the sort alone, the row gather of the
tokens (`tokens[src]`, the wire's forward) and of the rows into token order.

The ids are drawn as the cell's: --runs (16) runs of held pairs, each in
token order, --live (T) rows in all, the dead rows behind them; dead rows
hold Inf. A form's time is the sum of its device operations in a profiler
trace of --calls calls (no host clock), with its largest operations by
name; `max_err` is against the float32 segment sum. One JSON line a form,
appended to chiprun_out/wire_bench.jsonl.
"""

import argparse
import collections
import json
import os
import pathlib
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.moe import dropless as D
from deepspeed_tpu.ops.pallas import token_sum as TS

OUT = pathlib.Path("chiprun_out")


def cell_ids(rng, T, C, runs, n_live):
    """(src [C] int32, live [C] bool) of one chunk as the held wire lays
    it out: `runs` experts' runs of distinct tokens in token order, then
    the dead rows (pairs held elsewhere), in token order too."""
    sizes = rng.multinomial(n_live, np.ones(runs) / runs)
    parts = [np.sort(rng.choice(T, n, replace=False)) for n in sizes]
    parts.append(np.sort(rng.integers(0, T, C - n_live)))
    return (np.concatenate(parts).astype(np.int32),
            np.arange(C) < n_live)


def sum_forms(T):
    def select(rows, live):
        return jnp.where(live[:, None], rows, 0)

    def segment_sum(rows, src, live):
        return jax.ops.segment_sum(select(rows, live), src, num_segments=T)

    def sorted_scatter(rows, src, live):
        o = D.token_order(src, live, T)
        return jax.ops.segment_sum(
            select(rows[o.perm], o.ids < T), o.ids, num_segments=T,
            indices_are_sorted=True)

    def banded_kernel(rows, src, live):  # the package's own path on a TPU
        return D.sum_to_tokens(rows, D.token_order(src, live, T))

    return dict(segment_sum=segment_sum, sorted_scatter=sorted_scatter,
                banded_kernel=banded_kernel)


def gather_forms(T):
    def sort_alone(tokens, rows, src, live):
        o = D.token_order(src, live, T)
        return o.ids, o.perm

    def tokens_gather(tokens, rows, src, live):
        return D.rows_of(tokens, D.token_order(src, live, T))

    def rows_to_token_order(tokens, rows, src, live):
        return rows[D.token_order(src, live, T).perm]

    return dict(sort_alone=sort_alone, tokens_gather=tokens_gather,
                rows_to_token_order=rows_to_token_order)


def device_ms(fn, operands, calls, tag):
    """(ms a call, the largest operations' ms a call by name) from the
    device's own trace of `calls` calls."""
    from benchmarks.trace import reduce as R
    from benchmarks.trace.capture import Capture

    jax.block_until_ready(fn(*operands))
    cap = Capture(OUT / "wire_bench" / tag)
    os.makedirs(cap.out_dir, exist_ok=True)
    cap.start()
    for _ in range(calls):
        out = fn(*operands)
    jax.block_until_ready(out)
    td = cap.stop()
    by_name = collections.Counter()
    for e in R.leaves(R.in_window(td.ops.get(0, []), td.window)):
        by_name[R.base_name(e.name)] += e.dur * 1e3 / calls
    return sum(by_name.values()), {
        n: round(ms, 4) for n, ms in by_name.most_common(6)}


def emit(line):
    print(json.dumps(line), flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(OUT / "wire_bench.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")


def bench(args):
    T, C, E = args.tokens, args.rows, args.width
    rng = np.random.default_rng(args.seed)
    src, live = cell_ids(rng, T, C, args.runs, args.live or T)
    key = jax.random.PRNGKey(args.seed)
    rows = jnp.where(live[:, None],
                     jax.random.normal(key, (C, E), jnp.bfloat16), jnp.inf)
    tokens = jax.random.normal(jax.random.fold_in(key, 1), (T, E),
                               jnp.bfloat16)
    src, live = jnp.asarray(src), jnp.asarray(live)
    shape = dict(kernel=args.kernel, tokens=T, rows=C, width=E,
                 live=int(live.sum()), runs=args.runs, seed=args.seed,
                 device=jax.devices()[0].device_kind)
    if args.kernel == "gather":
        for name, fn in gather_forms(T).items():
            ms, ops = device_ms(jax.jit(fn), (tokens, rows, src, live),
                                args.calls, name)
            emit(dict(shape, form=name, ms=round(ms, 4), ops=ops))
        return
    want = jax.ops.segment_sum(
        jnp.where(live[:, None], rows.astype(jnp.float32), 0), src,
        num_segments=T)
    for name, fn in sum_forms(T).items():
        if args.forms and name not in args.forms:
            continue
        fn = jax.jit(fn)
        got = fn(rows, src, live).astype(jnp.float32)
        ms, ops = device_ms(fn, (rows, src, live), args.calls, name)
        emit(dict(shape, form=name, ms=round(ms, 4), ops=ops,
                  max_err=float(jnp.max(jnp.abs(got - want))),
                  finite=bool(jnp.all(jnp.isfinite(got)))))


def real_ids(args):
    """On the ids the cell's own router draws (its engine built as its
    runner builds it; the routing of a forward before the first step
    and after --steps of them, when the cut's router has starved the
    held experts), for the first chunk of every routed layer: the kernel
    against the XLA segment sum; and for the first layer of each draw
    the held wire's value and every gradient against the wire with the
    scatter forms in the movers' place (tests/test_dropless.py's
    oracle)."""
    import gc

    from benchmarks import harness
    from benchmarks.traffic import generate

    cell = harness.load_cell(args.cell)
    runner = harness.load_module(
        cell.bench_dir / "runners" / f"{cell.traffic['runner']}.py")
    wire, seen = D._held_wire, []

    def spy(tokens, idx, *rest):
        jax.debug.callback(lambda i: seen.append(np.asarray(i)), idx)
        return wire(tokens, idx, *rest)

    for seed in args.real_ids:
        engine, mcfg = runner.build_engine(cell, jax.devices()[:1], seed)
        batches = generate.token_batches(
            cell.traffic, seed, mcfg.vocab_size,
            engine.config.train_batch_size)
        drawn = {}
        for after in (0, args.steps):
            while engine.global_steps < after:
                engine.train_batch(next(batches))
            seen.clear()
            D._held_wire, engine._eval_step_fn = spy, None
            try:
                engine.eval_batch(next(batches))
            finally:
                D._held_wire, engine._eval_step_fn = wire, None
            drawn[after] = list(seen)
        held, n_experts = mcfg.experts_held, mcfg.n_experts
        del engine
        gc.collect()
        for after, layers in drawn.items():
            for n, idx in enumerate(layers):
                emit(dict(sum_check(idx, held, args.width, seed),
                          check="real_ids", cell=args.cell, seed=seed,
                          steps=after, layer=n))
        for after, layers in drawn.items():
            emit(dict(wire_check(layers[0], held, n_experts, args.width,
                                 seed),
                      check="held_wire_grads", cell=args.cell, seed=seed,
                      steps=after))


def first_chunk(idx, held):
    """(src, live) of the first chunk of the held wire's list, by numpy."""
    (T, K), (start, count) = idx.shape, held
    C = D.held_chunk_rows(T, K, count)
    local = idx.reshape(-1) - start
    key = np.where((local >= 0) & (local < count), local, count)
    order = np.argsort(key, kind="stable")[:C]
    return (order // K).astype(np.int32), key[order] < count


def sum_check(idx, held, width, seed):
    T = idx.shape[0]
    src, live = map(jnp.asarray, first_chunk(idx, held))
    rows = jnp.where(
        live[:, None],
        jax.random.normal(jax.random.PRNGKey(seed % (1 << 31)),
                          (src.shape[0], width), jnp.bfloat16), jnp.inf)
    forms = sum_forms(T)
    got = jax.jit(forms["banded_kernel"])(rows, src, live)
    old = jax.jit(forms["segment_sum"])(rows, src, live)
    want = jax.ops.segment_sum(
        jnp.where(live[:, None], rows.astype(jnp.float32), 0), src,
        num_segments=T)
    f32 = lambda a: a.astype(jnp.float32)
    err = lambda a, b: float(jnp.max(jnp.abs(f32(a) - f32(b))))
    return dict(tokens=T, rows=int(src.shape[0]), live=int(live.sum()),
                rows_a_token_max=int(np.bincount(
                    np.asarray(src)[np.asarray(live)], minlength=T).max()),
                kernel_vs_f32=err(got, want), segment_sum_vs_f32=err(old, want),
                kernel_vs_segment_sum=err(got, old),
                finite=bool(jnp.all(jnp.isfinite(got))),
                device=jax.devices()[0].device_kind)


def wire_check(idx, held, n_experts, width, seed, d_ff=1024):
    """_held_wire's output and gradients (tokens, the three stacks, the
    pairs' weights) with the movers against the same wire with a plain
    row gather and the segment sum, whose transposes jax derives."""
    (T, K), (start, count) = idx.shape, held
    key = jax.random.PRNGKey(seed % (1 << 31))
    bf = jnp.bfloat16
    tokens, w_in, w_gate, w_out, wts, cot = (
        jax.random.normal(jax.random.fold_in(key, i), shape, dt) * scale
        for i, (shape, dt, scale) in enumerate((
            ((T, width), bf, 1.0), ((count, width, d_ff), bf, 0.02),
            ((count, width, d_ff), bf, 0.02), ((count, d_ff, width), bf, 0.02),
            ((T, K), jnp.float32, 1.0), ((T, width), bf, 1.0))))
    idx = jnp.asarray(idx)
    counts = D.expert_counts(idx, n_experts)

    def loss(tokens, w_in, w_gate, w_out, wts):
        out, dropped, _ = D._held_wire(
            tokens, idx, jnp.abs(wts), counts, held, w_in, w_out, w_gate,
            jax.nn.silu, "ragged")
        return jnp.sum((out * cot).astype(jnp.float32)), (out, dropped)

    grad = lambda: jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(
            tokens, w_in, w_gate, w_out, wts)
    (_, (out, dropped)), grads = grad()
    movers = D.rows_of, D.sum_to_tokens
    D.rows_of = lambda t, o: jnp.where(o.live[:, None], t[o.src], 0)
    D.sum_to_tokens = lambda r, o: jax.ops.segment_sum(
        jnp.where(o.live[:, None], r, 0), o.src, num_segments=o.n_tokens)
    try:
        (_, (want_out, _)), want = grad()
    finally:
        D.rows_of, D.sum_to_tokens = movers
    f32 = lambda a: a.astype(jnp.float32)
    rel = lambda a, b: float(jnp.max(jnp.abs(f32(a) - f32(b)))
                             / jnp.maximum(jnp.max(jnp.abs(f32(b))), 1e-30))
    names = ("out", "d_tokens", "d_w_in", "d_w_gate", "d_w_out", "d_weights")
    return dict(tokens=T, dropped=int(dropped),
                finite=all(bool(jnp.all(jnp.isfinite(g)))
                           for g in (out, *grads)),
                max_diff_over_max={n: rel(a, b) for n, a, b in zip(
                    names, (out, *grads), (want_out, *want))},
                device=jax.devices()[0].device_kind)


def aot(args):
    """Compile every form for a described v5e: what Mosaic refuses, it
    refuses here."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    TS.kernels_runnable = lambda: True  # (the backend here is the CPU)
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:1x1",
        chips_per_host_bounds=(1, 1, 1))
    one = SingleDeviceSharding(topo.devices[0])
    T, C, E = args.tokens, args.rows, args.width
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)
    rows, src, live = (sds((C, E), jnp.bfloat16), sds((C,), jnp.int32),
                       sds((C,), jnp.bool_))
    for name, fn in sum_forms(T).items():
        c = jax.jit(fn).lower(rows, src, live).compile()
        m = c.memory_analysis()
        print(name, "temp MiB", m.temp_size_in_bytes >> 20,
              "kernel" if "tpu_custom_call" in c.as_text() else "", flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kernel", choices=("sum", "gather"), default="sum")
    ap.add_argument("--forms", nargs="*", help="of --kernel sum: these alone")
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--rows", type=int, default=32768)
    ap.add_argument("--width", type=int, default=2048)
    ap.add_argument("--live", type=int, help="live rows (default: --tokens)")
    ap.add_argument("--runs", type=int, default=16)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--real-ids", type=int, nargs="+", metavar="SEED")
    ap.add_argument("--cell", default="train-trinity-seq8k")
    ap.add_argument("--steps", type=int, default=40,
                    help="of --real-ids: train steps before the second draw")
    args = ap.parse_args()
    if args.aot:
        return aot(args)
    if args.real_ids:
        return real_ids(args)
    bench(args)


if __name__ == "__main__":
    main()
