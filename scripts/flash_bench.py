"""Standalone chip timing of the three flash kernels (flash_fwd,
flash_bwd_dq, flash_bwd_dkv) at the training cells' shapes: the table of
PERF.md section 6, PR 56, kept so that the next change to the kernels can
re-run it (walk_bench.py's sibling for ops/pallas/flash_attention.py).

  chiprun -- python scripts/flash_bench.py                # every shape
  chiprun -- python scripts/flash_bench.py trinity_win --slab-fwd 256 --slab-bwd 128
  ... --tree .scratch/parent --tag parent   another checkout's kernels (the
                                            parent's, unpacked with git archive)
  ... --knockout     WRONG ON PURPOSE: every live tile runs the interior body
                     (no mask anywhere), to read what the masks cost; its
                     outputs are not compared
  ... --batch 1 --heads 8 --kv-heads 2 --seq 2048 --head-dim 128 --window 0
      --tile 512     a shape by hand

A kernel's time is a program of --chain calls, each waiting for the last, so
the host's dispatch is paid once; best of 5 x 10 programs. dq and dk/dv are
the backward with the other kernel's outputs unused (XLA drops the call);
both include the backward's `delta` (one pass over o and do). Tile units run
over needed is the tree's own tile_census (a tree without one runs every
live tile whole); the roofline share is the least time the chip could take
for the NEEDED work, benchmarks/kernels/shapes.flash_flops_and_bytes scaled
by the pairs the window keeps of the causal half (kernels/afmoe.visible_pairs),
over the three kernels' time. `max_err`: o and dq of two heads against the
float32 XLA oracle; `vs_general`: every output against the same kernels with every live tile sent
to the general body (the mask over the whole tile). One JSON line a shape,
appended to chiprun_out/flash_bench.jsonl.
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# B sequences a chip, query / KV heads, sequence, head dim, window, tile
SHAPES = {
    "trinity_win": dict(B=2, H=32, KV=4, S=8192, D=128, window=2048,
                        tile=1024),
    "trinity_full": dict(B=2, H=32, KV=4, S=8192, D=128, window=0,
                         tile=1024),
    # the Mistral cells pass their published window at a sequence as long
    "mistral": dict(B=4, H=32, KV=8, S=4096, D=128, window=4096, tile=1024),
}


def tile_units(FA, c):
    """(tile units the kernels multiply, tile units needed) a head."""
    from benchmarks.kernels.afmoe import visible_pairs

    S, b, w = c["S"], min(c["tile"], c["S"]), c["window"]
    needed = visible_pairs(S, w) / (b * b)
    if hasattr(FA, "tile_census"):
        return FA.tile_census(S, w, b, b)["work"], needed
    n = -(-S // b)
    live = sum(1 for i in range(n) for j in range(i + 1)
               if not w or (j + 1) * b - 1 > i * b - w)
    return float(live), needed


def best_of(fn, chain, *operands):
    import jax

    out = fn(*operands)
    jax.block_until_ready(out)
    first = out
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(10):
            out = fn(*operands)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / 10 / chain)
    return first, best


def bench(args, FA, name, c):
    import jax
    import jax.numpy as jnp
    from benchmarks.kernels import shapes
    from benchmarks.kernels.afmoe import visible_pairs
    from deepspeed_tpu.ops.attention import _xla_attention

    B, H, KV, S, D, w = (c[k] for k in ("B", "H", "KV", "S", "D", "window"))
    b = min(c["tile"], S)
    key = jax.random.PRNGKey(args.seed)
    q, k, v, do = (
        jax.random.normal(jax.random.fold_in(key, i), (B * h, S, D),
                          jnp.bfloat16)
        for i, h in enumerate((H, KV, KV, H)))
    static = (True, b, b, H, KV, w, False)

    def tie(x, out):  # the next call waits for `out`
        return x + (out[:1, :1, :1] * 0).astype(x.dtype)

    def fwd(q, k, v):
        qq = q
        for _ in range(args.chain):
            o, lse = FA._flash_fwd(qq, k, v, None, *static)
            qq = tie(q, o)
        return o, lse

    def bwd(pick):  # 0: dq alone; 1: dk and dv alone
        def run(q, k, v, do, o, lse):
            qq = q
            for _ in range(args.chain):
                outs = FA._flash_bwd(qq, k, v, None, o, lse, do, *static)
                qq = tie(q, outs[pick])
            return outs[1:] if pick else outs[:1]
        return run

    (o, lse), t_fwd = best_of(jax.jit(fwd), args.chain, q, k, v)
    (dq,), t_dq = best_of(jax.jit(bwd(0)), args.chain, q, k, v, do, o, lse)
    (dk, dv), t_dkv = best_of(jax.jit(bwd(1)), args.chain, q, k, v, do, o, lse)

    err = vs_general = None
    if not args.knockout:
        # o and dq of two heads against the float32 oracle ...
        to4 = lambda x, n: x[:n].astype(jnp.float32).transpose(1, 0, 2)[None]

        def ref(q4, k4, v4):
            return _xla_attention(q4, jnp.repeat(k4, 2, 2),
                                  jnp.repeat(v4, 2, 2), causal=True, window=w)

        with jax.default_matmul_precision("highest"):
            ro, vjp = jax.vjp(ref, to4(q, 2), to4(k, 1), to4(v, 1))
            rdq = vjp(to4(do, 2))[0]
        err = {"o": float(jnp.max(jnp.abs(to4(o, 2) - ro))),
               "dq": float(jnp.max(jnp.abs(to4(dq, 2) - rdq)))}
        if hasattr(FA, "TileKinds"):
            # ... and every output against the same kernels with every
            # live tile sent to the general body (the iota mask)
            kinds = FA._tile_kinds

            def general(*a):
                live = kinds(*a).live
                return FA.TileKinds(live, False, False, False, live)

            FA._tile_kinds = general
            try:
                go, glse = FA._flash_fwd(q, k, v, None, *static)
                gd = FA._flash_bwd(q, k, v, None, go, glse, do, *static)
            finally:
                FA._tile_kinds = kinds
            vs_general = {
                n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                         - g.astype(jnp.float32))))
                for n, a, g in zip(("o", "dq", "dk", "dv"),
                                   (o, dq, dk, dv), (go, *gd))}

    hf = {"num_attention_heads": H, "num_key_value_heads": KV, "head_dim": D}
    need = shapes.flash_flops_and_bytes(hf, B, S)
    peaks = json.load(open(os.path.join(REPO, "benchmarks", "peaks.json")))[
        jax.devices()[0].device_kind]
    least = max(
        need["flops"] * visible_pairs(S, w) / (S * S / 2)
        / peaks["bf16_flops_per_s"],
        need["bytes"] / peaks["hbm_bytes_per_s"])
    work, needed = tile_units(FA, c)
    line = dict(
        tag=args.tag, shape=name, **c, slabs=(FA.SLAB_FWD, FA.SLAB_BWD),
        knockout=args.knockout, fwd_ms=round(t_fwd * 1e3, 3),
        dq_ms=round(t_dq * 1e3, 3), dkv_ms=round(t_dkv * 1e3, 3),
        all_ms=round((t_fwd + t_dq + t_dkv) * 1e3, 3),
        tile_units_run=round(work, 3), tile_units_needed=round(needed, 3),
        work_over_needed=round(work / needed, 4),
        needed_roofline_pct=round(100 * least / (t_fwd + t_dq + t_dkv), 2),
        max_err=err and {n: round(e, 4) for n, e in err.items()},
        vs_general=vs_general and {n: round(e, 4)
                                   for n, e in vs_general.items()},
        device=jax.devices()[0].device_kind)
    print(json.dumps(line), flush=True)
    with open("chiprun_out/flash_bench.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("shapes", nargs="*")
    for flag, kind in (("--batch", int), ("--heads", int), ("--kv-heads", int),
                       ("--seq", int), ("--head-dim", int), ("--window", int),
                       ("--tile", int)):
        ap.add_argument(flag, type=kind, help="a shape by hand (with --seq)")
    ap.add_argument("--slab-fwd", type=int, help="the tree's SLAB_FWD for "
                    "this run (a tree that has one), and --slab-bwd")
    ap.add_argument("--slab-bwd", type=int)
    ap.add_argument("--knockout", action="store_true")
    ap.add_argument("--tree", default=REPO)
    ap.add_argument("--tag", default="tree")
    ap.add_argument("--chain", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    sys.path.insert(0, REPO)                      # benchmarks.kernels.shapes
    sys.path.insert(0, os.path.abspath(args.tree))  # the kernels timed
    import deepspeed_tpu.ops.pallas.flash_attention as FA

    assert os.path.abspath(FA.__file__).startswith(
        os.path.abspath(args.tree)), FA.__file__
    FA.SLAB_FWD = args.slab_fwd or getattr(FA, "SLAB_FWD", None)
    FA.SLAB_BWD = args.slab_bwd or getattr(FA, "SLAB_BWD", None)
    if args.knockout:
        kinds = FA._tile_kinds

        def no_mask(*a):
            live = kinds(*a).live
            return FA.TileKinds(live, live, False, False, False)

        FA._tile_kinds = no_mask
    os.makedirs("chiprun_out", exist_ok=True)
    todo = {n: SHAPES[n] for n in args.shapes or SHAPES}
    if args.seq:
        todo = {"by_hand": dict(
            B=args.batch or 1, H=args.heads or 8, KV=args.kv_heads or 8,
            S=args.seq, D=args.head_dim or 128, window=args.window or 0,
            tile=args.tile or 1024)}
    for name, c in todo.items():
        bench(args, FA, name, c)


if __name__ == "__main__":
    main()
