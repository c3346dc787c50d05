"""Standalone chip timing of the shared-table K/V walk (paged_decode_grid,
its entry included) at the serving cells' shapes: the table of PERF.md
section 6, PR 50, kept so that the next change to the walk can re-run it.
--kernel kv_write times the write that goes before it (paged_kv_write, PR 52);
--kernel latent times the latent walk (paged_latent_attention, the same trace
name) at the latent cell's shape (PERF.md section 6, PR 54).

  chiprun -- python scripts/walk_bench.py                 # every shape
  chiprun -- python scripts/walk_bench.py mellum_full dense --kinds mixed,groups
  ... --tree .scratch/parent --tag parent   another checkout's kernel (the
                                            parent's, unpacked with git archive)
  chiprun -- python scripts/walk_bench.py dense --kernel kv_write \
      --distinct-blocks 128,1               # 128 rows in 128 blocks, then in ONE
  ... --kernel kv_write --rows 256 --kv-heads 4 --head-dim 128   a shape by hand
  chiprun -- python scripts/walk_bench.py --kernel latent  # us a (block, row)
  chiprun -- python scripts/walk_bench.py olmohybrid --pack 1:32,15,4:32,16:32
      the SAME K/V values, tables and contexts laid out f heads side by side in
      a pool head ([blocks, 128, held / f, f x D]; `f:held` first pads the KV
      heads with zero heads to `held`), packed and unpacked by the script's own
      hands, so that a layout the tree's rule (kv_pack) does not choose can be
      timed at any checkout (PERF.md section 6, PR 63; `1:32` is what a
      checkout before PR 63 held); without --pack the pool is the tree's own
      (kv_pack) and the entry lays the queries against it

A call's rows by --kinds: `mixed` (decode rows beside chunks of 32 rows on
one table, as the cell's steps are), `groups` (chunks alone), `decode` (rows
alone), `empty` (every context 0: the entry and the grid steps, no visit).
A write's rows by --distinct-blocks: `n` puts the call's rows in n blocks of
the pool, consecutive slots in each (rows: every row a block of its own, a
step of decode rows; 1: one chunk's rows); `chat` is a chat step, 95 decode
rows and a 33-row chunk over two blocks. Each chained write lands in other
blocks than the last, and the last call's pools are compared with the jnp
scatter's, whole.
--chain calls run in ONE program, each waiting for the last, so the host's
~200 us a dispatch is paid once and not a call; best of 5 x 40 programs. A
(KV head, block) cost is (a kind's time - `empty`) / (its visits x KV). One
JSON line a measurement, appended to chiprun_out/walk_bench.jsonl. The latent
walk has no KV heads: a visit there is a (block, row), every head at once, and a
line gives `us_a_visit` = (a kind's time - `empty`) / (its rows' live blocks),
`empty` run first.
"""
import argparse
import json
import os
import sys
import time

BLOCK = 128
# rows of a step, query / KV heads, head dim, pool blocks, table slots,
# window (mellum_win: a ring of 10 blocks named again and again)
SHAPES = {
    "mellum_full": dict(rows=256, H=32, KV=4, D=128, pool=3072, NB=80,
                        window=0),
    "mellum_win": dict(rows=256, H=32, KV=4, D=128, pool=960, NB=80,
                       window=1024, ring=10),
    "dense": dict(rows=128, H=32, KV=8, D=128, pool=704, NB=32, window=4096),
    "olmoe": dict(rows=128, H=16, KV=16, D=128, pool=704, NB=32, window=0),
    "lfm2": dict(rows=512, H=32, KV=8, D=64, pool=2048, NB=32, window=0),
    "qwen3next": dict(rows=256, H=16, KV=2, D=256, pool=1024, NB=32,
                      window=0),
    "granite": dict(rows=128, H=32, KV=8, D=128, pool=1024, NB=32, window=0),
    "nemotron": dict(rows=256, H=32, KV=2, D=128, pool=1024, NB=32, window=0),
    # decode rows of 1-7 blocks as the chat cell's are (402 live blocks a step)
    "olmohybrid": dict(rows=128, H=30, KV=30, D=128, pool=560, NB=32, window=0,
                       ctx_range=(96, 800)),
}
# --kernel latent: the pool is [blocks, BLOCK, C] and a row's H heads share it;
# a `mixed` call is the cell's step (PERF.md section 5): ~42 decode rows, then
# ~86 prompt tokens in chunks of 32 that start where the decode rows end
LATENT_SHAPES = {
    "latent": dict(rows=128, H=128, C=640, v_dim=512, pool=5632, NB=72,
                   window=0, mixed_runs=(32, 32, 22)),
}


def mix(kind, c, rng):
    """ctx [rows] and the runs [(first row, rows)] that share a table."""
    import numpy as np

    rows, cap = c["rows"], c["NB"] * BLOCK
    long_ctx = cap > 4096
    ctx = np.zeros(rows, np.int64)
    if kind == "empty":
        return ctx, []
    low, top = c.get("ctx_range") or (
        cap // 16, cap * 5 // 8 if long_ctx else cap // 3)
    if kind == "decode":
        ctx[:] = rng.integers(low, top, rows)
        return ctx, []
    if kind == "groups":
        lens = [32] * (rows // 32)
    else:
        lens = list(c.get("mixed_runs") or
                    [32] * (6 if long_ctx else max(1, rows // 96)))
    n_dec = rows - sum(lens)
    if kind == "mixed" and long_ctx:
        n_dec -= 2  # two pad rows at the end
    ctx[:n_dec] = rng.integers(low, top, n_dec)
    if long_ctx:  # chunks of prompts up to 8k
        firsts = [256, 1024, 2048, 3072, 5000, 8000, 1500, 4000]
    else:  # chat: the chunks of prompts of a few hundred tokens
        firsts = [1, 33, 65, 97, 129, 193, 257, 385,
                  1, 33, 65, 97, 129, 161, 225, 449]
    runs = []
    f = n_dec
    for g, n in enumerate(lens):
        ctx[f:f + n] = firsts[g % len(firsts)] + np.arange(n)
        runs.append((f, n))
        f += n
    return ctx, runs


def visits(c, ctx, runs):
    """(blocks rows alone visit, blocks the groups visit)."""
    import numpy as np

    w = c["window"]
    first = np.maximum(ctx - w, 0) // BLOCK if w else np.zeros_like(ctx)
    end = -(-ctx // BLOCK)
    riding = np.zeros(len(ctx), bool)
    grouped = 0
    for f, n in runs:
        riding[f:f + n] = True
        grouped += end[f:f + n].max() - first[f:f + n].min()
    return int((end - first)[~riding & (ctx > 0)].sum()), int(grouped)


def write_slots(spread, rows, pool, rng):
    """[rows] flat slots of one write over `pool` blocks, and the blocks
    it touches: `spread` blocks, consecutive slots in each."""
    import numpy as np

    if spread == "chat":
        runs = [1] * 95 + [20, 13]
        runs += [1] * (rows - sum(runs))
    else:
        n = max(int(spread), -(-rows // BLOCK))  # a block holds BLOCK rows
        runs = [rows // n + (i < rows % n) for i in range(n)]
    blocks = rng.permutation(pool)[:len(runs)]
    slots = np.concatenate([
        b * BLOCK + rng.integers(0, BLOCK - n + 1) + np.arange(n)
        for b, n in zip(blocks, runs)])
    return slots.astype(np.int32), len(runs)


def emit(line):
    """One measurement: a JSON line printed and kept."""
    print(json.dumps(line), flush=True)
    with open("chiprun_out/walk_bench.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")


def best_of(fn, chain, *operands):
    """(the last output, seconds a chained call): best of 5 x 40 programs."""
    for _ in range(3):
        out = fn(*operands)
    out.block_until_ready()
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(40):
            out = fn(*operands)
        out.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / 40 / chain)
    return out, best


def bench_latent(args, PA, name, c):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows, H, C, v_dim = c["rows"], c["H"], c["C"], c["v_dim"]
    key = jax.random.PRNGKey(0)
    pool = jax.random.normal(key, (c["pool"] + 1, BLOCK, C), jnp.bfloat16)
    q = (jax.random.normal(jax.random.fold_in(key, 2), (rows, H, C),
                           jnp.bfloat16) * C ** -0.5).astype(jnp.bfloat16)
    empty_us = None
    # `empty` first: every other kind's visits are sized less it
    kinds = sorted(args.kinds.split(","), key=lambda k: k != "empty")
    for kind in kinds:
        rng = np.random.default_rng(0)
        ctx, runs = mix(kind, c, rng)
        tbl = np.stack([rng.permutation(c["pool"])[:c["NB"]]
                        for _ in range(rows)])
        for f, n in runs:
            tbl[f:f + n] = tbl[f]
        tbl = jnp.asarray(tbl, jnp.int32)
        ctxd = jnp.asarray(ctx, jnp.int32)

        def chain(q, pool, tbl, ctxd):
            qq = q
            for _ in range(args.chain):
                out = PA.paged_latent_attention(qq, pool, tbl, ctxd, v_dim)
                qq = q + (out[..., :1] * 0).astype(q.dtype)
            return out

        out, best = best_of(jax.jit(chain), args.chain, q, pool, tbl, ctxd)
        pick = sorted({0, 1, 2, *[f + i for f, n in runs
                                  for i in (0, 1, n - 1)]})
        pick = np.asarray([i for i in pick if ctx[i] > 0][:12] or [0])
        with jax.default_matmul_precision("highest"):
            ref = PA.paged_latent_attention_xla(
                q[pick].astype(jnp.float32), pool.astype(jnp.float32),
                tbl[pick], ctxd[pick], v_dim)
        err = float(jnp.max(jnp.abs(out[pick].astype(jnp.float32) - ref)))
        blocks = -(-ctx // BLOCK)
        chunk = np.zeros(rows, bool)
        for f, n in runs:
            chunk[f:f + n] = True
        us = best * 1e6
        if kind == "empty":
            empty_us = us
        line = dict(tag=args.tag, kernel="latent", shape=name, kind=kind,
                    us=round(us, 1), decode_visits=int(blocks[~chunk].sum()),
                    chunk_visits=int(blocks[chunk].sum()),
                    max_err=round(err, 4),
                    device=jax.devices()[0].device_kind)
        if empty_us is not None and kind != "empty":
            line["us_a_visit"] = round(
                (us - empty_us) / max(1, int(blocks.sum())), 4)
        emit(line)


def bench_kv_write(args, PA, name, c):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows, KV, D = c["rows"], c["KV"], c["D"]
    pack = tree_pack(PA, KV, D)
    shape = (c["pool"] + 1, BLOCK, KV // pack, D * pack)
    key = jax.random.PRNGKey(0)
    kc = jax.random.normal(key, shape, jnp.bfloat16)
    vc = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.bfloat16)
    kn = jax.random.normal(jax.random.fold_in(key, 2), (rows, KV, D),
                           jnp.bfloat16)
    vn = jax.random.normal(jax.random.fold_in(key, 3), (rows, KV, D),
                           jnp.bfloat16)
    for spread in args.distinct_blocks.replace("rows", str(rows)).split(","):
        rng = np.random.default_rng(0)
        calls = [write_slots(spread, rows, c["pool"], rng)
                 for _ in range(args.chain)]
        slots = jnp.asarray(np.stack([s for s, _ in calls]))

        def chain(kc, vc, kn, vn, slots):
            for i in range(args.chain):
                kc, vc = PA.paged_kv_write(kc, vc, kn, vn, slots[i])
            return kc, vc

        # the pools are donated, as the engine's are: a write in place
        fn = jax.jit(chain, donate_argnums=(0, 1))
        ref_k, ref_v = kc, vc
        for i in range(args.chain):  # the scatter, before kc is donated
            flat = ref_k.reshape(-1, *shape[2:]), ref_v.reshape(-1, *shape[2:])
            ref_k, ref_v = (
                f.at[slots[i]].set(n.reshape(rows, *shape[2:])).reshape(shape)
                for f, n in zip(flat, (kn, vn)))
        kc, vc = fn(kc + 0, vc + 0, kn, vn, slots)
        same = bool(jnp.array_equal(kc, ref_k) & jnp.array_equal(vc, ref_v))
        for _ in range(2):
            kc, vc = fn(kc, vc, kn, vn, slots)
        kc.block_until_ready()
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(40):
                kc, vc = fn(kc, vc, kn, vn, slots)
            kc.block_until_ready()
            best = min(best, (time.perf_counter() - t0) / 40 / args.chain)
        path = getattr(PA, "kv_write_path", lambda *_: "blocks")(
            shape, jnp.bfloat16)
        line = dict(tag=args.tag, kernel="kv_write", shape=name, rows=rows,
                    kv=KV // pack, head_dim=D * pack, distinct_blocks=spread,
                    blocks=calls[0][1], path=path, us=round(best * 1e6, 1),
                    same_as_scatter=same,
                    device=jax.devices()[0].device_kind)
        emit(line)


def tree_pack(PA, kv_heads: int, head_dim: int) -> int:
    """The tree's rule for a bf16 pool (it reads the itemsize since PR 63)."""
    import inspect

    takes = len(inspect.signature(PA.kv_pack).parameters)
    return PA.kv_pack(kv_heads, head_dim, *([2] * (takes - 2)))


def laid_out(x, held: int, f: int):
    """x [..., KV, D] with zero heads appended to `held`, f heads side by
    side in one: [..., held / f, f x D], the same row-major values."""
    import jax.numpy as jnp

    x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(0, held - x.shape[-2]), (0, 0)])
    return x.reshape(*x.shape[:-2], held // f, f * x.shape[-1])


def pack_by_hand(PA, f: int, held: int, kv_heads: int, window: int):
    """attend(q [S, H, D], pools [.., held / f, f x D], table, ctx): the
    queries padded with zero heads to the held ones, each over its own
    head's lanes of its pool head and zeros in the others', a pool head's
    queries filled up to whole sublane tiles (so that a chunk's rows walk
    as one group); of the output each head's own lanes."""
    import jax.numpy as jnp

    def attend(q, kc, vc, tbl, ctx):
        S, H, D = q.shape
        G = H // kv_heads
        n, fg = held // f, f * G
        wide = jnp.pad(q, ((0, 0), (0, held * G - H), (0, 0)))
        place = jnp.arange(f)
        mine = (place[:, None] == place[None, :])[None, None, :, None, :, None]
        # [S, n, f, G, f, D]: head i of a pool head over lanes i alone
        wide = jnp.where(mine, wide.reshape(S, n, f, G, 1, D), 0).reshape(
            S, n, fg, f * D)
        rows = -(-fg // 8) * 8 if fg > 8 else fg
        wide = jnp.pad(wide, ((0, 0), (0, 0), (0, rows - fg), (0, 0)))
        out = PA.paged_decode_attention(
            wide.reshape(S, n * rows, f * D), kc, vc, tbl, ctx,
            window=window, scale=D ** -0.5)
        out = out.reshape(S, n, rows, f, D)[:, :, :fg].reshape(
            S, n, f, G, f, D)
        out = jnp.sum(jnp.where(mine, out, 0), axis=4)  # [S, n, f, G, D]
        return out.reshape(S, held * G, D)[:, :H]

    return attend


def bench_walk(args, PA, name, c):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rows, H, KV, D = c["rows"], c["H"], c["KV"], c["D"]
    key = jax.random.PRNGKey(0)
    plain = (c["pool"] + 1, BLOCK, KV, D)  # the values, a head a head
    kv = (jax.random.normal(key, plain, jnp.bfloat16),
          jax.random.normal(jax.random.fold_in(key, 1), plain, jnp.bfloat16))
    q = jax.random.normal(jax.random.fold_in(key, 2), (rows, H, D),
                          jnp.bfloat16)
    layouts = [None] if not args.pack else [
        tuple(int(n) for n in (lay + f":{KV}").split(":")[:2])
        for lay in args.pack.split(",")]
    for lay in layouts:
        if lay is None:  # the tree's own pool, its entry packing the queries
            f, held = tree_pack(PA, KV, D), KV

            def attend(q, kc, vc, tbl, ctxd):
                return PA.paged_decode_attention(q, kc, vc, tbl, ctxd,
                                                 window=c["window"])
        else:
            f, held = lay
            attend = pack_by_hand(PA, f, held, KV, c["window"])
        kc, vc = (laid_out(x, held, f) for x in kv)
        for kind in args.kinds.split(","):
            rng = np.random.default_rng(0)
            ctx, runs = mix(kind, c, rng)
            if c.get("ring"):
                base = rng.integers(0, c["pool"] // c["ring"], rows)
                tbl = (base[:, None] * c["ring"]
                       + np.arange(c["NB"])[None, :] % c["ring"])
            else:
                tbl = np.stack([rng.permutation(c["pool"])[:c["NB"]]
                                for _ in range(rows)])
            for f0, n in runs:
                tbl[f0:f0 + n] = tbl[f0]
            tbl = jnp.asarray(tbl, jnp.int32)
            ctxd = jnp.asarray(ctx, jnp.int32)

            def chain(q, kc, vc, tbl, ctxd):
                qq = q
                for _ in range(args.chain):
                    out = attend(qq, kc, vc, tbl, ctxd)
                    qq = q + (out * 0).astype(q.dtype)
                return out

            try:
                out, best = best_of(jax.jit(chain), args.chain,
                                    q, kc, vc, tbl, ctxd)
            except Exception as e:  # a layout Mosaic refuses: say so, go on
                line = dict(tag=args.tag, shape=name, kind=kind,
                            pool=list(kc.shape[2:]),
                            refused=str(e).strip().splitlines()[-1][:300])
                emit(line)
                break
            # a few rows against the float32 gather oracle
            pick = sorted({0, 1, 2, *[f0 + i for f0, n in runs
                                      for i in (0, n - 1)]})
            pick = np.asarray([i for i in pick if ctx[i] > 0][:8] or [0])
            with jax.default_matmul_precision("highest"):
                ref = PA.paged_decode_attention_xla(
                    q[pick].astype(jnp.float32), kv[0].astype(jnp.float32),
                    kv[1].astype(jnp.float32), tbl[pick], ctxd[pick],
                    window=c["window"])
            err = float(jnp.max(jnp.abs(out[pick].astype(jnp.float32) - ref)))
            alone, grouped = visits(c, ctx, runs)
            line = dict(tag=args.tag, shape=name, kind=kind,
                        us=round(best * 1e6, 1), decode_visits=alone,
                        group_visits=grouped, kv=held // f,
                        pool=list(kc.shape[2:]), max_err=round(err, 4),
                        device=jax.devices()[0].device_kind)
            emit(line)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("shapes", nargs="*")
    ap.add_argument("--kernel", choices=("walk", "kv_write", "latent"),
                    default="walk")
    ap.add_argument("--rows", type=int, help="kv_write: a shape by hand, "
                    "with --kv-heads and --head-dim, in place of a named one")
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--distinct-blocks", default="rows,1,chat",
                    help="kv_write: blocks a call's rows land in, a list")
    ap.add_argument("--kinds", default="mixed,groups,decode,empty")
    ap.add_argument("--pack", help="walk: layouts laid out by hand, a list of "
                    "f or f:held (KV heads side by side in a pool head; KV "
                    "heads held, zero heads beyond the shape's)")
    ap.add_argument("--tree", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--tag", default="tree")
    ap.add_argument("--chain", type=int, default=8)
    args = ap.parse_args()

    sys.path.insert(0, os.path.abspath(args.tree))
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu.ops.pallas.paged_attention as PA

    assert os.path.abspath(PA.__file__).startswith(
        os.path.abspath(args.tree)), PA.__file__
    os.makedirs("chiprun_out", exist_ok=True)
    if args.kernel == "latent":
        for name in args.shapes or LATENT_SHAPES:
            bench_latent(args, PA, name, LATENT_SHAPES[name])
        return
    args.shapes = args.shapes or list(SHAPES)
    if args.kernel == "kv_write":
        shapes = {n: SHAPES[n] for n in args.shapes}
        if args.rows:
            shapes = {"by_hand": dict(rows=args.rows, KV=args.kv_heads,
                                      D=args.head_dim, pool=704)}
        for name, c in shapes.items():
            bench_kv_write(args, PA, name, c)
        return
    for name in args.shapes:
        bench_walk(args, PA, name, SHAPES[name])


if __name__ == "__main__":
    main()
