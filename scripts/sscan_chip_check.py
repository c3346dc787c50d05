"""The selective scan's step kernel (`sscan_state`, ops/pallas/selective_scan.py)
COMPILED for the chip, not interpreted, against the recurrence, at the Phi-4-
mini-flash cell's own tile: a pool of 128 slots (and the pad rows' one) of
[40, 16, 128] float32, 64 and 128 rows a step. Tier-1 holds the kernel to its
oracle interpreted on the CPU, and the cell's logits check cannot see a scan's
state through seeded weights (PERF.md section 7), so the kernel Mosaic builds,
with its `consts` operand, is judged here:

  chiprun -- python scripts/sscan_chip_check.py [--seeds 1,3000000007]

Each case's rows go through `sscan_step` (the kernel) and are compared with the
recurrence in float64 on the host, run by run (a run from position 0 starts
from zero whatever its slot held: the slot holds NaN), and with `sscan_step_xla`
(the loop the CPU and decode_impl 'xla' run): every row's output, every slot a
run lives in, and bit for bit every slot that none does, the pad rows' among
them. Then the check is given a FAULT it must see (each run starting from its
neighbour's slot) so that a pass says something. One JSON line a case, appended
to chiprun_out/sscan_chip_check.jsonl; exit 1 where a case fails or a fault
passes.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SLOTS, LANE_ROWS, N, LANES = 128, 40, 16, 128
CHANNELS = LANE_ROWS * LANES
# |kernel - float64| over the largest |float64| of the case: float32 sums of
# 16 products a channel and one exponential a (channel, state) pair
RTOL = 1e-5


def cases(rng):
    """name -> (each row's slot, -1 a pad row; each row's position)."""
    import numpy as np

    deal = lambda n: rng.permutation(SLOTS)[:n]
    deep = lambda n: rng.integers(1, 4000, n)
    decode64 = (deal(64), deep(64))
    decode128 = (deal(128), deep(128))
    # the 128-row program's step: 64 decode rows, a chunk of 32 from deep in
    # its prompt, a chunk of 24 from position 0, 8 pad rows
    s = deal(66)
    start = int(rng.integers(32, 1500))
    mixed = (np.concatenate([s[:64], np.full(32, s[64]), np.full(24, s[65]),
                             np.full(8, -1)]),
             np.concatenate([deep(64), start + np.arange(32), np.arange(24),
                             np.zeros(8, np.int64)]))
    return {"64_decode_rows": decode64, "128_decode_rows": decode128,
            "64_decode_rows_two_chunks_8_pad_rows": mixed}


def recurrence(x, dt, A, Bm, Cm, pool, slots, pos):
    """The step in float64 on the host, run by run: (y [S, I], {slot: the
    state its run leaves, [I, N]})."""
    import numpy as np

    x, dt, A, Bm, Cm, pool = (np.asarray(a, np.float64)
                              for a in (x, dt, A, Bm, Cm, pool))
    view = lambda packed: np.swapaxes(packed, -1, -2).reshape(CHANNELS, N)
    y, left, h = np.zeros_like(x), {}, None
    for t, (slot, p) in enumerate(zip(slots, pos)):
        if slot < 0:
            continue
        if t == 0 or slots[t - 1] != slot:
            h = np.zeros((CHANNELS, N)) if p == 0 else view(pool[slot])
        h = h * np.exp(dt[t][:, None] * A) + (dt[t] * x[t])[:, None] * Bm[t]
        y[t] = h @ Cm[t]
        left[int(slot)] = h
    return y, left


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", default="1,3000000007")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas import selective_scan as SS

    device = jax.devices()[0]
    if device.platform != "tpu":
        print("scripts/sscan_chip_check.py: no TPU (tier-1 holds the kernel "
              "interpreted: tests/test_selective_scan.py)", file=sys.stderr)
        return 3
    out_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    kernel, xla = jax.jit(SS.sscan_step), jax.jit(SS.sscan_step_xla)
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        rng = np.random.default_rng(seed)
        normal = lambda *shape: rng.normal(size=shape).astype(np.float32)
        pool = normal(SLOTS + 1, LANE_ROWS, N, LANES)
        # decays exp(dt A) from ~0.05 to ~0.99 a token
        A = -np.exp(normal(CHANNELS, N) * 0.7)
        for name, (slots, pos) in cases(rng).items():
            S = len(slots)
            fresh = sorted({int(s) for s, p in zip(slots, pos)
                            if s >= 0 and p == 0})
            held = pool.copy()
            held[fresh] = np.nan    # another sequence's: must not be read
            x, Bm, Cm = normal(S, CHANNELS), normal(S, N), normal(S, N)
            dt = np.log1p(np.exp(normal(S, CHANNELS) - 1.0))
            want_y, want = recurrence(x, dt, A, Bm, Cm, held, slots, pos)
            scale = max(np.abs(want_y).max(),
                        max(np.abs(h).max() for h in want.values()))
            idle = sorted(set(range(SLOTS + 1)) - set(want))

            def errors(fn, slots_given, idle=idle):
                y, new = fn(*(jnp.asarray(a) for a in (
                    x, dt, A, Bm, Cm, held)),
                    jnp.asarray(slots_given, jnp.int32),
                    jnp.asarray(pos, jnp.int32))
                y, new = np.asarray(y, np.float64), np.asarray(new)
                rows = np.asarray(slots) >= 0
                state = max(np.abs(np.asarray(SS.state_view(new[s]),
                                              np.float64) - h).max()
                            for s, h in want.items())
                return {"y": float(np.abs(y - want_y)[rows].max() / scale),
                        "state": float(state / scale),
                        "idle_slots_bit_for_bit": bool(np.array_equal(
                            new[idle], held[idle], equal_nan=True))}

            # (the pad rows' slot is the XLA loop's scratch: a pad row of
            # the kernel writes nothing, a pad row of the loop writes there)
            got = {"kernel": errors(kernel, slots),
                   "xla": errors(xla, slots, idle[:-1])}
            # the fault: every run starts from the slot beside its own
            beside = np.where(np.asarray(slots) < 0, -1,
                              (np.asarray(slots) + 1) % SLOTS)
            fault = errors(kernel, beside)
            ok = all(e["y"] <= RTOL and e["state"] <= RTOL
                     and e["idle_slots_bit_for_bit"] for e in got.values())
            seen = not (fault["y"] <= RTOL)
            bad += (not ok) + (not seen)
            line = {"device": device.device_kind, "seed": seed, "case": name,
                    "rows": S, "pool": [SLOTS + 1, LANE_ROWS, N, LANES],
                    "scale": float(scale), "rtol": RTOL, **got,
                    "fault_neighbours_slot": fault, "ok": ok,
                    "fault_seen": seen}
            print(json.dumps(line), flush=True)
            with open(os.path.join(out_dir, "sscan_chip_check.jsonl"), "a") as f:
                f.write(json.dumps(line) + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
