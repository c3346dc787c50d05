#!/usr/bin/env python3
"""The controls of the Trinity (`afmoe`) comparison, and the chip audit
that sets the training cell's two logits limits.

A CONTROL is the plain reference (reference/afmoe.py) with ONE part of
the layer replaced by a wrong one. Put in the system's place, or held
against the system, it must come out not correct by the comparison's
own limits: tests/test_trinity.py holds the training forward's loss and
every gradient to each on the CPU, the audit below holds each to the
cell's logits rule at the published widths on the chip.

    python3 benchmarks/afmoe_audit.py --seeds 16 [--first-seed N] [--steps S]

(through chiprun, one chip) runs, a seed: the cell's OWN engine as
initialised (and, with --steps S, again after S of its training steps:
the two states the cell's check sees), then the cell's own comparison
(runners/train_routed.reference_numbers on 8,192 Zipf tokens, under
the traffic file's limits) of the system and of each of CHIP_CONTROLS
in the system's place. It prints one JSON line a seed (for the system
and for every control: the verdict, the largest and the median
position's logits error as shares of the largest |reference logit|,
the share of tokens whose router chose other experts and the largest
weight difference among the rest) and a summary; the limits in
traffic/seq8k-zipf-trinity.json are set from those lines (PERF.md §6,
PR 55).
"""

import argparse
import contextlib
import gc
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CELL = "train-trinity-seq8k"
CHIP_CONTROLS = ("window_ignored", "rope_on_the_full_layer", "scale_1",
                 "gate_left_out", "bf16_router")


def controls(ref):
    """name -> {function of reference/afmoe.py: its wrong twin}."""
    import jax
    import jax.numpy as jnp

    F32 = jnp.float32

    def whole_vector_norm(x, scale, eps):
        # the statistic over ALL heads of a token (OLMoE's form)
        var = jnp.mean(jnp.square(x), axis=(-2, -1), keepdims=True)
        return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)

    def weights(bias=False, scale=True, held_only=False):
        def chosen_weights(s, b, chosen, hf, held):
            w = jnp.take_along_axis(s + b if bias else s, chosen, axis=-1)
            over = jnp.where(held, w, 0.0) if held_only else w
            w = w / (jnp.sum(over, axis=-1, keepdims=True) + 1e-20)
            return w * hf["route_scale"] if scale else w
        return chosen_weights

    def bf16_scores(h, lw):
        bf = jnp.bfloat16
        return jax.nn.sigmoid(jnp.einsum(
            "...e,ex->...x", h.astype(bf), lw["w_router"].astype(bf),
            preferred_element_type=bf)).astype(F32)

    return {
        "window_ignored": {"window_of": lambda hf, li: None},
        "rope_on_the_full_layer": {"rotates": lambda hf, li: True},
        "gate_left_out": {"output_gate": lambda att, g: att},
        "whole_vector_qk_norm": {"head_norm": whole_vector_norm},
        "bias_in_the_weights": {"chosen_weights": weights(bias=True)},
        "scale_1": {"chosen_weights": weights(scale=False)},
        "normalised_over_the_held": {"chosen_weights": weights(held_only=True)},
        "shared_expert_left_out": {
            "shared_expert": lambda h, lw: jnp.zeros_like(h)},
        "post_norms_left_out": {"post_norm": lambda x, scale, eps: x},
        "embedding_unscaled": {"embed_scale": lambda hf: 1.0},
        "bf16_router": {"router_scores": bf16_scores},
    }


@contextlib.contextmanager
def control(ref, name):
    """The reference with control `name` in place (None: as it is)."""
    swap = controls(ref)[name] if name else {}
    kept = {k: getattr(ref, k) for k in swap}
    try:
        for k, fn in swap.items():
            setattr(ref, k, fn)
        yield ref
    finally:
        for k, fn in kept.items():
            setattr(ref, k, fn)


def in_the_systems_place(names, hf, width):
    """`stand_ins` of runners/train_routed.reference_numbers: each
    control of `names` where the system stands."""
    import jax

    from benchmarks.runners.train_routed import combine_matrix

    def stand_ins(ref, top, layer):
        def one(name):
            def logits(row):
                with control(ref, name):
                    return ref.forward_logits(top, layer, row[None], hf)

            def routing(lw, h):
                with control(ref, name), \
                        jax.default_matmul_precision("highest"):
                    return combine_matrix(*ref.route(h, lw, hf), width)

            return logits, routing

        return {name: one(name) for name in names}

    return stand_ins


def verdicts(cell, mcfg, params, toks, mesh, state, names=CHIP_CONTROLS,
             ceiling=True):
    """The cell's own comparison (runners/train_routed.reference_numbers
    under the traffic file's limits) of the system and of each control
    in its place: name -> {ok, the compared numbers, the line}."""
    from benchmarks.runners import train_routed as R

    out = {}
    for name, numbers in R.reference_numbers(
            cell, mcfg, params, toks, mesh,
            in_the_systems_place(names, cell.config, mcfg.n_experts)).items():
        ok, line, kept = R.reference_verdict(cell.traffic, numbers, state,
                                             ceiling)
        out[name] = dict(kept, ok=ok, line=line)
    return out


def audit(seeds, first_seed, steps=0):
    import jax
    import numpy as np

    from benchmarks import harness
    from benchmarks.runners import train_routed
    from benchmarks.traffic import generate

    harness.require_tpu(1)
    harness.enable_compile_cache()
    cell = harness.load_cell(CELL)
    mix = cell.traffic
    rows = []
    for seed in range(first_seed, first_seed + seeds):
        engine, mcfg = train_routed.build_engine(cell, jax.devices()[:1], seed)
        batches = generate.token_batches(
            mix, seed, mcfg.vocab_size, engine.config.train_batch_size)
        toks = next(generate.token_batches(
            mix, seed + 1, mcfg.vocab_size, 1))["tokens"][:, :-1]
        # the two states the cell's check sees: as initialised, and
        # (--steps) after as many steps as a run of the cell takes
        for n in (0, steps) if steps else (0,):
            for _ in range(n):
                engine.train_batch(next(batches))
            got = verdicts(cell, mcfg, engine.state.params, toks,
                           engine.mesh, f"after {n} steps", ceiling=n == 0)
            row = {"seed": seed, "steps": n,
                   **{k: {a: v[a] for a in v if a != "line"}
                      for k, v in got.items()}}
            print(json.dumps(row), flush=True)
            rows.append(row)
        del engine
        gc.collect()  # the step program's memory goes with the engine
    out = {"seeds": seeds, "first_seed": first_seed}
    for n in sorted({r["steps"] for r in rows}):
        at = [r for r in rows if r["steps"] == n]
        for name in ("system",) + CHIP_CONTROLS:
            o = out.setdefault(f"after {n} steps", {}).setdefault(name, {})
            o["correct_on"] = f"{sum(r[name]['ok'] for r in at)} of {len(at)}"
            for stat in ("max_share", "median_share", "flipped_share",
                         "weight_err"):
                xs = np.array([r[name][stat] for r in at])
                o[stat] = [float(xs.min()), float(np.median(xs)),
                           float(xs.max())]
    print(json.dumps(out), flush=True)
    (ROOT / "chiprun_out").mkdir(exist_ok=True)
    with open(ROOT / "chiprun_out"
              / f"afmoe_audit_{first_seed}_steps{steps}.json", "w") as f:
        json.dump({"rows": rows, "summary": out}, f)


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=16)
    ap.add_argument("--first-seed", type=int, default=2_718_281_828)
    ap.add_argument("--steps", type=int, default=0)
    a = ap.parse_args()
    audit(a.seeds, a.first_seed, a.steps)
