"""Standalone chip timing of serving's routed block alone, by expert path:
the table inference/model.py expert_path's bounds are set from (PERF.md
section 6, PR 37), kept so that the next family can re-run it.

  chiprun -- python scripts/expert_bench.py            # stream / grouped / ragged
  python scripts/expert_bench.py --aot                 # compile only, no chip
  ... --errors 512        each path against a float32 `highest` oracle
  ... --paths grouped_tke,grouped_segsum,grouped_sort,grouped_nokernel
                          the grouped entry with another combine, with a sort
                          for its layout, or with its kernel stubbed out
  ... --sweep 32,64,128   the grouped entry by row block

Each path is forced by patching expert_path's bounds, never by a flag of the
program. The routed block of --what's layers (lfm2: 12 of 32 x [2048, 1792]
top-4; olmoe: 8 of 64 x [2048, 1024] top-8) with a residual and a norm
between them, best of 3 x 20 calls. One JSON line a measurement, appended to
chiprun_out/expert_bench.jsonl.
"""
import argparse
import contextlib
import json
import os
import sys
import time
import unittest.mock as um

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import model as M
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.moe.dropless import dropless_topk_gating
from deepspeed_tpu.ops.pallas import expert_stream as ES

ALWAYS = (0.0, float("inf"))
NEVER = (float("inf"),) * 2
GROUPED = ("grouped", "grouped_tke", "grouped_segsum", "grouped_nokernel",
           "grouped_sort")


def cfg_for(what):
    if what == "lfm2":
        return T.TransformerConfig(
            vocab_size=256, n_layers=12, n_heads=16, d_model=2048, d_ff=1792,
            max_seq=256, variant="llama", use_flash=False, n_experts=32,
            moe_top_k=4, moe_norm_topk_prob=True, moe_scoring="sigmoid",
            moe_dropless=True), 12
    return T.TransformerConfig(
        vocab_size=256, n_layers=8, n_heads=16, d_model=2048, d_ff=1024,
        max_seq=256, variant="llama", use_flash=False, n_experts=64,
        moe_top_k=8, moe_norm_topk_prob=False, moe_dropless=True), 8


def layer_shapes(cfg):
    E, F, X = cfg.d_model, cfg.d_ff, cfg.n_experts
    bf = jnp.bfloat16
    return {"w_router": ((E, X), jnp.float32), "w_gate": ((X, E, F), bf),
            "w_in": ((X, E, F), bf), "w_out": ((X, F, E), bf)}


def make_layers(cfg, n_layers, key):
    layers = []
    for li in range(n_layers):
        lp = {}
        for i, (name, (shape, dt)) in enumerate(layer_shapes(cfg).items()):
            k = jax.random.fold_in(jax.random.fold_in(key, li), i)
            lp[name] = (jax.random.normal(k, shape, dt) * 0.02).astype(dt)
        layers.append(lp)
    return layers


def mlp_grouped_variant(h, lp, cfg, combine):
    """_mlp's grouped branch with another combine: 'tke' = ONE [T, k, E]
    gather and a weighted sum; 'segsum' = each buffer row weighted and
    scatter-added to its token."""
    X, k = cfg.n_experts, cfg.moe_top_k
    with jax.named_scope("moe_route"):
        logits = h.astype(jnp.float32) @ lp["w_router"].astype(jnp.float32)
        if cfg.moe_scoring == "sigmoid":
            idx, wts = M._sigmoid_topk_gating(logits, cfg, None)
        else:
            idx, wts, _, _ = dropless_topk_gating(
                logits, k, renormalize=cfg.moe_norm_topk_prob)
        row_token, pair_row, starts, counts = ES.group_rows(idx, X)
        xs = h[row_token]
    with jax.named_scope("moe_experts"):
        ys = ES.expert_grouped_mlp(xs, starts, counts, lp["w_gate"],
                                   lp["w_in"], lp["w_out"], T._act_fn(cfg))
        if combine == "tke":
            return jnp.einsum("tke,tk->te", ys[pair_row], wts).astype(h.dtype)
        row_w = jnp.zeros((ys.shape[0],), jnp.float32).at[
            pair_row.reshape(-1)].set(wts.reshape(-1))
        return jnp.zeros(h.shape, jnp.float32).at[row_token].add(
            ys * row_w[:, None]).astype(h.dtype)


def group_rows_sort(idx, n_experts):
    """group_rows by a stable argsort and the scatter that inverts it."""
    from deepspeed_tpu.moe.dropless import expert_counts, sort_by_expert
    T_, k = idx.shape
    A = T_ * k
    order, src, sorted_e = sort_by_expert(idx)
    counts = expert_counts(idx, n_experts).astype(jnp.int32)
    padded = (counts + 15) // 16 * 16
    starts = jnp.cumsum(padded) - padded
    first = jnp.cumsum(counts) - counts
    pos_sorted = (starts[sorted_e] + jnp.arange(A, dtype=jnp.int32)
                  - first[sorted_e]).astype(jnp.int32)
    pos = jnp.zeros((A,), jnp.int32).at[order].set(
        pos_sorted, unique_indices=True)
    row_token = jnp.zeros((ES.grouped_rows(T_, k, n_experts),), jnp.int32).at[
        pos_sorted].set(src.astype(jnp.int32), unique_indices=True)
    return row_token, pos.reshape(T_, k), starts.astype(jnp.int32), counts


def block_fn(cfg, path):
    kernels = path in ("stream",) + GROUPED

    def block(h, layers):
        for lp in layers:
            if path in ("grouped_tke", "grouped_segsum"):
                y = h + mlp_grouped_variant(h, lp, cfg, path[8:])
            else:
                y = h + M._mlp(h, lp, cfg, None, kernels, None)
            h = (y * jax.lax.rsqrt(jnp.mean(jnp.square(
                y.astype(jnp.float32)), -1, keepdims=True) + 1e-6).astype(
                    y.dtype))
        return h
    return block


def patches(path, knobs):
    ps = [um.patch.object(M, "_STREAM_RIDGE_TOKENS",
                          0 if path in GROUPED else float("inf")),
          um.patch.object(M, "_STREAM_ROWS_PER_EXPERT",
                          ALWAYS if path in ("stream",) + GROUPED else NEVER),
          um.patch.object(M, "_SCAN_ROWS_PER_EXPERT",
                          ALWAYS if path == "scan" else NEVER)]
    for k, v in knobs.items():
        ps.append(um.patch.object(ES, k, v))
    if path == "grouped_sort":
        ps.append(um.patch.object(M, "group_rows", group_rows_sort))
    if path == "grouped_nokernel":
        ps.append(um.patch.object(
            M, "expert_grouped_mlp",
            lambda xs, starts, counts, *a: xs.astype(jnp.float32)
            * (starts.sum() + counts.sum()).astype(jnp.float32)))
    return ps


def oracle(h, lp, cfg):
    """One layer's routed block in float32 at `highest` precision, every
    expert over every token under its combine column."""
    # the router as _mlp computes it (default precision: the SAME choices;
    # a `highest` router flips a few near-ties and every path alike then
    # reads 40-55% off on those tokens), the experts at `highest`
    f = lambda a: a.astype(jnp.float32)
    x = f(h)
    logits = x @ lp["w_router"].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        if cfg.moe_scoring == "sigmoid":
            idx, wts = M._sigmoid_topk_gating(logits, cfg, None)
        else:
            idx, wts, _, _ = dropless_topk_gating(
                logits, cfg.moe_top_k, renormalize=cfg.moe_norm_topk_prob)
        w = jnp.zeros(logits.shape, jnp.float32).at[
            jnp.arange(x.shape[0])[:, None], idx].add(wts)

        def one(acc, ws):
            wg, wi, wo, col = ws
            y = (jax.nn.silu(x @ f(wg)) * (x @ f(wi))) @ f(wo)
            return acc + col[:, None] * y, None
        out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
            lp["w_gate"], lp["w_in"], lp["w_out"], w.T))
        return out


def errors(what, T_, out):
    """grouped / stream / ragged against the oracle on the same seeded
    inputs, one layer, weights at three seeds."""
    cfg, _ = cfg_for(what)
    for seed in range(int(os.environ.get('ERR_SEEDS', '6'))):
        lp = make_layers(cfg, 1, jax.random.PRNGKey(100 + seed))[0]
        # weights large enough that outputs are O(1)
        h = jax.random.normal(jax.random.PRNGKey(7 + seed),
                              (T_, cfg.d_model), jnp.bfloat16)
        want = np.asarray(jax.jit(lambda h, lp: oracle(h, lp, cfg))(h, lp),
                          np.float64)
        top = float(np.abs(want).max())
        rec = {"what": what, "tokens": T_, "errors_seed": seed,
               "largest_output": top}
        for path in ("stream", "grouped", "ragged"):
            with contextlib.ExitStack() as st:
                for p in patches(path, {}):
                    st.enter_context(p)
                assert M.expert_path(T_, cfg, lp, path != "ragged") == path
                got = jax.jit(lambda h, lp: M._mlp(
                    h, lp, cfg, None, path != "ragged", None))(h, lp)
            got = np.asarray(got.astype(jnp.float32), np.float64)
            rec[path + "_max_err_share"] = float(np.abs(got - want).max() / top)
            rec[path + "_mean_err_share"] = float(np.abs(got - want).mean() / top)
            rec[path + "_tokens_over_5pct"] = int(
                (np.abs(got - want).max(-1) / top > 0.05).sum())
        # the bf16 rounding of the oracle itself: the floor both share
        rounded = np.asarray(jnp.asarray(want, jnp.bfloat16).astype(
            jnp.float32), np.float64)
        rec["bf16_cast_max_err_share"] = float(np.abs(rounded - want).max() / top)
        print(json.dumps(rec), flush=True)
        out.write(json.dumps(rec) + "\n")
        out.flush()


def run(what, tokens, paths, knob_sets, aot, out):
    cfg, n_layers = cfg_for(what)
    if aot:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        layers = [{n: jax.ShapeDtypeStruct(s, d, sharding=one)
                   for n, (s, d) in layer_shapes(cfg).items()}
                  for _ in range(n_layers)]
    else:
        layers = make_layers(cfg, n_layers, jax.random.PRNGKey(0))
        jax.block_until_ready(layers)
    for T_ in tokens:
        if aot:
            h = jax.ShapeDtypeStruct((T_, cfg.d_model), jnp.bfloat16,
                                     sharding=one)
        else:
            h = jax.random.normal(jax.random.PRNGKey(T_),
                                  (T_, cfg.d_model), jnp.bfloat16)
        for path in paths:
            for knobs in (knob_sets if path == "grouped" else [{}]):
                rec = {"what": what, "tokens": T_, "path": path, **knobs}
                with contextlib.ExitStack() as st:
                    for p in patches(path, knobs):
                        st.enter_context(p)
                    want = "grouped" if path in GROUPED else path
                    got = M.expert_path(T_, cfg, layers[0],
                                        path in ("stream",) + GROUPED, None)
                    st3 = (layers[0]["w_gate"], layers[0]["w_in"],
                           layers[0]["w_out"])
                    if path in GROUPED:
                        rec["rows"] = ES.grouped_rows(
                            T_, cfg.moe_top_k, cfg.n_experts)
                        rec["tile"] = ES.grouped_f_tile(
                            T_, cfg.moe_top_k, *st3)
                    elif path == "stream":
                        rec["tile"] = ES.stream_f_tile(T_, *st3)
                    if got != want:
                        rec["skipped"] = f"expert_path says {got}"
                        print(json.dumps(rec), flush=True)
                        out.write(json.dumps(rec) + "\n")
                        continue
                    f = jax.jit(block_fn(cfg, path))
                    try:
                        t0 = time.perf_counter()
                        if aot:
                            c = f.lower(h, layers).compile()
                            rec["compile_s"] = round(
                                time.perf_counter() - t0, 2)
                            rec["custom_calls"] = c.as_text().count(
                                "tpu_custom_call")
                            print(json.dumps(rec), flush=True)
                            continue
                        y = jax.block_until_ready(f(h, layers))
                        rec["compile_s"] = round(time.perf_counter() - t0, 2)
                    except Exception as e:  # a refusal is a result
                        rec["error"] = str(e)[:600]
                        print(json.dumps(rec), flush=True)
                        out.write(json.dumps(rec) + "\n")
                        continue
                jax.block_until_ready(f(h, layers))
                times = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    for _ in range(20):
                        y = f(h, layers)
                    jax.block_until_ready(y)
                    times.append((time.perf_counter() - t0) / 20 * 1e3)
                rec["ms"] = round(min(times), 3)
                rec["ms_all"] = [round(t, 3) for t in times]
                print(json.dumps(rec), flush=True)
                out.write(json.dumps(rec) + "\n")
                out.flush()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--what", default="lfm2,olmoe")
    ap.add_argument("--tokens", default="128,192,256,288,320,384,512,768")
    ap.add_argument("--paths", default="stream,grouped,ragged")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--errors", default="")
    a = ap.parse_args()
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/expert_bench.jsonl", "a")
    print(json.dumps({"device": str(jax.devices()[0]),
                      "kind": jax.devices()[0].device_kind}), flush=True)
    tokens = [int(t) for t in a.tokens.split(",") if t]
    knobs = [{}]
    if a.sweep:
        knobs = [{"_GROUP_ROW_TILE": int(r)} for r in a.sweep.split(",")]
    for what in a.what.split(","):
        for T_ in [int(t) for t in a.errors.split(",") if t]:
            errors(what, T_, out)
        run(what, tokens, a.paths.split(","), knobs, a.aot, out)


if __name__ == "__main__":
    main()
