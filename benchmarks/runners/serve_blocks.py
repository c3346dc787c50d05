"""Runner kind `serve_blocks`: a model that generates by DIFFUSION OVER
BLOCKS (TransformerConfig.block_length) through runners/serve.py's
set-up, engine, window and reporting (loaded by name, nothing of theirs
copied), with the one thing of its own that such a model needs: its
logits check. runners/serve.py's teacher-forces single-token steps
against a causal reference; here a step is a block's pass.

The check, at the cell's widths, through `engine.put()` and the paged
cache, teacher-forced on seeded tokens (`block_feeds`): two prompts
prefilled whole, a continuation chunk, then `blocks` blocks, each fed
as the scheduler feeds it: T = denoising_steps passes at the SAME
positions (commit=False: K/V written, nothing committed), the block's
positions unmasked in a seeded order, ceil(B / T) a pass, then the
commit pass on the final tokens. EVERY row of every pass (the B logits
`put()` returns of a block-diffusion model) is compared with the plain
float32 reference's forward of the same partially masked sequence
(`reference_logits`: the reference batched over the variants, one
sequence of one layer at a time, its experts a slice at a time).

The rule is the serving cells': no position's largest |difference| above
`logits_check.rtol` of the largest |reference logit|, the median position
under `typical_rtol`. `control_errors` puts wrong models and wrong
protocols in the engine's place against the same reference (the
reference's MUTANTS; `no_commit_pass`: later blocks see a block's K/V as
its LAST DENOISING pass left them; `positions_advance`: a pass's rows at
positions that move with the pass; `float8_weights`: the precision below
the stated one): what benchmarks/sdar_audit.py reads on the chip to set
the limits, and tests/test_sdar_moe.py holds at a tiny size.
"""

import pathlib
from typing import Any, Dict, List

import numpy as np

from benchmarks import harness

serve = harness.load_module(pathlib.Path(__file__).with_name("serve.py"))

PROTOCOL_CONTROLS = ("no_commit_pass", "positions_advance")


def denoising_steps(hf, B: int) -> int:
    return int(hf.get("serve", {}).get("scheduler", {}).get(
        "denoising_steps", 0)) or B


def block_feeds(hf, mcfg, chk, seed) -> Dict[str, Any]:
    """The teacher-forced protocol of one check, from the seed alone:
    for each prompt its tokens (prompt, chunk, blocks; never the mask
    id), and the list of FEEDS in order. A feed is (name, start, rows
    [prompts][n] as fed, commit): what one `engine.put()` call takes.
    Every feed but the whole-prompt prefill is whole blocks from a block
    boundary on; the rows compared are the feed's last block."""
    B, mask = mcfg.block_length, mcfg.mask_token_id
    T = denoising_steps(hf, B)
    per_pass = -(-B // T)
    rng = np.random.default_rng([int(seed), 0xB10C])
    lens = [int(n) for n in chk["prompt_lens"]]
    k, n_blocks = int(chk["chunk"]), int(chk["blocks"])
    assert all(n % B == 0 for n in lens) and k % B == 0, (lens, k, B)
    full = []
    for n in lens:
        t = rng.integers(0, mcfg.vocab_size - 1, n + k + n_blocks * B)
        full.append((t + (t >= mask)).astype(np.int32))  # never the mask id
    orders = [[rng.permutation(B) for _ in range(n_blocks)] for _ in lens]
    feeds = [("prefill", [0] * len(lens), [f[:n] for f, n in zip(full, lens)],
              True),
             ("chunk", lens, [f[n:n + k] for f, n in zip(full, lens)], True)]
    for b in range(n_blocks):
        start = [n + k + b * B for n in lens]
        for t in range(T):
            rows = []
            for f, s, order in zip(full, start, orders):
                blk = f[s:s + B].copy()
                blk[order[b][t * per_pass:]] = mask  # not revealed yet
                rows.append(blk)
            feeds.append((f"block {b} pass {t}", start, rows, False))
        feeds.append((f"block {b} commit", start,
                      [f[s:s + B] for f, s in zip(full, start)], True))
    return {"full": full, "feeds": feeds, "orders": orders, "B": B,
            "T": T, "per_pass": per_pass, "mask": mask}


def engine_logits(eng, proto) -> np.ndarray:
    """[prompts, feeds, B, V]: what put() returns of each feed."""
    uids = [10_000_000 + i for i in range(len(proto["full"]))]
    got = [np.asarray(eng.put(uids, rows, commit=commit), np.float32)
           for _, _, rows, commit in proto["feeds"]]
    for u in uids:
        eng.flush(u)
    return np.stack(got, axis=1)


def variants(proto, control=None):
    """What the reference is asked for, prompt by prompt: (tokens
    [feeds, L], rotary positions [feeds, L], the B positions read of
    each). Feed j's sequence is everything committed before it, then
    its rows; what follows is padding no compared position can see.
    `control`: a wrong PROTOCOL in the engine's place (module doc)."""
    B, mask, T = proto["B"], proto["mask"], proto["T"]
    out = []
    for i, f in enumerate(proto["full"]):
        L = len(f)
        toks = np.zeros((len(proto["feeds"]), L), np.int32)
        pos = np.broadcast_to(np.arange(L), toks.shape).copy()
        read = []
        for j, (name, start, rows, _) in enumerate(proto["feeds"]):
            s, n = start[i], len(rows[i])
            toks[j, :s] = f[:s]
            toks[j, s:s + n] = rows[i]
            read.append(np.arange(s + n - B, s + n))
            if not name.startswith("block"):
                continue
            b, what = int(name.split()[1]), name.split()[2:]
            if control == "no_commit_pass":
                # every block left as its last denoising pass fed it:
                # the position revealed last still masked; its own
                # commit pass never ran, so that reads the same
                first = s - b * B  # where the first generated block starts
                for e in range(b + (what[0] == "commit")):
                    last = proto["orders"][i][e][-proto["per_pass"]:]
                    toks[j, first + e * B + last] = mask
            if control == "positions_advance":
                t = T if what[0] == "commit" else int(what[1])
                pos[j, s:s + B] += B * (t + 1)
        out.append((toks, pos, np.stack(read)))
    return out


def reference_logits(ref, top, layer, hf, proto, control=None, mutate=None):
    """[prompts, feeds, B, V] float32 of the reference (or of a control
    in the engine's place), one prompt's variants a forward."""
    out = []
    for toks, pos, read in variants(proto, control):
        out.append(np.asarray(ref.forward_logits(
            top, layer, toks, hf, mutate, rows=read,
            positions=pos if control == "positions_advance" else None)))
    return np.stack(out)


def block_errors(cell, eng, mcfg, host_params, seed) -> Dict[str, Any]:
    """The engine against the reference at every row of every feed:
    err [prompts, feeds x B], each position's max |difference|."""
    ref = harness.load_module(
        cell.bench_dir / "reference" / f"{cell.config['reference']}.py")
    proto = block_feeds(cell.config, mcfg, cell.traffic["logits_check"], seed)
    got = engine_logits(eng, proto)
    top, layer = serve.reference_inputs(host_params)
    want = reference_logits(ref, top, layer, cell.config, proto)
    err = np.abs(got - want).max(axis=-1)
    return {"err": err.reshape(err.shape[0], -1), "want": want, "got": got,
            "ref_max": float(np.abs(want).max()), "proto": proto, "ref": ref,
            "finite": bool(np.isfinite(got).all())}


def control_errors(cell, host_params, e, names=None) -> Dict[str, np.ndarray]:
    """{control: err [prompts, positions]} of each control put in the
    engine's place against the reference `e` (block_errors') holds."""
    import jax.numpy as jnp

    ref, proto, hf = e["ref"], e["proto"], cell.config
    top, layer = serve.reference_inputs(host_params)
    f8 = serve.reference_inputs(host_params, cast=lambda a: a.astype(
        jnp.float8_e4m3fn).astype(a.dtype))
    out = {}
    for name in names or (("float8_weights",) + PROTOCOL_CONTROLS
                          + tuple(ref.MUTANTS)):
        if name == "float8_weights":
            got = reference_logits(ref, *f8, hf, proto)
        elif name in PROTOCOL_CONTROLS:
            got = reference_logits(ref, top, layer, hf, proto, control=name)
        else:
            got = reference_logits(ref, top, layer, hf, proto, mutate=name)
        err = np.abs(got - e["want"]).max(axis=-1)
        out[name] = err.reshape(err.shape[0], -1)
    return out


def position_name(proto, p: int) -> str:
    return f"{proto['feeds'][p // proto['B']][0]} row {p % proto['B']}"


def verdict(chk, e) -> Dict[str, Any]:
    """The traffic file's rule on `block_errors`' numbers: `rtol` the
    ceiling on every position, `typical_rtol` the limit on the median,
    both as shares of the largest |reference logit|."""
    share = np.asarray(e["err"], np.float64) / e["ref_max"]
    rtol, typical = float(chk["rtol"]), float(chk["typical_rtol"])
    i, p = np.unravel_index(int(np.argmax(share)), share.shape)
    out = {"max_abs_err": float(np.max(e["err"])),
           "ref_max_abs": e["ref_max"], "rtol": rtol, "typical_rtol": typical,
           "max_share": float(share.max()),
           "median_share": float(np.median(share)),
           "positions": int(share.size),
           "worst": f"prompt {i} {position_name(e['proto'], p)}"}
    broken = []
    if not e["finite"]:
        broken.append("a logit is not finite")
    if not out["max_share"] <= rtol:
        broken.append(
            f"{out['worst']}: max |err| {out['max_share']:.5f} of the largest "
            f"|reference logit| {e['ref_max']:.3f} is above rtol {rtol}")
    if not out["median_share"] <= typical:
        broken.append(
            f"the median over {share.size} positions, "
            f"{out['median_share']:.5f} of {e['ref_max']:.3f}, is above "
            f"typical_rtol {typical}")
    return dict(out, ok=not broken, broken=broken)


def block_check(cell, eng, mcfg, host_params, seed, log) -> Dict[str, Any]:
    e = block_errors(cell, eng, mcfg, host_params, seed)
    v = verdict(cell.traffic["logits_check"], e)
    B = e["proto"]["B"]
    by_feed = e["err"].reshape(e["err"].shape[0], -1, B).max(axis=(0, 2))
    log(f"[bench] block logits vs float32 reference over "
        f"{v['positions']} positions ({len(e['proto']['feeds'])} feeds of "
        f"{B} rows, 2 prompts): max |err| by feed "
        f"{by_feed.round(5).tolist()} on logits of max "
        f"|{e['ref_max']:.3f}|: largest {v['max_share']:.5f} of that at "
        f"{v['worst']} (allowed {v['rtol']}), median over the positions "
        f"{v['median_share']:.5f} (allowed {v['typical_rtol']})")
    return v


def block_readings(mcfg, d: Dict[str, Any], cell) -> Dict[str, Any]:
    """What the window's counters must read of a model that generates by
    blocks: every committed block took its passes."""
    B = mcfg.block_length
    T = denoising_steps(cell.config, B)
    per_token = (d["block_rows"] / d["block_tokens"]
                 if d.get("block_tokens") else None)
    return {"per_token": per_token, "T": T,
            "ok": per_token is not None
            and d["block_rows"] == B * (d["block_passes"] + d["block_commits"])
            and d["block_passes"] >= d["block_commits"]}


def run(ctx: harness.RunContext) -> harness.Outcome:
    from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

    hf = ctx.cell.config
    B = config_from_hf(hf, **hf["serve"]["model_overrides"]).block_length
    if not B:
        raise ValueError(
            f"runner kind serve_blocks serves a model that generates by "
            f"diffusion over blocks; {ctx.cell.config_name} is causal")
    eng, mcfg, host_params, phases = serve.setup(ctx)
    per_pass = -(-B // denoising_steps(hf, B))
    if per_pass != 1:
        # serve.setup warmed the epilogue that reveals one position a
        # pass (engine.warmup's default): this deployment's, once more
        # over programs that are compiled already
        eng.warmup(widths=ctx.cell.traffic["warmup_widths"], footprint=False,
                   reveals=(per_pass,))
    m = serve.measure(ctx, eng, mcfg, ctx.seed)
    compared = []           # every number compared, beside its limit

    def say(msg):
        compared.append(msg)
        ctx.log(msg)

    lc = block_check(ctx.cell, eng, mcfg, host_params, ctx.seed, say)
    m["checks"]["matches_reference"] = lc["ok"]
    br = block_readings(mcfg, m["notes"]["counters_delta"], ctx.cell)
    m["checks"]["blocks_took_their_passes"] = br["ok"]
    m["readings"]["blocks_took_their_passes"] = (
        f"{br['per_token']} rows fed a committed token (a block of "
        f"{mcfg.block_length} in {br['T']} passes and a commit), rows = "
        f"block_length x (passes + commits)")
    say("[bench] compared: " + "; ".join(m["readings"].values()))
    say(f"[bench] checks {m['checks']}")
    serve.report_false_checks(m["checks"], m["readings"], lc, say)
    m["notes"].update(logits=lc, setup_phases=phases, checks=m["checks"],
                      compared=compared, blocks=br,
                      compile_s_total=ctx.compiles.seconds,
                      programs_compiled=ctx.compiles.n)
    return harness.Outcome(
        correct=all(m["checks"].values()), attempted=m["attempted"],
        failed=m["failed"], end_to_end=m["end_to_end"], obs=m["obs"],
        notes=m["notes"])
