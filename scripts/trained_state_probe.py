"""What a routed training cell's `correct` reads ALONG a run, and whether a
reading belongs to the parameters or to the forward that judges them (PERF.md
section 6, PR 61 (f): one run of `train-trinity-seq8k` read its trained
parameters' median logit error above `typical_rtol`). The cell's engine is
built as its runner builds it and trained on the cell's own batches; nothing
is timed. Trees of parameters go between processes (and between a checkout
of the parent and the change, each running this file from its own root)
through --dir, outside the checkout.

  train  --seed S --steps N --check-at 60 99 101 --save-at 0 101 --tag change
      every step's loss, gradient norm and held pairs (repr, so two trees
      can be compared to the bit), the cell's own comparison
      (runners/train_routed.reference_numbers) at the steps of --check-at,
      the compute-dtype parameters saved at the steps of --save-at
  check  --seed S --params FILE...
      this tree's forward against the float32 reference on saved parameters
  grads  --seed S --params FILE... --batch K... --tag change
      loss and every gradient of this tree's loss function on each saved
      tree of parameters and the run's K-th batch, saved
  diff   --trees A B
      two saved trees leaf by leaf: largest |a - b| over largest |b|, the
      share of elements that differ, and each side's rms

One JSON line a reading, appended to chiprun_out/trained_state_probe.jsonl.
"""

import argparse
import json
import os
import pathlib
import pickle
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import harness
from benchmarks.traffic import generate

OUT = pathlib.Path("chiprun_out")


def emit(line):
    print(json.dumps(line), flush=True)
    OUT.mkdir(exist_ok=True)
    with open(OUT / "trained_state_probe.jsonl", "a") as f:
        f.write(json.dumps(line) + "\n")


def save(tree, path):
    leaves, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "wb") as f:
        pickle.dump([(jax.tree_util.keystr(k), np.asarray(v))
                     for k, v in leaves], f, protocol=4)


def load_leaves(path):
    with open(path, "rb") as f:
        return pickle.load(f)


def the_cell(args):
    cell = harness.load_cell(args.cell, args.root)
    runner = harness.load_module(
        cell.bench_dir / "runners" / f"{cell.traffic['runner']}.py")
    return cell, runner


def eval_tokens(cell, seed, vocab):
    ev = cell.traffic["reference_check"]
    return next(generate.token_batches(
        dict(cell.traffic, seq_len=ev["seq_len"]), seed + 1, vocab,
        int(ev["sequences_per_chip"])))["tokens"]


def compared(cell, runner, mcfg, params, tokens, mesh):
    """The cell's own comparison of `params` (runners/train_routed)."""
    n = runner.reference_numbers(cell, mcfg, params, tokens[:, :-1],
                                 mesh)["system"]
    _, _, kept = runner.reference_verdict(cell.traffic, n, "probe",
                                          ceiling=False)
    return kept


def train(args):
    cell, runner = the_cell(args)
    engine, mcfg = runner.build_engine(cell, jax.devices()[:1], args.seed)
    batches = generate.token_batches(
        cell.traffic, args.seed, mcfg.vocab_size,
        engine.config.train_batch_size)
    ev = eval_tokens(cell, args.seed, mcfg.vocab_size)
    base = dict(mode="train", tag=args.tag, cell=args.cell, seed=args.seed,
                device=jax.devices()[0].device_kind)
    for step in range(args.steps + 1):
        if step in args.save_at:
            save(engine.state.params,
                 args.dir / f"{args.tag}_seed{args.seed}_step{step}.pkl")
        if step in args.check_at:
            got = float(engine.eval_batch({"tokens": ev}))
            emit(dict(base, step=step, eval_loss=got, **compared(
                cell, runner, mcfg, engine.state.params, ev, engine.mesh)))
        if step == args.steps:
            break
        m = engine.train_batch(next(batches))
        if step < args.print_steps or step + 1 in args.check_at:
            emit(dict(base, step=step + 1, loss=repr(m["loss"]),
                      grad_norm=repr(m["grad_norm"]),
                      pairs_held=int(m["moe_pairs_held"]),
                      chunks_run=int(m.get("moe_chunks_run", -1))))


def on_device(path):
    """A saved tree of parameters back as the engine's nested dict."""
    tree = {}
    for key, v in load_leaves(path):
        names = [p.strip("'\"") for p in key.strip("[]").split("][")]
        at = tree
        for n in names[:-1]:
            at = at.setdefault(n, {})
        at[names[-1]] = jnp.asarray(v)
    return tree


def model_of(cell):
    from deepspeed_tpu.platform.mesh import build_mesh
    from deepspeed_tpu.utils.hf_checkpoint import config_from_hf

    tr = cell.config["train"]
    return (config_from_hf(cell.config, **tr["model_overrides"]), tr,
            build_mesh(tr["mesh"], devices=jax.devices()[:1]))


def check(args):
    cell, runner = the_cell(args)
    mcfg, _, mesh = model_of(cell)
    ev = eval_tokens(cell, args.seed, mcfg.vocab_size)
    for path in args.params:
        emit(dict(mode="check", tag=args.tag, params=path.name,
                  seed=args.seed, **compared(cell, runner, mcfg,
                                             on_device(path), ev, mesh)))


def grads(args):
    from deepspeed_tpu.models import transformer as T

    cell, _ = the_cell(args)
    mcfg, tr, mesh = model_of(cell)
    batches = generate.token_batches(
        cell.traffic, args.seed, mcfg.vocab_size,
        tr["ds_config"]["train_micro_batch_size_per_gpu"])
    drawn = [next(batches) for _ in range(max(args.batch))]
    loss_fn = T.make_loss_fn(mcfg, loss_chunks=tr["loss_chunks"],
                             has_aux=True)
    grad = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
    for path, k in zip(args.params, args.batch, strict=True):
        with jax.sharding.set_mesh(mesh):
            (loss, aux), g = grad(on_device(path), drawn[k - 1],
                                  jax.random.PRNGKey(0))
        name = f"{args.tag}_grads_{path.stem}_batch{k}.pkl"
        save(g, args.dir / name)
        del g
        emit(dict(mode="grads", tag=args.tag, params=path.name, batch=k,
                  loss=repr(float(loss)), saved=name,
                  pairs_dropped=int(aux["moe_pairs_dropped"])))


def diff(args):
    a, b = (dict(load_leaves(p)) for p in args.trees)
    f32 = lambda v: np.asarray(v, np.float32)
    rms = lambda v: float(np.sqrt(np.mean(np.square(v, dtype=np.float64))))
    worst = 0.0
    for key in a:
        x, y = f32(a[key]), f32(b[key])
        top = float(np.max(np.abs(y)))
        rel = float(np.max(np.abs(x - y))) / max(top, 1e-30)
        worst = max(worst, rel)
        emit(dict(mode="diff", a=args.trees[0].name, b=args.trees[1].name,
                  leaf=key, max_diff_over_max=rel,
                  differ_share=float(np.mean(x != y)), rms_a=rms(x),
                  rms_b=rms(y), rms_diff=rms(x - y),
                  finite=bool(np.isfinite(x).all() and np.isfinite(y).all())))
    emit(dict(mode="diff", a=args.trees[0].name, b=args.trees[1].name,
              leaf="ALL", max_diff_over_max=worst))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("train", "check", "grads", "diff"))
    ap.add_argument("--cell", default="train-trinity-seq8k")
    ap.add_argument("--root", type=pathlib.Path, default=harness.ROOT,
                    help="the checkout whose BENCHMARK.json names the cell")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--tag", default="tree")
    ap.add_argument("--dir", type=pathlib.Path, default=pathlib.Path(
        tempfile.gettempdir()) / "trained_state_probe")
    ap.add_argument("--steps", type=int, default=101)
    ap.add_argument("--print-steps", type=int, default=8)
    ap.add_argument("--check-at", type=int, nargs="*", default=[])
    ap.add_argument("--save-at", type=int, nargs="*", default=[])
    ap.add_argument("--params", type=pathlib.Path, nargs="*")
    ap.add_argument("--batch", type=int, nargs="*", default=[1])
    ap.add_argument("--trees", type=pathlib.Path, nargs=2)
    args = ap.parse_args()
    if args.mode != "diff":
        harness.enable_compile_cache()
    dict(train=train, check=check, grads=grads, diff=diff)[args.mode](args)


if __name__ == "__main__":
    main()
