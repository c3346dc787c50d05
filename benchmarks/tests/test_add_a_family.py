"""The proof of the data-driven rule: a family at other widths is ADDED
to a copy of this checkout's benchmark, as a later `model_config` PR
would add it (a configuration with its published file, a reference
under another name, a traffic file, a rehearsal cell with its tiny
configuration, and BENCHMARK.json entries), no file that was there is
touched, and every check the real cells are held to passes on it."""

import json
import shutil

import jax
import pytest

from benchmarks import harness
from benchmarks.tests import helpers
from deepspeed_tpu.ops.pallas import interpret_kernels

# a dense family of its own widths: none of them another cell's
PUBLISHED = {"architectures": ["MistralForCausalLM"], "hidden_size": 2048,
             "intermediate_size": 5632, "num_attention_heads": 16,
             "num_key_value_heads": 4, "num_hidden_layers": 22,
             "vocab_size": 50304, "max_position_embeddings": 4096,
             "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
             "sliding_window": None, "tie_word_embeddings": False}


def snapshot(root):
    return {p.relative_to(root): p.read_bytes()
            for p in root.rglob("*") if p.is_file()}


def add_family(src, tag="other"):
    """Files and entries only, every name made from `tag`. Returns the
    new cell's name."""
    bench_dir, data = helpers.bench_dir(src), helpers.data_dir(src)
    bench = helpers.load(src / "BENCHMARK.json")
    like = bench["workloads"][0]                 # a serving cell to sit beside
    like_cfg = helpers.load(src / {c["name"]: c for c in bench["configs"]}[
        like["config"]]["file"])
    config, cell = f"{tag}-1b-serve-l8", f"serve-{tag}-chat"
    (bench_dir / "configs" / "published" / f"{tag}-1b.json").write_text(
        json.dumps(dict(PUBLISHED, _source="a paper")))
    shutil.copy(bench_dir / "reference" / f"{like_cfg['reference']}.py",
                bench_dir / "reference" / f"{tag}.py")
    (bench_dir / "configs" / f"{config}.json").write_text(json.dumps(dict(
        PUBLISHED, num_hidden_layers=8, published=f"{tag}-1b", reference=tag,
        source="a paper", assumed={"weights": "seeded random"},
        stands_for="another family, served on one chip",
        reduced={"num_hidden_layers": {"published": 22, "here": 8}},
        serve=like_cfg["serve"])))
    mix = helpers.load(bench_dir / "traffic" / f"{like['traffic']}.json")
    mix["logits_check"].update(rtol=0.1, rtol_why="other widths, other noise")
    (bench_dir / "traffic" / f"{tag}-chat.json").write_text(json.dumps(mix))
    # its rehearsal: a tiny configuration naming the new reference, the
    # tiny mix that is there, and the cell file that says what it reports as
    tiny = next(rc for rc in helpers.rehearsal_cells(src)
                if rc["reports_as"] == like["name"])
    tiny_cfg = helpers.load(data / "configs" / f"{tiny['config']}.json")
    (data / "configs" / f"tiny-{tag}.json").write_text(json.dumps(dict(
        tiny_cfg, hidden_size=128, intermediate_size=384, reference=tag)))
    (data / "cells" / f"tiny-{tag}-serve.json").write_text(json.dumps({
        k: v for k, v in dict(
            tiny, name=f"tiny-{tag}-serve", config=f"tiny-{tag}",
            reports_as=cell).items()
        if k not in ("runner", "reference")}))
    bench["configs"].append({
        "name": config, "source": "a paper",
        "file": f"{bench_dir.name}/configs/{config}.json",
        "reduced": ["num_hidden_layers"], "why": "another family"})
    bench["workloads"].append(dict(
        like, name=cell, config=config, traffic=f"{tag}-chat"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if like["name"] in m.get("workloads", ()):
            m["workloads"].append(cell)
    (src / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


@pytest.fixture
def grown(tmp_path):
    src = helpers.copy_checkout(tmp_path / "src")
    before = snapshot(src)
    name = add_family(src)
    return src, before, name


def test_a_family_at_other_widths_is_added_as_files_and_entries(grown, tmp_path):
    src, before, name = grown
    after = snapshot(src)
    changed = {p for p in before if after.get(p) != before[p]}
    assert changed == {(src / "BENCHMARK.json").relative_to(src)}
    assert len(after) == len(before) + 6
    bench = helpers.load(src / "BENCHMARK.json")
    helpers.check_contract(bench)
    for w in bench["workloads"]:               # the new cell and the old ones
        helpers.check_cell(src, w["name"])
    helpers.check_references_rehearsed(src)
    cell = harness.load_cell(name, src)
    assert cell.config["hidden_size"] == PUBLISHED["hidden_size"]
    assert cell.traffic["logits_check"]["rtol"] == 0.1
    # the tests' own root takes the rehearsal cell by listing it
    tiny = helpers.make_tiny_root(tmp_path / "tiny", root=src)
    got = harness.load_cell("tiny-other-serve", tiny)
    assert got.config["reference"] == "other" and got.config["hidden_size"] == 128
    like = harness.load_cell(bench["workloads"][0]["name"], src)
    assert {m["name"] for m in got.per_layer} == {m["name"] for m in like.per_layer}
    for rc in helpers.rehearsal_cells(src):
        harness.load_cell(rc["name"], tiny)
    assert snapshot(src) == after              # building it edited nothing


def test_a_reference_no_rehearsal_runs_is_refused(grown):
    src, _, _ = grown
    (helpers.data_dir(src) / "cells" / "tiny-other-serve.json").unlink()
    with pytest.raises(AssertionError, match="other"):
        helpers.check_references_rehearsed(src)


def test_the_added_rehearsal_runs_its_reference_through_its_runner(grown, tmp_path):
    """End to end on the CPU: the runner finds reference/other.py by
    the name in the added configuration."""
    src, _, _ = grown
    tiny = helpers.make_tiny_root(tmp_path / "tiny", root=src)
    rc = next(rc for rc in helpers.rehearsal_cells(src)
              if rc["name"] == "tiny-other-serve")
    cell = harness.load_cell(rc["name"], tiny)
    for f in (tiny / cell.bench_dir.name / "reference").glob("*.py"):
        if f.stem != cell.config["reference"]:
            f.unlink()                          # no other reference can serve
    logs = []
    with interpret_kernels():
        line = json.loads(harness.run_cell(
            cell, seed=11, seconds=rc["seconds"], trace=False,
            devices=jax.devices()[:cell.chips], t_process_start=harness.now(),
            log=logs.append, out_root=tiny / "out"))
    assert line["correct"], logs
    assert set(line["metrics"]) == set(rc["expect"]["end_to_end"])
