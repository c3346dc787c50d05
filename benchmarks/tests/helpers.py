"""Builds the tests' checkout-shaped directory."""

import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"


def make_tiny_root(tmp_path):
    """A checkout-shaped directory whose benchmark is the real one plus
    files ADDED from the tests' own data: a tiny configuration, tiny
    mixes, a dummy per-layer metric, and a BENCHMARK.json naming them.
    Nothing that is there is edited."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(ROOT / "benchmarks", bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for f in DATA.glob("tiny-*.json"):
        shutil.copy(f, bench / ("configs" if "mistral" in f.name else "traffic"))
    shutil.copy(DATA / "dummy_answer.py", bench / "metrics")
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [("tiny-train", "tiny-mistral", "tiny-train", 1),
             ("tiny-train-zero3", "tiny-mistral-zero3", "tiny-train", 4),
             ("tiny-serve", "tiny-mistral", "tiny-serve", 1),
             ("tiny-serve-sat", "tiny-mistral", "tiny-serve-sat", 1)]
    for cfg in ("tiny-mistral", "tiny-mistral-zero3"):
        real["configs"].append({
            "name": cfg, "source": "tests", "reduced": [],
            "file": f"benchmarks/configs/{cfg}.json", "why": "CPU rehearsal"})
    for name, cfg, traffic, chips in cells:
        real["workloads"].append({
            "name": name, "config": cfg, "traffic": traffic,
            "chips": chips, "why": "CPU rehearsal"})
    # each tiny cell reports what the real cell of its kind reports
    twin = {"train-seq4k": "tiny-train", "train-seq4k-zero3": "tiny-train-zero3",
            "serve-chat-saturated": "tiny-serve-sat"}
    for m in real["end_to_end"] + real["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [twin[w] for w in m["workloads"]]
    # the cell BELOW its knee has no real twin yet: it brings its own
    # end-to-end metric and reader, as a later PR's cell would, and
    # shares the token gap and its readers with the saturated cell
    for m in real["end_to_end"] + real["per_layer"]:
        if "tpot_p50_ms" in (m["name"], m.get("moves")):
            m["workloads"].append("tiny-serve")
    real["end_to_end"].append({
        "name": "ttft_p50_ms", "unit": "ms", "better": "lower", "bound": 0.05,
        "source": "host_clock", "workloads": ["tiny-serve"]})
    real["per_layer"].append({
        "name": "dummy_answer", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "ttft_p50_ms", "workloads": ["tiny-serve"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(real))
    return tmp_path
