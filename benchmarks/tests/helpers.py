"""The tests' tables, read from data, and the checks every cell, every
configuration and every reference is held to, each taking the root of
the checkout it looks at: the proof test runs them on a temporary root
to which a family was ADDED.

Rehearsal cells are listed, never named here: one JSON file each under
`data/cells/` (name, configuration, traffic, chips, `reports_as`: the
real cell whose metrics it reports, or null with `shares` / `adds`),
tiny configurations under `data/configs/`, tiny mixes under
`data/traffic/`, readers under `data/metrics/`.
"""

import json
import pathlib
import re
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# the contract's widths: a hidden, intermediate, latent, state or
# projection size, a head size, an expert width, a key that ends in
# `_dim` / `_rank`, an expansion factor, experts per token, the window
WIDTH_KEY = re.compile(
    r"(hidden|intermediate|latent|state|head|expert|proj\w*)_size$"
    r"|_dim$|_rank$|_width$|^d_(model|ff|inner|state)$|expan(d|sion)"
    r"|experts_per_tok|top_?k$|window")
# counts a cut may divide, and only where the file states the
# deployment whose share this chip holds (`share_of`)
COUNT_KEY = re.compile(r"^(num|n)_\w*(heads?|experts)$|^vocab_size$")


def load(path):
    return json.loads(pathlib.Path(path).read_text())


def bench_dir(root) -> pathlib.Path:
    return pathlib.Path(root) / load(pathlib.Path(root) / "BENCHMARK.json")["paths"][0]


def data_dir(root=ROOT) -> pathlib.Path:
    return bench_dir(root) / "tests" / "data"


def rehearsal_cells(root=ROOT):
    """Every rehearsal cell the tests' data lists, with its runner
    kind and the reference its tiny configuration names."""
    data = data_dir(root)
    out = []
    for f in sorted((data / "cells").glob("*.json")):
        rc = load(f)
        assert rc["name"] == f.stem, f
        rc["runner"] = load(data / "traffic" / f"{rc['traffic']}.json")["runner"]
        rc["reference"] = load(data / "configs" / f"{rc['config']}.json")["reference"]
        out.append(rc)
    return out


def copy_checkout(dst, root=ROOT):
    """A copy of a checkout's benchmark (tests and their data included)
    and BENCHMARK.json: a root that a later PR's files can be added to."""
    name = bench_dir(root).name
    shutil.copytree(pathlib.Path(root) / name, pathlib.Path(dst) / name,
                    ignore=shutil.ignore_patterns("__pycache__", "recorded"))
    shutil.copy(pathlib.Path(root) / "BENCHMARK.json", dst)
    return pathlib.Path(dst)


def make_tiny_root(tmp_path, root=ROOT):
    """A checkout-shaped directory whose benchmark is the one of `root`
    plus files ADDED from its tests' data: tiny configurations, tiny
    mixes, a dummy per-layer metric, and a BENCHMARK.json naming them
    and the rehearsal cells. Nothing that is there is edited."""
    src, data = bench_dir(root), data_dir(root)
    bench = pathlib.Path(tmp_path) / src.name
    shutil.copytree(src, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for kind in ("configs", "traffic", "metrics"):
        for f in sorted((data / kind).iterdir()):
            if f.is_file():
                assert not (bench / kind / f.name).exists(), f
                shutil.copy(f, bench / kind)
    real = load(pathlib.Path(root) / "BENCHMARK.json")
    real_cells = {w["name"] for w in real["workloads"]}
    metrics = real["end_to_end"] + real["per_layer"]
    cells = rehearsal_cells(root)
    for cfg in sorted({rc["config"] for rc in cells}):
        real["configs"].append({
            "name": cfg, "source": "tests", "reduced": [],
            "file": f"{src.name}/configs/{cfg}.json", "why": "CPU rehearsal"})
    for rc in cells:
        real["workloads"].append({
            "name": rc["name"], "config": rc["config"],
            "traffic": rc["traffic"], "chips": rc["chips"],
            "why": "CPU rehearsal"})
        twin = rc.get("reports_as")
        if twin is not None and twin not in real_cells:
            raise ValueError(
                f"rehearsal cell {rc['name']!r} reports as {twin!r}, which "
                f"BENCHMARK.json does not have: {sorted(real_cells)}")
        for m in metrics:
            # what the real cell reports, and what a cell with no twin
            # shares with those that exist
            if twin in m.get("workloads", ()) or (
                    "workloads" in m and
                    {m["name"], m.get("moves")} & set(rc.get("shares", ()))):
                m["workloads"].append(rc["name"])
    for rc in cells:
        for group in ("end_to_end", "per_layer"):
            real[group] += rc.get("adds", {}).get(group, [])
    (pathlib.Path(tmp_path) / "BENCHMARK.json").write_text(json.dumps(real))
    return pathlib.Path(tmp_path)


# -- the checks ------------------------------------------------------------

def check_contract(bench):
    """BENCHMARK.json keeps the driver's contract."""
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["source"] in SOURCES
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in bench["workloads"])
        assert c["file"].startswith(bench["paths"][0] + "/")


def check_cell(root, name):
    """The cell finds its files, reports enough, and its configuration
    keeps its own published widths."""
    from benchmarks import harness

    cell = harness.load_cell(name, pathlib.Path(root))
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert len(cell.per_layer) >= 1
    assert all(m["moves"] in e2e for m in cell.per_layer)
    assert (cell.bench_dir / "runners" / f"{cell.traffic['runner']}.py").is_file()
    assert (cell.bench_dir / "reference" / f"{cell.config['reference']}.py").is_file()
    for m in cell.per_layer:
        assert (cell.bench_dir / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    check_logits_limit(cell.traffic)
    # every key the cut changed from the source is listed
    bench = load(pathlib.Path(root) / "BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}[cell.config_name]
    assert sorted(cell.config["reduced"]) == sorted(entry["reduced"])
    for key in ("source", "assumed", "stands_for"):
        assert cell.config[key]
    check_published_widths(cell.config, cell.bench_dir)


def check_logits_limit(traffic):
    """A mix that states limits of its own for the logits check (`rtol`,
    the ceiling on the largest error; `typical_rtol`, the limit on the
    median over the positions; any key ending in `rtol`) says why, each
    in `<key>_why`."""
    chk = traffic.get("logits_check", {})
    limits = {k for k in chk if k.endswith("rtol")}
    reasons = {k[:-len("_why")] for k in chk if k.endswith("rtol_why")}
    assert limits == reasons, \
        f"a traffic file that states a limit of its own says why: " \
        f"{sorted(limits ^ reasons)}"
    for k in limits:
        assert 0 < chk[k] < 1 and chk[f"{k}_why"].strip(), \
            f"a traffic file that states its own {k} says why"
    if "typical_rtol" in chk:
        assert chk["typical_rtol"] < chk.get("rtol", 1), \
            "the limit on the median lies under the ceiling"


def width_changes(published, here, path=""):
    """Keys that are widths and differ, at any depth."""
    out = []
    for k in published.keys() & here.keys():
        if isinstance(published[k], dict) and isinstance(here[k], dict):
            out += width_changes(published[k], here[k], f"{path}{k}.")
        elif published[k] != here[k] and WIDTH_KEY.search(k):
            out.append(path + k)
    return out


def check_published_widths(config, bench):
    """The configuration against the published file it names: every
    key of that file is here and equal, unless `reduced` lists it with
    the published value; no width is ever cut; a count (heads, experts,
    vocabulary rows) only where `share_of` states the deployment whose
    share this chip holds."""
    published = {k: v for k, v in load(
        bench / "configs" / "published" / f"{config['published']}.json").items()
        if not k.startswith("_")}
    reduced = config["reduced"]
    assert set(reduced) <= set(published), \
        f"`reduced` lists keys the source does not have: {set(reduced) - set(published)}"
    assert not width_changes(published, config), width_changes(published, config)
    for k, want in published.items():
        assert k in config, f"published key {k!r} is left out"
        if config[k] == want:
            assert k not in reduced, f"{k!r} is listed as reduced and is not"
            continue
        assert k in reduced, f"{k!r} differs from the source and is not in `reduced`"
        assert reduced[k]["published"] == want and reduced[k]["here"] == config[k], k
        if COUNT_KEY.search(k):
            assert str(config.get("share_of", "")).strip(), \
                f"{k!r} is a count: the file must state the deployment " \
                f"whose share this chip holds (`share_of`)"


def check_references_rehearsed(root):
    """Every reference a configuration names runs through its runner in
    at least one rehearsal cell on the CPU."""
    from benchmarks import harness

    bench = load(pathlib.Path(root) / "BENCHMARK.json")
    rehearsed = {(rc["reference"], rc["runner"]) for rc in rehearsal_cells(root)}
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], pathlib.Path(root))
        pair = (cell.config["reference"], cell.traffic["runner"])
        assert pair in rehearsed, \
            f"cell {w['name']!r}: no rehearsal cell runs reference " \
            f"{pair[0]!r} through runner {pair[1]!r}"
