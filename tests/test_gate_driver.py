"""The one gate driver (scripts/ds_gate.py) and the rule its baselines
keep: a change that alters no finding alters no baseline byte.

Cheap on purpose: no canonical program is compiled here. The driver's
table, the three AST analyzers (determinism's host pass, concurrency's
static half, lifecycle's ledger) over sources moved down by forty lines,
the driver's exit codes, and NUMERICS.json's waiver. The gates' own
CLI round trips are in tests/test_determinism_gate.py,
tests/test_lifecycle.py, tests/test_numerics.py (and, slow, in
tests/test_costmodel.py and tests/test_schedule.py).
"""

import importlib.util
import json
import os
import re
import shutil

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(*path):
    spec = importlib.util.spec_from_file_location(
        path[-1][:-3], os.path.join(REPO, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


G = _load_script("scripts", "ds_gate.py")

FIFTEEN = ["lint", "budget", "numerics", "schedule", "fleet", "chaos",
           "elastic", "sdc", "overload", "autoscale", "moe", "pipe",
           "race", "determinism", "lifecycle"]
NO_BASELINE = {"lint", "fleet", "chaos"}


def _committed(name):
    with open(os.path.join(REPO, name), encoding="utf-8") as fh:
        return json.load(fh)


# ----------------------------------------------------------------------
# (a) the table
# ----------------------------------------------------------------------

def test_the_table_is_the_fifteen():
    assert list(G.GATES) == FIFTEEN


def _strings(node):
    """Every key and every string value of a JSON document."""
    if isinstance(node, dict):
        for k, v in node.items():
            yield k
            yield from _strings(v)
    elif isinstance(node, list):
        for v in node:
            yield from _strings(v)
    elif isinstance(node, str):
        yield node


@pytest.mark.parametrize("gate", FIFTEEN)
def test_baseline_holds_no_position_and_no_inventory(gate):
    build, baseline, compare = G.GATES[gate]
    assert callable(build)
    if baseline is None:
        assert gate in NO_BASELINE and compare is None
        return
    doc = _committed(baseline)
    for s in _strings(doc):
        assert not re.search(r"\.py:\d+", s), (baseline, s)
        assert not re.search(r"@\d+", s), (baseline, s)
        assert s not in ("functions", "files"), baseline


# ----------------------------------------------------------------------
# (b) positions do not matter
# ----------------------------------------------------------------------

MOVED = ("deepspeed_tpu/inference/engine.py",
         "deepspeed_tpu/inference/scheduler.py")
PREFIX = "\n" * 40 + \
    "def _a_helper_nobody_calls(x):\n    return x\n\n\n"


def _moved(pairs):
    return [(rel, PREFIX + src if rel.replace(os.sep, "/") in MOVED
             else src) for rel, src in pairs]


def _same_bytes(measured, committed):
    assert json.dumps(measured, indent=1, sort_keys=True) == \
        json.dumps(committed, indent=1, sort_keys=True)


def test_moved_lines_move_no_byte_of_determinism_host_pass():
    from deepspeed_tpu.analysis import determinism as D

    draws = D.check_draw_keys(REPO, sources=_moved(
        D._iter_scope(D.DRAW_KEY_SCOPE, REPO)))
    ordering = D.check_host_ordering(REPO, sources=_moved(
        D._iter_scope(D.ORDERING_SCOPE, REPO)))
    assert draws.suppressed, "the moved files carry the D004 waivers"
    _same_bytes(
        {"ordering": {"suppressed": ordering.suppressed_sites},
         "draw_keys": {"suppressed": draws.suppressed_sites}},
        _committed("DETERMINISM.json")["host"])


def test_moved_lines_move_no_byte_of_concurrency_ledger():
    from deepspeed_tpu.analysis import concurrency as C

    sources = []
    for path in C._iter_py([os.path.join(REPO, "deepspeed_tpu")]):
        with open(path, encoding="utf-8") as fh:
            sources.append((os.path.relpath(path, REPO), fh.read()))
    rep = C.analyze_sources(_moved(sources))
    assert rep.findings == []
    _same_bytes({"suppressed": rep.suppressed_sites,
                 "classes": rep.ledger},
                _committed("CONCURRENCY.json")["static"])


def test_moved_lines_move_no_byte_of_lifecycle_ledger(tmp_path):
    from deepspeed_tpu.analysis.lifecycle import analyze_tree

    shutil.copytree(os.path.join(REPO, "deepspeed_tpu"),
                    tmp_path / "deepspeed_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in os.listdir(REPO):  # the lanes L003 reads, as they are
        if name in ("tests", "scripts", "bench.py") or \
                name.endswith(".json"):
            os.symlink(os.path.join(REPO, name), tmp_path / name)
    for rel in MOVED:
        path = tmp_path / rel
        path.write_text(PREFIX + path.read_text(encoding="utf-8"),
                        encoding="utf-8")
    rep = analyze_tree(str(tmp_path))
    assert rep.findings == []
    committed = _committed("LIFECYCLE.json")
    _same_bytes({"ledger": rep.ledger, "coverage": rep.coverage},
                {k: committed[k] for k in ("ledger", "coverage")})


def test_site_keys_name_the_function_and_count_within_it():
    from deepspeed_tpu.analysis.report import Finding, site_keys

    src = ("class A:\n"
           "    def f(self):\n"
           "        x = 1\n"
           "        y = 2\n"
           "    def g(self):\n"
           "        def inner():\n"
           "            z = 3\n"
           "z = 4\n")
    at = lambda line, rule="D004": Finding(  # noqa: E731
        rule=rule, path="m.py", line=line, severity="error", message="")
    assert site_keys([at(4), at(3), at(7), at(8), at(3, "D003")],
                     {"m.py": src}) == [
        "m.py::<module> D004", "m.py::A.f D003", "m.py::A.f D004",
        "m.py::A.f D004#2", "m.py::A.g.inner D004"]


def test_an_inline_root_is_named_by_its_order_not_its_line():
    from deepspeed_tpu.analysis.concurrency import analyze_sources

    src = ("import atexit\n"
           "class Pool:\n"
           "    def __init__(self):\n"
           "        self.n = 0\n"
           "        def _close():\n"
           "            self.n += 1\n"
           "        atexit.register(_close)\n")
    names = [set(analyze_sources([("p.py", pad + src)])
                 .ledger["p.py::Pool"]["roots"]) for pad in ("", "\n" * 9)]
    assert names[0] == names[1] == {"__init__.<atexit#1>"}


# ----------------------------------------------------------------------
# (c), (d) the driver's exit codes
# ----------------------------------------------------------------------

def test_an_unknown_gate_is_a_usage_error_that_names_the_table(capsys):
    with pytest.raises(SystemExit) as e:
        G.main(["no-such-gate", "--check"])
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert all(name in err for name in FIFTEEN)


def test_capture_and_check_exclude_each_other():
    with pytest.raises(SystemExit) as e:
        G.main(["lifecycle", "--check", "--capture"])
    assert e.value.code == 2


def test_a_finding_gone_from_the_baseline_is_red_and_named(tmp_path,
                                                            capsys):
    doc = _committed("LIFECYCLE.json")
    doc["coverage"].pop("spill.io")  # as if no lane armed the point
    path = tmp_path / "lifecycle.json"
    path.write_text(json.dumps(doc))
    assert G.main(["lifecycle", "--check", "--baseline", str(path)]) == 1
    out = capsys.readouterr()
    assert "coverage drift: spill.io" in out.err
    assert json.loads(out.err.strip().splitlines()[-1]) == {
        "ok": False, "gate": "ds_lifecycle", "strict": False}
    last = json.loads(out.out.strip().splitlines()[-1])
    assert [f["rule"] for f in last["findings"]] == ["ledger"]


def test_a_waiver_gone_from_the_baseline_warns_then_strict_fails(
        tmp_path, capsys):
    doc = _committed("CONCURRENCY.json")
    gone = doc["static"]["suppressed"].pop()
    path = tmp_path / "concurrency.json"
    path.write_text(json.dumps(doc))
    argv = ["race", "--static-only", "--baseline", str(path)]
    assert G.main(argv) == 0
    assert "suppression drift" in capsys.readouterr().err
    assert G.main(argv + ["--strict"]) == 1
    assert gone in capsys.readouterr().err


def test_strip_suppressions_follows_its_patterns():
    doc = {"static": {"suppressed": ["a"], "classes": {
        "x": {"suppressed": 1, "locks": ["l"]}}}, "lanes": {}}
    assert G._strip_suppressions(doc, [
        ("static", "suppressed"),
        ("static", "classes", "*", "suppressed")]) == {
        "static": {"classes": {"x": {"locks": ["l"]}}}, "lanes": {}}
    assert doc["static"]["suppressed"] == ["a"]  # a copy, not in place


# ----------------------------------------------------------------------
# (e) NUMERICS.json's waiver
# ----------------------------------------------------------------------

def _n001(program="train_step_moe", count=1, op="reduce", dtype="bf16",
          rule="N001"):
    return {"rule": rule, "severity": "error", "where": program,
            "message": f"{count} {op} op(s) accumulate in {dtype} but the "
                       "policy declares f32 accumulation (compute=bf16)"}


def test_numerics_waiver_covers_exactly_the_one_finding():
    waivers = _committed("NUMERICS.json")["waived"]
    assert len(waivers) == 1 and waivers[0]["reason"]
    tree = [_n001(), _n001("train_step"), _n001(dtype="f16"),
            _n001(op="all-reduce")]
    assert [G.waived(f, waivers) is not None for f in tree] == \
        [True, False, False, False]


@pytest.mark.parametrize("other", [
    _n001(count=2),                 # a second such reduce in the program
    _n001(count=11),                # not a prefix match on the count
    _n001("train_step_pipe3d"),     # the same reduce, another program
    _n001(rule="N002"),             # another rule
], ids=["second-reduce", "eleven", "other-program", "other-rule"])
def test_numerics_waiver_covers_no_other_finding(other):
    assert G.waived(other, _committed("NUMERICS.json")["waived"]) is None


# ----------------------------------------------------------------------
# bench.py: a table of lanes, and no lane that quotes a rate
# ----------------------------------------------------------------------

@pytest.mark.parametrize("argv", [
    [], ["--overlap-probe"], ["--prefix-microbench"], ["--serving-sim"],
    ["--serving-sim", "--replicas", "1"],
    ["--train-chaos", "--moe-sim"]],
    ids=["none", "overlap-probe", "prefix-microbench", "serving-sim",
         "one-replica", "two-lanes"])
def test_bench_usage_errors(argv):
    bench = _load_script("bench.py")
    with pytest.raises(SystemExit) as e:
        bench.main(argv)
    assert e.value.code == 2


def test_bench_lanes_are_the_plan_lanes_of_the_gates():
    bench = _load_script("bench.py")
    assert sorted(bench.LANES) == [
        "autoscale-sim", "moe-sim", "overload-sim", "pipe-sim",
        "sdc-chaos", "train-chaos"]
    assert all(callable(fn) for fn in bench.LANES.values())
