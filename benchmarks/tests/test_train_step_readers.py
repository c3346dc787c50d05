"""The readers of the train step's device scopes and of its collective
manifest (PR 38): on a hand-made traced step whose arithmetic is known,
on one recorded step of each training cell (events kept from this PR's
chip runs with their paths, and the manifest's ids the program's span
`train.compile.collectives` carried there: the readers' arithmetic on
real names, never a device number), and on a trace of the parent, which
has the model's scopes alone: nothing is returned, nothing raises.

The readers are NOT entries of BENCHMARK.json yet, and no PR but one of
kind `benchmark` can make them so (PERF.md section 7; the same reason
as test_mla_readers.py's seven and test_lfm2_readers.py's eight).
`ENTRIES` below is what that PR appends AFTER those, in this order.
`grad_reduce_ms_per_step` ships as a reader with no entry: in both
training cells (`gradient_accumulation_steps` 1, bf16) its scope holds
no instruction, and an entry lists the cells in which its reader finds
something to read."""

import json
import pathlib

import pytest

from benchmarks import harness
from benchmarks.trace import program_spans as PS
from benchmarks.trace import reduce as R
from benchmarks.trace.capture import load_recorded

BENCH = pathlib.Path(__file__).resolve().parents[1]
RECORDED = BENCH / "trace" / "recorded"
CELLS = ("train-seq4k", "train-seq4k-zero3")
NEW = ("optimizer_ms_per_step", "param_cast_ms_per_step",
       "grad_clip_ms_per_step", "grad_reduce_ms_per_step",
       "zero_gather_ms_per_step", "layer_stack_overhead_ms_per_step",
       "train_step_named_share", "collective_in_fusion_ms_per_step",
       "all_gather_achieved_gbps")
OLD = ("mlp_ms_per_step", "attention_ms_per_step", "head_loss_ms_per_step",
       "scope_named_share")
SPAN = "train.compile.collectives"


def read(name, obs):
    return harness.load_module(BENCH / "metrics" / f"{name}.py").read(obs)


def with_manifest(obs, ids):
    """The always-kept set-up spans as program_spans.setup_spans would
    hand them over (memoised in obs), holding the manifest's span."""
    obs[PS.MEMO + "_setup"] = [
        PS.PSpan("train.compile", 0.0, 4.0, 1, 0, {"step": 1}),
        PS.PSpan(SPAN, 3.9, 4.0, 2, 1, ids)]
    return obs


def hand_made():
    """Two steps of 100 ms. Each: 2 layers of a 1 ms prefetch gather, a
    2 ms slice of the stacked weights, 20 ms of mlp of which a 3 ms
    fusion holds the gradient's all-reduce, 10 ms of attention; a head
    of 12 ms with its 1 ms gather; 4 ms of the loss's copies, 1 ms of
    accumulation, 2 ms of clipping, an 8 ms optimizer pass whose root is
    the copy entered inside it, a 0.5 ms gather of updated parameters;
    1.5 ms of copies XLA inserted."""
    S, J = R.Event, "jit(step_fn)/while/body/closed_call/"
    L = J + "transpose(jvp(layer_stack))/while/body/closed_call/"
    ops, modules = [], []
    for i in range(2):
        t = [0.1 * i]

        def ev(name, ms, path=""):
            ops.append(S(name, t[0], ms * 1e-3, path))
            t[0] += ms * 1e-3

        ev("convert_element_type.4", 4, J + "jvp(param_cast)/convert_element_type:")
        for _ in range(2):
            ev("all-gather.9", 1, L + "zero_gather/sharding_constraint:")
            ev("dynamic-slice_bitcast_fusion.2", 2, L + "squeeze:")
            ev("fusion.30", 17, L + "checkpoint/mlp/bsf,fe->bse/dot_general:")
            ev("fusion.31", 3, L + "checkpoint/mlp/bse,ef->bsf/dot_general:")
            ev("fusion.32", 10, L + "checkpoint/attention/dot_general:")
        ev("all-gather-start.2", 0.01, J + "jvp(lm_head)/zero_gather/sharding_constraint:")
        ev("fusion.40", 11, J + "jvp(lm_head)/while/body/dot_general:")
        ev("add_fusion.1", 1, "jit(step_fn)/grad_reduce/add:")
        ev("multiply_reduce_fusion.3", 2, "jit(step_fn)/grad_clip/reduce_sum:")
        ev("fusion.50", 8, "jit(step_fn)/optimizer/param_cast/convert_element_type:")
        ev("all-gather.11", 0.5, "jit(step_fn)/optimizer/zero_gather/sharding_constraint:")
        ev("copy.7", 1.5)
        modules.append(S("jit_step_fn(1)", 0.1 * i, t[0] - 0.1 * i))
    td = R.from_events({0: ops}, {0: modules}, [S(R.WINDOW_SPAN, 0.0, 0.2)])
    td.async_ops = {0: [S("all-gather-start.2", 0.064 + 0.1 * i, 0.00099)
                        for i in range(2)]}
    ids = {"all_gather_n": 3, "all_gather_bytes": 300_000_000,
           "all_reduce_n": 1, "all_reduce_bytes": 120_000_000,
           "in_fusion_n": 1, "in_fusion_bytes": 120_000_000,
           "sites": "fusion.31:all-reduce:120000000,"
                    "all-gather.9:all-gather:100000000,"
                    "all-gather-start.2:all-gather:150000000,"
                    "all-gather.11:all-gather:50000000"}
    return with_manifest({"trace": td, "traced_steps": 2}, ids)


def test_the_readers_on_a_hand_made_step(capsys):
    obs = hand_made()
    assert read("param_cast_ms_per_step", obs) == pytest.approx(4.0)
    assert read("grad_reduce_ms_per_step", obs) == pytest.approx(1.0)
    assert read("grad_clip_ms_per_step", obs) == pytest.approx(2.0)
    # the pass whose root is the copy entered INSIDE the scope, and the
    # gather of the updated parameters after it
    assert read("optimizer_ms_per_step", obs) == pytest.approx(8.5)
    # anywhere in a path: the layers', the head's launch, the finalizer's
    assert read("zero_gather_ms_per_step", obs) == pytest.approx(2.51)
    assert read("layer_stack_overhead_ms_per_step", obs) == pytest.approx(4.0)
    # the old readers count a scope's whole subtree, gathers and fused
    # all-reduces included: they read what they read before the scopes
    assert read("mlp_ms_per_step", obs) == pytest.approx(40.0)
    assert read("head_loss_ms_per_step", obs) == pytest.approx(11.01)
    busy = 4 + 2 * 33 + 0.01 + 11 + 1 + 2 + 8 + 0.5 + 1.5
    assert read("train_step_named_share", obs) == pytest.approx(
        100 * (busy - 1.5) / busy)
    out = capsys.readouterr().out
    assert "outside every scope" in out and "no op_name (copy) 3.0" in out
    assert read("scope_named_share", obs) == pytest.approx(
        100 * (2 * 30 + 11.01) / busy)
    # the one site that is no collective's name: 2 layers x 3 ms
    assert read("collective_in_fusion_ms_per_step", obs) == pytest.approx(6.0)
    assert "fusion.31 6.000 (layer_stack/dot_general)" in capsys.readouterr().out
    # 4 x 100 MB in 4 ms, 2 x 150 MB over two 0.99 ms spans (not the
    # launches' 0.01 ms), 2 x 50 MB in 1 ms
    assert read("all_gather_achieved_gbps", obs) == pytest.approx(
        (400e6 + 300e6 + 100e6) / (4e-3 + 2 * 0.99e-3 + 1e-3) / 1e9)


def recorded(cell):
    path = RECORDED / f"{cell}-step-scopes.json"
    obs = {"trace": load_recorded(path), "traced_steps": 1}
    return with_manifest(obs, json.loads(path.read_text())["collectives"])


@pytest.mark.parametrize("cell", CELLS)
def test_the_readers_on_the_recorded_step(cell):
    """One whole step of each training cell kept from a chip run of this
    PR's tree: every instruction's time is booked once."""
    from benchmarks.metrics import train_step_named_share as N

    obs = recorded(cell)
    td = obs["trace"]
    got = {n: read(n, obs) for n in NEW + OLD}
    four = cell.endswith("zero3")
    assert got["grad_reduce_ms_per_step"] is None
    for n in ("optimizer_ms_per_step", "param_cast_ms_per_step",
              "grad_clip_ms_per_step", "layer_stack_overhead_ms_per_step"):
        assert got[n] > 0, n
    for n in ("zero_gather_ms_per_step", "collective_in_fusion_ms_per_step",
              "all_gather_achieved_gbps"):
        assert (got[n] is not None) == four, n
    # the optimizer's pass is several times the loss's copies, which are
    # several times the clipping; the scan's own slicing is dearer on
    # eight layers than on two
    assert got["optimizer_ms_per_step"] > got["param_cast_ms_per_step"] \
        > got["grad_clip_ms_per_step"]
    # every work event is booked to one scope or to none
    evs = R.leaves(R.in_window(td.ops[0], td.window))
    by = {}
    for e in evs:
        by[N.booked(e.scope)] = by.get(N.booked(e.scope), 0.0) + 1e3 * e.dur
    assert sum(by.values()) == pytest.approx(1e3 * sum(e.dur for e in evs))
    assert by["optimizer"] == pytest.approx(got["optimizer_ms_per_step"])
    assert by["layer_stack"] == pytest.approx(
        got["layer_stack_overhead_ms_per_step"])
    assert by["mlp"] == pytest.approx(got["mlp_ms_per_step"])
    assert by["lm_head"] <= got["head_loss_ms_per_step"] + 1e-9
    # the train step's scopes name what the model's left dark
    assert got["scope_named_share"] < 90 < 98 < got["train_step_named_share"]
    assert got["train_step_named_share"] <= 100.0
    if four:
        # the gathers of the layer stack's prefetch, all under `layer_stack`
        assert got["zero_gather_ms_per_step"] <= by.get("zero_gather", 0.0) \
            + got["head_loss_ms_per_step"]
        # the gradients' all-reduces sit in fusions the partitioner gave
        # the matmuls' paths: more time than every collective a name tells
        total, _ = R.collective_seconds(td)
        assert got["collective_in_fusion_ms_per_step"] > 1e3 * total > 0
        assert got["collective_in_fusion_ms_per_step"] < got["mlp_ms_per_step"]
        # a rate under the chip's 200 GB/s of interconnect
        assert 50 < got["all_gather_achieved_gbps"] < 200


@pytest.mark.parametrize("name", NEW)
def test_a_trace_of_the_parent_reads_nothing(name):
    """The parent's step has the model's six scopes and no other, and
    its program no `train.compile.collectives` span."""
    for kept, steps in (("train-seq4k-scopes.json", 1),
                        ("train-seq4k-zero3.json", 3)):
        obs = {"trace": load_recorded(RECORDED / kept), "traced_steps": steps,
               PS.MEMO + "_setup": [PS.PSpan("train.compile", 0.0, 4.0, 1, 0,
                                             {"step": 1})]}
        assert read(name, obs) is None
    assert read(name, {"trace": None, PS.MEMO + "_setup": None}) is None
    # this tree's program on one chip: the span is there, its sites empty
    one = with_manifest(
        {"trace": load_recorded(RECORDED / "train-seq4k-scopes.json"),
         "traced_steps": 1}, {"all_gather_n": 0, "sites": ""})
    assert read(name, one) is None


def test_the_readers_names_are_the_programs():
    from benchmarks.metrics import train_step_named_share as N
    from benchmarks.metrics import scope_named_share as old
    from deepspeed_tpu.utils import profiler

    assert N.STEP_SCOPES == profiler.TRAIN_STEP_SCOPES
    assert N.MODEL_SCOPES == profiler.MODEL_SCOPES
    assert set(old.SCOPES) < set(N.MODEL_SCOPES)
    assert N.booked("jit(f)/while/body/transpose(jvp(layer_stack))/while/body/"
                    "checkpoint/mlp/dot_general:") == "mlp"
    assert N.booked("jit(f)/jvp(layer_stack)/while/body/squeeze:") == "layer_stack"
    assert N.booked("jit(f)/optimizer/param_cast/convert_element_type:") == "optimizer"
    assert N.booked("jit(f)/while/body/closed_call:") is None
    assert N.booked("") is None


def _entry(name, unit, better, source, layer, cells):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "train_tokens_per_s_per_chip",
            "workloads": list(cells)}


ENTRIES = [
    _entry("optimizer_ms_per_step", "ms", "lower", "device_trace",
           "train entry", CELLS),
    _entry("param_cast_ms_per_step", "ms", "lower", "device_trace",
           "train entry", CELLS),
    _entry("grad_clip_ms_per_step", "ms", "lower", "device_trace",
           "train entry", CELLS),
    _entry("zero_gather_ms_per_step", "ms", "lower", "device_trace",
           "ZeRO / sharding", CELLS[1:]),
    _entry("layer_stack_overhead_ms_per_step", "ms", "lower", "device_trace",
           "model + flash", CELLS),
    _entry("train_step_named_share", "%", "higher", "device_trace",
           "train entry", CELLS),
    _entry("collective_in_fusion_ms_per_step", "ms", "lower", "device_trace",
           "ZeRO / sharding", CELLS[1:]),
    _entry("all_gather_achieved_gbps", "GB/s", "higher", "device_trace",
           "ZeRO / sharding", CELLS[1:]),
]


@pytest.mark.parametrize("entry", ENTRIES, ids=lambda e: e["name"])
def test_the_entry_a_benchmark_pr_appends(entry):
    """Each reader's entry in the accepted form (a layer BENCHMARK.json
    already names, the cells in which the recorded step gives its reader
    something to read), and BENCHMARK.json either lacks it, as this PR
    must leave it, or holds exactly it."""
    doc = harness.load_json(BENCH.parent / "BENCHMARK.json")
    assert [e["name"] for e in ENTRIES] == [
        n for n in NEW if n != "grad_reduce_ms_per_step"]
    assert (BENCH / "metrics" / f"{entry['name']}.py").is_file()
    assert entry["layer"] in {m["layer"] for m in doc["per_layer"]
                              if m["name"] not in NEW}
    moved = next(m for m in doc["end_to_end"] if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    for cell in CELLS:
        found = read(entry["name"], recorded(cell)) is not None
        assert found == (cell in entry["workloads"]), cell
    assert [m for m in doc["per_layer"]
            if m["name"] == entry["name"]] in ([], [entry])
