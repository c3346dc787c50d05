"""What every cell shares: finding a cell's files by the names in
BENCHMARK.json, the device gate, the compile listener, the profiler
window, the per-layer metric readers and the one result line.

Everything that belongs to ONE configuration, traffic mix, runner kind
or per-layer metric is a file found by name:

  benchmarks/configs/<configuration>.json   (path from BENCHMARK.json)
  benchmarks/traffic/<traffic>.json         parameters of one mix
  benchmarks/runners/<kind>.py              `run(ctx) -> Outcome`
  benchmarks/metrics/<metric>.py            `read(obs) -> float | None`

so a later PR adds a cell, a configuration or a metric by adding files
and one entry to BENCHMARK.json; nothing here names any of them.
"""

import dataclasses
import importlib.util
import json
import pathlib
import sys
import time
from typing import Any, Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoAcceleratorError(RuntimeError):
    """JAX's backend is not a TPU, or holds fewer chips than the cell
    asks for. The command exits non-zero and prints no result line."""


# -- files by name ---------------------------------------------------------

def load_json(path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import one benchmark file by path (runner, metric reader,
    reference): the name in a data file is the file's stem."""
    if not path.is_file():
        raise FileNotFoundError(f"the benchmark has no file {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix("").parts),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of BENCHMARK.json `workloads`, with its files read."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # the configuration file, as run
    traffic_name: str
    traffic: Dict[str, Any]         # the mix's parameters
    end_to_end: List[Dict[str, Any]]  # metric entries this cell reports
    per_layer: List[Dict[str, Any]]
    bench_dir: pathlib.Path = BENCH_DIR


def _applies(metric: Dict[str, Any], cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell `name` of <root>/BENCHMARK.json. The traffic file is
    traffic/<traffic>.json under the first of `paths`."""
    bench = load_json(root / "BENCHMARK.json")
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json; it has "
            f"{sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    bench_dir = root / bench["paths"][0]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    e2e_names = {m["name"] for m in e2e}
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=load_json(root / cfg_entry["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        end_to_end=e2e,
        # a per-layer metric is reported only where the metric it moves is
        per_layer=[m for m in bench["per_layer"]
                   if _applies(m, name) and m["moves"] in e2e_names],
        bench_dir=bench_dir)


def peaks_for(device_kind: str, bench_dir: pathlib.Path = BENCH_DIR) -> Dict[str, float]:
    """Published peaks of the chip. An unknown kind is an error, never
    a default."""
    table = load_json(bench_dir / "peaks.json")
    if device_kind not in table:
        raise KeyError(
            f"peaks.json has no entry for device kind {device_kind!r} "
            f"(it has {sorted(k for k in table if not k.startswith('_'))})")
    return table[device_kind]


# -- device ------------------------------------------------------------------

def require_tpu(chips: int):
    """The devices the cell runs on. No fallback: a measurement path
    that finds no chip fails."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAcceleratorError(
            f"this benchmark needs a TPU; JAX's backend is "
            f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAcceleratorError(
            f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's own cache switch (honours JAX_COMPILATION_CACHE_DIR,
    else <checkout>/.jax_cache), caching every program however small, so
    that a second run of a cell compiles nothing. Returns the directory."""
    import jax

    from deepspeed_tpu.platform.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def device_report(devices) -> Dict[str, Any]:
    import jax

    peak = 0
    for d in devices:
        peak = max(peak, int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def hbm_in_use_bytes(devices) -> int:
    """What the fullest chip holds NOW (`bytes_in_use`): the weights and
    the pool, or the training state. The process-lifetime peak in the
    result line's `device` is a set-up transient in every cell so far
    (PERF.md §4) and does not move with what the cell holds."""
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0))
               for d in devices)


class CompileCounter:
    """Programs this process had to build, counted from jax.monitoring
    (the listener chip_smoke.py's _Report uses). The event fires once
    per program new to the process, whether XLA compiles it or the
    persistent cache supplies it (then in milliseconds; seen on the
    chip, PR 22: 40 events, 0.8 s, on a warm cache): `n` inside the
    window must be 0 either way, `seconds` is what they cost."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.seconds += duration


# -- what a runner hands back ----------------------------------------------

@dataclasses.dataclass
class Outcome:
    """A runner's result. `end_to_end` holds every end-to-end value the
    runner can compute (the harness keeps those the cell reports);
    `obs` is what per-layer readers read: counters, the harness's logs,
    host-clock samples, and (traced runs) the reduced trace."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    obs: Dict[str, Any]
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RunContext:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: Any
    t_process_start: float          # perf_counter() at process start
    compiles: CompileCounter
    out_dir: pathlib.Path
    log: Callable[[str], None]
    peaks: Optional[Dict[str, float]] = None


def read_per_layer(cell: Cell, obs: Dict[str, Any],
                   log: Callable[[str], None]) -> Dict[str, Dict[str, Any]]:
    """Run the cell's per-layer readers. A reader that finds nothing to
    read returns None and its metric is left out of the line."""
    out = {}
    for m in cell.per_layer:
        reader = load_module(cell.bench_dir / "metrics" / f"{m['name']}.py")
        value = reader.read(obs)
        if value is None:
            log(f"per-layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(outcome: Outcome, metrics: Dict[str, Dict[str, Any]],
                device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    """The contract's last line: exactly these keys."""
    line = {"correct": bool(outcome.correct),
            "attempted": int(outcome.attempted),
            "failed": int(outcome.failed),
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             devices, t_process_start: float,
             log: Callable[[str], None] = print,
             out_root: Optional[pathlib.Path] = None) -> str:
    """One cell, one process: run the cell's runner kind, reduce, and
    return the result line. `devices` come from require_tpu() on the
    command; tests pass CPU devices with kernels in interpret mode."""
    from benchmarks.trace import reduce as trace_reduce

    out_dir = (out_root or ROOT / "chiprun_out") / "bench" / cell.name
    out_dir.mkdir(parents=True, exist_ok=True)
    ctx = RunContext(
        cell=cell, seed=seed, seconds=seconds, trace=trace, devices=devices,
        t_process_start=t_process_start, compiles=CompileCounter(),
        out_dir=out_dir, log=log,
        # a CPU rehearsal has no peaks: its readers return nothing
        peaks=(peaks_for(devices[0].device_kind, cell.bench_dir)
               if devices[0].platform == "tpu" else None))
    runner = load_module(
        cell.bench_dir / "runners" / f"{cell.traffic['runner']}.py")
    outcome = runner.run(ctx)
    device = device_report(devices)
    outcome.obs["peaks"] = ctx.peaks
    log(f"[bench] notes: {json.dumps(outcome.notes, default=str)}")
    breakdown = None
    if trace:
        td = outcome.obs.get("trace")
        if td is None:
            raise RuntimeError("traced run produced no trace")
        device["busy_s"] = td.busy_s
        device["window_s"] = td.window_s
        breakdown = trace_reduce.breakdown(td)
        metrics = read_per_layer(cell, outcome.obs, log)
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in outcome.end_to_end:
                raise RuntimeError(
                    f"runner {cell.traffic['runner']!r} did not produce "
                    f"end-to-end metric {m['name']!r}")
            metrics[m["name"]] = {
                "value": float(outcome.end_to_end[m["name"]]),
                "unit": m["unit"]}
    line = result_line(outcome, metrics, device, breakdown)
    with open(out_dir / f"seed{seed}_trace{int(trace)}.json", "w") as f:
        json.dump({"line": json.loads(line), "notes": outcome.notes,
                   "end_to_end_all": outcome.end_to_end}, f, default=str)
    # each number compared beside its limit, as the last lines of the
    # standard error too: of a run that is not correct the driver's
    # record keeps the end of that
    for msg in outcome.notes.get("compared", ()):
        print(msg, file=sys.stderr, flush=True)
    return line


def now() -> float:
    """The one clock of the benchmark (the scheduler stamps requests
    with the same one)."""
    return time.perf_counter()
