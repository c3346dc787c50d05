"""Start and stop the JAX profiler around a few steady iterations and
hand back the reduced trace. Only traced runs (--trace 1) come here.

The Python tracer is off: it records every interpreter call, which
slows exactly the host loop a serving cell measures. What remains on
the host plane is the benchmark's own `bench.*` TraceAnnotation spans
and the runtime's events.
"""

import glob
import json
import os
import pathlib
import shutil

from benchmarks.trace import reduce as R

# how much of a trace is kept as JSON beside the result (the .xplane.pb
# itself is deleted: what chiprun brings back is capped)
KEEP_EVENTS = 4000


class Capture:
    def __init__(self, out_dir: pathlib.Path):
        self.dir = pathlib.Path(out_dir) / "profile"
        self.out_dir = pathlib.Path(out_dir)
        self._window = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self._window = jax.profiler.TraceAnnotation(R.WINDOW_SPAN)
        self._window.__enter__()

    def stop(self) -> R.TraceData:
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        paths = sorted(glob.glob(str(self.dir / "plugins/profile/*/*.xplane.pb")),
                       key=os.path.getmtime)
        if not paths:
            raise RuntimeError(f"the profiler wrote no .xplane.pb under {self.dir}")
        td = R.load_xplane(paths[-1])
        self._keep(td)
        shutil.rmtree(self.dir, ignore_errors=True)
        return td

    def _keep(self, td: R.TraceData) -> None:
        def rows(evs):
            return [[e.name, e.start, e.dur] + ([e.scope] if e.scope else [])
                    for e in evs[:KEEP_EVENTS]]

        with open(self.out_dir / "trace_events.json", "w") as f:
            json.dump({
                "window": list(td.window),
                "ops": {str(d): rows(R.in_window(evs, td.window))
                        for d, evs in td.ops.items() if d == 0},
                "async_ops": {str(d): rows(R.in_window(evs, td.window))
                              for d, evs in td.async_ops.items() if d == 0},
                "modules": {str(d): rows(R.in_window(evs, td.window))
                            for d, evs in td.modules.items() if d == 0},
                "spans": rows(td.spans)}, f)


def load_recorded(path) -> R.TraceData:
    """A trace kept as JSON by `_keep` (benchmarks/trace/recorded/)."""
    with open(path) as f:
        d = json.load(f)

    def evs(rows):   # [name, start, duration] and, since PR 26, the scope
        return [R.Event(*row) for row in rows]

    td = R.from_events({int(k): evs(v) for k, v in d["ops"].items()},
                       {int(k): evs(v) for k, v in d["modules"].items()},
                       evs(d["spans"]))
    td.async_ops = {int(k): evs(v) for k, v in d.get("async_ops", {}).items()}
    td.window = tuple(d["window"])
    return td
