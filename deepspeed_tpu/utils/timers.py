"""Wall-clock + throughput timers.

TPU-native analog of the reference timer utilities
(ref: deepspeed/utils/timer.py — SynchronizedWallClockTimer:43,
ThroughputTimer:198). Device sync is `jax.block_until_ready` on a token
array instead of CUDA events; everything under jit is async-dispatched,
so a timer stop optionally synchronizes the device stream first.
"""

import time
from typing import Dict, List, Optional

import jax

from .logging import logger

BATCH_TIMER = "train_batch"


class _Timer:
    def __init__(self, name: str):
        self.name = name
        self._start: Optional[float] = None
        self._elapsed = 0.0
        self._record: List[float] = []
        self.started = False

    def start(self):
        if self.started:
            return
        self._start = time.perf_counter()
        self.started = True

    def stop(self, record: bool = True, sync: bool = False, wait_for=None):
        """`wait_for`: array(s) produced by the timed computation — the only
        reliable device fence under async dispatch (effects_barrier drains
        effects, not pure compute). Callers that read results anyway (e.g.
        metrics→host floats) can skip it."""
        if not self.started:
            return
        if wait_for is not None:
            jax.block_until_ready(wait_for)
        elif sync:
            jax.effects_barrier()
        dt = time.perf_counter() - self._start
        self._elapsed += dt
        if record:
            self._record.append(dt)
        self.started = False

    def add(self, seconds: float) -> None:
        """Book an interval the caller measured itself (the train
        step's phase stamps: one clock reading serves both)."""
        self._elapsed += seconds
        self._record.append(seconds)

    def elapsed(self, reset: bool = True) -> float:
        out = self._elapsed
        if reset:
            self._elapsed = 0.0
        return out

    def mean(self) -> float:
        return sum(self._record) / max(len(self._record), 1)

    def reset(self):
        self._start = None
        self._elapsed = 0.0
        self._record = []
        self.started = False


class SynchronizedWallClockTimer:
    """Named timer registry (ref: deepspeed/utils/timer.py:43)."""

    def __init__(self):
        self.timers: Dict[str, _Timer] = {}

    def __call__(self, name: str) -> _Timer:
        if name not in self.timers:
            self.timers[name] = _Timer(name)
        return self.timers[name]

    def log(self, names: List[str], normalizer: float = 1.0, reset: bool = True):
        parts = []
        for name in names:
            if name in self.timers:
                ms = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                parts.append(f"{name}: {ms:.2f}ms")
        if parts:
            logger.info("time (ms) | " + " | ".join(parts))

    def get_mean(self, names: List[str]) -> Dict[str, float]:
        return {n: self.timers[n].mean() for n in names if n in self.timers}


class ThroughputTimer:
    """Samples/sec estimator (ref: deepspeed/utils/timer.py:198). It has
    no clock of its own: the engine books each step's seconds from the
    same phase stamps BATCH_TIMER and the `train.*` spans are made of
    (docs/tracing.md), so the log's `samples/s` and `time: step=` lines
    cannot disagree."""

    def __init__(self, batch_size: int, start_step: int = 2):
        self.batch_size = max(batch_size, 1)
        self.start_step = start_step
        self.global_step_count = 0
        self.total_elapsed_time = 0.0

    def add(self, seconds: float) -> None:
        """Book one global step that took `seconds` (the first
        `start_step` steps, which compile, are counted but not timed)."""
        self.global_step_count += 1
        if self.global_step_count > self.start_step:
            self.total_elapsed_time += seconds

    @property
    def avg_samples_per_sec(self) -> float:
        steps = max(self.global_step_count - self.start_step, 1)
        if self.total_elapsed_time == 0:
            return 0.0
        return self.batch_size * steps / self.total_elapsed_time


def see_memory_usage(message: str, force: bool = False):
    """Device memory telemetry (ref: deepspeed/utils engine-wide see_memory_usage)."""
    if not force:
        return
    try:
        stats = jax.local_devices()[0].memory_stats()
        if stats:
            in_use = stats.get("bytes_in_use", 0) / 2**30
            limit = stats.get("bytes_limit", 0) / 2**30
            logger.info(f"{message} | device mem: {in_use:.2f}GB in use / {limit:.2f}GB limit")
            return
    except Exception:
        pass
    logger.info(f"{message} | device memory stats unavailable on this platform")
