"""Preemption-tolerant elastic training loop: peer-redundant shards +
checkpoint-free resharding (docs/elasticity.md, docs/fault_tolerance.md).

`run_elastic` (agent.py) already restarts a world that lost a host —
but its workers resume from the last committed DISK checkpoint, paying
a full restore plus every step since the last save. This module is the
Bamboo/Gemini upgrade for the in-process half of that journey: the
trainer mirrors each rank's ZeRO shard slice to a neighbor every K
steps (resilience/redundancy.py), and when a preemption kills <= R
ranks it

  1. reconstructs the lost shards from surviving peers (host memory,
     no disk),
  2. rolls the world back to the last mirror boundary (<= K-1 steps),
  3. rebuilds the engine at an elastic-compatible surviving world size
     and lays the assembled state onto the new mesh
     (`reshard_state(old_mesh -> new_mesh)`),
  4. restores the dataloader position carried in the same snapshot, so
     the replay consumes exactly the samples the dead world would have
     — the committed (step -> sample ids) ledger is byte-identical to
     an uninterrupted run (no loss, no duplication).

`resize()` is the regrow half: when preempted capacity returns, the
live state reshards onto the bigger mesh with no rollback at all.
Model RNG needs no carrying — the engine derives every step's stream
from fold_in(seed, step).

The same trainer drives the deterministic training chaos lane
(`bench.py --train-chaos`, gated by scripts/ds_gate.py elastic): a FaultPlan
preempts a rank mid-run via the 'engine.step' fault point and the gate
asserts peer recovery with zero disk restores and a loss trajectory
matching the uninterrupted run.
"""

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..resilience.faults import (
    InjectedIOError,
    RankPreemptedError,
    fault_point,
)
from ..resilience.integrity import (
    AnomalyDetector,
    PersistentAnomalyError,
)
from ..resilience.redundancy import (
    PeerRedundantStore,
    UnrecoverableWorldError,
    assemble_state,
    export_rank_payloads,
    reshard_state,
    stage_payload_bytes,
)
from ..utils.logging import log_dist
from .agent import WorldDegradedError
from .elasticity import compute_elastic_config

__all__ = ["ElasticTrainer"]


class ElasticTrainer:
    """Drive a DeepSpeedTPUEngine through preemptions without disk.

    make_engine(world) must return a FRESH engine whose data-parallel
    world equals `world` (an elastic-batch config re-derives the same
    global batch at every compatible size, so the trajectory is
    comparable across resizes). `loader` needs the stateful-loader
    contract (runtime/dataloader.py): iteration, state_dict /
    load_state_dict, and last_batch_indices for the exactly-once
    ledger.

    elastic_block: the config's "elasticity" dict — consulted on
    shrink so the trainer lands on a world size every worker would
    accept instead of burning a generation discovering it.
    """

    def __init__(
        self,
        make_engine: Callable[[int], Any],
        world: int,
        loader,
        every_k_steps: int = 1,
        spare: int = 1,
        min_world: int = 1,
        elastic_block: Optional[Dict[str, Any]] = None,
        checkpoint_dir: Optional[str] = None,
        straggler_factor: float = 3.0,
        clock=time.perf_counter,
        guardian=None,
    ):
        self.make_engine = make_engine
        self.loader = loader
        self.every_k = int(every_k_steps)
        self.spare = int(spare)
        self.min_world = int(min_world)
        self.elastic_block = elastic_block
        self.checkpoint_dir = checkpoint_dir
        self.straggler_factor = float(straggler_factor)
        self.clock = clock

        self.world = int(world)
        self.generation = 0
        self.engine = self._launch(self.world)
        # pipeline-parallel engines mirror a GRID of logical ranks:
        # stage-major rank r = s*dp + d owns stage s's slice of ZeRO
        # shard d, so a preempted stage HOST recovers from peer mirrors
        # exactly like a ZeRO rank (docs/pipeline.md). The pipe degree
        # is a property of the model config — make_engine(world) keeps
        # it fixed while the dp world resizes.
        self.pipe_world = int(self.engine.mesh.shape.get("pipe", 1))
        self._past_mirror_integrity = 0  # failures of replaced stores
        self.stage_mirror_bytes = 0
        store_world = self.world * self.pipe_world
        self.store = PeerRedundantStore(
            store_world, spare=min(self.spare, store_world - 1))

        # -- SDC guardian (docs/fault_tolerance.md SDC section) --------
        # guardian: an AnomalyDetector, a dict of its kwargs (plus
        # 'persistent_trips'), True for defaults, or None to follow the
        # engine config's integrity block. A trip means the step's
        # loss/grad-norm readout is not to be trusted: the step is NOT
        # committed and the world rolls back to the last digest-
        # verified peer mirror.
        icfg = getattr(self.engine.config, "integrity", None)
        self.persistent_trips = int(
            getattr(icfg, "persistent_trips", 2) or 2)
        if guardian is None and icfg is not None and icfg.enabled:
            guardian = {"zscore": icfg.zscore, "window": icfg.window,
                        "warmup": icfg.warmup_steps,
                        "rel_floor": icfg.rel_floor,
                        "persistent_trips": icfg.persistent_trips}
        if guardian is True:
            guardian = {}
        if isinstance(guardian, dict):
            kw = dict(guardian)
            self.persistent_trips = int(
                kw.pop("persistent_trips", self.persistent_trips))
            guardian = AnomalyDetector(**kw)
        self.guardian: Optional[AnomalyDetector] = guardian or None
        self.anomalies_detected = 0
        self.integrity_rollbacks = 0
        self.skipped_steps = 0
        # rollbacks already spent answering an anomaly AT a given step
        # number — when the same step trips again after a verified
        # rollback + replay, the corruption is persistent (the mirror
        # itself is suspect) and the guardian escalates to disk
        self._anomaly_rollbacks_at: Dict[int, int] = {}

        # committed trajectory: step -> loss / (epoch, sample ids).
        # A rollback TRUNCATES these — what remains is exactly the
        # trajectory an uninterrupted run commits.
        self.history: Dict[int, float] = {}
        self.ledger: Dict[int, Tuple[int, Tuple[int, ...]]] = {}

        self.reconstructions = 0
        self.disk_restores = 0
        self.last_rollback_steps = 0
        self.last_reconstruction_s = 0.0
        self.straggler_steps = 0
        self.straggler_ranks: Dict[int, int] = {}
        self._step_times: List[float] = []
        self._compile_steps = 1  # steps to exempt from straggler stats
        self._data_iter = iter(loader)

        self.mirror()  # step-0 snapshot: recoverable from the first step

    def _replace_store(self, world: int) -> None:
        """Fresh PeerRedundantStore for a new world, carrying the old
        store's digest-mismatch count into the trainer-lifetime
        `mirror_integrity_failures` metric."""
        self._past_mirror_integrity += self.store.integrity_failures
        store_world = world * self.pipe_world
        self.store = PeerRedundantStore(
            store_world, spare=min(self.spare, store_world - 1))

    @property
    def mirror_integrity_failures(self) -> int:
        """Digest mismatches seen across every reconstruct this
        trainer ever ran (monitor.training_resilience_events)."""
        return self._past_mirror_integrity + self.store.integrity_failures

    # -- generation machinery -------------------------------------------
    def _launch(self, world: int):
        fault_point("elastic.generation", generation=self.generation,
                    world=world)
        engine = self.make_engine(world)
        if int(engine.dp_world_size) != world:
            raise ValueError(
                f"make_engine({world}) built a dp world of "
                f"{engine.dp_world_size}")
        return engine

    def mirror(self) -> None:
        """One redundancy round: slice the live state per rank, mirror
        to neighbors, and carry the dataloader position + slice dims so
        a recovery is self-describing (the dead engine's spec objects
        are not needed to reassemble)."""
        payloads, dims = export_rank_payloads(self.engine)
        shared = {"loader": self.loader.state_dict(), "dims": dims}
        self.store.snapshot(self.engine.global_steps, payloads, shared)
        if self.pipe_world > 1:
            self.stage_mirror_bytes += stage_payload_bytes(payloads, dims)
        from .. import comm

        # mirrors must be exchanged before the next step may commit —
        # rides the guarded control-plane barrier (comm.collective
        # fault point; single-process worlds no-op)
        comm.barrier("post-mirror")

    def _compatible_world(self, after_loss: int) -> int:
        """Largest elastic-compatible world <= after_loss (>= min_world)."""
        valid = None
        if self.elastic_block is not None:
            _, valid = compute_elastic_config(
                {"elasticity": self.elastic_block})
        w = after_loss
        while w >= self.min_world:
            if valid is None or w in valid:
                return w
            w -= 1
        raise UnrecoverableWorldError(
            [f"no elastic-compatible world in [{self.min_world}, "
             f"{after_loss}]"])

    def recover(self, lost_ranks: List[int]) -> None:
        """The preemption path: lose the ranks, reconstruct their
        shards from peers, reshard onto the surviving world, rewind the
        loader — all in host memory. Falls back to the newest verified
        disk checkpoint ONLY when more ranks died than the redundancy
        degree covers (counted in disk_restores; the chaos gate asserts
        the counter stays 0)."""
        t0 = self.clock()
        before = self.engine.global_steps
        self.store.lose(lost_ranks)
        # lost ranks are LOGICAL grid ranks (stage-major s*dp + d under
        # pipeline parallelism; plain ZeRO ranks otherwise). The dp
        # world shrinks by the number of distinct shard COLUMNS that
        # lost a host — the pipe degree is fixed by the model config,
        # so a dead stage host retires its whole dp column's capacity
        # while every surviving (stage, shard) slice still feeds the
        # reconstruction.
        dp_lost = {int(r) % self.world for r in set(lost_ranks)}
        new_world = self._compatible_world(self.world - len(dp_lost))
        try:
            step, payloads, shared = self.store.reconstruct()
        except UnrecoverableWorldError:
            if self.checkpoint_dir is None:
                raise
            self._disk_fallback(new_world)
            return
        full = assemble_state(payloads, shared["dims"])
        self.generation += 1
        self.world = new_world
        self.engine = self._launch(new_world)
        self._compile_steps = 1
        reshard_state(self.engine, full, global_steps=step)
        self.loader.load_state_dict(shared["loader"])
        self._data_iter = iter(self.loader)
        # truncate the committed trajectory to the mirror boundary —
        # the replayed steps recommit with identical sample order
        self.history = {s: v for s, v in self.history.items() if s <= step}
        self.ledger = {s: v for s, v in self.ledger.items() if s <= step}
        self._replace_store(new_world)
        self.mirror()
        self.reconstructions += 1
        self.last_rollback_steps = before - step
        self.last_reconstruction_s = self.clock() - t0
        log_dist(
            f"elastic-trainer: ranks {sorted(set(lost_ranks))} preempted "
            f"at step {before}; peer-reconstructed step {step} onto "
            f"world {new_world} (generation {self.generation}) in "
            f"{self.last_reconstruction_s * 1e3:.1f}ms, no disk restore",
            ranks=[0])

    def _disk_fallback(self, new_world: int) -> None:
        """Too many ranks died: the classic resume (load the newest
        verified tag) — the expensive path peer redundancy avoids."""
        self.generation += 1
        self.world = new_world
        self.engine = self._launch(new_world)
        self._compile_steps = 1
        self.engine.load_checkpoint(self.checkpoint_dir)
        self.disk_restores += 1
        self.engine.disk_restores = 0  # counted above; the metrics sum both
        step = self.engine.global_steps
        self.history = {s: v for s, v in self.history.items() if s <= step}
        self.ledger = {s: v for s, v in self.ledger.items() if s <= step}
        self._replace_store(new_world)
        self.mirror()

    def resize(self, new_world: int) -> None:
        """Live reshard (regrow when capacity returns, or a graceful
        shrink ahead of a planned preemption): current state, no
        rollback, no disk."""
        import jax

        if new_world == self.world:
            return
        host = {"params": jax.device_get(self.engine.state.params)}
        if self.engine.state.master is not None:
            host["master"] = jax.device_get(self.engine.state.master)
        if self.engine.state.opt is not None:
            host["opt"] = jax.device_get(self.engine.state.opt)
        step = self.engine.global_steps
        self.generation += 1
        self.world = int(new_world)
        self.engine = self._launch(self.world)
        self._compile_steps = 1
        reshard_state(self.engine, host, global_steps=step)
        self._replace_store(self.world)
        self.mirror()
        log_dist(
            f"elastic-trainer: resharded step {step} onto world "
            f"{self.world} (generation {self.generation})", ranks=[0])

    # -- the step loop ---------------------------------------------------
    def _fetch_batch(self, retries: int = 2):
        """Next batch with bounded retry on transient I/O (the
        dataloader.fetch fault point raises BEFORE the loader position
        advances, so a retry re-fetches the same batch)."""
        for attempt in range(retries + 1):
            try:
                batch = next(self._data_iter)
                return batch, (self.loader.last_batch_epoch,
                               tuple(self.loader.last_batch_indices))
            except (InjectedIOError, OSError):
                if attempt == retries:
                    raise
                # the raise closed the generator; re-enter at the (still
                # unadvanced) persisted position
                self._data_iter = iter(self.loader)
        raise AssertionError("unreachable")

    def step(self) -> Optional[Dict[str, float]]:
        """One committed global step, or None when nothing was
        committed: a preemption was absorbed (recover() rolled back),
        the compiled step skipped itself on a non-finite gradient
        (fp16 overflow / the integrity non-finite guard), or the SDC
        guardian vetoed the step (anomaly -> verified-mirror
        rollback). In every None case the caller just keeps
        stepping."""
        batch, sample_meta = self._fetch_batch()
        t0 = self.clock()
        try:
            metrics = self.engine.train_batch(batch)
        except RankPreemptedError as e:
            spec = getattr(e, "spec", None)
            lost = int(spec.value) if spec is not None else 0
            self.recover([lost])
            return None
        except WorldDegradedError as e:
            self.recover(list(e.failed_ranks))
            return None
        wall = (self.clock() - t0) + self.engine.drain_fault_delay()
        if metrics.get("skipped", 0):
            # the compiled step found a non-finite gradient and skipped
            # the update in-graph: device state (and state.step) are
            # untouched — re-sync the host counter so the next clean
            # step commits under the SAME step number, keeping the
            # (step -> sample ids) ledger gap-free. The batch is
            # consumed (reference overflow semantics); nothing is
            # committed, and the anomaly window never sees the
            # non-finite readout.
            self.engine.global_steps -= 1
            self.skipped_steps += 1
            if self.guardian is not None:
                self.guardian.note_skip()
            return None
        if self.guardian is not None:
            verdict = self.guardian.observe(
                {"loss": float(metrics["loss"]),
                 "grad_norm": float(metrics["grad_norm"])})
            if verdict != "ok":
                self.anomalies_detected += 1
                self._integrity_rollback(verdict)
                return None
        self._note_step_time(wall)
        step_no = self.engine.global_steps
        self.history[step_no] = float(metrics["loss"])
        self.ledger[step_no] = sample_meta
        if step_no % self.every_k == 0:
            self.mirror()
        return metrics

    def _integrity_rollback(self, verdict: str) -> None:
        """Answer a guardian trip: the just-run (uncommitted) step's
        readout or update is suspect. Roll the live state back to the
        last digest-VERIFIED peer mirror (a corrupted holder copy falls
        over to the next holder — resilience/redundancy.py), rewind the
        loader to the mirror boundary and replay; nothing the trip
        tainted ever reaches the history/ledger or a mirror round. A
        step that trips again after a verified rollback + replay is a
        persistent corruption (the snapshot itself, or a deterministic
        flip): escalate to the newest verified disk checkpoint, or
        raise PersistentAnomalyError without one."""
        before = self.engine.global_steps  # the vetoed step's number
        spent = self._anomaly_rollbacks_at.get(before, 0)
        if spent >= self.persistent_trips:
            if self.checkpoint_dir is None:
                raise PersistentAnomalyError(
                    f"step {before} anomalous ({verdict}) after {spent} "
                    "verified-mirror rollbacks and no checkpoint_dir to "
                    "escalate to")
            log_dist(
                f"sdc-guardian: step {before} still anomalous after "
                f"{spent} verified rollbacks; escalating to disk",
                ranks=[0])
            self._disk_fallback(self.world)
            return
        self._anomaly_rollbacks_at[before] = spent + 1
        try:
            step, payloads, shared = self.store.reconstruct()
        except UnrecoverableWorldError:
            if self.checkpoint_dir is None:
                raise
            self._disk_fallback(self.world)
            return
        full = assemble_state(payloads, shared["dims"])
        # same world, same mesh: lay the verified state straight onto
        # the live engine (no rebuild, no recompile) and rewind
        reshard_state(self.engine, full, global_steps=step)
        self.loader.load_state_dict(shared["loader"])
        self._data_iter = iter(self.loader)
        self.history = {s: v for s, v in self.history.items() if s <= step}
        self.ledger = {s: v for s, v in self.ledger.items() if s <= step}
        self.integrity_rollbacks += 1
        self.last_rollback_steps = before - step
        log_dist(
            f"sdc-guardian: {verdict} at step {before} "
            f"(loss/grad_norm={self.guardian.last_trip}); rolled back "
            f"to verified mirror at step {step} and replaying "
            f"({before - step} steps)", ranks=[0])

    def run(self, total_steps: int, regrow_at: Optional[int] = None,
            regrow_to: Optional[int] = None) -> Dict[int, float]:
        """Step until `total_steps` are committed. regrow_at/regrow_to
        model preempted capacity returning at a known step (the chaos
        lane's world-restore half)."""
        while self.engine.global_steps < total_steps:
            if (regrow_at is not None
                    and self.engine.global_steps >= regrow_at
                    and self.world < (regrow_to or self.world)):
                self.resize(regrow_to)
            self.step()
        return dict(self.history)

    # -- observability ---------------------------------------------------
    def _note_step_time(self, wall: float) -> None:
        """Straggler detection on THIS controller's step time (each
        controller of a multi-host world flags its own rank; the
        monitor aggregates the fleet view). The first step after every
        generation launch pays a compile — exempt, not a straggler."""
        import jax
        import numpy as np

        if self._compile_steps > 0:
            self._compile_steps -= 1
            return
        self._step_times.append(wall)
        prior = self._step_times[:-1]
        if len(prior) >= 3 and wall > self.straggler_factor * float(
                np.median(prior)):
            self.straggler_steps += 1
            rank = int(jax.process_index())
            self.straggler_ranks[rank] = self.straggler_ranks.get(rank, 0) + 1

    def resilience_metrics(self) -> Dict[str, float]:
        """Flat float metrics for the monitor feed
        (monitor.training_resilience_events)."""
        import numpy as np

        st = self._step_times
        out = {
            "generation": float(self.generation),
            "world": float(self.world),
            "redundancy_staleness_steps": float(
                self.store.staleness(self.engine.global_steps)),
            "mirrors_taken": float(self.store.mirrors_taken),
            "bytes_mirrored": float(self.store.bytes_mirrored),
            "reconstructions": float(self.reconstructions),
            "last_reconstruction_ms": round(
                self.last_reconstruction_s * 1e3, 3),
            "last_rollback_steps": float(self.last_rollback_steps),
            "disk_restores": float(
                self.disk_restores + self.engine.disk_restores),
            # SDC guardian feed (docs/fault_tolerance.md SDC section)
            "anomalies_detected": float(self.anomalies_detected),
            "integrity_rollbacks": float(self.integrity_rollbacks),
            "skipped_steps": float(self.skipped_steps),
            "mirror_integrity_failures": float(
                self.mirror_integrity_failures),
            "straggler_steps": float(self.straggler_steps),
            "step_time_p50_ms": round(
                float(np.median(st)) * 1e3, 3) if st else 0.0,
            "step_time_max_ms": round(max(st) * 1e3, 3) if st else 0.0,
        }
        for r, n in sorted(self.straggler_ranks.items()):
            out[f"rank{r}/straggler_flags"] = float(n)
        if self.pipe_world > 1:
            # pipeline feed: the stage-mirror byte counter plus the
            # grid geometry (the bubble/skew half of the pipeline feed
            # lives in monitor.training_events, which reads the engine)
            out["pipe_world"] = float(self.pipe_world)
            out["stage_mirror_bytes"] = float(self.stage_mirror_bytes)
        return out
