"""Config-space autotuner.

TPU-native redesign of the reference autotuner
(ref: deepspeed/autotuning/autotuner.py Autotuner:42, tune():404 — which
launches short profiling JOBS per candidate config through the launcher,
writes per-experiment result dirs, and picks the best metric;
model-info profile run :663, micro-batch search :741-851).

On TPU a "job" collapses into an in-process build+compile+measure: each
candidate config constructs an engine over the same mesh, runs a few
timed steps (compile excluded), and is scored by throughput. What the
reference pays in process restarts we pay in recompiles — seconds, not
minutes. Memory-infeasible candidates surface as XLA RESOURCE_EXHAUSTED
and are skipped, exactly like the reference's OOM-pruned experiments.

The search space mirrors the reference's fast mode: ZeRO stages ×
micro-batch sizes (doubling from 1 until failure or the cap), GAS fixed
by the batch triangle.
"""

import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..utils.logging import log_dist, logger


class Autotuner:
    def __init__(
        self,
        base_config: Dict[str, Any],
        loss_fn: Callable,
        param_init_fn: Callable,
        param_logical_specs: Any = None,
        make_batch: Optional[Callable[[int], Any]] = None,
        results_dir: Optional[str] = None,
        make_pipelined: Optional[Callable[[int, int], Dict[str, Any]]] = None,
    ):
        """make_batch(global_batch_size) -> host batch pytree for one step.

        make_pipelined(pipe_stages, interleave) -> {'loss_fn',
        'param_init_fn', 'param_logical_specs'}: the pipeline-parallel
        variant of the model for candidates carrying a 'pipe_stages'
        axis (the layer stack partitions [P, L/P] / [v, P, lc] at init,
        so the flat loss/init cannot serve those candidates — e.g.
        models.transformer.make_pipelined_loss_fn over a
        pipeline_stages=P config). Without it, pipe candidates score
        infeasible instead of raising mid-search."""
        self.base_config = dict(base_config)
        at_block = self.base_config.pop("autotuning", {}) or {}
        self.metric = at_block.get("metric", "throughput")
        self.fast = at_block.get("fast", True)
        self.results_dir = results_dir or at_block.get(
            "results_dir", "autotuning_results"
        )
        self.loss_fn = loss_fn
        self.param_init_fn = param_init_fn
        self.param_logical_specs = param_logical_specs
        self.make_batch = make_batch
        self.make_pipelined = make_pipelined
        self.results: List[Dict[str, Any]] = []

    # ------------------------------------------------------------------
    def model_info(self) -> Dict[str, Any]:
        """Param count + per-step flops of the base config (ref:
        autotuner.py model-info profile run :663 — there a whole job,
        here eval_shape + one compile's cost analysis)."""
        import jax
        import numpy as np

        rng = jax.random.PRNGKey(0)
        shapes = jax.eval_shape(self.param_init_fn, rng)
        n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(shapes))
        return {"num_params": n_params}

    def _build_engine(self, config: Dict[str, Any],
                      cand: Optional[Dict[str, Any]] = None):
        """Construct the candidate's engine: the flat model, or (when
        the candidate carries pipe_stages > 1) the pipelined variant
        from the make_pipelined hook — pipeline depth is one more
        search dimension, not a separate tuner."""
        import deepspeed_tpu as ds

        P = int((cand or {}).get("pipe_stages") or 1)
        V = int((cand or {}).get("interleave") or 1)
        if P > 1:
            if self.make_pipelined is None:
                raise ValueError(
                    "candidate has pipe_stages > 1 but the Autotuner "
                    "was built without make_pipelined")
            parts = self.make_pipelined(P, V)
            return ds.initialize(
                config,
                loss_fn=parts["loss_fn"],
                param_init_fn=parts["param_init_fn"],
                param_logical_specs=parts.get("param_logical_specs"),
                pipelined=True,
                pipeline_virtual_stages=V,
            )
        return ds.initialize(
            config,
            loss_fn=self.loss_fn,
            param_init_fn=self.param_init_fn,
            param_logical_specs=self.param_logical_specs,
        )

    def _measure(self, config: Dict[str, Any], steps: int,
                 cand: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        t_build = time.perf_counter()
        engine = self._build_engine(config, cand)
        batch = self.make_batch(engine.config.train_batch_size)
        engine.train_batch(batch)  # compile + warmup
        compile_s = time.perf_counter() - t_build
        t0 = time.perf_counter()
        for _ in range(steps):
            engine.train_batch(batch)
        dt = (time.perf_counter() - t0) / steps
        return {
            "step_time_s": dt,
            "samples_per_sec": engine.config.train_batch_size / dt,
            "compile_s": compile_s,
        }

    # ------------------------------------------------------------------
    # candidate space + application
    # ------------------------------------------------------------------
    def _apply_candidate(self, cand: Dict[str, Any]) -> Dict[str, Any]:
        cfg = json.loads(json.dumps(self.base_config))
        if cand.get("zero_stage") is not None:
            cfg.setdefault("zero_optimization", {})["stage"] = \
                cand["zero_stage"]
        if cand.get("micro_batch_size") is not None:
            cfg["train_micro_batch_size_per_gpu"] = cand["micro_batch_size"]
            cfg.pop("train_batch_size", None)
        if cand.get("mesh") is not None:
            cfg["mesh"] = dict(cand["mesh"])
        if cand.get("gas") is not None:
            cfg["gradient_accumulation_steps"] = cand["gas"]
        if cand.get("remat") is not None:
            cfg["activation_checkpointing"] = {
                "partition_activations": False,
                "policy": cand["remat"],
            }
        if cand.get("offload_optimizer") is not None:
            cfg.setdefault("zero_optimization", {})["offload_optimizer"] = {
                "device": cand["offload_optimizer"]
            }
        # comm/compute overlap knobs (runtime/overlap.py, docs/overlap.md):
        # overlap=False builds the serialized twin (collectives scored at
        # full wire time), prefetch_depth 0 / >= 1 turns the ZeRO-3
        # in-body layer gather off / on (the depth itself is unused),
        # bucket_mb is the reduce-scatter launch granularity.
        if cand.get("overlap") is not None:
            cfg.setdefault("zero_optimization", {})["overlap_comm"] = \
                bool(cand["overlap"])
        if cand.get("prefetch_depth") is not None:
            cfg.setdefault("zero_optimization", {})["prefetch_depth"] = \
                int(cand["prefetch_depth"])
        if cand.get("bucket_mb") is not None:
            cfg.setdefault("zero_optimization", {})["bucket_mb"] = \
                float(cand["bucket_mb"])
        if int(cand.get("pipe_stages") or 1) > 1:
            # pipeline depth axis: carve a 'pipe' mesh dim; without an
            # explicit candidate mesh the data axis absorbs the rest of
            # the devices (wildcard). The engine is built through the
            # make_pipelined hook (see _build_engine).
            mesh = dict(cfg.get("mesh") or {})
            mesh.setdefault("pipe", int(cand["pipe_stages"]))
            if "data" not in mesh:
                mesh["data"] = -1
            cfg["mesh"] = mesh
        return cfg

    # ------------------------------------------------------------------
    # AOT scoring (analysis/schedule.py S009): rank configs by the
    # critical-path step-time projection of their COMPILED step — no
    # step executes. The reference pays a profiling job per candidate;
    # the trial-execution path above pays a compile + timed steps; this
    # pays a compile only, so the whole (mesh, microbatch x gas, zero
    # stage) space is scoreable from the 8-device CPU mesh and only the
    # top-k candidates ever run.
    # ------------------------------------------------------------------
    def _aot_key(self, cand: Dict[str, Any]) -> str:
        """Canonical tie-break key: the top-k trial list must be
        deterministic across runs regardless of dict ordering."""
        return json.dumps(
            {k: v for k, v in cand.items() if not k.startswith("aot_")},
            sort_keys=True, default=str)

    def aot_score(self, cand: Dict[str, Any],
                  target_devices: Optional[int] = None,
                  hbm_budget_bytes: Optional[int] = None,
                  ) -> Dict[str, Any]:
        """Statically score ONE candidate: compile its train step
        (engine.sanitize — compile-time only) and read the S009
        step-time projection off the attached CostReport. Returns the
        candidate extended with aot_ok / aot_samples_per_sec /
        aot_step_time_s / aot_exposed_comm_s (or aot_error).
        Infeasible candidates — failed compile, or an S004
        over-budget finding at the target — score 0."""
        exp = dict(cand)
        try:
            engine = self._build_engine(self._apply_candidate(cand), cand)
            batch = self.make_batch(engine.config.train_batch_size)
            rep = engine.sanitize(
                batch, hbm_budget_bytes=hbm_budget_bytes,
                target_devices=target_devices)
            cost = rep.cost
            over_budget = any(
                f.rule == "S004" and f.severity == "error"
                for f in rep.findings)
            if cost is None or cost.step_time_s <= 0:
                exp.update({"aot_ok": False, "aot_samples_per_sec": 0.0,
                            "aot_error": "no cost artifacts on this "
                                         "backend"})
            else:
                exp.update({
                    "aot_ok": not over_budget,
                    "aot_step_time_s": cost.step_time_s,
                    "aot_exposed_comm_s": cost.exposed_comm_s,
                    "aot_peak_hbm_bytes": cost.peak_hbm_bytes,
                    "aot_samples_per_sec": (
                        0.0 if over_budget else
                        engine.config.train_batch_size
                        / cost.step_time_s),
                })
                if over_budget:
                    exp["aot_error"] = "S004 over budget at target"
        except Exception as e:  # infeasible shape / bad combo
            exp.update({"aot_ok": False, "aot_samples_per_sec": 0.0,
                        "aot_error": f"{type(e).__name__}: {e}"})
        return exp

    def aot_rank(self, candidates: Sequence[Dict[str, Any]],
                 target_devices: Optional[int] = None,
                 hbm_budget_bytes: Optional[int] = None,
                 ) -> List[Dict[str, Any]]:
        """Score every candidate AOT and return them ranked: feasible
        candidates by descending projected samples/sec, ties and
        infeasibles in canonical-key order (deterministic)."""
        scored = [self.aot_score(c, target_devices=target_devices,
                                 hbm_budget_bytes=hbm_budget_bytes)
                  for c in candidates]
        scored.sort(key=lambda e: (-e.get("aot_samples_per_sec", 0.0),
                                   self._aot_key(e)))
        for e in scored:
            log_dist(f"autotune aot: {e}", ranks=[0])
        return scored

    def tune_aot(
        self,
        candidates: Optional[Sequence[Dict[str, Any]]] = None,
        zero_stages: Sequence[int] = (2, 3),
        micro_batch_sizes: Sequence[int] = (1, 2),
        mesh_shapes: Optional[Sequence[Dict[str, int]]] = None,
        gas_values: Optional[Sequence[int]] = None,
        pipe_configs: Optional[Sequence[Tuple[int, int]]] = None,
        prefetch_depths: Optional[Sequence[int]] = None,
        bucket_mbs: Optional[Sequence[float]] = None,
        top_k: int = 3,
        steps: int = 3,
        trial: bool = True,
        target_devices: Optional[int] = None,
        hbm_budget_bytes: Optional[int] = None,
    ) -> Dict[str, Any]:
        """AOT-first search: enumerate (zero stage x micro-batch x mesh
        x gas x pipeline depth) candidates (or take them verbatim),
        rank them all by the S009 projection without executing a step,
        then trial-execute only the top_k (trial=False skips even that
        and returns the best projected config). Returns the tuned
        config dict; the ranked ledger (including infeasibles) lands in
        <results_dir>/exps.jsonl like every other strategy.

        pipe_configs: (pipe_stages P, interleave V) pairs — pipeline
        depth as one more search dimension (docs/pipeline.md; needs
        the make_pipelined hook for P > 1 entries). For pipelined
        candidates the gas axis IS the microbatch count M of the
        (P, V, M) schedule triple, so the three pipeline knobs are all
        searchable; candidates are scored by the same S009 projection
        (the interleave bubble saving shows up as fewer wasted-FLOP
        scan steps) and pruned by S004 exactly like every other axis.

        prefetch_depths / bucket_mbs: the comm/compute-overlap knobs
        (runtime/overlap.py, docs/overlap.md) as two more axes —
        prefetch_depth 0 / >= 1 turns the ZeRO-3 in-body layer gather
        off / on, bucket_mb is the reduce-scatter launch granularity. Both change
        WHERE collectives land in the compiled schedule, and the S009
        projection's slack-credit model prices exactly that, so the
        overlapped candidate outranks its serialized twin without
        either running a step (tests/test_overlap.py pins this
        ordering)."""
        if self.make_batch is None:
            raise ValueError("Autotuner needs make_batch to generate step data")
        if candidates is None:
            meshes = list(mesh_shapes) if mesh_shapes else [None]
            gases = list(gas_values) if gas_values else [None]
            pipes = list(pipe_configs) if pipe_configs else [(1, 1)]
            depths = list(prefetch_depths) if prefetch_depths else [None]
            buckets = list(bucket_mbs) if bucket_mbs else [None]
            candidates = [
                {"zero_stage": st, "micro_batch_size": mb,
                 **({"mesh": m} if m is not None else {}),
                 **({"gas": g} if g is not None else {}),
                 **({"pipe_stages": int(p), "interleave": int(v)}
                    if int(p) > 1 else {}),
                 **({"prefetch_depth": int(d)} if d is not None else {}),
                 **({"bucket_mb": float(bk)} if bk is not None else {})}
                for st in zero_stages for mb in micro_batch_sizes
                for m in meshes for g in gases for (p, v) in pipes
                for d in depths for bk in buckets
            ]
        ranked = self.aot_rank(candidates, target_devices=target_devices,
                               hbm_budget_bytes=hbm_budget_bytes)
        self.results.extend({"phase": "aot", **e} for e in ranked)
        top = [e for e in ranked if e.get("aot_ok")][: max(1, top_k)]
        if not top:
            self._flush_results()
            raise RuntimeError(
                f"AOT scoring found no feasible config; see "
                f"{self.results_dir}")
        if not trial:
            self._flush_results()
            best = top[0]
            log_dist(
                f"autotune aot best (no trial): {self._aot_key(best)} "
                f"({best['aot_samples_per_sec']:.1f} projected "
                "samples/s)", ranks=[0])
            return self._apply_candidate(best)
        best = None
        for cand in top:
            exp = self._run_exp(
                {k: v for k, v in cand.items()
                 if not k.startswith("aot_")}, steps)
            if exp.get("ok") and (
                    best is None
                    or exp["samples_per_sec"] > best["samples_per_sec"]):
                best = dict(exp)
        self._flush_results()
        if best is None:
            raise RuntimeError(
                f"every AOT top-{top_k} candidate failed trial "
                f"execution; see {self.results_dir}")
        log_dist(
            f"autotune aot best: {self._aot_key(best)} "
            f"({best['samples_per_sec']:.1f} samples/s)", ranks=[0])
        return self._apply_candidate(best)

    def _run_exp(self, cand: Dict[str, Any], steps: int) -> Dict[str, Any]:
        exp = dict(cand)
        try:
            exp.update(self._measure(self._apply_candidate(cand), steps,
                                     cand=cand))
            exp["ok"] = True
        except Exception as e:  # OOM / infeasible shape / bad combo
            exp.update({"ok": False, "error": f"{type(e).__name__}: {e}"})
        self.results.append(exp)
        log_dist(f"autotune exp: {exp}", ranks=[0])
        return exp

    def _flush_results(self):
        os.makedirs(self.results_dir, exist_ok=True)
        with open(os.path.join(self.results_dir, "exps.jsonl"), "w") as f:
            for r in self.results:
                f.write(json.dumps(r) + "\n")

    def tune(
        self,
        zero_stages: Sequence[int] = (0, 1, 2, 3),
        micro_batch_sizes: Optional[Sequence[int]] = None,
        steps: int = 3,
        max_micro_batch: int = 64,
        strategy: str = "fast",
        remat_policies: Optional[Sequence[Optional[str]]] = None,
        offload_devices: Optional[Sequence[Optional[str]]] = None,
        num_trials: Optional[int] = None,
        seed: int = 0,
    ) -> Dict[str, Any]:
        """Search the config space → best config dict (ref: autotuner.py
        tune:404 + autotuning/tuner/base_tuner.py strategy classes).

        strategy:
          'fast'   — the reference's fast mode: zero-stage × micro-batch
                     doubling with an OOM wall break (remat/offload axes
                     excluded to keep the sweep short)
          'grid'   — GridSearchTuner: every combination, including the
                     TPU-relevant remat and offload_optimizer axes
          'random' — RandomTuner: num_trials uniform samples of the grid
          'model'  — ModelBasedTuner: half the budget explores at random,
                     then an additive performance model (axis-wise mean
                     deviations over measured points) ranks the rest and
                     the top predictions are measured

        remat_policies: values for activation_checkpointing.policy
        (None = leave base config; e.g. ('none','dots','full')).
        offload_devices: zero_optimization.offload_optimizer.device
        values (None = leave base; e.g. (None,'cpu')) — the knobs that
        actually matter on TPU (HBM is the binding constraint).

        Results (including failures) land in <results_dir>/exps.jsonl —
        the per-experiment record the reference writes per exp dir.
        """
        if self.make_batch is None:
            raise ValueError("Autotuner needs make_batch to generate step data")
        if micro_batch_sizes is None:
            mbs: List[int] = []
            m = 1
            while m <= max_micro_batch:
                mbs.append(m)
                m *= 2
        else:
            mbs = list(micro_batch_sizes)
        remats = list(remat_policies) if remat_policies else [None]
        offloads = list(offload_devices) if offload_devices else [None]

        best = None

        def consider(exp):
            nonlocal best
            if exp.get("ok") and (
                best is None or exp["samples_per_sec"] > best["samples_per_sec"]
            ):
                best = dict(exp)

        if strategy == "fast":
            for stage in zero_stages:
                stage_failed = 0
                for mb in mbs:
                    exp = self._run_exp(
                        {"zero_stage": stage, "micro_batch_size": mb}, steps)
                    consider(exp)
                    if self.fast and not exp.get("ok"):
                        stage_failed += 1
                        if stage_failed >= 2:
                            break  # OOM wall: larger micros only get worse
        elif strategy in ("grid", "random", "model"):
            import random as _random

            r = _random.Random(seed)
            grid = [
                {"zero_stage": st, "micro_batch_size": mb,
                 "remat": rm, "offload_optimizer": off}
                for st in zero_stages for mb in mbs
                for rm in remats for off in offloads
            ]
            if strategy == "grid":
                for cand in grid:
                    consider(self._run_exp(cand, steps))
            elif strategy == "random":
                n = min(num_trials or len(grid), len(grid))
                for cand in r.sample(grid, n):
                    consider(self._run_exp(cand, steps))
            else:
                # ModelBasedTuner analog: explore, fit, exploit
                budget = min(num_trials or len(grid), len(grid))
                explore = grid if budget >= len(grid) else r.sample(
                    grid, max(budget // 2, 1))
                measured = {}
                for cand in explore:
                    exp = self._run_exp(cand, steps)
                    consider(exp)
                    measured[tuple(sorted(cand.items()))] = exp
                remaining = [g for g in grid
                             if tuple(sorted(g.items())) not in measured]
                scored = [e for e in measured.values() if e.get("ok")]
                if scored and remaining and len(measured) < budget:
                    gmean = sum(e["samples_per_sec"] for e in scored) / len(scored)

                    def axis_dev(key, val):
                        pts = [e["samples_per_sec"] for e in scored
                               if e.get(key) == val]
                        return (sum(pts) / len(pts) - gmean) if pts else 0.0

                    def predict(c):
                        return gmean + sum(axis_dev(k, v) for k, v in c.items())

                    remaining.sort(key=predict, reverse=True)
                    for cand in remaining[: budget - len(measured)]:
                        consider(self._run_exp(cand, steps))
        else:
            raise ValueError(
                f"unknown strategy '{strategy}' (expected fast|grid|random|model)"
            )

        self._flush_results()
        if best is None:
            raise RuntimeError(
                f"autotuning found no feasible config; see {self.results_dir}"
            )
        tuned = self._apply_candidate(best)
        log_dist(
            f"autotune best ({strategy}): stage={best['zero_stage']} "
            f"micro={best['micro_batch_size']} "
            f"remat={best.get('remat')} offload={best.get('offload_optimizer')} "
            f"({best['samples_per_sec']:.1f} samples/s)",
            ranks=[0],
        )
        return tuned
