"""Config system: one JSON/dict → typed config tree.

TPU-native analog of the reference config plumbing
(ref: runtime/config.py DeepSpeedConfig, runtime/config_utils.py
DeepSpeedConfigModel). Uses pydantic v2. Field names intentionally match
the reference JSON schema (train_micro_batch_size_per_gpu, zero_optimization,
bf16/fp16 blocks, optimizer/scheduler type+params) so configs written for
the reference parse here; batch-triangle resolution reproduces
runtime/config.py's train/micro/GAS coupling with the data-parallel world
size coming from the mesh rather than torch.distributed.
"""

import json
from enum import IntEnum
from typing import Any, Dict, List, Optional, Union

from pydantic import BaseModel, ConfigDict, Field, model_validator


class ConfigModel(BaseModel):
    """Base for all config blocks (ref: config_utils.py DeepSpeedConfigModel)."""

    model_config = ConfigDict(extra="forbid", validate_assignment=True, populate_by_name=True)


class ZeroStage(IntEnum):
    disabled = 0
    optimizer_states = 1  # shard optimizer state over 'data'
    gradients = 2  # + reduce-scatter grads
    weights = 3  # + shard parameters


class OffloadDevice:
    none = "none"
    cpu = "cpu"
    nvme = "nvme"


class OffloadConfig(ConfigModel):
    """ref: runtime/zero/offload_config.py"""

    device: str = OffloadDevice.none
    nvme_path: Optional[str] = None
    buffer_count: int = 5
    pin_memory: bool = False


class ZeroConfig(ConfigModel):
    """ref: runtime/zero/config.py DeepSpeedZeroConfig:83"""

    stage: int = 0
    # ZeRO-3 persistence threshold: params smaller than this stay replicated
    # (ref: stage3 param_persistence_threshold / parameter_offload.py:242).
    param_persistence_threshold: int = 10_000
    # Sub-mesh ("MiCS"/hpZ-style) sharding: shard params over groups of this
    # size and replicate across groups (ref: runtime/zero/mics.py:64,
    # zero_hpz_partition_size config.py:264).
    zero_hpz_partition_size: int = 0  # 0 = full data-axis sharding
    # ZeRO++ quantized collectives (ref: zero/config.py:268/:280).
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    # ref: zero/config.py zero_quantized_nontrainable_weights — resident
    # int8 storage for frozen weights. Not implemented (all engine params
    # are trainable here; serve frozen models via inference PTQ instead) —
    # parses when false so stock ZeRO++ configs load, raises when true.
    zero_quantized_nontrainable_weights: bool = False
    offload_optimizer: OffloadConfig = Field(default_factory=OffloadConfig)
    offload_param: OffloadConfig = Field(default_factory=OffloadConfig)
    # Comm/compute overlap master switch (runtime/overlap.py,
    # docs/overlap.md): the ZeRO-3 layer gather inside the layer body,
    # bucketed gradient reduce-scatter launches, pipeline permute
    # overlap, and the schedule analyzer's latency-hiding credit. false =
    # the serialized twin ds_schedule commits (every collective modeled
    # fully exposed, no in-body gather / bucket restructure) — the reference's overlap_comm
    # semantics (ref: stage_1_and_2.py overlap_comm reduction during bwd).
    overlap_comm: bool = True
    # 0 leaves the ZeRO-3 layer gathers to the partitioner (per-use
    # gathers at the consumer); >= 1 gathers each layer's shards inside
    # that layer's body (runtime/overlap.py). Nothing reads the depth
    # itself since the scan stopped carrying gathered buffers ahead
    # (the barrier that pinned them made every gather synchronous on the
    # chip: docs/overlap.md); the key keeps parsing (ref:
    # partitioned_param_coordinator.py fetch_sub_module's look-ahead)
    # and tune_aot still enumerates it.
    prefetch_depth: int = 1
    # Gradient reduce-scatter launch-group size in MiB (ref:
    # stage_1_and_2.py reduce_bucket_size IPG buckets). 0 = one
    # serialized constraint wall at the accumulation boundary; >0 =
    # software-pipelined bucket launches (runtime/overlap.bucketed_apply).
    # tune_aot searches this axis.
    bucket_mb: float = 32.0
    # Accepted no-op on TPU: buffers are always contiguous under XLA.
    contiguous_gradients: bool = True


class BF16Config(ConfigModel):
    """ref: runtime/config.py bf16 block"""

    enabled: bool = False
    # Keep a fp32 master copy partitioned ZeRO-1 style (ref: bf16_optimizer.py:30).
    master_weights: bool = True


class FP16Config(ConfigModel):
    """ref: runtime/fp16/loss_scaler.py DynamicLossScaler + config keys"""

    enabled: bool = False
    loss_scale: float = 0.0  # 0 = dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    consecutive_hysteresis: bool = False
    min_loss_scale: float = 1.0


class DataTypesConfig(ConfigModel):
    """ref: runtime/config.py data_types block. `grad_accum_dtype`
    declares the gradient-accumulation/reduction precision the compiled
    step must honor (None = fp32, the engine's construction); the
    numerics sanitizer (analysis/numerics.py N001) verifies the HLO
    against it."""

    grad_accum_dtype: Optional[str] = None  # None -> fp32

    @model_validator(mode="after")
    def _check_dtype(self):
        if self.grad_accum_dtype is not None and \
                self.grad_accum_dtype.lower() not in (
                    "fp32", "float32", "f32", "bf16", "bfloat16",
                    "fp16", "float16", "f16"):
            raise ValueError(
                f"data_types.grad_accum_dtype={self.grad_accum_dtype!r}; "
                "expected fp32/bf16/fp16")
        return self


class IntegrityConfig(ConfigModel):
    """Silent-data-corruption guardian (resilience/integrity.py,
    docs/fault_tolerance.md SDC section). `enabled` turns on (a) the
    in-graph non-finite gradient guard in the compiled train step —
    `precision.found_inf_in_grads` over the grad pytree, skipping the
    optimizer update exactly like the fp16 overflow path (fp16 keeps
    its own loss-scale-coupled check either way) — and (b) the default
    EMA z-score anomaly detector the ElasticTrainer builds when no
    explicit guardian is passed. Off by default: the guard adds
    branchless selects to the compiled step, and the committed
    MEMBUDGET/NUMERICS baselines pin the un-guarded canonical
    programs.

    zscore/window/warmup_steps/rel_floor parameterize the detector
    (see AnomalyDetector); persistent_trips bounds how many times the
    guardian may answer the SAME step's anomaly with a verified-mirror
    rollback before escalating to the disk checkpoint (or raising
    PersistentAnomalyError without one)."""

    enabled: bool = False
    zscore: float = 8.0
    window: int = 16
    warmup_steps: int = 4
    rel_floor: float = 0.02
    persistent_trips: int = 2

    @model_validator(mode="after")
    def _check(self):
        if self.zscore <= 0 or self.window < 1 or self.warmup_steps < 1:
            raise ValueError(
                "integrity needs zscore > 0, window >= 1, "
                "warmup_steps >= 1")
        if self.persistent_trips < 1:
            raise ValueError("integrity.persistent_trips must be >= 1")
        return self


class OptimizerConfig(ConfigModel):
    """ref: runtime/config.py optimizer block → ops/adam etc."""

    type: str = "adamw"
    params: Dict[str, Any] = Field(default_factory=dict)


class SchedulerConfig(ConfigModel):
    """ref: runtime/lr_schedules.py"""

    type: Optional[str] = None
    params: Dict[str, Any] = Field(default_factory=dict)


class MeshConfig(ConfigModel):
    """Parallel topology — the analog of PipeModelDataParallelTopology
    (ref: runtime/pipe/topology.py:244) expressed as mesh axis sizes.
    -1 on exactly one axis means "all remaining devices"."""

    pipe: int = 1
    data: int = -1
    # ZeRO sub-group axis (MiCS): set directly, or derived from
    # zero_optimization.zero_hpz_partition_size by the engine.
    zero: int = 1
    expert: int = 1
    seq: int = 1
    model: int = 1

    def axis_sizes(self) -> Dict[str, int]:
        return {"pipe": self.pipe, "data": self.data, "zero": self.zero,
                "expert": self.expert, "seq": self.seq, "model": self.model}


class ActivationCheckpointingConfig(ConfigModel):
    """ref: runtime/activation_checkpointing/config.py:94

    `policy` drives jax.checkpoint around each micro-step's loss in the
    compiled train step (the engine-level analog of the reference's
    configure()+checkpoint() pair):
      'none'          — no rematerialization (save everything)
      'full'          — recompute everything in backward
      'dots'          — save MXU dot/matmul outputs only
      'dots_no_batch' — save dot outputs without batch dims
    Models may additionally carry their own finer-grained remat (e.g.
    per-scanned-layer); the engine wrap composes around it.

    `cpu_checkpointing` (with policy='dots_no_batch') offloads the saved
    dot outputs to host DRAM instead of keeping them in HBM
    (jax.checkpoint_policies.offload_dot_with_no_batch_dims — ref:
    checkpointing.py:989 cpu_checkpointing).

    `partition_activations` is an accepted no-op BY DESIGN: under XLA
    SPMD the saved residuals are computed and kept in their sharded
    layout (the model's TP/Ulysses activation constraints), so saved
    activations are never replicated across model ranks — which is the
    entire job of the reference's partition_activations
    (checkpointing.py partition_activations + gather on backward).
    tests/test_engine.py asserts the per-device remat footprint shrinks
    with the model axis."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    number_checkpoints: Optional[int] = None
    policy: str = "none"

    @model_validator(mode="after")
    def _check_policy(self):
        if self.policy not in ("none", "full", "dots", "dots_no_batch"):
            raise ValueError(
                f"unknown activation_checkpointing.policy '{self.policy}' "
                "(expected none|full|dots|dots_no_batch)"
            )
        return self


class AioConfig(ConfigModel):
    """ref: csrc/aio handle knobs (deepspeed_py_aio_handle.h:15-39, config
    'aio' block). Drives the native I/O library (csrc/aio/ds_aio.cpp)
    behind NVMe offload: block_size chunks each request across the pool,
    thread_count sizes the pool. queue_depth/single_submit/overlap_events
    are libaio submission details the thread pool subsumes — accepted for
    config compatibility, no separate effect."""

    block_size: int = 1 << 20
    queue_depth: int = 8
    thread_count: int = 4
    single_submit: bool = False
    overlap_events: bool = True


class CommsLoggerConfig(ConfigModel):
    """ref: deepspeed/utils/comms_logging.py + comm config"""

    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False


class FlopsProfilerConfig(ConfigModel):
    """ref: deepspeed/profiling/config.py"""

    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class MonitorConfig(ConfigModel):
    """ref: deepspeed/monitor/config.py"""

    enabled: bool = False
    tensorboard: Dict[str, Any] = Field(default_factory=dict)
    csv_monitor: Dict[str, Any] = Field(default_factory=dict)
    wandb: Dict[str, Any] = Field(default_factory=dict)


class PrefixCacheConfig(ConfigModel):
    """Automatic prefix caching for the ragged inference engine
    (inference/ragged.py): content-addressed reuse of full KV blocks
    across sequences sharing a prompt prefix, vLLM-PagedAttention style.

    pool_blocks caps the LRU pool of retired-but-cached blocks
    (refcount 0, contents kept for future hits): -1 keeps every retired
    cached block until allocation pressure evicts it; 0 disables
    parking (blocks shared only while a live sequence holds them)."""

    enabled: bool = True
    pool_blocks: int = -1


class PressureConfig(ConfigModel):
    """Memory-pressure governor for the serving scheduler
    (inference/pressure.py PressureGovernor; docs/fault_tolerance.md
    pressure section). Off by default: the committed serving baselines
    (MEMBUDGET / serving-sim / chaos lanes) pin the un-governed control
    plane, and flush-and-recompute preemption stays the legacy
    behavior until a deployment opts in.

    Watermarks are LIVE block-pool occupancy fractions (parked
    prefix-cache blocks are evictable headroom, not pressure), scaled
    down when the S004 warmup footprint crowds the HBM budget past
    `static_headroom` (see PressureGovernor.watermark_scale):

      occupancy >= yellow    evict up to yellow_trim_blocks LRU-parked
                             prefix-cache blocks per iteration
      occupancy >= red       preemption victims spill their paged KV to
                             the bounded pinned-host tier (spill_host_mb;
                             resume = import_kv, recompute on any
                             failure) instead of discarding it
      occupancy >= brownout  speculative mode degrades to plain decode,
                             the prefill chunk shrinks by
                             brownout_chunk_div, admission caps at
                             brownout_admit requests per iteration, and
                             the router engages fleet-wide fair shed

    hysteresis: the margin occupancy must clear a level's entry
    watermark by before the governor relaxes one level (per update)."""

    enabled: bool = False
    yellow: float = 0.65
    red: float = 0.85
    brownout: float = 0.95
    hysteresis: float = 0.05
    static_headroom: float = 0.8
    yellow_trim_blocks: int = 4
    spill_enabled: bool = True
    spill_host_mb: float = 256.0
    brownout_chunk_div: int = 4
    brownout_admit: int = 1

    @model_validator(mode="after")
    def _check(self):
        if not (0.0 < self.yellow <= self.red <= self.brownout <= 1.0):
            raise ValueError(
                "pressure watermarks need 0 < yellow <= red <= "
                "brownout <= 1")
        if self.hysteresis < 0 or self.hysteresis >= self.yellow:
            raise ValueError(
                "pressure.hysteresis must be in [0, yellow)")
        if self.static_headroom <= 0 or self.static_headroom > 1:
            raise ValueError("pressure.static_headroom must be in (0, 1]")
        if self.yellow_trim_blocks < 0 or self.spill_host_mb < 0:
            raise ValueError(
                "yellow_trim_blocks and spill_host_mb must be >= 0")
        if self.brownout_chunk_div < 1 or self.brownout_admit < 0:
            raise ValueError(
                "brownout_chunk_div must be >= 1, brownout_admit >= 0")
        return self


class ServingSchedulerConfig(ConfigModel):
    """Continuous-batching serving scheduler (inference/scheduler.py
    ServingScheduler) — the request-level control plane over the paged
    KV substrate.

    max_num_batched_tokens: per-iteration token budget (Sarathi-Serve's
    chunked-prefill knob): decode rows spend 1 token each, prefill
    chunks fill the remainder — so a long prompt never stalls decode.
    prefill_chunk: max prompt tokens one sequence feeds per iteration.
    decode_chunk: steady-state fused decode depth — when every active
    sequence is decoding (no prefill in flight), the scheduler
    dispatches ONE compiled multi-step program covering decode_chunk
    tokens (tokens stay device-resident between steps).
    admission: 'fcfs' stops at the first waiting request that does not
    fit the KV pool (strict arrival order); 'skip' keeps scanning the
    queue for later requests that do fit (no head-of-line blocking on
    capacity, mild reordering).
    prefill_mode: 'chunked' feeds prompts through the decode path in
    prefill_chunk pieces piggybacked on decode iterations (serving
    default); 'wave' prefills whole prompts through the compiled
    cross-prompt prefill waves (the generate() parity path).
    warmup: AOT-precompile the (bucket width x chunk) decode/sample
    grid at scheduler construction so steady-state serving triggers
    zero recompiles (engine.warmup).
    hbm_budget_gb: per-device HBM budget the warmup-measured bucket
    footprints are validated against at admit-config time (analysis/
    costmodel S004); 0 = auto from the running chip
    (platform/accelerator.py hbm_per_device).
    max_preemptions: preemption-starvation bound — a request preempted
    this many times becomes PROTECTED (never selected as a victim
    again; the requester yields instead), so every admitted request
    makes forward progress under sustained pressure. 0 disables the
    bound (the legacy youngest-first-always policy, which can ping-pong
    two similar-age requests forever).
    slo_classes: named SLO classes mapped to TTFT deadlines in modeled
    seconds (inference/pressure.py cost model) — submit(slo_class=...)
    resolves a deadline through this table; submit(deadline_s=...)
    passes one directly. A request whose admission-time TTFT estimate
    exceeds its deadline is rejected with finish_reason='deadline'
    BEFORE any KV block is touched.
    pressure: the memory-pressure governor block (PressureConfig).
    denoising_steps: for a model that generates by diffusion over blocks
    (TransformerConfig.block_length B) and no other: the denoising
    passes T a block of B masked positions takes, ceil(B / T) positions
    revealed a pass, the most confident first; 0 = B (one a pass)."""

    max_num_batched_tokens: int = 256
    prefill_chunk: int = 32
    decode_chunk: int = 1
    admission: str = "fcfs"
    prefill_mode: str = "chunked"
    warmup: bool = True
    hbm_budget_gb: float = 0.0
    max_preemptions: int = 8
    slo_classes: Dict[str, float] = Field(default_factory=dict)
    pressure: PressureConfig = Field(default_factory=PressureConfig)
    denoising_steps: int = 0

    @model_validator(mode="after")
    def _check(self):
        if self.denoising_steps < 0:
            raise ValueError("denoising_steps must be >= 0 (0 = one "
                             "position a pass)")
        if self.max_preemptions < 0:
            raise ValueError("max_preemptions must be >= 0 (0 = off)")
        for name, dl in self.slo_classes.items():
            if dl <= 0:
                raise ValueError(
                    f"slo_classes[{name!r}] deadline must be > 0 s")
        if self.admission not in ("fcfs", "skip"):
            raise ValueError(
                f"unknown admission policy '{self.admission}' "
                "(expected fcfs|skip)")
        if self.prefill_mode not in ("chunked", "wave"):
            raise ValueError(
                f"unknown prefill_mode '{self.prefill_mode}' "
                "(expected chunked|wave)")
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if self.decode_chunk < 1:
            raise ValueError("decode_chunk must be >= 1")
        if self.max_num_batched_tokens < 1:
            raise ValueError("max_num_batched_tokens must be >= 1")
        if self.hbm_budget_gb < 0:
            raise ValueError("hbm_budget_gb must be >= 0 (0 = auto)")
        return self


class AutoscalerConfig(ConfigModel):
    """SLO-class autoscaler policy loop (inference/autoscaler.py
    Autoscaler; docs/autoscaling.md). Off by default — a fleet stays
    at its constructed size until a deployment opts in.

    Replica-count bounds: min_replicas / max_replicas clamp every
    decision (the policy never drains below min or spins past max).

    Scale-up signals, evaluated every evaluation_interval_s on the
    injectable clock (virtual-time sim and wall clock share one path):
    any replica's pressure level >= scale_up_pressure
    (inference/pressure.py: 1 yellow / 2 red / 3 brownout), fleet
    queue depth per live replica > scale_up_queue_per_replica, or a
    shed/deadline-rejection delta since the last evaluation. A signal
    must hold for up_hysteresis CONSECUTIVE evaluations before the
    fleet grows (occupancy noise at a watermark must not flap the
    fleet size), except when the delta includes a class named in
    premium_classes — a premium-impact event is already an SLO breach,
    so it bypasses hysteresis (cooldown still applies).

    Scale-down: pressure GREEN everywhere, queue depth per replica <
    scale_down_queue_per_replica, and no shed/rejection activity, held
    for down_hysteresis consecutive evaluations. Cooldowns are
    asymmetric (scale_up_cooldown_s < scale_down_cooldown_s: growing
    is cheap and urgent, shrinking wrong costs a spin-up later), and
    any scale action resets both.

    Spin-up failure policy: a failed add_replica (the chaos point
    'replica.spinup' models a replica killed mid-scale-up) burns the
    attempt and retries after spinup_retry_backoff_s, doubling up to
    spinup_max_retries attempts before the policy loop re-arms on the
    next scale-up signal."""

    enabled: bool = False
    min_replicas: int = 1
    max_replicas: int = 8
    evaluation_interval_s: float = 1.0
    scale_up_pressure: int = 2
    scale_up_queue_per_replica: float = 4.0
    scale_down_queue_per_replica: float = 1.0
    up_hysteresis: int = 2
    down_hysteresis: int = 4
    scale_up_cooldown_s: float = 5.0
    scale_down_cooldown_s: float = 30.0
    spinup_retry_backoff_s: float = 1.0
    spinup_max_retries: int = 3
    premium_classes: List[str] = Field(default_factory=list)

    @model_validator(mode="after")
    def _check(self):
        if self.min_replicas < 1:
            raise ValueError("min_replicas must be >= 1")
        if self.max_replicas < self.min_replicas:
            raise ValueError("max_replicas must be >= min_replicas")
        if self.evaluation_interval_s <= 0:
            raise ValueError("evaluation_interval_s must be > 0")
        if not (0 <= self.scale_up_pressure <= 3):
            raise ValueError(
                "scale_up_pressure must be a pressure level in [0, 3]")
        if self.scale_up_queue_per_replica < 0 \
                or self.scale_down_queue_per_replica < 0:
            raise ValueError("queue watermarks must be >= 0")
        if self.scale_down_queue_per_replica \
                > self.scale_up_queue_per_replica:
            raise ValueError(
                "scale_down_queue_per_replica must be <= "
                "scale_up_queue_per_replica (the dead band must exist)")
        if self.up_hysteresis < 1 or self.down_hysteresis < 1:
            raise ValueError("hysteresis counts must be >= 1")
        if self.scale_up_cooldown_s < 0 or self.scale_down_cooldown_s < 0:
            raise ValueError("cooldowns must be >= 0")
        if self.spinup_retry_backoff_s <= 0 or self.spinup_max_retries < 0:
            raise ValueError(
                "spinup_retry_backoff_s must be > 0, "
                "spinup_max_retries >= 0")
        return self


class ServingRouterConfig(ConfigModel):
    """Multi-replica serving front door (inference/router.py
    ServingRouter) — the fleet layer over N ServingScheduler-backed
    engine replicas.

    replicas: fleet size (informational when engines are passed
    explicitly; a mismatch with the engine list raises).
    policy: 'prefix_aware' scores each replica by
    ``load/max_batch - cache_weight * cached_prefix_fraction`` using
    the blake2b hash-chain prefix index as the locality signal;
    'round_robin' ignores locality (the comparison baseline).
    cache_weight: how many normalized-load units a fully-cached prompt
    is worth — 0 reduces prefix_aware to pure least-loaded.
    session_affinity: pin multi-turn sessions to their replica (turn
    N+1 extends turn N's cached prefix); a pin breaks when the pinned
    replica's backlog exceeds the least-loaded replica's by
    affinity_evict_margin requests.
    mode: 'colocated' replicas each run prefill AND decode;
    'disaggregated' dedicates the first prefill_replicas replicas to
    chunked prefill and hands finished sequences' paged KV blocks to
    the decode replicas (DistServe/Splitwise) — fleets too small to
    split fall back to colocated with a log line.
    speculative_replicas: run the LAST K decode replicas' schedulers
    in speculative mode (prompt-lookup self-drafting, greedy-only) —
    the per-replica mode flag the router reports through metrics().
    scheduler: the per-replica ServingSchedulerConfig.

    Self-healing (deepspeed_tpu/resilience, docs/fault_tolerance.md):
    health_enabled turns on the per-replica circuit breaker — a
    replica whose dispatch raises (or, with dispatch_deadline_s > 0,
    overruns the deadline) failure_threshold times in a row is failed
    over AUTOMATICALLY (the fail_replica requeue machinery, no manual
    call), then probed after an exponential backoff
    (breaker_backoff_s doubling by breaker_backoff_mult up to
    breaker_backoff_max_s) and restored when the probe succeeds.
    handoff_timeout_s > 0 bounds each KV export+import; a timed-out or
    failed transfer falls back to the token-identical
    requeue-for-recompute path. max_fleet_queue > 0 bounds the fleet's
    total waiting queue; over it, submissions shed per shed_policy:
    'fair' sheds the queue-heaviest session's newest waiting request
    (the submitting session itself when it is the heaviest),
    'reject' always sheds the new request.

    Pressure integration (inference/pressure.py; active only when the
    per-replica scheduler's pressure governor is enabled):
    pressure_routing_weight folds each replica's pressure level into
    its routing score (normalized level x weight in load units — a RED
    replica must be much cheaper on every other axis to win a pick,
    and BROWNOUT replicas are skipped entirely while a calmer replica
    exists). max_handoff_backlog > 0 bounds each prefill replica's
    handoff_ready backlog: pump() stops moving sequences to decode
    replicas that are saturated (batch-full or pressure >= RED),
    leaving them parked instead of force-recomputing, and routing
    stops picking prefill replicas already at the backlog bound
    (counters handoff_backpressure / prefill_backpressure in
    router.metrics()). brownout_shed engages the fair-shed machinery
    fleet-wide while EVERY live replica sits at BROWNOUT, even when
    max_fleet_queue is unbounded (the effective bound becomes the
    fleet's live batch capacity)."""

    # -- replica lifecycle (docs/autoscaling.md) ------------------------
    # warm_prefix_limit: how many of the donor's hottest parked prefix
    # chains a joining replica imports at spin-up (add_replica warm
    # boot; 0 = always join cache-cold). autoscaler: the SLO-class
    # autoscaler policy block (inference/autoscaler.py; disabled by
    # default — construction-time fleet size is final until enabled).
    warm_prefix_limit: int = 8
    autoscaler: AutoscalerConfig = Field(default_factory=AutoscalerConfig)

    replicas: int = 1
    policy: str = "prefix_aware"
    cache_weight: float = 2.0
    session_affinity: bool = True
    affinity_evict_margin: int = 4
    mode: str = "colocated"
    prefill_replicas: int = 1
    speculative_replicas: int = 0
    health_enabled: bool = True
    failure_threshold: int = 3
    dispatch_deadline_s: float = 0.0
    breaker_backoff_s: float = 1.0
    breaker_backoff_mult: float = 2.0
    breaker_backoff_max_s: float = 30.0
    handoff_timeout_s: float = 0.0
    max_fleet_queue: int = 0
    shed_policy: str = "fair"
    pressure_routing_weight: float = 1.0
    max_handoff_backlog: int = 0
    brownout_shed: bool = True
    scheduler: ServingSchedulerConfig = Field(
        default_factory=ServingSchedulerConfig)

    @model_validator(mode="after")
    def _check(self):
        if self.warm_prefix_limit < 0:
            raise ValueError("warm_prefix_limit must be >= 0 (0 = cold)")
        if self.pressure_routing_weight < 0:
            raise ValueError("pressure_routing_weight must be >= 0")
        if self.max_handoff_backlog < 0:
            raise ValueError(
                "max_handoff_backlog must be >= 0 (0 = unbounded)")
        if self.policy not in ("prefix_aware", "round_robin"):
            raise ValueError(
                f"unknown routing policy '{self.policy}' "
                "(expected prefix_aware|round_robin)")
        if self.mode not in ("colocated", "disaggregated"):
            raise ValueError(
                f"unknown router mode '{self.mode}' "
                "(expected colocated|disaggregated)")
        if self.replicas < 1:
            raise ValueError("replicas must be >= 1")
        if self.prefill_replicas < 1:
            raise ValueError("prefill_replicas must be >= 1")
        if self.speculative_replicas < 0:
            raise ValueError("speculative_replicas must be >= 0")
        if self.cache_weight < 0:
            raise ValueError("cache_weight must be >= 0")
        if self.affinity_evict_margin < 0:
            raise ValueError("affinity_evict_margin must be >= 0")
        if self.failure_threshold < 1:
            raise ValueError("failure_threshold must be >= 1")
        if self.dispatch_deadline_s < 0 or self.handoff_timeout_s < 0:
            raise ValueError("deadlines/timeouts must be >= 0 (0 = off)")
        if self.breaker_backoff_s <= 0 or self.breaker_backoff_mult < 1 \
                or self.breaker_backoff_max_s < self.breaker_backoff_s:
            raise ValueError(
                "breaker backoff needs backoff_s > 0, mult >= 1, "
                "max >= backoff_s")
        if self.max_fleet_queue < 0:
            raise ValueError("max_fleet_queue must be >= 0 (0 = unbounded)")
        if self.shed_policy not in ("fair", "reject"):
            raise ValueError(
                f"unknown shed_policy '{self.shed_policy}' "
                "(expected fair|reject)")
        return self


class CurriculumConfig(ConfigModel):
    """ref: runtime/data_pipeline/curriculum_scheduler.py config (the
    legacy 'curriculum_learning' block). Consumed by the engine: with
    curriculum_type='seqlen' every train batch is truncated to the
    scheduled difficulty (each difficulty level costs one recompile)."""

    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 8
    max_difficulty: int = 1024
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = Field(default_factory=dict)


class ElasticityConfig(ConfigModel):
    """ref: deepspeed/elasticity/config.py ElasticityConfig — consumed by
    deepspeed_tpu.elasticity.compute_elastic_config and the engine (which
    derives the batch triangle from the current device count)."""

    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: list = Field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    prefer_larger_batch: bool = True
    ignore_non_elastic_batch_info: bool = False
    # scheduler-level knobs, accepted for config compatibility
    min_time: int = 0
    version: float = 0.1
    model_parallel_size: int = 1
    num_gpus_per_node: int = 1


class AutotuningConfig(ConfigModel):
    """ref: deepspeed/autotuning/config.py — consumed by
    deepspeed_tpu.autotuning.Autotuner (the engine itself ignores it,
    matching the reference where the launcher drives tuning)."""

    enabled: bool = False
    fast: bool = True
    results_dir: str = "autotuning_results"
    metric: str = "throughput"


class CheckpointConfig(ConfigModel):
    """ref: runtime/checkpoint_engine + engine save/load knobs"""

    use_node_local_storage: bool = False
    load_universal: bool = False
    async_save: bool = False


class ProgressiveLayerDropConfig(ConfigModel):
    """ref: runtime/progressive_layer_drop.py ProgressiveLayerDrop:10 +
    constants PLD_THETA/PLD_GAMMA. theta(t) = (1-θ)·exp(-γt) + θ decays
    from 1 (keep everything) toward θ; the engine injects it into each
    micro-batch and the model drops layer l with prob
    (l+1)/L · (1-theta) via lax.cond (compute actually skipped)."""

    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001


class DataEfficiencyConfig(ConfigModel):
    """ref: runtime/data_pipeline/config.py get_data_efficiency_config +
    constants.py field names. `data_sampling.curriculum_learning` is
    consumed by runtime/data_analyzer.py build_curriculum_sampler (the
    DeepSpeedDataSampler analog); the analyzer artifacts it reads come
    from runtime/data_analyzer.py DataAnalyzer."""

    enabled: bool = False
    seed: int = 1234
    data_sampling: Dict[str, Any] = Field(default_factory=dict)
    data_routing: Dict[str, Any] = Field(default_factory=dict)

    @model_validator(mode="after")
    def _check_routing(self):
        routing = dict(self.data_routing or {})
        if (self.enabled and routing.get("enabled")
                and routing.get("random_ltd", {}).get("enabled")):
            # random-LTD is a model-graph transform here, not a dataloader
            # one — refuse the dataloader-side knob rather than no-op it
            raise NotImplementedError(
                "data_routing.random_ltd is configured on the model in "
                "deepspeed_tpu (TransformerConfig random_ltd_* fields drive "
                "the in-graph token-drop layers); the dataloader-side block "
                "has no consumer"
            )
        return self


class NebulaConfig(ConfigModel):
    """Tiered checkpoint service knobs (ref: nebula/config.py
    DeepSpeedNebulaConfig + nebula/constants.py defaults). Consumed by
    runtime/checkpoint.py TieredCheckpointEngine: fast node-local tier
    with version retention + interval-persisted durable tier."""

    enabled: bool = False
    persistent_storage_path: Optional[str] = None
    persistent_time_interval: float = 100.0
    num_of_version_in_retention: int = 2
    enable_nebula_load: bool = True
    load_path: Optional[str] = None


class DeepSpeedTPUConfig(ConfigModel):
    """The full config tree (ref: runtime/config.py DeepSpeedConfig)."""

    train_batch_size: Optional[int] = None
    train_micro_batch_size_per_gpu: Optional[int] = None
    gradient_accumulation_steps: Optional[int] = None

    steps_per_print: int = 10
    wall_clock_breakdown: bool = False
    gradient_clipping: float = 0.0
    prescale_gradients: bool = False
    seed: int = 1234
    # ref: runtime/config.py communication_data_type — the dtype
    # gradient-reduction collectives are DECLARED to carry (None = the
    # compute dtype, the reference default for fp16/bf16 training).
    # Verified against the compiled HLO by analysis/numerics.py N001.
    communication_data_type: Optional[str] = None

    optimizer: OptimizerConfig = Field(default_factory=OptimizerConfig)
    scheduler: SchedulerConfig = Field(default_factory=SchedulerConfig)
    zero_optimization: ZeroConfig = Field(default_factory=ZeroConfig)
    bf16: BF16Config = Field(default_factory=BF16Config)
    fp16: FP16Config = Field(default_factory=FP16Config)
    data_types: DataTypesConfig = Field(default_factory=DataTypesConfig)
    integrity: IntegrityConfig = Field(default_factory=IntegrityConfig)
    mesh: MeshConfig = Field(default_factory=MeshConfig)
    activation_checkpointing: ActivationCheckpointingConfig = Field(
        default_factory=ActivationCheckpointingConfig
    )
    comms_logger: CommsLoggerConfig = Field(default_factory=CommsLoggerConfig)
    flops_profiler: FlopsProfilerConfig = Field(default_factory=FlopsProfilerConfig)
    monitor: MonitorConfig = Field(default_factory=MonitorConfig)
    checkpoint: CheckpointConfig = Field(default_factory=CheckpointConfig)
    nebula: NebulaConfig = Field(default_factory=NebulaConfig)
    data_efficiency: DataEfficiencyConfig = Field(default_factory=DataEfficiencyConfig)
    aio: AioConfig = Field(default_factory=AioConfig)
    elasticity: ElasticityConfig = Field(default_factory=ElasticityConfig)
    autotuning: AutotuningConfig = Field(default_factory=AutotuningConfig)
    curriculum_learning: CurriculumConfig = Field(default_factory=CurriculumConfig)
    progressive_layer_drop: ProgressiveLayerDropConfig = Field(
        default_factory=ProgressiveLayerDropConfig
    )
    # compression training (ref: compression/config.py — deep free-form
    # schema validated by compression.init_compression at engine build)
    compression_training: Optional[Dict[str, Any]] = None

    @model_validator(mode="after")
    def _check_precision(self):
        if self.bf16.enabled and self.fp16.enabled:
            raise ValueError("bf16 and fp16 cannot both be enabled")
        return self

    @model_validator(mode="after")
    def _check_implemented(self):
        """Unimplemented knobs raise instead of silently doing nothing
        (VERDICT r1 W2: 'dead config knobs are silent lies')."""
        z = self.zero_optimization
        unimpl = []
        if z.zero_quantized_nontrainable_weights:
            unimpl.append(
                "zero_optimization.zero_quantized_nontrainable_weights "
                "(serve frozen models via inference PTQ: init_inference "
                "quantization={'bits': 8})"
            )
        if z.offload_param.device != OffloadDevice.none:
            # ZeRO-Infinity param tier is a stage-3 feature, matching the
            # reference's assertion (zero/config.py offload_param is
            # consumed only by stage3.py / parameter_offload.py). The nvme
            # tier additionally requires offload_optimizer=nvme (engine
            # check — params re-materialize from the optimizer swap files).
            if z.stage != 3:
                raise ValueError(
                    "zero_optimization.offload_param requires zero stage 3"
                )
        if (
            self.activation_checkpointing.cpu_checkpointing
            and self.activation_checkpointing.policy != "dots_no_batch"
        ):
            # the host tier offloads the saved dot outputs — there must BE a
            # saveable-dots policy to offload (ref: checkpointing.py:989
            # cpu_checkpointing moves the checkpointed activations to CPU)
            raise ValueError(
                "activation_checkpointing.cpu_checkpointing requires "
                "policy='dots_no_batch' (the saved dot outputs are what "
                "moves to host DRAM)"
            )
        if self.checkpoint.use_node_local_storage:
            unimpl.append(
                "checkpoint.use_node_local_storage (use the nebula block: "
                "fast node-local tier + durable persistent_storage_path)"
            )
        if self.prescale_gradients:
            unimpl.append("prescale_gradients")
        if unimpl:
            raise NotImplementedError(
                "config enables features not yet implemented in deepspeed_tpu: "
                + "; ".join(unimpl)
            )
        return self

    # --- batch triangle (ref: runtime/config.py batch assertions) --------
    def resolve_batch_sizes(self, dp_world_size: int) -> None:
        """Solve train = micro × GAS × dp_world, filling in missing values.

        Reproduces the reference's resolution order: given any two of
        (train, micro, GAS) derive the third; given one, assume the others.
        """
        train, micro, gas = (
            self.train_batch_size,
            self.train_micro_batch_size_per_gpu,
            self.gradient_accumulation_steps,
        )
        if train is not None and micro is not None and gas is None:
            if train % (micro * dp_world_size) != 0:
                raise ValueError(
                    f"train_batch_size {train} not divisible by micro*dp = "
                    f"{micro}*{dp_world_size}"
                )
            gas = train // (micro * dp_world_size)
        elif train is not None and gas is not None and micro is None:
            if train % (gas * dp_world_size) != 0:
                raise ValueError(
                    f"train_batch_size {train} not divisible by gas*dp = "
                    f"{gas}*{dp_world_size}"
                )
            micro = train // (gas * dp_world_size)
        elif micro is not None:
            gas = gas or 1
            train = train or micro * gas * dp_world_size
        elif train is not None:
            gas = gas or 1
            if train % (gas * dp_world_size) != 0:
                raise ValueError(
                    f"train_batch_size {train} not divisible by gas*dp = "
                    f"{gas}*{dp_world_size}"
                )
            micro = train // (gas * dp_world_size)
        else:
            raise ValueError(
                "config must set at least one of train_batch_size / "
                "train_micro_batch_size_per_gpu"
            )
        if train != micro * gas * dp_world_size:
            raise ValueError(
                f"batch triangle inconsistent: train={train} != micro={micro} "
                f"× gas={gas} × dp={dp_world_size}"
            )
        self.train_batch_size = train
        self.train_micro_batch_size_per_gpu = micro
        self.gradient_accumulation_steps = gas

    # --- convenience ----------------------------------------------------
    @property
    def zero_stage(self) -> int:
        return self.zero_optimization.stage

    @property
    def compute_dtype(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32


# Reference-era keys with no TPU meaning, accepted and dropped WITH a
# warning so stock reference configs parse here (the module docstring's
# compatibility promise). Keyed by block path ("" = top level). These are
# knobs whose function is subsumed by XLA (bucket sizes, prefetch limits,
# process-level fetch machinery) or by torch-only machinery we don't port
# (SURVEY §7 "what we explicitly do NOT port").
_REFERENCE_NOOP_KEYS: Dict[str, tuple] = {
    # communication_data_type / data_types became REAL knobs in PR 5
    # (the numerics sanitizer's declared precision policy) — no longer
    # dropped here.
    "": (
        "zero_allow_untested_optimizer",
        "sparse_gradients", "amp", "dump_state", "memory_breakdown",
        "gradient_predivide_factor", "dataloader_drop_last",
        "use_data_before_expert_parallel_",
    ),
    "zero_optimization": (
        # bucketing/prefetch/fetch machinery → XLA SPMD scheduling
        "allgather_partitions", "allgather_bucket_size", "reduce_scatter",
        "reduce_bucket_size", "stage3_prefetch_bucket_size",
        "stage3_max_live_parameters", "stage3_max_reuse_distance",
        "stage3_gather_16bit_weights_on_model_save", "sub_group_size",
        "round_robin_gradients", "ignore_unused_parameters",
        "legacy_stage1", "stage3_gather_fp16_weights_on_model_save",
        "elastic_checkpoint",
    ),
    "fp16": ("auto_cast", "fp16_master_weights_and_grads"),
    "bf16": ("immediate_grad_update",),
    "activation_checkpointing": (
        "contiguous_memory_optimization", "synchronize_checkpoint_boundary",
        "profile",
    ),
    "autotuning": (
        # launcher/experiment plumbing subsumed by in-process measurement
        "exps_dir", "overwrite", "start_profile_step", "end_profile_step",
        "metric_path", "arg_mappings", "max_train_batch_size",
        "min_train_batch_size", "max_train_micro_batch_size_per_gpu",
        "min_train_micro_batch_size_per_gpu", "num_tuning_micro_batch_sizes",
        "tuner_type", "tuner_early_stopping", "tuner_num_trials",
        "model_info", "model_info_path", "mp_size", "num_nodes", "num_gpus",
    ),
}

# Renames: reference key → our key (same block).
_REFERENCE_RENAMES: Dict[str, Dict[str, str]] = {
    "zero_optimization": {"stage3_param_persistence_threshold": "param_persistence_threshold"},
}

# Whole reference config blocks naming features that do not exist yet —
# presence raises (silent acceptance would be a lie).
_UNIMPLEMENTED_BLOCKS = ()


def _compat_filter(config: Dict[str, Any]) -> Dict[str, Any]:
    from ..utils.logging import logger

    config = {k: (dict(v) if isinstance(v, dict) else v) for k, v in config.items()}

    def _enabled(block):
        # stock reference configs often carry disabled blocks
        # ({"autotuning": {"enabled": false}}) — those parse fine
        if isinstance(block, dict) and "enabled" in block:
            return bool(block["enabled"])
        return bool(block)

    if "hybrid_engine" in config and _enabled(config.get("hybrid_engine")):
        raise NotImplementedError(
            "the hybrid_engine config block has no engine-level consumer; "
            "wrap the training engine explicitly: "
            "deepspeed_tpu.runtime.hybrid_engine.HybridEngine(engine, "
            "model_config, inference_config)"
        )
    config.pop("hybrid_engine", None)
    if "sparse_attention" in config and _enabled(config.get("sparse_attention")):
        raise NotImplementedError(
            "the sparse_attention config block is not carried: block-sparse "
            "attention layouts are not implemented here (sliding windows "
            "are TransformerConfig(sliding_window=...))"
        )
    config.pop("sparse_attention", None)
    present = [b for b in _UNIMPLEMENTED_BLOCKS
               if b in config and _enabled(config.pop(b))]
    if present:
        raise NotImplementedError(
            f"config blocks not yet implemented in deepspeed_tpu: {present}"
        )
    if float(config.get("gradient_predivide_factor", 1.0) or 1.0) != 1.0:
        raise NotImplementedError(
            "gradient_predivide_factor != 1.0 is not implemented (grad "
            "reduction is a fused fp32 psum-mean on TPU)"
        )
    for path, keys in _REFERENCE_NOOP_KEYS.items():
        block = config if path == "" else config.get(path)
        if not isinstance(block, dict):
            continue
        dropped = [k for k in keys if k in block]
        for k in dropped:
            block.pop(k)
        if dropped:
            where = path or "config"
            logger.warning(
                f"{where}: ignoring reference-era keys with no TPU meaning: {dropped}"
            )
    for path, renames in _REFERENCE_RENAMES.items():
        block = config.get(path)
        if isinstance(block, dict):
            for old, new in renames.items():
                if old in block and new not in block:
                    block[new] = block.pop(old)
    return config


def parse_config(config: Union[str, Dict[str, Any], DeepSpeedTPUConfig, None]) -> DeepSpeedTPUConfig:
    """Accept a path to a JSON file, a dict, or an already-built config.

    Reference-schema compatibility: known no-op keys are dropped with a
    warning; keys/blocks naming unimplemented features raise."""
    if config is None:
        return DeepSpeedTPUConfig()
    if isinstance(config, DeepSpeedTPUConfig):
        return config
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    if not isinstance(config, dict):
        raise TypeError(f"config must be path/dict/DeepSpeedTPUConfig, got {type(config)}")
    return DeepSpeedTPUConfig(**_compat_filter(config))
