"""Continuous-batching serving scheduler: the request-level control
plane over the paged KV substrate.

`InferenceEngine.generate()` is run-to-completion: a fixed prompt set
prefills together and decodes until the LAST sequence finishes — new
requests cannot join mid-flight and finished sequences hold their KV
blocks until the batch drains. `ServingScheduler` replaces that with
Orca-style iteration-level scheduling (ref: Orca OSDI'22 continuous
batching; vLLM's scheduler; DeepSpeed-FastGen's DynamicSplitFuse /
Sarathi-Serve's chunked-prefill piggybacking), built for XLA's
static-shape world:

- **admission** pops waiting requests whenever the KV pool fits their
  (prefix-cache-credited) prompt — a prompt whose leading blocks hash-
  match the prefix index admits at suffix cost only.
- **chunked prefill interleaves with decode**: a newly admitted prompt
  feeds through the decode path in `prefill_chunk` pieces, sharing ONE
  compiled program with the running sequences' decode rows (the ragged
  "virtual rows" put() already uses for continuations), bounded by the
  per-iteration `max_num_batched_tokens` budget — a long prompt never
  stalls another request's inter-token latency.
- **immediate retirement**: a sequence hitting EOS/length is flushed at
  the iteration it finishes; its blocks go straight back to the
  allocator (or park in the prefix-cache LRU) instead of idling until
  the batch drains.
- **preemption over failure**: under KV-block pressure the YOUNGEST
  sequence is preempted — flushed and re-queued for recompute — rather
  than raising RuntimeError like strict put()/generate(). Recompute is
  exact: sampling streams are keyed by (seed, stream, position), so a
  recomputed sequence re-draws identical tokens; with the prefix cache
  on, its own registered blocks usually make the re-prefill nearly
  free.

Performance comes from two pipelining layers:

- **AOT-warmed shape buckets**: `engine.warmup()` precompiles the
  (bucket width x chunk) decode/sample grid at init, so steady-state
  serving triggers zero S003 recompiles (tracked by the engine's
  always-on RecompileTracker; asserted in tests/test_scheduler.py).
- **look-ahead dispatch**: run() launches step N+1 BEFORE it reads
  step N back, whenever N+1 can be composed without the VALUES of N's
  tokens. Which rows exist, their contexts, block tables, draw counters
  and prefill chunks are host state, and a finish by length is known
  from counts; only the sampled token ids are missing, and those stay
  DEVICE-RESIDENT: row i of step N+1 gathers its token from row src[i]
  of N's sampled array (engine._next_tokens_fn). The readback of N
  (token ids only, utils.sync.serving_readback) then lands while N+1
  runs, so the device never waits for the host's admit / select /
  build. Steps the host cannot compose that way (speculation, the
  presence bitmap, a mesh, wave or fused parts, a reservation that
  must preempt, a handoff's first token) keep the order readback,
  then dispatch. With `decode_chunk > 1` the steady state fuses
  decode_chunk steps into one compiled program (model.decode_multi),
  amortizing dispatch entirely.

A model that generates by DIFFUSION OVER BLOCKS
(TransformerConfig.block_length B; docs/serving_scheduler.md "A step
that yields a block") runs through the same loop and the same compiled
program, with one difference of kind: a running sequence's step is B
rows and yields no token. Its block is fed whole, pass after pass, at
the same positions (`_Block`): denoising passes whose sample epilogue
reveals the most confident of the masked positions ON THE DEVICE
(sampling.block_unmask; the look-ahead feeds pass t + 1 from pass t's
array as it feeds a causal row its token), then one commit pass that
leaves the block's K/V for good, at which `seen_tokens` advances by B
and `Request.output` grows by the block.

`generate()` and `generate_speculative()` are thin wrappers over this
scheduler (prefill_mode='wave', warmup off) — one control plane serves
batch generation, speculative decoding, and online serving.
"""

import dataclasses
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..config.config import ServingSchedulerConfig
from ..ops.pallas.paged_attention import (
    latent_walk_reads,
    walk_reads,
)
from ..resilience.faults import fault_point
from ..resilience.integrity import HandoffIntegrityError
from ..utils import profiler
from ..utils.logging import log_dist
from ..utils.sync import serving_readback
from .engine import (
    InferenceEngine,
    _bucket,
    refuse_block_diffusion,
    refuse_for_pools,
)
from .model import kv_pool_pack, kv_pool_shape
from .pressure import BROWNOUT, RED, PressureGovernor, estimate_ttft
from .ragged import KVCacheExhaustedError

__all__ = ["Request", "ServingScheduler", "ServingSchedulerConfig",
           "SchedulerConfig"]

# module-local alias: `scheduler.SchedulerConfig` reads naturally here,
# while the pydantic model lives in config/config.py under a distinct
# name (config.SchedulerConfig is the LR-schedule block, reference
# schema — the two must not collide)
SchedulerConfig = ServingSchedulerConfig

WAITING, PREFILL, RUNNING, FINISHED, HANDOFF = (
    "waiting", "prefill", "running", "finished", "handoff")

# The phases that tile one scheduling iteration (docs/tracing.md): each
# feeds its counter (seconds) always and a `sched.<phase>` span when
# tracing is active. `readback` is the host waiting for the device;
# the other seven are host work the device may or may not overlap.
PHASES = {"tick": "tick_s", "admit": "admit_s", "select": "select_s",
          "build": "build_s", "launch": "launch_s", "commit": "commit_s",
          "readback": "readback_wait_s", "accept": "accept_s"}
# an iteration whose time outside `readback` exceeds this is counted in
# counters["slow_iterations"] and leaves an always-kept span: a stalled
# host loop names itself even with tracing off. The seconds a run loses
# in one piece are INSIDE `readback`, and a 90 ms pause of a 20 ms
# iteration is under this mark: those fall to the stall rule of
# `profiler.Phases.end()` (STALL_FACTOR, STALL_FLOOR_NS beside it),
# which counts in counters["stall_iterations"] and keeps the same span
SLOW_ITERATION_NS = 50_000_000
# finished requests whose TTFT/TPOT metrics() percentiles are taken over
LATENCY_WINDOW = 4096
# the counter of run tokens (ServingScheduler._count_state), by the
# kind of layer whose heads carry a matrix from row to row of a run
_RUN_TOKENS = {"linear_attention": "gdn_run_tokens",
               "state_space": "ssm_run_tokens",
               "selective_scan": "sscan_run_tokens"}


@dataclasses.dataclass
class Request:
    """One serving request through its whole lifecycle."""

    rid: int
    prompt: List[int]
    max_new_tokens: int
    eos_token_id: Optional[int]
    stream: int                      # sampling stream id (defaults to rid)
    arrival: float                   # perf_counter() at submit
    admit_t: Optional[float] = None  # perf_counter() at FIRST admission
    state: str = WAITING
    uid: Optional[int] = None        # engine uid while admitted
    fed: int = 0                     # base tokens already in the KV cache
    output: List[int] = dataclasses.field(default_factory=list)
    pending: Optional[int] = None    # sampled, not-yet-fed token
    presence: Optional[np.ndarray] = None  # [V] uint8, rep-penalty only
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    finish_reason: Optional[str] = None    # eos | length | capacity
    preemptions: int = 0
    n_cached: int = 0                # prefix-cache-served prompt tokens
    # prefill/decode disaggregation (inference/router.py): a handoff
    # request parks after its FIRST sampled token — KV intact — for the
    # router to transfer to a decode replica, instead of decoding here
    handoff: bool = False
    # SLO admission (inference/pressure.py): optional TTFT deadline in
    # modeled seconds; an unservable deadline rejects at submit() with
    # finish_reason='deadline' before any KV block is touched
    deadline_s: Optional[float] = None
    slo_class: Optional[str] = None
    # preempt-to-host (RED pressure): key of this request's spilled KV
    # payload in the scheduler's HostKvSpillStore — resume imports the
    # pages instead of recomputing; None = recompute on re-admission
    spill_key: Optional[int] = None
    # a block-diffusion model's request while RUNNING: the block it is
    # generating (it has no `pending` token)
    block: Optional["_Block"] = None

    @property
    def base(self) -> List[int]:
        """The token stream that must be in the cache before the next
        draw: prompt + accepted output (recompute target after a
        preemption — positions are absolute, so re-drawn tokens are
        identical)."""
        return self.prompt + self.output

    @property
    def done(self) -> bool:
        return self.state == FINISHED


@dataclasses.dataclass(eq=False)
class _Block:
    """The block a RUNNING request of a block-diffusion model is
    generating: B positions from `seen_tokens` on, fed whole every pass.
    Identity matters (a preemption drops the block, and a pass of it
    still in flight must find it gone), so no equality by value."""

    tokens: List[int]       # the host's newest copy: mask id where masked
    n_fixed: int            # leading tokens that are the prompt's remainder
    masked: int             # still masked after every pass dispatched so far
    # the commit pass went out on the device's copy of the tokens: the
    # block is accepted when the last denoising pass is read back
    committing: bool = False


class _Part:
    """One dispatched compiled program of an iteration (a step may hold
    several: prefill wave(s) + the mixed decode program)."""

    def __init__(self, kind: str, sample_rows, tok_dev, n_steps: int = 1,
                 blocks: Optional[Dict[int, _Block]] = None):
        self.kind = kind              # wave | mixed | fused
        # [(req, row_index)]; of a block-diffusion model [(req, the first
        # of its block's rows)], its denoising passes alone
        self.sample_rows = sample_rows
        # [bucket] or [n_steps, bucket] int32; of a block-diffusion
        # model the tokens every row is fed next pass
        self.tok_dev = tok_dev
        self.n_steps = n_steps
        self.blocks = blocks          # {rid: the block its pass denoised}


class _Step:
    def __init__(self, parts: List[_Part], n_tokens: int):
        self.parts = parts
        self.n_tokens = n_tokens      # batched tokens this iteration
        # what a look-ahead dispatch of the NEXT step settled about this
        # step's sampled requests before their tokens were read, by rid:
        # True = ends with this token whatever its value (blocks already
        # released), False = its token was fed on from the device (the
        # next step's commit is waiting for the id). Absent: nothing
        # was settled, _accept decides from the counts as it reads
        self.settled: Dict[int, bool] = {}


class ServingScheduler:
    """Iteration-level scheduler driving one InferenceEngine.

    sampling: SamplingConfig kwargs shared by every request (compiled
    into the decode/sample programs; greedy when omitted); seed + each
    request's stream id + token position define every draw, so outputs
    are reproducible and independent of batch composition, preemption,
    and arrival order. speculative={'ngram': n, 'draft_len': k} switches
    running sequences to prompt-lookup self-speculation (greedy only;
    the generate_speculative() control plane)."""

    def __init__(
        self,
        engine: InferenceEngine,
        config: Union[ServingSchedulerConfig, Dict[str, Any], None] = None,
        sampling: Optional[Dict[str, Any]] = None,
        seed: int = 0,
        speculative: Optional[Dict[str, int]] = None,
    ):
        from .sampling import SamplingConfig

        self.engine = engine
        if isinstance(config, dict):
            config = ServingSchedulerConfig(**config)
        self.cfg = config or ServingSchedulerConfig()
        self.scfg = SamplingConfig(**(sampling or {}))
        self.seed = int(seed)
        self._spec = dict(speculative) if speculative else None
        if self._spec and not self.scfg.greedy:
            raise ValueError("speculative decoding is greedy-only")
        if self._spec:
            refuse_for_pools(engine.cfg, "speculation")
            refuse_block_diffusion(engine.cfg, "speculation")
        # a model that generates by diffusion over blocks: its block
        # length (0: a causal model), the rows a live sequence takes of
        # an iteration, and the positions a denoising pass reveals
        self._block = B = engine.cfg.block_length
        self._rows_per_seq = B or 1
        self._reveal = -(-B // (self.cfg.denoising_steps or B)) if B else 0
        if B:
            for feature, asked in (
                    ("decode_multi", self.cfg.decode_chunk > 1),
                    ("wave", self.cfg.prefill_mode == "wave"),
                    ("presence", self.scfg.needs_presence)):
                if asked:
                    refuse_block_diffusion(engine.cfg, feature)
            if self.cfg.prefill_chunk % B or B > min(
                    self.cfg.max_num_batched_tokens,
                    engine.config.max_batch_size):
                raise ValueError(
                    f"prefill_chunk {self.cfg.prefill_chunk} is not whole "
                    f"blocks of block_length {B}, or an iteration's rows "
                    f"(max_num_batched_tokens "
                    f"{self.cfg.max_num_batched_tokens}, max_batch_size "
                    f"{engine.config.max_batch_size}) hold no block: a "
                    "prompt is fed in whole blocks, each seeing through "
                    "its own end, and a block is fed whole")
        # of the engine's count of ring blocks turned over, what this
        # scheduler's counter holds already (_count_rings)
        self._rings_recycled_seen = engine.state.rings_recycled
        self.waiting: "deque[Request]" = deque()
        self.active: List[Request] = []   # admission order; PREFILL/RUNNING
        self.finished: Dict[int, Request] = {}
        # prefill-complete handoff requests awaiting KV transfer to a
        # decode replica (router.pump() drains this; disaggregated mode)
        self.handoff_ready: "deque[Request]" = deque()
        self._next_rid = 0
        self.counters: Dict[str, float] = {
            "steps": 0, "admitted": 0, "finished": 0, "preemptions": 0,
            "batched_tokens": 0, "fused_steps": 0, "lookahead_steps": 0,
            "wave_prefills": 0, "handoffs": 0, "adopted": 0,
            "spills": 0, "spill_resumes": 0, "spill_fallbacks": 0,
            "spill_rejects": 0, "spill_integrity_failures": 0,
            "spill_releases": 0, "lookahead_fallbacks": 0,
            "deadline_rejections": 0, "starvation_protected": 0,
            # seconds of step()/run() spent in each phase (PHASES):
            # together they are the loop's wall time
            "tick_s": 0.0, "admit_s": 0.0, "select_s": 0.0,
            "build_s": 0.0, "launch_s": 0.0, "commit_s": 0.0,
            "readback_wait_s": 0.0, "accept_s": 0.0,
            # sum over first admissions of admit_t - arrival
            "queue_wait_s": 0.0,
            "slow_iterations": 0,
            # the stall rule (profiler.Phases.end: an iteration of over
            # three times the loop's typical one): how many, the seconds
            # they took beyond a typical iteration (what a window lost
            # to them), and the part of that inside `readback` (beyond
            # a typical readback wait: the rest is the host's own)
            "stall_iterations": 0, "stall_s": 0.0, "stall_readback_s": 0.0,
            # Python's collector inside iterations, on the phases' clock
            # (profiler's gc.callbacks hook): seconds and collections
            "gc_s": 0.0, "gc_collections": 0,
            # (token, expert) assignments the batched tokens made in ONE
            # routed layer: batched tokens x top-k (0 for a dense model);
            # over steps and experts, the rows an expert sees a step
            "moe_token_expert_pairs": 0,
            # dispatched programs whose routed layers take the streamed
            # pass over the expert stack (engine.expert_path of the
            # program's width: one pipelined kernel a layer); over
            # steps, 1.0 where every step is one such program
            "moe_stream_steps": 0,
            # and those whose routed layers take that pass's second
            # entry (expert_path 'grouped': each expert's weight tile
            # against its own rows alone)
            "moe_grouped_steps": 0,
            # KV cache blocks the decode rows of the dispatched programs
            # had to read: sum over rows (and fused steps) of
            # ceil(ctx / kv_block_size); over steps, what the paged
            # attention kernel's time should follow
            "kv_live_blocks": 0,
            # of those, the blocks the shared-table walk FETCHES: rows
            # of one prefill chunk follow each other on one table and
            # walk as a group whose blocks are read once, by its longest
            # row (paged_attention.walk_reads: the kernel's own
            # grouping); equal to kv_live_blocks where every row is a
            # sequence of its own. And the rows that rode on another
            # row's walk. 1 - kv_block_reads / kv_live_blocks is the
            # share of block visits the groups saved
            "kv_block_reads": 0,
            "kv_grouped_rows": 0,
            # cached tokens the rows of the dispatched programs attended
            # over in ONE latent-attention layer (sum over rows of ctx;
            # 0 unless the model caches a latent): what the latent walk
            # multiplies, whatever it reads once a table
            "mla_cache_tokens": 0,
            # of a latent model's rows, those whose visit was a TILE's:
            # adjacent rows of one table that the latent walk multiplies
            # a block by together (paged_attention.latent_walk_reads, by
            # the kernel's own latent_tiles; kv_grouped_rows is the K/V
            # walk's rule and stays 0 for a latent model)
            "mla_grouped_rows": 0,
            # recurrent state (0 unless the model has state layers):
            # slots held by tracked sequences, summed over dispatched
            # steps; sequences that (re)started at position 0 in a slot,
            # at admission or after a flush, whose first row resets it
            # on the device (model._carry_rows reads nothing of a slot
            # at position 0); admissions whose prompt the prefix index
            # held and that were given no credit for it
            "state_slots_live": 0,
            "state_slot_resets": 0,
            "state_prefix_credits_refused": 0,
            # a model of mixed windows (0 for every other): counted once
            # a dispatched SEQUENCE a step (a chunk's rows are one read,
            # by its longest row). Cached tokens a FULL layer's walk
            # reads for it (its context) and those a WINDOWED layer's
            # does (its context, at most the window); ring blocks its
            # writes have turned over (a block entered whose ring slot
            # held an older one: StateManager.rings_recycled); rings
            # held by tracked sequences, summed over dispatched steps;
            # and admission passes that left a request waiting because
            # the paged blocks, or the rings, were short
            "kv_full_tokens": 0,
            "kv_window_tokens": 0,
            "kv_ring_blocks_recycled": 0,
            "kv_rings_live": 0,
            "admit_waits_full_pool": 0,
            "admit_waits_window_pool": 0,
            # a model some of whose layers read ANOTHER layer's K/V (0
            # for every other), a dispatched sequence a step as above:
            # cached tokens those layers' walks read of the pool they do
            # not own (its context, once a reader layer); the token rows
            # that went through the readers' layers, and those of them
            # whose logits were read (a prompt chunk's other rows need
            # the layers up to the donor alone)
            "kv_shared_tokens": 0,
            "cross_rows_run": 0,
            "cross_rows_needed": 0,
            # bytes of their slots the dispatched programs' sequences
            # read and wrote, over all state layers (a step over rows
            # reads and writes each live sequence's slot once a layer,
            # a whole-prompt prefill writes it); and, where some layers'
            # heads carry a matrix (_RUN_TOKENS: by their kind), the
            # tokens of runs longer than one (prefill chunks, whole
            # prompts): rows whose matrices come from the row before
            # and not from a slot
            "state_bytes_moved": 0,
            "gdn_run_tokens": 0,
            "ssm_run_tokens": 0,
            "sscan_run_tokens": 0,
            # dispatched steps over rows whose state layers run their
            # short convolution as the one-pass kernel
            # (engine.carry_kernel of the program's width); over steps,
            # 1.0 where every step is one such program
            "state_carry_kernel_steps": 0,
            # and those whose layers with a matrix a head advance it
            # through their step kernel (engine.step_kernel), not the
            # loop over rows in XLA
            "state_step_kernel_steps": 0,
            # tokens that entered a request's `output` (`batched_tokens`
            # counts the ROWS the programs were fed: prompt rows, and a
            # position once for every pass it is fed in)
            "output_tokens": 0,
            # a model that generates by diffusion over blocks (0 for
            # every other), a sequence a dispatched program: denoising
            # passes, commit passes, the rows fed in either, of those
            # the rows that held the mask id when fed (the rows whose
            # logits were needed), the tokens committed blocks added to
            # `output`, and blocks a preemption dropped unfinished
            "block_passes": 0,
            "block_commits": 0,
            "block_rows": 0,
            "block_masked_rows": 0,
            "block_tokens": 0,
            "block_restarts": 0,
        }
        self._phases = profiler.Phases("sched", "iteration", PHASES,
                                       sums=self.counters, wait="readback")
        self._iteration = 0
        self._it_rows, self._it_kind, self._it_delay0 = 0, "idle", 0.0
        # the step run() launched ahead of this iteration's readback
        self._it_ahead: Optional[_Step] = None
        self.spec_stats: Dict[str, float] = {
            "steps": 0, "verified_chunks": 0, "draft_tokens": 0,
            "accepted_tokens": 0, "draft_collapsed_steps": 0,
            "mean_accepted": 0.0,
        }
        # SLO-class breakdown of deadline rejections: the autoscaler's
        # premium-impact signal (inference/autoscaler.py) needs to know
        # WHOSE deadlines the fleet is failing, not just how many
        self.slo_rejections: Dict[str, int] = {}
        # the last LATENCY_WINDOW finished requests (bounded: a
        # long-lived server must not grow them, and its percentiles
        # should describe recent traffic)
        self._ttft: "deque[float]" = deque(maxlen=LATENCY_WINDOW)
        self._tpot: "deque[float]" = deque(maxlen=LATENCY_WINDOW)
        # set by ServingRouter (fault-point ctx + health identity);
        # standalone schedulers leave it None
        self.replica_index: Optional[int] = None
        # injected straggler time (resilience/faults 'delay' kind)
        # accrues here: virtual-clock drivers charge it to their
        # clocks, wall drivers fold it into the health observation
        self.fault_delay_s = 0.0
        if self.cfg.warmup:
            use_pres = self.scfg.needs_presence
            chunks = ((self.cfg.decode_chunk,)
                      if self.cfg.decode_chunk > 1 and not self._spec
                      else ())
            engine.warmup(sampling=sampling, decode_chunks=chunks,
                          presence=use_pres,
                          reveals=(self._reveal,) if B else ())
        # admit-config budget validation: the warmed per-bucket
        # footprints vs the per-device HBM budget (analysis/costmodel
        # S004) — logged once here, surfaced via metrics()/monitor
        self.budget_report = self._validate_budget()
        # memory-pressure governor + pinned-host spill tier
        # (inference/pressure.py, docs/fault_tolerance.md): opt-in —
        # with pressure off, preemption stays flush-and-recompute
        self.governor: Optional[PressureGovernor] = None
        self.spill_store = None
        self._spill_seq = 0
        pcfg = self.cfg.pressure
        if pcfg.enabled:
            budget = (int(self.cfg.hbm_budget_gb * 1e9)
                      if self.cfg.hbm_budget_gb > 0 else 0)
            if budget == 0 and getattr(engine, "warmup_footprints", {}):
                from ..platform.accelerator import get_accelerator

                budget = get_accelerator().hbm_per_device()
            self.governor = PressureGovernor(pcfg, engine,
                                             budget_bytes=budget)
            if pcfg.spill_enabled:
                refuse_for_pools(engine.cfg, "page_transfer")
                from .offload_store import HostKvSpillStore

                self.spill_store = HostKvSpillStore(
                    int(pcfg.spill_host_mb * 2**20))

    # -- admit-config budget validation ----------------------------------
    def _validate_budget(self):
        """S004 at admit-config time: the widest warmed decode bucket's
        static footprint (params + paged KV cache + scratch, from
        engine.warmup's cost reports) must fit the per-device HBM
        budget, and `max_num_batched_tokens` must not overcommit the KV
        pool's token capacity in a single iteration. Findings are
        logged, not raised — serving proceeds, CI reads the report."""
        from ..analysis.report import Finding, SanitizerReport

        eng = self.engine
        rep = SanitizerReport(label="serving/admit_budget")
        fps = getattr(eng, "warmup_footprints", {})
        if fps:
            if self.cfg.hbm_budget_gb > 0:
                budget = int(self.cfg.hbm_budget_gb * 1e9)
            else:
                from ..platform.accelerator import get_accelerator

                budget = get_accelerator().hbm_per_device()
            peak = max(f["peak_hbm_bytes"] for f in fps.values())
            if peak > budget:
                gib = 1 / 2**30
                rep.findings.append(Finding(
                    rule="S004", path="serving/warmup", line=0,
                    severity="error",
                    message=(
                        f"widest warmed decode bucket needs "
                        f"{peak * gib:.2f} GiB but the per-device budget "
                        f"is {budget * gib:.2f} GiB — steady-state "
                        "serving OOMs before the first request"),
                    fix_hint=(
                        "shrink num_kv_blocks/max_batch_size, quantize "
                        "or TP-shard the weights, or raise "
                        "hbm_budget_gb if the budget is wrong"),
                ))
        pool_tokens = eng.config.num_kv_blocks * eng.config.kv_block_size
        if self.cfg.max_num_batched_tokens > pool_tokens:
            rep.findings.append(Finding(
                rule="S004", path="serving/admission", line=0,
                severity="warning",
                message=(
                    f"max_num_batched_tokens "
                    f"{self.cfg.max_num_batched_tokens} exceeds the KV "
                    f"pool's {pool_tokens}-token capacity — one "
                    "iteration can overcommit the allocator and thrash "
                    "preemption"),
                fix_hint=("lower max_num_batched_tokens or grow "
                          "num_kv_blocks"),
            ))
        for f in rep.findings:
            log_dist(f"serving budget check: {f.message}", ranks=[0])
        return rep

    # -- request intake --------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               eos_token_id: Optional[int] = None,
               stream: Optional[int] = None,
               handoff: bool = False,
               deadline_s: Optional[float] = None,
               slo_class: Optional[str] = None) -> int:
        """Queue one request; returns its request id. The stream id
        (default: the rid) keys the request's PRNG stream — generate()
        passes 0..n-1 so a fixed seed reproduces its exact batch.
        handoff=True marks a disaggregated prefill request: it parks in
        handoff_ready after its first sampled token instead of decoding
        here (inference/router.py transfers its KV to a decode
        replica).

        SLO admission: deadline_s (modeled seconds of TTFT slack, the
        inference/pressure.py cost model's units) or slo_class (a name
        resolved through config.slo_classes) attaches a deadline; when
        the queue-depth TTFT estimate already exceeds it, the request
        is rejected HERE — finish_reason='deadline', done=True, zero KV
        blocks touched — instead of queueing to time out after
        consuming pool capacity."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if handoff:
            refuse_for_pools(self.engine.cfg, "page_transfer")
            refuse_block_diffusion(self.engine.cfg, "handoff")
        if len(prompt) > self.engine.config.max_seq_len:
            raise ValueError(
                f"prompt of {len(prompt)} > max_seq_len "
                f"{self.engine.config.max_seq_len}")
        deadline = float(deadline_s) if deadline_s is not None else None
        if deadline is None and slo_class is not None:
            deadline = self.cfg.slo_classes.get(slo_class)
            if deadline is None:
                raise ValueError(
                    f"unknown slo_class {slo_class!r}; configure it in "
                    "ServingSchedulerConfig.slo_classes")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      eos_token_id=eos_token_id,
                      stream=int(stream) if stream is not None else rid,
                      arrival=time.perf_counter(),
                      handoff=bool(handoff),
                      deadline_s=deadline, slo_class=slo_class)
        if deadline is not None \
                and estimate_ttft(self, len(prompt)) > deadline:
            req.state = FINISHED
            req.finish_reason = "deadline"
            req.finish_t = time.perf_counter()
            self.finished[rid] = req
            self.counters["deadline_rejections"] += 1
            if slo_class is not None:
                self.slo_rejections[slo_class] = \
                    self.slo_rejections.get(slo_class, 0) + 1
            return rid
        if self.scfg.needs_presence:
            pres = np.zeros((self.engine.cfg.vocab_size,), np.uint8)
            toks = np.asarray(prompt, np.int64)
            pres[toks[(toks >= 0) & (toks < pres.size)]] = 1
            req.presence = pres
        self.waiting.append(req)
        return rid

    def requeue(self, req: Request) -> None:
        """Accept an EXISTING Request for (re)compute on this replica —
        the router's failover / handoff-capacity-fallback path. The
        request keeps its identity (stream, arrival, accepted output),
        so the re-drawn continuation is token-identical to never having
        moved: draws key on (seed, stream, position). The dead/source
        replica's KV is NOT flushed here — it is gone or already
        released by the caller."""
        req.uid = None
        req.fed = 0
        req.pending = None
        req.block = None
        req.state = WAITING
        req.preemptions += 1
        # a spill payload lives in the SOURCE scheduler's host tier —
        # unreachable from here; this replica recomputes
        req.spill_key = None
        # a foreign rid may collide with a local one: re-key it so
        # self.finished stays one-entry-per-request
        req.rid = self._next_rid
        self._next_rid += 1
        self.waiting.append(req)

    def release_spill(self, req: Request) -> None:
        """Drop req's host-tier spill payload from THIS scheduler's
        store. The ownership-transfer contract (analysis/lifecycle.py
        L001): a spill payload lives in the SOURCE scheduler's host
        tier, and requeue() on a DESTINATION scheduler cannot reach
        it — so every router path that moves a WAITING request off a
        replica (rebalance, drain, failover, shed) must release the
        payload here first or the bytes strand until process exit."""
        if req.spill_key is None:
            return
        if self.spill_store is not None:
            self.spill_store.discard(req.spill_key)
            self.counters["spill_releases"] += 1
        req.spill_key = None

    def adopt(self, req: Request, payload: Dict[str, Any]) -> None:
        """Admit a request whose KV arrives by block transfer
        (engine.import_kv payload): a prefill-complete sequence starts
        RUNNING here with its first token pending, a MID-PREFILL one
        (a drain migration caught between chunks — the payload carries
        only its written blocks, like a spill) re-reserves the rest of
        its base and continues chunking — no recompute either way.
        Raises RuntimeError when the batch or the KV pool cannot take
        it (callers fall back to requeue())."""
        refuse_block_diffusion(self.engine.cfg, "handoff")
        if len(self.active) >= self.engine.config.max_batch_size:
            raise RuntimeError(
                f"decode replica at max_batch_size "
                f"{self.engine.config.max_batch_size}")
        uid = self._alloc_uid()
        try:
            self.engine.import_kv(uid, payload)  # may raise: pool exhausted
        except Exception:
            # a failed import must not leak half-allocated blocks —
            # callers fall back to requeue-for-recompute on this engine
            if self.engine.state.get(uid) is not None:
                self.engine.flush(uid)
            raise
        seen = int(payload["seen_tokens"])
        if req.output and seen == len(req.base) - 1:
            req.pending = req.output[-1]
            req.state = RUNNING
        else:
            # mid-prefill: chunked prefill continues at `fed` (the
            # _resume_from_spill geometry — import laid down only the
            # written blocks, so room for the remainder is re-reserved
            # exactly as admission would have)
            try:
                self.engine.state.extend(uid, len(req.base) - seen)
            except KVCacheExhaustedError:
                self.engine.flush(uid)
                raise
            req.pending = None
            req.state = PREFILL
        req.uid = uid
        req.rid = self._next_rid
        self._next_rid += 1
        req.handoff = False
        req.fed = seen
        self.active.append(req)
        self._stamp_admission(req)
        self.counters["adopted"] += 1
        self.counters["admitted"] += 1

    def _stamp_admission(self, req: Request) -> None:
        if req.admit_t is None:
            req.admit_t = time.perf_counter()
            self.counters["queue_wait_s"] += req.admit_t - req.arrival

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.active)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    # -- uid / capacity management ---------------------------------------
    def _alloc_uid(self) -> int:
        taken = set(self.engine.state.tracked_uids)
        cand = 0
        while cand in taken:
            cand += 1
        return cand

    def _try_spill(self, victim: Request) -> bool:
        """Preempt-to-host (RED pressure): export the victim's paged KV
        through the serialized-gather handoff path (digest envelope
        attached) into the bounded pinned-host tier, so re-admission
        resumes with an import_kv scatter instead of recomputing the
        whole prefix. Returns False — the flush-and-recompute fallback
        — when pressure is below RED, the tier lacks room, or the
        export/put leg fails (including an injected 'spill.io'
        fault)."""
        store = self.spill_store
        gov = self.governor
        if store is None or gov is None:
            return False
        # the level was set at dispatch START; admission may have
        # filled the pool since (that is WHY this preemption fired) —
        # the spill decision reads instantaneous occupancy as well,
        # so RED-grade pressure inside an iteration still spills
        if gov.level < RED and \
                gov.occupancy() < gov.cfg.red * gov.watermark_scale():
            return False
        seq = self.engine.state.get(victim.uid)
        if seq is None or seq.seen_tokens < 1:
            return False
        nbytes = self.engine.kv_payload_nbytes(len(seq.blocks))
        if store.used_bytes + nbytes > store.capacity_bytes:
            self.counters["spill_rejects"] += 1
            return False
        key = self._spill_seq
        self._spill_seq += 1
        try:
            payload = self.engine.export_kv(victim.uid)
            if not store.put(key, payload):
                self.counters["spill_rejects"] += 1
                return False
        except Exception as e:
            log_dist(
                f"serving scheduler: KV spill of rid={victim.rid} "
                f"failed ({e!r}); falling back to recompute", ranks=[0])
            self.counters["spill_fallbacks"] += 1
            return False
        victim.spill_key = key
        self.counters["spills"] += 1
        return True

    def _preempt(self, victim: Request) -> None:
        """Flush the victim's KV blocks and re-queue it (front of the
        queue: it has the oldest claim among preempted). Under RED
        pressure with the spill tier on, the pages are exported to host
        FIRST (spill_key set), so re-admission resumes by block import
        instead of recompute — token-identical either way, since draws
        key on (seed, stream, position)."""
        self._try_spill(victim)
        self.engine.state.flush(victim.uid)
        victim.uid = None
        victim.fed = 0
        victim.pending = None
        if victim.block is not None:
            # the block in flight is dropped: the request resumes at its
            # last committed block (output holds whole blocks alone)
            self.counters["block_restarts"] += 1
            victim.block = None
        victim.state = WAITING
        victim.preemptions += 1
        self.counters["preemptions"] += 1
        self.active.remove(victim)
        self.waiting.appendleft(victim)

    def _reserve(self, req: Request, n: int) -> bool:
        """Reserve KV room for n more tokens of req, preempting the
        youngest OTHER active sequence under pressure. Returns False
        when req itself was preempted or finished (its row must be
        dropped from this iteration).

        Starvation bound (config.max_preemptions): a request preempted
        that many times is PROTECTED — skipped in victim selection —
        so two similar-age requests can no longer ping-pong
        (preempt + requeue-front) forever under sustained pressure;
        when every eligible victim is protected, the REQUESTER yields
        instead, and the protected sequences run to completion."""
        bound = self.cfg.max_preemptions
        while True:
            try:
                self.engine.state.extend(req.uid, n)
                return True
            except KVCacheExhaustedError:
                victim = None
                if self.active[-1] is not req:
                    # youngest-first among the OTHER active sequences,
                    # skipping protected ones (preemptions >= bound)
                    for r in reversed(self.active):
                        if r is req:
                            continue
                        if bound and r.preemptions >= bound:
                            continue
                        victim = r
                        break
                if victim is None:
                    if len(self.active) == 1:
                        # alone and still does not fit: genuine capacity
                        # exhaustion, not contention — finish truncated
                        # instead of raising (the generate() behavior
                        # this scheduler replaces)
                        self._finish(req, "capacity")
                        return False
                    if self.active[-1] is not req:
                        # protection forced the requester to yield
                        self.counters["starvation_protected"] += 1
                    self._preempt(req)
                    return False
                self._preempt(victim)

    def _release(self, req: Request) -> None:
        """Give req's KV blocks back to the allocator and its place in
        the batch to admission. _finish does it as it retires a
        request; the look-ahead does it one step EARLIER for a request
        whose token in flight is its last by the counts, so that the
        place refills in the iteration it would have."""
        if req.uid is not None and self.engine.state.get(req.uid) is not None:
            self.engine.flush(req.uid)
        req.uid = None
        if req in self.active:
            self.active.remove(req)

    def _finish(self, req: Request, reason: str) -> None:
        """Retire NOW: blocks go back to the allocator at the iteration
        the sequence finishes, not when the batch drains."""
        self._release(req)
        if req.spill_key is not None and self.spill_store is not None:
            # a spilled payload whose request retires another way
            # (shed, length while queued) must not strand host bytes
            self.spill_store.discard(req.spill_key)
            req.spill_key = None
        req.state = FINISHED
        req.finish_reason = reason
        req.finish_t = time.perf_counter()
        self.finished[req.rid] = req
        self.counters["finished"] += 1
        if req.first_token_t is not None:
            self._ttft.append(req.first_token_t - req.arrival)
            if len(req.output) > 1:
                self._tpot.append((req.finish_t - req.first_token_t)
                                  / (len(req.output) - 1))
        if profiler.active():
            self._record_request(req)

    @staticmethod
    def _record_request(req: Request) -> None:
        """The finished request's life as spans under its rid, from the
        stamps it carries (perf_counter seconds, the spans' clock)."""
        def ns(t):
            return int(t * 1e9)

        root = profiler.record(
            "request", ns(req.arrival), ns(req.finish_t), parent=0,
            rid=req.rid, reason=req.finish_reason, tokens=len(req.output),
            preemptions=req.preemptions)
        if req.admit_t is None:
            return
        profiler.record("request.queue", ns(req.arrival), ns(req.admit_t),
                        parent=root, rid=req.rid)
        if req.first_token_t is not None:
            profiler.record("request.prefill", ns(req.admit_t),
                            ns(req.first_token_t), parent=root, rid=req.rid)
            profiler.record("request.decode", ns(req.first_token_t),
                            ns(req.finish_t), parent=root, rid=req.rid)

    # -- admission -------------------------------------------------------
    def _resume_from_spill(self, req: Request) -> str:
        """Re-admit a spilled preemption victim by importing its host-
        tier KV payload (a donated scatter — no recompute). Returns
        'resumed' (admitted RUNNING/PREFILL), 'recompute' (payload
        lost/corrupt/faulted: fall through to normal admission), or
        'defer' (the pool cannot take the pages right now: the payload
        is back in the tier and the caller stops admitting — recompute
        would need the same blocks, so waiting is strictly better)."""
        key, req.spill_key = req.spill_key, None
        store = self.spill_store
        try:
            payload = store.get(key)
        except Exception as e:
            log_dist(
                f"serving scheduler: spill readback of rid={req.rid} "
                f"failed ({e!r}); recomputing", ranks=[0])
            self.counters["spill_fallbacks"] += 1
            return "recompute"
        if payload is None:
            self.counters["spill_fallbacks"] += 1
            return "recompute"
        uid = self._alloc_uid()
        try:
            self.engine.import_kv(uid, payload)
        except HandoffIntegrityError as e:
            # a bit flipped while the payload sat in host DRAM: the
            # digest envelope catches it BEFORE any page is scattered
            log_dist(
                f"serving scheduler: spilled KV of rid={req.rid} "
                f"failed digest verification ({e}); recomputing",
                ranks=[0])
            self.counters["spill_integrity_failures"] += 1
            self.counters["spill_fallbacks"] += 1
            return "recompute"
        except KVCacheExhaustedError:
            if self.engine.state.get(uid) is not None:
                self.engine.flush(uid)
            req.spill_key = key
            store.restore(key, payload)
            return "defer"
        seen = int(payload["seen_tokens"])
        req.uid = uid
        req.fed = seen
        if self._block and seen == self._prompt_end(req):
            # every whole block of its base is in the pages: it opens
            # the block it had in flight again (_reserve grows the table)
            self._open_block(req)
        elif req.output and seen == len(req.base) - 1 and not self._block:
            # mid-decode victim: its next draw's input is the pending
            # (sampled, not-yet-fed) token — exactly where it stopped
            # (per-step _reserve grows the block table from here)
            req.pending = req.output[-1]
            req.state = RUNNING
        else:
            # mid-prefill victim: chunked prefill continues at `fed`.
            # The payload only carried the WRITTEN blocks; re-reserve
            # room for the rest of the base, as admission would have
            try:
                self.engine.state.extend(uid, self._prompt_end(req) - seen)
            except KVCacheExhaustedError:
                self.engine.flush(uid)
                req.spill_key = key
                store.restore(key, payload)
                return "defer"
            req.pending = None
            req.state = PREFILL
        self.active.append(req)
        self._stamp_admission(req)
        self.counters["admitted"] += 1
        self.counters["spill_resumes"] += 1
        return "resumed"

    def _red_admission_gate(self) -> bool:
        """Under RED pressure NEW admissions pause (the vLLM admission-
        watermark idea): every block a fresh prompt takes is a block a
        RUNNING sequence's growth will preempt it for one iteration
        later — admit-then-evict churn that burns prefill work for
        zero progress. Preempted requests re-entering (preemptions > 0
        or a spill to resume) are exempt: they ARE the in-flight work
        the gate protects. Instantaneous occupancy, not the iteration-
        start level: admissions themselves move it."""
        gov = self.governor
        if gov is None:
            return False
        return (gov.level >= RED
                or gov.occupancy() >= gov.cfg.red * gov.watermark_scale())

    def _admit(self) -> None:
        """Admit waiting requests while a slot and (prefix-cache-
        credited) KV room exist. fcfs stops at the first misfit; skip
        scans past it. Spilled preemption victims resume by block
        import (_resume_from_spill). Under RED pressure fresh
        admissions pause (_red_admission_gate); under BROWNOUT
        admission caps at pressure.brownout_admit per iteration."""
        eng = self.engine
        scanned: List[Request] = []
        admitted_now = 0
        cap = (self.cfg.pressure.brownout_admit
               if self.governor is not None
               and self.governor.level >= BROWNOUT else -1)
        while self.waiting:
            # a live sequence takes _rows_per_seq rows of an iteration
            if (len(self.active) * self._rows_per_seq
                    >= eng.config.max_batch_size):
                break
            if 0 <= cap <= admitted_now:
                break
            req = self.waiting.popleft()
            if req.spill_key is not None:
                outcome = self._resume_from_spill(req)
                if outcome == "resumed":
                    admitted_now += 1
                    continue
                if outcome == "defer":
                    self.waiting.appendleft(req)
                    break
                # 'recompute': fall through to the normal path below
            if req.preemptions == 0 and self._red_admission_gate():
                # fresh work waits out the RED window; preempted
                # requests re-enter ahead of it (queue front)
                self.waiting.appendleft(req)
                break
            base = req.base
            if len(base) > eng.config.max_seq_len:
                # recompute target overfills the context window —
                # nothing further can be drawn
                self._finish(req, "length")
                continue
            uid = self._alloc_uid()
            # a block-diffusion model's prompt is fed, and credited by
            # the prefix index, in whole blocks; the remainder opens the
            # first block it generates
            base = base[:self._prompt_end(req)]
            try:
                _, match = eng.state.extend(uid, len(base), token_ids=base,
                                            align=self._rows_per_seq)
            except KVCacheExhaustedError as short:
                if eng.state.num_rings:
                    self.counters[f"admit_waits_{short.pool}_pool"] += 1
                if not self.active:
                    # alone against an empty pool and still no fit: the
                    # prompt needs more blocks than the cache holds —
                    # permanent, not contention
                    self._finish(req, "capacity")
                    continue
                if self.cfg.admission == "fcfs":
                    self.waiting.appendleft(req)
                    break
                scanned.append(req)
                continue
            if match.cow is not None:
                # shared full-match tail: clone the page before the
                # recomputed last token writes into it
                eng._copy_block(*match.cow)
            req.uid = uid
            req.fed = eng.state.get(uid).seen_tokens  # = match.n_cached
            req.n_cached += match.n_cached
            if eng.cache.state:
                self.counters["state_slot_resets"] += 1
                self.counters["state_prefix_credits_refused"] += (
                    match.declined > 0)
            req.state = PREFILL
            if self._block and req.fed == len(base):
                self._open_block(req)  # a prompt of less than a block
            self.active.append(req)
            self._stamp_admission(req)
            self.counters["admitted"] += 1
            admitted_now += 1
        for req in reversed(scanned):  # preserve arrival order
            self.waiting.appendleft(req)

    # -- a model that generates by diffusion over blocks -----------------
    def _prompt_end(self, req: Request) -> int:
        """How many of req's base tokens are fed as prompt before it
        runs: all of them, or for a block-diffusion model its whole
        blocks (the remainder is the fixed head of the first block)."""
        n = len(req.base)
        return n - n % self._block if self._block else n

    def _open_block(self, req: Request) -> None:
        """req, its base's whole blocks fed, starts (or starts again)
        the block after them: the base's remainder, then the mask id.
        A block the context has no room for ends the request."""
        B = self._block
        fixed = req.base[self._prompt_end(req):]
        req.block = _Block(
            tokens=fixed + [self.engine.cfg.mask_token_id] * (B - len(fixed)),
            n_fixed=len(fixed), masked=B - len(fixed))
        req.state = RUNNING
        if self._prompt_end(req) + B > self.engine.config.max_seq_len:
            self._finish(req, "length")

    def _block_dispatched(self, req: Request, chunk, denoise: bool) -> None:
        """A pass of req's block went out: what it settles by counts.
        A denoising pass reveals its share of the masked positions
        whatever they turn out to be. The commit pass advances the
        sequence by the block; the block is accepted here if the host
        holds its tokens (chunk), else when the pass that reveals the
        last of them is read back (_finalize)."""
        blk, c, B = req.block, self.counters, self._block
        c["block_rows"] += B
        if denoise:
            c["block_passes"] += 1
            c["block_masked_rows"] += blk.masked
            blk.masked -= min(self._reveal, blk.masked)
            return
        c["block_commits"] += 1
        if chunk[0] is None:
            self.engine.state.commit(req.uid, B)
            blk.committing = True
            return
        self.engine.state.commit(req.uid, B,
                                 token_ids=[int(t) for t in chunk])
        self._accept_block(req, time.perf_counter())

    def _accept_block(self, req: Request, now: float) -> None:
        """req's block is committed and its tokens are on the host:
        `output` grows by what the block generated (_accept's rules, a
        block at a time: cut at EOS, cut at the output budget), and the
        request ends or opens its next block."""
        blk = req.block
        new = blk.tokens[blk.n_fixed:][:req.max_new_tokens - len(req.output)]
        eos = req.eos_token_id is not None and req.eos_token_id in new
        if eos:
            new = new[:new.index(req.eos_token_id) + 1]
        if req.first_token_t is None:
            req.first_token_t = now
        req.output.extend(new)
        req.block = None
        self.counters["block_tokens"] += len(new)
        self.counters["output_tokens"] += len(new)
        if eos:
            self._finish(req, "eos")
        elif len(req.output) >= req.max_new_tokens:
            self._finish(req, "length")
        else:
            self._open_block(req)

    def _unmask_part(self, logits_dev, toks_dev, sample_rows, positions,
                     bucket: int) -> Any:
        """Device-side epilogue of the denoising passes in one
        dispatch's [bucket, V] logits (sampling.block_unmask): every
        masked row of the blocks in `sample_rows` samples a token and
        its confidence, the most confident of a block are revealed.
        Returns the device array of the tokens EVERY row is fed next
        pass, not read back here. A draw's counter is its position and
        the pass (the masked positions left tell it), so a recomputed
        block draws what it drew."""
        eng, B = self.engine, self._block
        ph = self._phases
        ph.mark("build")
        streams = np.zeros((bucket,), np.uint32)
        active = np.zeros((bucket,), bool)
        for req, row in sample_rows:
            streams[row:row + B] = req.stream
            active[row + req.block.n_fixed:row + B] = True
            positions[row:row + B] += (
                eng.config.max_seq_len * (B - req.block.masked))
        eng.recompile_tracker.record(
            f"serving_unmask[w{bucket},r{self._reveal}]", (positions,))
        ph.mark("launch", kind="unmask", rows=bucket)
        return eng._block_unmask_fn(self.scfg, self._reveal)(
            logits_dev, toks_dev, eng._dev(active),
            eng._row_keys(self.seed, streams), eng._dev(positions))

    # -- dispatch construction -------------------------------------------
    def _sample_part(self, logits_dev, sample_rows, bucket: int) -> Any:
        """Device-side sampling epilogue over one dispatch's [bucket, V]
        logits (mirrors put().sample_rows: one compiled program per
        bucket width). Returns the device token array — NOT read back
        here; the caller decides when the readback lands."""
        eng, scfg = self.engine, self.scfg
        ph = self._phases
        ph.mark("build")
        streams = np.zeros((bucket,), np.uint32)
        steps = np.zeros((bucket,), np.int32)
        for req, row in sample_rows:
            streams[row] = req.stream
            # draw counter = the sampled token's POSITION = seen_tokens
            # after this dispatch's commit (put()/generate() contract)
            steps[row] = eng.state.get(req.uid).seen_tokens
        if scfg.needs_presence:
            V = self.engine.cfg.vocab_size
            pres = np.zeros((bucket, V), np.uint8)
            for req, row in sample_rows:
                pres[row] = req.presence
            eng.recompile_tracker.record(
                f"serving_sample[w{bucket}]", (steps, pres))
            ph.mark("launch", kind="sample", rows=bucket)
            return eng._sample_fn(scfg, True)(
                logits_dev, eng._row_keys(self.seed, streams),
                eng._dev(steps), eng._dev(pres))
        eng.recompile_tracker.record(f"serving_sample[w{bucket}]", (steps,))
        ph.mark("launch", kind="sample", rows=bucket)
        return eng._sample_fn(scfg, False)(
            logits_dev, eng._row_keys(self.seed, streams), eng._dev(steps))

    def _dispatch_wave(self, reqs: List[Request]) -> List[_Part]:
        """Whole-prompt prefill waves (put()'s grouped compiled waves):
        blocks were reserved at admission; each wave is one program over
        a (batch-bucket, token-bucket) and samples its last-token rows
        on device."""
        eng = self.engine
        ph = self._phases
        ph.mark("build")
        self._it_kind = "wave"
        reqs = sorted(reqs, key=lambda r: len(r.base))
        groups: Dict[int, List[Request]] = {}
        for r in reqs:
            groups.setdefault(
                _bucket(len(r.base), eng.config.min_prefill_bucket), []
            ).append(r)
        cap = 1 << (eng.config.max_batch_size.bit_length() - 1)
        waves = [g[w0:w0 + cap] for _, g in sorted(groups.items())
                 for w0 in range(0, len(g), cap)]
        parts: List[_Part] = []
        for wave in waves:
            ph.mark("build")
            tp = _bucket(max(len(r.base) for r in wave),
                         eng.config.min_prefill_bucket)
            bp = _bucket(len(wave), 1)
            toks_b = np.zeros((bp, tp), np.int32)
            n_real = np.zeros((bp,), np.int32)
            tables = np.zeros((bp, eng.config.blocks_per_seq), np.int32)
            slots = np.full((bp,), -1, np.int32)
            rings = np.full((bp,), -1, np.int32)
            for row, r in enumerate(wave):
                base = r.base
                toks_b[row, :len(base)] = base
                n_real[row] = len(base)
                tables[row] = eng.state.block_table(
                    [r.uid], eng.config.blocks_per_seq)[0]
                seq = eng.state.get(r.uid)
                slots[row], rings[row] = seq.slot, seq.ring
            eng.recompile_tracker.record(
                f"serving_prefill[b{bp},t{tp}]", (toks_b, n_real, tables))
            ph.mark("launch", kind="wave", rows=int(n_real.sum()))
            logits, eng.cache = eng._prefill_batch_fn(bp, tp)(
                eng.params, eng.cache, eng._dev(toks_b),
                eng._dev(n_real), eng._dev(tables),
                *eng.state_args(slots, rings))
            ph.mark("commit")
            sample_rows = []
            for row, r in enumerate(wave):
                eng.state.commit(r.uid, len(r.base), token_ids=r.base)
                r.fed = len(r.base)
                r.state = RUNNING  # pending arrives at finalize
                sample_rows.append((r, row))
            tok_dev = self._sample_part(logits, sample_rows, bp)
            ph.mark("commit")
            parts.append(_Part("wave", sample_rows, tok_dev))
            self.counters["wave_prefills"] += len(wave)
            self._count_tokens(int(n_real.sum()), bp * tp)
            self._count_rings(n_real[:len(wave)])
            self._count_cross(int(n_real.sum()), len(wave))
            self._count_state(n_real[:len(wave)].tolist(), bp * tp,
                              reads=False)
            self._it_rows += int(n_real.sum())
        return parts

    def _count_tokens(self, n: int, width: int, ctx=None,
                      steps: int = 1, tables=None) -> None:
        """Tokens a dispatched program batched out of its `width` token
        rows, the token-expert pairs they make in a routed layer and
        whether that layer streams its experts in one pass (over every
        row, or over each expert's own), and, where
        the program reads the paged cache, the live KV blocks of its
        rows: ctx is the host array of context lengths it was launched
        with (0 = pad row), each row one token longer in every further
        fused step. tables: the rows' block tables where rows may share
        one (the shared-table program), for what its walk fetches."""
        self.counters["batched_tokens"] += n
        cfg = self.engine.cfg
        if cfg.n_experts > 0:
            self.counters["moe_token_expert_pairs"] += n * cfg.moe_top_k
            path = self.engine.expert_path(width)
            self.counters["moe_stream_steps"] += path == "stream"
            self.counters["moe_grouped_steps"] += path == "grouped"
        if ctx is not None:
            live = ctx[ctx > 0][:, None] + np.arange(steps)
            bs = self.engine.config.kv_block_size
            blocks = int(np.sum(-(-live // bs)))
            self.counters["kv_live_blocks"] += blocks
            if tables is not None and cfg.is_latent:
                # the latent walk's own rule: a table's blocks once a
                # table, a uniform tile's rows in one visit
                blocks, tiled = latent_walk_reads(tables, ctx, bs,
                                                  cfg.n_heads)
                self.counters["mla_grouped_rows"] += tiled
            elif tables is not None:
                blocks, rode = walk_reads(
                    tables, ctx, bs, cfg.n_heads // kv_pool_shape(cfg)[0],
                    kv_pool_pack(self.engine.cache, cfg))
                self.counters["kv_grouped_rows"] += rode
            self.counters["kv_block_reads"] += blocks
            if cfg.is_latent:
                self.counters["mla_cache_tokens"] += int(np.sum(live))
        if cfg.n_state_layers:
            self.counters["state_slots_live"] += self.engine.state.n_tracked

    def _count_rings(self, contexts) -> None:
        """What a dispatched step's SEQUENCES read of the two kinds of
        K/V a model of mixed windows holds: `contexts` is each one's
        context after the step (a chunk's rows are one read, by its
        longest row); and what the layers that read ANOTHER layer's
        pool walk of it."""
        state = self.engine.state
        readers = self.engine.cfg.n_kv_reader_layers
        if not (state.num_rings or readers):
            return
        ctx = np.asarray(contexts, np.int64)
        self.counters["kv_shared_tokens"] += int(ctx.sum()) * readers
        if not state.num_rings:
            return
        self.counters["kv_full_tokens"] += int(ctx.sum())
        self.counters["kv_window_tokens"] += int(
            np.minimum(ctx, self.engine.cfg.widest_window).sum())
        self.counters["kv_rings_live"] += state.rings_live
        self.counters["kv_ring_blocks_recycled"] += (
            state.rings_recycled - self._rings_recycled_seen)
        self._rings_recycled_seen = state.rings_recycled

    def _count_cross(self, run: int, needed: int) -> None:
        """The token rows of a dispatched program that went through the
        layers that read another layer's K/V, and those of them whose
        logits were read: what skipping those layers for the other rows
        would save (a model without such layers counts neither)."""
        if self.engine.cfg.n_kv_reader_layers:
            self.counters["cross_rows_run"] += run
            self.counters["cross_rows_needed"] += needed

    def _count_state(self, runs: Sequence[int], width: int, steps: int = 1,
                     reads: bool = True) -> None:
        """What a dispatched program of a model with recurrent state
        moves of its sequences' slots: `runs` holds the tokens of each
        sequence it advances, `width` its token rows. A step over rows
        reads and writes each slot once a layer (`reads`; through the
        convolution's and the matrices' kernels or not, by its width),
        a whole-prompt prefill writes it."""
        cfg = self.engine.cfg
        if not cfg.n_state_layers:
            return
        self.counters["state_carry_kernel_steps"] += (
            reads and self.engine.carry_kernel(width))
        self.counters["state_step_kernel_steps"] += (
            reads and self.engine.step_kernel(width))
        self.counters["state_bytes_moved"] += (
            len(runs) * steps * (2 if reads else 1)
            * self.engine.state_slot_bytes)
        for counter in {_RUN_TOKENS[k] for k in cfg.layer_types
                        if k in _RUN_TOKENS}:
            self.counters[counter] += sum(r for r in runs if r > 1)

    def _dispatch_mixed(self, rows, ahead_of: Optional[_Step] = None,
                        src: Optional[Dict[int, int]] = None
                        ) -> Optional[_Part]:
        """One compiled decode program over the iteration's ragged rows:
        1-token decode rows + multi-token prefill chunk rows (the
        Sarathi piggyback). rows: [(req, chunk, sample)]. A chunk of
        [None] is a decode row whose token the host does not hold yet:
        it is row src[req.rid] of the sampled tokens of `ahead_of`, the
        step still in flight, and is gathered on the device.

        A block-diffusion model's RUNNING request gives its block's B
        rows (sample: a denoising pass; not: the commit pass), [None] *
        B where the device holds them: rows src[req.rid] .. + B of
        `ahead_of`. Its rows come before every prompt chunk's, so that
        a block is B adjacent rows from a multiple of B on
        (sampling.block_unmask takes them as a group)."""
        eng = self.engine
        B = self._block
        ph = self._phases
        ph.mark("build")
        n_rows = sum(len(c) for _, c, _ in rows)
        if n_rows == 0:
            return None
        if self._it_kind == "idle":
            self._it_kind = "mixed"
        self._it_rows += n_rows
        sp = _bucket(n_rows, 8)
        toks = np.zeros((sp,), np.int32)
        srcs = np.full((sp,), -1, np.int32)  # >= 0: a row of ahead_of
        ctx = np.zeros((sp,), np.int32)  # pad rows: ctx 0 = inert
        tables = np.full((sp, eng.config.blocks_per_seq),
                         eng.pad_block, np.int32)
        slots = np.full((sp,), -1, np.int32)  # each row's state slot
        # and its ring (a model of mixed windows alone)
        rings = (np.full((sp,), -1, np.int32) if eng.state.num_rings
                 else None)
        # a block-diffusion model's rows: where each stands, apart from
        # what it sees
        positions = np.zeros((sp,), np.int32) if B else None
        sample_rows: List[Tuple[Request, int]] = []
        row = 0
        for req, chunk, sample in rows:
            seq = eng.state.get(req.uid)
            base_seen = seq.seen_tokens
            table = eng.state.block_table(
                [req.uid], eng.config.blocks_per_seq, eng.pad_block)[0]
            slots[row:row + len(chunk)] = seq.slot
            if rings is not None:
                rings[row:row + len(chunk)] = seq.ring
            for j, tok in enumerate(chunk):
                if tok is None:
                    srcs[row] = src[req.rid] + (j if B else 0)
                else:
                    toks[row] = int(tok)
                ctx[row] = base_seen + j + 1
                tables[row] = table
                row += 1
            if B:
                positions[row - len(chunk):row] = base_seen + np.arange(
                    len(chunk))
            if sample:  # a block's FIRST row, a causal chunk's last
                sample_rows.append((req, row - (len(chunk) if B else 1)))
        if B:  # a row sees through the end of its block
            ctx[:n_rows] = eng.block_ctx(positions[:n_rows])
        unique = not B and all(len(c) == 1 for _, c, _ in rows)
        eng.recompile_tracker.record(
            f"serving_decode[w{sp},u{int(unique)}]", (toks, tables, ctx))
        prev_toks = None  # the sampled tokens some row is gathered from
        if (srcs >= 0).any():
            prev_toks = ahead_of.parts[0].tok_dev
            eng.recompile_tracker.record(
                f"serving_tokens[w{prev_toks.shape[0]},w{sp}]", (srcs,))
        ph.mark("launch", kind="mixed", rows=n_rows,
                ahead=int(ahead_of is not None))
        toks_dev = eng._dev(toks)
        if prev_toks is not None:
            toks_dev = eng._next_tokens_fn()(prev_toks, toks_dev,
                                             eng._dev(srcs))
        logits, eng.cache = eng._decode_fn(sp, unique)(
            eng.params, eng.cache, toks_dev, eng._dev(tables),
            eng._dev(ctx), *eng.position_args(positions),
            *eng.state_args(slots, rings))
        # host bookkeeping overlaps the in-flight device program
        ph.mark("commit")
        blocks = ({req.rid: req.block for req, _ in sample_rows} if B
                  else None)
        for req, chunk, sample in rows:
            if B and req.state == RUNNING:
                self._block_dispatched(req, chunk, sample)
                continue
            if chunk[0] is None:
                # the id follows when ahead_of is read back (_accept)
                eng.state.commit(req.uid, 1)
                ahead_of.settled[req.rid] = False
                continue
            eng.state.commit(req.uid, len(chunk),
                             token_ids=[int(t) for t in chunk])
            if req.state == PREFILL:
                req.fed += len(chunk)
                if B and req.fed == self._prompt_end(req):
                    self._open_block(req)
                elif req.fed == len(req.base):
                    req.state = RUNNING
        # mid-prompt chunks produce no token: skip the sample epilogue
        if not sample_rows:
            tok_dev = None
        elif B:
            tok_dev = self._unmask_part(logits, toks_dev, sample_rows,
                                        positions.copy(), sp)
        else:
            tok_dev = self._sample_part(logits, sample_rows, sp)
        ph.mark("commit")
        self._count_tokens(n_rows, sp, ctx, tables=tables)
        if rings is not None or eng.cfg.n_kv_reader_layers:
            self._count_rings([eng.state.get(req.uid).seen_tokens
                               for req, _, _ in rows])
        self._count_cross(n_rows, len(sample_rows))
        self._count_state([len(c) for _, c, _ in rows], sp)
        return _Part("mixed", sample_rows, tok_dev, blocks=blocks)

    def _dispatch_fused(self, running: List[Request], C: int) -> _Part:
        """Steady-state fused decode: C steps per compiled program
        (model.decode_multi) — sampled tokens never leave the device
        between the C steps; one [C, width] readback per chunk."""
        eng, scfg = self.engine, self.scfg
        ph = self._phases
        ph.mark("build")
        self._it_kind = "fused"
        self._it_rows += len(running) * C
        width = _bucket(len(running), 8)
        toks = np.zeros((width,), np.int32)
        ctx = np.zeros((width,), np.int32)
        steps = np.zeros((width,), np.int32)
        streams = np.zeros((width,), np.uint32)
        tables = np.full((width, eng.config.blocks_per_seq),
                         eng.pad_block, np.int32)
        V = eng.cfg.vocab_size
        use_sampler = not (scfg.greedy and not scfg.needs_presence)
        pres_rows = (np.zeros((width, V), np.uint8)
                     if scfg.needs_presence and use_sampler else None)
        sample_rows = []
        slots = np.full((width,), -1, np.int32)
        rings = np.full((width,), -1, np.int32)
        for r, req in enumerate(running):
            seq = eng.state.get(req.uid)
            base = seq.seen_tokens
            slots[r], rings[r] = seq.slot, seq.ring
            eng.state.extend(req.uid, C)  # capacity pre-checked by caller
            toks[r] = req.pending
            ctx[r] = base + 1
            steps[r] = base + 1  # first in-chunk draw's position
            streams[r] = req.stream
            if pres_rows is not None:
                pres_rows[r] = req.presence
            sample_rows.append((req, r))
        tables[:len(running)] = eng.state.block_table(
            [r.uid for r in running], eng.config.blocks_per_seq,
            eng.pad_block)
        eng.recompile_tracker.record(
            f"serving_fused[w{width},c{C}]", (toks, tables, ctx, steps))
        ph.mark("launch", kind="fused", rows=len(running) * C)
        fn = eng.decode_multi_fn(
            width, C, sampling=scfg if use_sampler else None,
            with_presence=pres_rows is not None)
        args = [eng.params, eng.cache, eng._dev(toks), eng._dev(tables),
                eng._dev(ctx)]
        if use_sampler:
            args.append(eng._row_keys(self.seed, streams))
            args.append(eng._dev(steps))
            if pres_rows is not None:
                args.append(eng._dev(pres_rows))
        args += eng.state_args(slots, rings)
        gen, _, eng.cache, _ = fn(*args)
        ph.mark("commit")
        for req in running:
            eng.state.commit(req.uid, C)
        self._count_tokens(len(running) * C, width, ctx, steps=C)
        for i in range(C):
            self._count_rings(ctx[:len(running)] + i)
        self._count_cross(len(running) * C, len(running) * C)
        self._count_state([1] * len(running), width, steps=C)
        self.counters["fused_steps"] += 1
        return _Part("fused", sample_rows, gen, n_steps=C)

    # -- the scheduling iteration ----------------------------------------
    def _fused_depth(self, running: List[Request]) -> int:
        """How many fused steps the steady state supports (0 = use the
        mixed single-step program)."""
        if self.cfg.decode_chunk < 2 or self._spec or not running:
            return 0
        if any(r.state != RUNNING for r in self.active):
            return 0  # prefill in flight: keep chunks interleaving
        eng = self.engine
        C = min(
            self.cfg.decode_chunk,
            min(r.max_new_tokens - len(r.output) for r in running),
            min(eng.config.max_seq_len - 1
                - eng.state.get(r.uid).seen_tokens for r in running),
        )
        if C < 2:
            return 0
        if not eng.can_schedule([r.uid for r in running],
                                [C + 1] * len(running)):
            return 0  # pressure: step singly, preempting as needed
        return C

    def _brownout(self) -> bool:
        return (self.governor is not None
                and self.governor.level >= BROWNOUT)

    def _dispatch(self, ahead_of: Optional[_Step] = None,
                  governed: bool = False) -> Optional[_Step]:
        """Build and launch one iteration; returns None when idle.
        Host-side state (commits, next tables) is updated after the
        async launch, overlapping the device program. The pressure
        governor (when enabled) updates FIRST — its level steers this
        iteration's admission cap, victim policy, and brownout
        degradations.

        ahead_of is run()'s look-ahead: the previous step, launched and
        not read back. The iteration is then composed from host state
        and the COUNTS of that step (`_in_flight`), its decode rows
        taking their tokens from the step's device-resident array; None
        is also returned, and counted in `lookahead_fallbacks`, when
        this iteration turns out to need the step's VALUES first (a
        wave or fused program, a reservation that has to preempt).
        Nothing it did until then has to be undone: the early releases
        and the admissions are the ones the readback would have led
        to, and a reservation is taken once however often it is asked
        for. The governor's update is not one of those: it moves its
        level one step and trims parked blocks per call, so the
        dispatch that follows a handed-back look-ahead says `governed`
        and leaves it at the one update of this iteration."""
        ph = self._phases
        ph.mark("admit")
        src = self._in_flight(ahead_of) if ahead_of is not None else {}
        if self.governor is not None and not governed:
            self.governor.update()
        self._admit()
        if not self.active:
            return None
        if ahead_of is None:  # a look-ahead step counts once launched
            self.counters["steps"] += 1
        ph.mark("select")
        if self._spec and not self._brownout():
            # BROWNOUT degrades speculation to plain decode: draft rows
            # burn batch capacity the pool no longer has, and greedy
            # verification == greedy decode token for token, so the
            # degradation is output-invisible
            return self._dispatch_spec()
        running = [r for r in self.active if r.state == RUNNING]
        prefill = [r for r in self.active if r.state == PREFILL]
        C = self._fused_depth(running)
        if C:
            if ahead_of is not None:
                return self._needs_readback()
            return _Step([self._dispatch_fused(running, C)],
                         len(running) * C)
        parts: List[_Part] = []
        if prefill and self.cfg.prefill_mode == "wave":
            wave = [r for r in prefill if r.fed == 0]
            if wave:
                if ahead_of is not None:
                    return self._needs_readback()
                parts.extend(self._dispatch_wave(wave))
                ph.mark("select")
                prefill = [r for r in prefill if r.state == PREFILL]
        budget = self.cfg.max_num_batched_tokens
        row_budget = self.engine.config.max_batch_size
        pchunk = self.cfg.prefill_chunk
        if self.engine.state.num_rings:  # what a ring takes in one step
            pchunk = min(pchunk, self.engine.config.kv_block_size)
        n = self._rows_per_seq  # a block, or a token
        if self._brownout():
            # shrink the prefill chunk: under brownout every reserved
            # prefill token is pool pressure the decode rows pay for
            pchunk = max(1, pchunk // self.cfg.pressure.brownout_chunk_div)
            pchunk = max(n, pchunk - pchunk % n)  # whole blocks still
        rows: List[Tuple[Request, List[Optional[int]], bool]] = []
        for req in list(running):  # oldest first; preemption takes youngest
            if budget < n or row_budget < n:
                break
            if req.state != RUNNING:
                continue  # preempted/finished while reserving earlier rows
            if ahead_of is None:
                if not self._reserve(req, n):
                    continue
            else:
                # a reservation that does not fit has to preempt, and
                # preemption needs every token read: the normal order
                # resolves it (pressure, KVCacheExhaustedError, or a
                # row whose KV died under it), counted so a hot
                # fall-back loop shows in the metrics (L004)
                try:
                    self.engine.state.extend(req.uid, n)
                except RuntimeError:
                    self.counters["lookahead_fallbacks"] += 1
                    return None
            # a token still in flight stays on the device: None
            if self._block:
                # the block whole: a denoising pass while a position is
                # masked, then the commit pass
                rows.append((req, [None] * n if req.rid in src
                             else list(req.block.tokens),
                             req.block.masked > 0))
            else:
                rows.append(
                    (req, [None if req.rid in src else req.pending], True))
            budget -= n
            row_budget -= n
        for req in prefill:
            if budget < 1 or row_budget < 1:
                break
            if req.state != PREFILL:
                continue  # preempted while reserving decode rows
            remaining = req.base[req.fed:self._prompt_end(req)]
            c = min(pchunk, budget, row_budget, len(remaining))
            c -= c % n  # whole blocks
            if c < 1:
                continue
            chunk = remaining[:c]
            # a block-diffusion model's prompt yields no token
            rows.append((req, chunk, not self._block
                         and req.fed + c == len(req.base)))
            budget -= c
            row_budget -= c
        part = self._dispatch_mixed(rows, ahead_of, src)
        if part is not None:
            parts.append(part)
        if ahead_of is not None:
            if part is None:
                return self._needs_readback()
            self.counters["steps"] += 1
            self.counters["lookahead_steps"] += 1
        if not parts:
            return None
        return _Step(parts, sum(len(c) for _, c, _ in rows))

    # -- look-ahead: compose step n+1 while step n is in flight ----------
    def _ends_by_count(self, req: Request, n_out: int) -> bool:
        """Does req end by length once it holds n_out output tokens
        (output budget or context capacity)? Counts only: whatever the
        tokens are, so it is known before they are read."""
        return (n_out >= req.max_new_tokens
                or self.engine.state.get(req.uid).seen_tokens + 1
                >= self.engine.config.max_seq_len)

    def _can_look_ahead(self, prev: _Step) -> bool:
        """May the next iteration be composed and launched before prev
        is read back? Read from what the scheduler and prev ARE, not
        from anything a user sets. Not with speculation (verification
        is a host decision over the values), not with the presence
        bitmap (it needs the host token before the next draw), not on a
        mesh engine (a committed device array would re-specialise the
        mesh program), not over a wave or fused part (their tokens are
        laid out otherwise), and not while a handoff request's first
        token is in flight (it parks for the router instead of decoding
        here)."""
        if self._spec or self.scfg.needs_presence \
                or self.engine.mesh is not None:
            return False
        if len(prev.parts) != 1 or prev.parts[0].kind != "mixed":
            return False
        return not any(req.handoff for req, _ in prev.parts[0].sample_rows)

    def _in_flight(self, prev: _Step) -> Dict[int, int]:
        """What prev, launched and unread, settles by counts alone.
        Each request it samples for either ends with that token
        whatever its value (output budget, context capacity): its
        blocks and its place go back NOW, in the order the readback
        would have freed them, so admission refills the place in the
        iteration it would have. Or it carries on, unless the token
        turns out to be its EOS: its next input is row `src` of prev's
        token array. Returns {rid: src} for those."""
        src: Dict[int, int] = {}
        for req, row in prev.parts[0].sample_rows:
            if req.done:
                continue  # ended on EOS while this row was in flight
            if self._block:
                # a denoising pass ends nothing: the block's next pass
                # takes its rows from row `row` on (unless the block is
                # gone: preempted since)
                if req.block is prev.parts[0].blocks[req.rid]:
                    src[req.rid] = row
                continue
            if self._ends_by_count(req, len(req.output) + 1):
                self._release(req)
                prev.settled[req.rid] = True
            else:
                src[req.rid] = row
        return src

    def _needs_readback(self) -> None:
        """The look-ahead met an iteration it cannot compose without
        the values of the step in flight: nothing is launched, run()
        reads back and dispatches in the normal order."""
        self.counters["lookahead_fallbacks"] += 1
        return None

    # -- finalize: readback + accept + retire ----------------------------
    def _accept(self, req: Request, tok: int, now: float,
                ends: Optional[bool] = None) -> None:
        """Mirror generate()'s accept: append, then finish on EOS /
        output budget / context capacity — retiring immediately.
        `ends` is what a look-ahead dispatch settled about the length
        before this token was read (_Step.settled); None = decide here.
        Either way it is decided ONCE, from the same counts."""
        if req.first_token_t is None:
            req.first_token_t = now
        req.output.append(tok)
        self.counters["output_tokens"] += 1
        if req.presence is not None and 0 <= tok < req.presence.size:
            req.presence[tok] = 1
        if req.eos_token_id is not None and tok == req.eos_token_id:
            self._finish(req, "eos")
            return
        if ends is None:
            ends = self._ends_by_count(req, len(req.output))
        elif not ends:
            # fed on from the device a step ago: the id its commit
            # went without, so the prefix index registers what the
            # normal order would have
            self.engine.state.supply_tokens(req.uid, (tok,))
        if ends:
            self._finish(req, "length")
            return
        req.pending = tok
        if req.handoff:
            # disaggregated prefill: first token produced, KV complete —
            # park for the router's block transfer instead of decoding
            # here. Blocks stay allocated until export; finish-path
            # cases above (EOS / budget-of-1) never reach this, so a
            # request that needs no decode never pays a transfer.
            req.state = HANDOFF
            self.active.remove(req)
            self.handoff_ready.append(req)
            self.counters["handoffs"] += 1
            return
        req.state = RUNNING

    def _finalize(self, step: _Step) -> None:
        """Read the step's sampled tokens back and accept them: the
        host's first sight of each, so `first_token_t`, `finish_t` and
        `len(output)` are stamped and grown here whenever the step was
        launched."""
        for part in step.parts:
            if part.tok_dev is None:
                continue  # mid-prompt prefill chunks: nothing sampled
            self._phases.mark("readback")
            toks = serving_readback(part.tok_dev)
            self._phases.mark("accept")
            now = time.perf_counter()
            if part.blocks is not None:
                # denoising passes: the host's copy of each block, and
                # the block itself where its commit pass went out on
                # the device's copy
                B = self._block
                for req, row in part.sample_rows:
                    blk = part.blocks[req.rid]
                    if req.block is not blk:
                        continue  # dropped by a preemption since
                    blk.tokens = [int(t) for t in toks[row:row + B]]
                    if blk.committing:
                        self.engine.state.supply_tokens(req.uid, blk.tokens)
                        self._accept_block(req, now)
            elif part.kind == "fused":
                # gen [C, width]: distribute each row's chunk in order,
                # stopping at the first finish (generate()'s mid-chunk
                # EOS contract — later tokens in the row are discarded)
                for req, r in part.sample_rows:
                    if req.done:
                        continue
                    for j in range(part.n_steps):
                        self._accept(req, int(toks[j, r]), now)
                        if req.done:
                            break
            else:
                for req, row in part.sample_rows:
                    if req.done:
                        # the look-ahead row of a request that ended on
                        # EOS a step ago: nothing past EOS is kept
                        continue
                    self._accept(req, int(toks[row]), now,
                                 step.settled.get(req.rid))

    # -- speculative iteration (generate_speculative control plane) ------
    def _dispatch_spec(self) -> Optional[_Step]:
        """Prompt-lookup self-speculation under scheduler lifecycle:
        prefill via waves, then each iteration verifies
        [pending + drafts] chunks through engine._verify_chunks and
        accepts the greedy-consistent prefix. Synchronous per step (the
        verification IS a host decision), so no _Part machinery."""
        eng = self.engine
        ph = self._phases
        prefill = [r for r in self.active if r.state == PREFILL]
        if prefill:
            # whole prompts through compiled waves; prefix-cache-hit
            # suffixes (fed > 0) through chunked decode rows
            parts: List[_Part] = []
            wave = [r for r in prefill if r.fed == 0]
            if wave:
                parts.extend(self._dispatch_wave(wave))
                ph.mark("select")
            rows = []
            row_budget = eng.config.max_batch_size
            for req in prefill:
                if req.state != PREFILL or row_budget < 1:
                    continue
                remaining = req.base[req.fed:]
                c = min(len(remaining), row_budget)
                rows.append((req, remaining[:c],
                             req.fed + c == len(req.base)))
                row_budget -= c
            part = self._dispatch_mixed(rows)
            if part is not None:
                parts.append(part)
            return _Step(parts, sum(len(r.base) for r in prefill))
        running = [r for r in self.active if r.state == RUNNING]
        if not running:
            return None
        ngram = int(self._spec.get("ngram", 3))
        draft_len = int(self._spec.get("draft_len", 4))
        n_live = len(running)
        per_seq = max(1, eng.config.max_batch_size // n_live)
        st = self.spec_stats
        collapsed = per_seq == 1 and draft_len > 0
        chunks: List[Tuple[Request, np.ndarray]] = []
        for req in list(running):
            if req.state != RUNNING:
                continue  # preempted while reserving earlier chunks
            # output includes the pending (undrafted) token, so the
            # draft budget is max_new - len(output) further tokens
            budget = req.max_new_tokens - len(req.output)
            k = min(draft_len, budget, per_seq - 1)
            # history INCLUDING the pending token drafts the continuation
            draft = eng._ngram_draft(req.base, ngram, k)
            room = eng.config.max_seq_len \
                - eng.state.get(req.uid).seen_tokens
            if room < 1:
                self._finish(req, "length")
                continue
            chunk = np.asarray([req.pending] + draft[:max(0, room - 1)],
                               np.int32)
            if not self._reserve(req, len(chunk)):
                continue
            chunks.append((req, chunk))
        if not chunks:
            return None
        # collapse accounting is per DISPATCHED step (counted only once
        # chunks exist), so draft_collapsed_steps can never exceed
        # steps — the invariant the stats contract promises and the
        # pre-scheduler engine loop kept
        if collapsed:
            if st["draft_collapsed_steps"] == 0:
                log_dist(
                    "speculative serving: max_batch_size "
                    f"{eng.config.max_batch_size} // {n_live} live "
                    "sequences leaves no draft rows (per_seq=1, k=0); "
                    "speculation is running as plain decode — raise "
                    "max_batch_size or lower concurrency",
                    ranks=[0],
                )
            st["draft_collapsed_steps"] += 1
        st["steps"] += 1
        st["verified_chunks"] += len(chunks)
        st["draft_tokens"] += sum(len(c) - 1 for _, c in chunks)
        self._it_kind = "spec"
        self._it_rows += sum(len(c) for _, c in chunks)
        # the verification is launch AND wait: the engine hands back
        # host logits, so the device time of a spec step reads as launch
        ph.mark("launch", kind="spec", rows=self._it_rows)
        all_logits = eng._verify_chunks([r.uid for r, _ in chunks],
                                        [c for _, c in chunks])
        ph.mark("accept")
        now = time.perf_counter()
        for (req, chunk), lg in zip(chunks, all_logits):
            accepted = 1
            while (accepted < len(chunk)
                   and int(np.argmax(lg[accepted - 1]))
                   == int(chunk[accepted])):
                accepted += 1
            st["accepted_tokens"] += accepted
            eng.state.commit(req.uid, accepted,
                             token_ids=[int(t) for t in chunk[:accepted]])
            # chunk[0] == pending == output[-1]: the newly ACCEPTED
            # tokens are chunk[1:accepted] plus the next committed draw
            for t in [int(t) for t in chunk[1:accepted]] \
                    + [int(np.argmax(lg[accepted - 1]))]:
                self._accept(req, t, now)
                if req.done:
                    break
        rows = sum(len(c) for _, c in chunks)
        self._count_tokens(rows, _bucket(rows, 8))
        return _Step([], 0)  # already finalized (host verification)

    # -- public driving --------------------------------------------------
    def drain_fault_delay(self) -> float:
        """Collect and reset injected straggler time (0.0 outside chaos
        runs)."""
        d, self.fault_delay_s = self.fault_delay_s, 0.0
        return d

    def step(self) -> bool:
        """One scheduling iteration (dispatch + finalize). Returns False
        when there was nothing to do. Chaos fault point
        'scheduler.step' fires BEFORE dispatch: an injected replica
        death raises with no state half-mutated (requeue is safe), an
        injected straggler delay accrues to fault_delay_s."""
        self._begin_iteration("admit")
        try:
            act = fault_point("scheduler.step", replica=self.replica_index)
            if act is not None and act.kind == "delay":
                self.fault_delay_s += act.value
            st = self._dispatch()
            if st is None:
                return False
            self._finalize(st)
            return True
        finally:
            self._end_iteration()

    def _begin_iteration(self, phase: str) -> None:
        self._iteration += 1
        self._it_rows, self._it_kind = 0, "idle"
        self._it_delay0 = self.fault_delay_s
        self._it_ahead = None
        self._phases.begin(phase, iteration=self._iteration)

    def _end_iteration(self) -> None:
        """Close the iteration's phases and book the collector's time.
        Two rules count an iteration and keep it as ONE always-kept
        span, `sched.slow_iteration`, tracing on or off: `host`, when
        the host held the loop (everything but the readback wait, plus
        any injected straggler time) for over SLOW_ITERATION_NS, and
        `stall`, when the whole iteration took over three times what
        an iteration that launches a step typically takes
        (profiler.Phases.end). The span says what a later reader needs
        to tell whose the time was (docs/tracing.md); a stall also
        logs one line."""
        ph, c = self._phases, self.counters
        total = ph.end(feed=self._it_kind != "idle",
                       rows=self._it_rows, kind=self._it_kind)
        if ph.gc_n:
            c["gc_s"] += ph.gc_ns * 1e-9
            c["gc_collections"] += ph.gc_n
        delay = self.fault_delay_s - self._it_delay0
        slow = total - ph.ns["readback"] + int(delay * 1e9) > SLOW_ITERATION_NS
        if not (slow or ph.excess_ns):
            return
        # did the device run through it? The step launched ahead of
        # this iteration's readback is done by now if it did: asked
        # once, of an array the loop already holds
        ahead_ready = None
        if self._it_ahead is not None:
            toks = [p.tok_dev for p in self._it_ahead.parts
                    if p.tok_dev is not None]
            if toks:
                ahead_ready = bool(toks[-1].is_ready())
        if slow:
            c["slow_iterations"] += 1
        if ph.excess_ns:
            c["stall_iterations"] += 1
            c["stall_s"] += ph.excess_ns * 1e-9
            c["stall_readback_s"] += ph.excess_wait_ns * 1e-9
            ph.log_stall(
                f"iteration {self._iteration}",
                f"step ahead ready={ahead_ready}; {self._it_rows} rows, "
                f"{len(self.active)} active, {len(self.waiting)} waiting")
        ph.keep(
            "sched.slow_iteration", iteration=self._iteration,
            rows=self._it_rows, kind=self._it_kind, fault_delay_s=delay,
            rule="+".join(r for r, on in (("host", slow),
                                          ("stall", ph.excess_ns)) if on),
            ahead_ready=ahead_ready, waiting=len(self.waiting),
            active=len(self.active))

    def run(self, tick=None) -> None:
        """Drive until idle. tick(scheduler), when given, runs once per
        iteration before admission — the arrival-injection hook the
        serving simulator uses. The loop keeps one step in flight and
        LOOKS AHEAD: iteration n+1 is composed from host state and the
        counts of step n, launched on n's device-resident tokens, and
        only then is n read back (`_dispatch(ahead_of=)`), in every
        iteration whose composition allows it (`_can_look_ahead`, and
        the fall-backs `_dispatch` finds on its way); the others read
        back first, then dispatch, as step() always does. Either order
        gives the same tokens, finish reasons and prefix index."""
        prev: Optional[_Step] = None
        stalls = 0
        while True:
            # every statement of the loop body sits in a phase; the
            # finally closes the iteration when the tick raises, too
            self._begin_iteration("tick")
            try:
                if tick is not None:
                    tick(self)
                self._phases.mark("select")
                st, looked = None, False
                if prev is not None:
                    looked = self._can_look_ahead(prev)
                    if looked:
                        st = self._it_ahead = self._dispatch(ahead_of=prev)
                    # with st launched, the readback overlaps its compute
                    self._finalize(prev)
                    prev = None
                if st is None:
                    st = self._dispatch(governed=looked)
                if st is None:
                    if not self.has_work:
                        break
                    # every active sequence was preempted/finished this
                    # iteration: the next _admit makes progress (freed
                    # blocks) or capacity-finishes — a third idle pass
                    # with work pending is a scheduler bug, not pressure
                    stalls += 1
                    if stalls > 2:
                        raise RuntimeError(
                            "serving scheduler stalled with work pending "
                            f"({len(self.waiting)} waiting)")
                    continue
                stalls = 0
                if st.parts:
                    prev = st
            finally:
                self._end_iteration()

    # -- observability ---------------------------------------------------
    def metrics(self) -> Dict[str, float]:
        """Flat float counters for the monitor sinks
        (monitor.serving_events): TTFT/TPOT percentiles (ms, host wall
        time over the last LATENCY_WINDOW finished requests, **timed
        from `submit()`**, not from when a client meant to send: a
        stalled loop that delays its own submissions hides that wait),
        queue depth, preemptions, the per-phase time sums of the loop
        (`tick_s` ... `accept_s`, `readback_wait_s`, `queue_wait_s`,
        `slow_iterations`: docs/tracing.md), and the engine recompile
        count."""
        def pct(xs, q):
            return float(np.percentile(np.asarray(xs), q) * 1e3) if xs \
                else 0.0

        m: Dict[str, float] = {
            "queue_depth": float(len(self.waiting)),
            "active": float(len(self.active)),
            "ttft_p50_ms": pct(self._ttft, 50),
            "ttft_p95_ms": pct(self._ttft, 95),
            "tpot_p50_ms": pct(self._tpot, 50),
            "tpot_p95_ms": pct(self._tpot, 95),
            "recompiles": float(len(self.engine.recompile_tracker.findings)),
            "budget_findings": float(
                len(getattr(self, "budget_report").findings)
                if getattr(self, "budget_report", None) else 0),
            # KV-pool residency (engine.kv_bytes_per_token): bytes one
            # resident token costs, and whether the pool is the int8
            # per-block quantized layout (docs/paged_attention.md)
            "kv_bytes_per_token": float(self.engine.kv_bytes_per_token()),
            "kv_pool_quantized": (
                1.0 if self.engine.cache.quantized else 0.0),
            # 1.0 = the Pallas serving kernels, 0.0 = the jnp oracle
            # (what decode_impl='auto' resolved to on this backend)
            "decode_kernel": (
                1.0 if self.engine.resolved_impl == "pallas" else 0.0),
        }
        # warmup-measured static footprint per decode bucket (costmodel)
        fps = getattr(self.engine, "warmup_footprints", {})
        if fps:
            m["hbm_peak_mb"] = max(
                f["peak_hbm_bytes"] for f in fps.values()) / 2**20
            for w, f in sorted(fps.items()):
                m[f"hbm_w{w}_mb"] = f["peak_hbm_bytes"] / 2**20
        # pressure governor + spill tier (inference/pressure.py;
        # present only when config.pressure.enabled)
        if self.governor is not None:
            m.update(self.governor.metrics())
        if self.spill_store is not None:
            m.update(self.spill_store.stats())
        # MoE expert-utilization census (InferenceConfig.moe_census):
        # cumulative routed-token share per expert plus the imbalance
        # ratio max/mean — 1.0 is a perfectly balanced router, and a
        # rising ratio means hot experts serialize the grouped GEMM
        if getattr(self.engine, "_census_enabled", False):
            census = self.engine.moe_expert_census()
            total = int(census.sum())
            m["moe_census_tokens"] = float(total)
            if total:
                for i, c in enumerate(census):
                    m[f"moe_expert_{i}_share"] = float(c) / total
                m["moe_imbalance"] = float(
                    census.max() / max(float(census.mean()), 1e-9))
            held = self.engine.cfg.experts_held
            if held is not None:
                # a chip that holds a share of the experts: the pairs
                # that REACHED the held ones (the census counts every
                # routed layer), beside what a router that spreads its
                # pairs evenly would send them: count / of a pair
                start, count = held
                m["moe_census_held_pairs"] = float(
                    census[start:start + count].sum())
                m["moe_census_held_pairs_expected"] = (
                    total * count / len(census))
        for k, v in self.counters.items():
            m[k] = float(v)
        for cls, v in sorted(self.slo_rejections.items()):
            m[f"deadline_rejections_{cls}"] = float(v)
        if self.counters["steps"]:
            m["batched_tokens_per_step"] = (
                self.counters["batched_tokens"] / self.counters["steps"])
        if self._spec:
            for k, v in self.spec_summary().items():
                m[f"spec_{k}"] = float(v)
        return m

    def spec_summary(self) -> Dict[str, float]:
        """The speculative-decoding stats with their derived rates
        folded in: mean_accepted (tokens committed per verified chunk,
        includes the guaranteed pending token, so >= 1) and
        draft_acceptance_rate (accepted DRAFT tokens / proposed draft
        tokens — the policy signal: 0 means the n-gram draft never
        lands, collapse aside). One authority for both the engine's
        generate_speculative(return_stats=True) and the router's
        per-replica reporting."""
        st = dict(self.spec_stats)
        vc = st["verified_chunks"]
        st["mean_accepted"] = st["accepted_tokens"] / vc if vc else 0.0
        # every verified chunk's slot 0 is the already-committed pending
        # token — only the remainder of `accepted` came from drafts
        drafts = st["draft_tokens"]
        st["draft_acceptance_rate"] = (
            (st["accepted_tokens"] - vc) / drafts if drafts else 0.0)
        self.spec_stats["mean_accepted"] = st["mean_accepted"]
        return st
