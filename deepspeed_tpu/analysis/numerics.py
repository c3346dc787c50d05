"""Numerics sanitizer: precision-flow analysis of compiled programs.

Mixed precision is the blueprint's highest-risk correctness surface:
fp16/bf16 compute with fp32 master weights, dynamic loss scaling, and
error-feedback compressed collectives all corrupt training SILENTLY
when a dtype downcast or a dropped residual sneaks into a compiled
program — the loss still goes down, just to a worse model. Like the
rest of `analysis/`, every check here reads an artifact: the declared
policy comes from the config (`runtime/precision.precision_policy`),
the actual dtypes from the HLO.

Ground-truth subtlety: accumulation dtypes must be read from the
PRE-OPTIMIZATION module (`profiling.hlo.preopt_hlo_text`) — backend
legalization rewrites them (CPU upcasts bf16 compute to f32, TPU may
fuse converts), so the optimized text shows the backend's choice, not
the program's declaration. Collective payloads and entry-parameter /
alias facts come from the compiled text, where SPMD partitioning has
happened.

Four checks (findings ride the sanitizer report machinery):

  N001  check_accumulation_dtypes — additive reductions (and, under a
        declared-fp32 policy, dots) accumulating below the policy's
        precision; low-precision reduce-class collectives carrying
        gradient-sized payloads.
  N002  check_master_integrity   — the fp32 master-weight/optimizer
        update chain: leaves stored below fp32, compiled below fp32,
        or donated but NOT in the compiled input_output_alias table
        (the S001 alias table reused: an un-aliased donated master
        means the updated copy materialized in fresh storage — dtype
        or layout drifted mid-chain).
  N003  check_loss_scale         — a loss-scaled program that never
        inf-checks its gradients; scaled grads entering compressed
        collectives; error-feedback residual buffers carried below
        fp32.
  N004  check_quantized_groups   — 1-bit/qgZ group geometry (worker
        groups must divide leaf sizes: zero-padding dilutes the shared
        scale), full-precision payloads leaking onto the compressed
        wire, and dequantization landing below fp32.

`engine.sanitize()` runs N001-N003 on every train-step flavor (fused,
fp16-loss-scaled, 1-bit/0-1-Adam, offload-grad) and N004 on the
compressed programs; `InferenceEngine.sanitize_numerics()` covers the
serving decode buckets. `scripts/ds_gate.py numerics` persists per-program
dtype ledgers to NUMERICS.json as a tier-1 pre-test gate.
"""

from typing import Any, Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np

from ..profiling.hlo import (
    LOW_PRECISION_FLOATS,
    parse_entry_parameters,
    parse_hlo_dtype_ops,
    preopt_hlo_text,
)
from ..runtime.precision import PrecisionPolicy, hlo_dtype_name
from .report import Finding, SanitizerReport, merge_reports

__all__ = [
    "check_accumulation_dtypes",
    "check_master_integrity",
    "check_loss_scale",
    "check_quantized_groups",
    "check_program_numerics",
    "dtype_ledger",
    "grad_elem_counts",
]

# precision ordering for "accumulates BELOW the declared dtype"
_RANK = {"f8e4m3fn": 0, "f8e4m3": 0, "f8e5m2": 0,
         "f16": 1, "bf16": 1, "f32": 2, "f64": 3}
_LOW = set(LOW_PRECISION_FLOATS)
_REDUCE_COLLECTIVES = ("all-reduce", "reduce-scatter")
# error-feedback residual keys of the 1-bit/0-1-Adam optimizer state —
# N003's territory (check_master_integrity skips them)
_RESIDUAL_KEYS = ("error_",)


def _rank(dtype: Optional[str]) -> Optional[int]:
    return _RANK.get(dtype or "")


def _accumulating_reduce(r: Dict) -> bool:
    """Does this reduce record actually ACCUMULATE? Combiner must be
    additive, and the reduced extent must exceed 1 — shard_map's
    manual-axis machinery emits identity reduces over size-1 worker
    dims (operand elems == result elems), which sum nothing and carry
    no precision risk."""
    if r["op"] not in ("reduce", "reduce-window") or \
            r["reduce_kind"] not in ("add", "multiply"):
        return False
    data_elems = [n for _, n in r["operands"][:1] if n]
    return not data_elems or data_elems[0] > r["elems"]


def grad_elem_counts(tree: Any, dp: int = 1) -> Set[int]:
    """Element counts a gradient-reduction collective over `tree`'s
    leaves could legitimately carry: the leaf counts themselves plus
    the worker-major [dp, ...] variants of the partial-gradient paths."""
    counts: Set[int] = set()
    for leaf in jax.tree.leaves(tree):
        shape = tuple(getattr(leaf, "shape", ()))
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        counts.add(n)
        if dp > 1:
            counts.add(n * dp)
    return counts


# ----------------------------------------------------------------------
# check N001: accumulation dtypes
# ----------------------------------------------------------------------

def check_accumulation_dtypes(
    policy: PrecisionPolicy,
    compiled_text: Optional[str] = None,
    preopt_text: Optional[str] = None,
    grad_elem_counts: Optional[Set[int]] = None,
    label: str = "jit",
) -> SanitizerReport:
    """N001: the program accumulates below the declared precision.

    From the PRE-OPT text (declared dtypes): additive reduces
    (combiner add/multiply — max/min/and select, they don't
    accumulate) whose result dtype ranks below `policy.grad_accum`;
    under a declared-fp32 policy also dots computing in f16/bf16 (a
    downcast snuck into a program the config says is full precision).
    From the COMPILED text: reduce-class collectives (all-reduce /
    reduce-scatter) whose payload dtype ranks below the declared
    `policy.grad_comm` (the `communication_data_type` contract —
    defaults to the compute dtype, so the reference-standard f16/bf16
    gradient psum is legitimate) — scoped to gradient-sized payloads
    via `grad_elem_counts` under a mixed policy, where low-precision
    ACTIVATION collectives (TP partial sums) are always legitimate.
    Findings aggregate per (op, dtype)."""
    report = SanitizerReport(label=f"{label}/accumulation")
    accum_rank = _RANK.get(policy.grad_accum, 2)
    comm_rank = _RANK.get(policy.grad_comm, 2)

    hits: Dict[tuple, int] = {}
    if preopt_text:
        for r in parse_hlo_dtype_ops(preopt_text):
            dt = r["dtype"]
            if dt not in _LOW:
                continue
            if _accumulating_reduce(r) and _RANK.get(dt, 0) < accum_rank:
                hits[(r["op"], dt)] = hits.get((r["op"], dt), 0) + 1
            elif r["op"] == "dot" and policy.compute == "f32":
                hits[("dot", dt)] = hits.get(("dot", dt), 0) + 1
    if compiled_text:
        for r in parse_hlo_dtype_ops(compiled_text):
            dt = r["dtype"]
            if r["op"] not in _REDUCE_COLLECTIVES or dt not in _LOW or \
                    _RANK.get(dt, 0) >= comm_rank:
                continue
            if policy.compute != "f32":
                # mixed policy: only gradient-sized payloads are
                # accumulation; TP activation partial sums are compute
                if not grad_elem_counts:
                    continue
                elems = {r["elems"]} | {n for _, n in r["operands"] if n}
                if not (elems & grad_elem_counts):
                    continue
            hits[(r["op"], dt)] = hits.get((r["op"], dt), 0) + 1

    for (op, dt), count in sorted(hits.items()):
        if op in _REDUCE_COLLECTIVES:
            declared = f"{policy.grad_comm} collective payloads " \
                       "(communication_data_type)"
        else:
            declared = f"{policy.grad_accum} accumulation"
        report.findings.append(Finding(
            rule="N001", path=label, line=0, severity="error",
            message=(
                f"{count} {op} op(s) accumulate in {dt} but the policy "
                f"declares {declared} (compute={policy.compute}): "
                "partial sums are carried in low precision — silent "
                "loss of gradient mass"),
            fix_hint=(
                "accumulate in fp32 (jnp reductions upcast by default — "
                "a low-precision reduce means an explicit lax.reduce/"
                "dtype= override), or declare the lower precision "
                "(data_types.grad_accum_dtype / "
                "communication_data_type)"),
        ))
    return report


# ----------------------------------------------------------------------
# check N002: fp32 master-weight integrity
# ----------------------------------------------------------------------

def _is_residual_key(path) -> bool:
    for p in path:
        key = getattr(p, "key", None)
        if isinstance(key, str) and key.startswith(_RESIDUAL_KEYS):
            return True
    return False


def check_master_integrity(
    compiled: Any = None,
    master: Any = None,
    opt: Any = None,
    argnames: Sequence[str] = ("state.master", "state.opt"),
    donated: bool = True,
    label: str = "jit",
) -> SanitizerReport:
    """N002: the fp32 master/optimizer state survives the compiled
    update chain. Per floating leaf of `master`/`opt` (error-feedback
    residuals excluded — N003's territory):

      leaf stored below fp32            — error (the authoritative
                                          copy has already lost bits)
      entry param compiled below fp32   — error (the program consumes
                                          a downcast view)
      donated but NOT in the compiled   — error (the updated state
      input_output_alias table            materialized in fresh
                                          storage: dtype/layout drift
                                          mid-chain broke in-place
                                          donation — the S001 table
                                          reused with N002 semantics)

    Leaves absent from the entry parameters are DCE'd (unused), not
    findings. Works tree-only (compiled=None) for host-tier state."""
    report = SanitizerReport(label=f"{label}/master_integrity")
    aliased: Set[int] = set()
    by_name: Dict[str, Dict] = {}
    if compiled is not None:
        from .sanitizer import _compiled_alias_info

        text = compiled.as_text()
        aliased = _compiled_alias_info(compiled)[0]
        by_name = {
            r["op_name"]: r
            for r in parse_entry_parameters(text)
            if r["op_name"] is not None
        }
    for argname, tree in zip(argnames, (master, opt)):
        if tree is None:
            continue
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        for path, leaf in flat:
            dt = getattr(leaf, "dtype", None)
            if dt is None or not jnp.issubdtype(dt, jnp.floating):
                continue
            if _is_residual_key(path):
                continue
            name = f"{argname}{jax.tree_util.keystr(path)}"
            if hlo_dtype_name(dt) != "f32":
                report.findings.append(Finding(
                    rule="N002", path=name, line=0, severity="error",
                    message=(
                        f"master/optimizer leaf {name} is stored as "
                        f"{np.dtype(dt).name} — the fp32 update chain "
                        "has already lost precision at rest"),
                    fix_hint="keep master weights and moments fp32; cast "
                             "only the compute view (cast_params)",
                ))
                continue
            rec = by_name.get(name)
            if rec is None:
                continue  # DCE'd (unused) — or tree-only mode
            if rec["dtype"] != "f32":
                report.findings.append(Finding(
                    rule="N002", path=name, line=0, severity="error",
                    message=(
                        f"{name} enters the compiled step as "
                        f"{rec['dtype']} — the program consumes a "
                        "downcast view of the fp32 state"),
                    fix_hint="pass the fp32 tree; downcasts belong inside "
                             "the program (cast_params on a copy)",
                ))
            elif donated and rec["index"] not in aliased:
                report.findings.append(Finding(
                    rule="N002", path=name, line=0, severity="error",
                    message=(
                        f"donated fp32 state {name} is NOT in the "
                        "compiled input_output_alias table: the updated "
                        "value materialized in fresh storage — the "
                        "update chain changed its dtype/shape/sharding "
                        "mid-stream (and the buffer is copied every "
                        "step)"),
                    fix_hint=(
                        "keep the update fp32 end-to-end so the output "
                        "matches the donated input, or drop it from "
                        "donate_argnums"),
                ))
    return report


# ----------------------------------------------------------------------
# check N003: loss-scale coverage
# ----------------------------------------------------------------------

def check_loss_scale(
    policy: PrecisionPolicy,
    compiled_text: Optional[str] = None,
    opt: Any = None,
    label: str = "jit",
) -> SanitizerReport:
    """N003: loss-scaling blind spots. A loss-scaled (fp16) program
    whose HLO contains no `is-finite` check lets inf/nan gradients
    reach the optimizer un-gated (the skip-update path can never
    trigger); loss-scaled gradients entering compressed collectives
    pollute the error-feedback residuals with the scale (the residual
    carries scale-dependent error across scale changes); and
    error-feedback residual buffers (`error_*` optimizer leaves)
    stored below fp32 defeat the compensation they exist to provide."""
    report = SanitizerReport(label=f"{label}/loss_scale")
    if policy.loss_scaled:
        if compiled_text is not None and "is-finite" not in compiled_text:
            report.findings.append(Finding(
                rule="N003", path=label, line=0, severity="error",
                message=(
                    "loss-scaled step compiles WITHOUT an is-finite "
                    "check: overflowed fp16 gradients reach the "
                    "optimizer un-gated and the skip-update/backoff "
                    "path is dead code"),
                fix_hint="gate the update on "
                         "precision.found_inf_in_grads (or the "
                         "grad-norm isfinite check) before applying it",
            ))
        if policy.compressed:
            report.findings.append(Finding(
                rule="N003", path=label, line=0, severity="error",
                message=(
                    "loss-scaled gradients enter the "
                    f"{policy.compressed} compressed-collective path: "
                    "the error-feedback residuals absorb the CURRENT "
                    "scale, so every rescale replays stale scaled "
                    "error into the momentum"),
                fix_hint="use bf16 (no scaler) with 1-bit/qgZ, as the "
                         "engine enforces at build time",
            ))
    if opt is not None and isinstance(opt, dict):
        for key, tree in opt.items():
            if not key.startswith(_RESIDUAL_KEYS):
                continue
            flat, _ = jax.tree_util.tree_flatten_with_path(tree)
            for path, leaf in flat:
                dt = getattr(leaf, "dtype", None)
                if dt is None or not jnp.issubdtype(dt, jnp.floating):
                    continue
                if hlo_dtype_name(dt) != "f32":
                    report.findings.append(Finding(
                        rule="N003",
                        path=f"opt['{key}']{jax.tree_util.keystr(path)}",
                        line=0, severity="error",
                        message=(
                            f"error-feedback residual opt['{key}'] is "
                            f"carried as {np.dtype(dt).name}: the "
                            "compensation buffer quantizes the very "
                            "error it must remember — compression "
                            "bias stops cancelling"),
                        fix_hint="allocate residuals fp32 "
                                 "(comm.compressed.init_error_buffers)",
                    ))
    return report


# ----------------------------------------------------------------------
# check N004: quantized-collective sanity
# ----------------------------------------------------------------------

def check_quantized_groups(
    params: Any,
    dp: int,
    policy: Optional[PrecisionPolicy] = None,
    block: Optional[int] = None,
    compiled_text: Optional[str] = None,
    label: str = "compressed",
) -> SanitizerReport:
    """N004: 1-bit/qgZ group geometry and wire dtypes.

    Geometry (from the param tree + mesh): every floating leaf must
    split evenly into `dp` worker groups — the error buffers zero-pad
    the remainder, and padded zeros DILUTE the shared scale
    (`mean(|c|)` over a row that is part padding), biasing every
    reconstructed magnitude low. A leaf smaller than the worker count
    degenerates to pure padding. qgZ `block` windows that do not
    divide the per-worker chunk are padded per block (benign — the
    block's own absmax is 0) and reported as a warning.

    Wire (from the compiled compressed step): the two-hop exchange
    must move int8 codes — a full-precision (f32/bf16/f16) all-to-all
    or all-gather carrying a gradient-sized payload means the dequant
    was hoisted across the collective (the optimization-barrier
    failure mode) and the compression saved nothing; a convert from
    s8 landing below fp32 breaks the error-feedback arithmetic."""
    report = SanitizerReport(label=f"{label}/quantized_groups")
    counts: Set[int] = set()
    dp = int(dp)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for path, leaf in flat:
        dt = getattr(leaf, "dtype", None)
        if dt is not None and not jnp.issubdtype(dt, jnp.floating):
            continue
        shape = tuple(getattr(leaf, "shape", ()))
        n = int(np.prod(shape, dtype=np.int64)) if shape else 1
        name = f"params{jax.tree_util.keystr(path)}"
        if dp > 1:
            from ..comm.compressed import padded_cols

            npad = padded_cols(n, dp)
            counts.update({n, npad, dp * npad, dp * n})
            if n < dp:
                report.findings.append(Finding(
                    rule="N004", path=name, line=0, severity="error",
                    message=(
                        f"leaf {name} has {n} element(s) for {dp} "
                        "compression worker groups: most groups are "
                        "pure zero-padding — the shared scale is "
                        "meaningless"),
                    fix_hint="fuse small leaves before compression or "
                             "exclude them from the compressed path",
                ))
            elif n % dp:
                report.findings.append(Finding(
                    rule="N004", path=name, line=0, severity="error",
                    message=(
                        f"group size {dp} does not divide leaf {name} "
                        f"({n} elements): {npad - n} padded zeros "
                        "dilute the per-row scale mean(|c|) — every "
                        "reconstructed magnitude biases low"),
                    fix_hint="pad/shape the leaf to a multiple of the "
                             "data-parallel worker count, or shrink "
                             "the group",
                ))
            if block:
                C0 = (n + dp - 1) // dp
                beff = min(int(block), C0) if C0 else 1
                if beff and C0 % beff:
                    report.findings.append(Finding(
                        rule="N004", path=name, line=0,
                        severity="warning",
                        message=(
                            f"qgZ block {beff} does not divide the "
                            f"per-worker chunk ({C0} elements) of "
                            f"{name}: the tail block is padded "
                            "(benign scale, wasted wire bytes)"),
                        fix_hint="align quantization_block to the "
                                 "chunk size for zero padding waste",
                    ))
        else:
            counts.add(n)
    if compiled_text:
        for r in parse_hlo_dtype_ops(compiled_text):
            if r["op"] in ("all-to-all", "all-gather") and \
                    r["dtype"] in ("f32",) + LOW_PRECISION_FLOATS:
                elems = {r["elems"]} | {n for _, n in r["operands"] if n}
                if elems & counts:
                    report.findings.append(Finding(
                        rule="N004", path=label, line=0,
                        severity="error",
                        message=(
                            f"compressed exchange moves a {r['dtype']} "
                            f"{r['op']} with a gradient-sized payload: "
                            "the dequant was hoisted across the "
                            "collective and full precision went on "
                            "the wire"),
                        fix_hint="pin the int8 codes at the collective "
                                 "with jax.lax.optimization_barrier "
                                 "(comm/compressed.py pattern)",
                    ))
            elif r["op"] == "convert" and r["dtype"] in _LOW and any(
                    dt == "s8" for dt, _ in r["operands"]):
                report.findings.append(Finding(
                    rule="N004", path=label, line=0, severity="error",
                    message=(
                        f"dequantization converts s8 -> {r['dtype']}: "
                        "reconstruction must land fp32 (the error-"
                        "feedback residual subtracts it at fp32) "
                        "before any compute-dtype cast"),
                    fix_hint="dequantize to f32 first; cast to the "
                             "param dtype only at the storage boundary",
                ))
    return report


# ----------------------------------------------------------------------
# orchestration + the NUMERICS.json ledger
# ----------------------------------------------------------------------

def check_program_numerics(
    compiled: Any,
    policy: PrecisionPolicy,
    lowered: Any = None,
    master: Any = None,
    opt: Any = None,
    grad_counts: Optional[Set[int]] = None,
    donated: bool = True,
    label: str = "jit",
) -> SanitizerReport:
    """Run the N-series over one compiled step: N001 against the
    pre-opt (declared) and compiled (partitioned) texts, N002 on the
    master/opt update chain, N003 on loss-scale coverage. N004 is
    geometry-scoped — engines call check_quantized_groups directly on
    their compressed programs."""
    try:
        compiled_text = compiled.as_text()
    except Exception:
        compiled_text = None
    pre = preopt_hlo_text(lowered) if lowered is not None else None
    reports = [
        check_accumulation_dtypes(
            policy, compiled_text=compiled_text, preopt_text=pre,
            grad_elem_counts=grad_counts, label=label),
        check_loss_scale(policy, compiled_text=compiled_text, opt=opt,
                         label=label),
    ]
    if master is not None or opt is not None:
        reports.append(check_master_integrity(
            compiled, master=master, opt=opt, donated=donated,
            label=label))
    return merge_reports(f"{label}/numerics", *reports)


def dtype_ledger(compiled: Any = None, lowered: Any = None) -> Dict:
    """The per-program dtype ledger NUMERICS.json persists: additive-
    reduce / dot dtype histograms and convert chains from the pre-opt
    text (declared precision — deterministic for a fixed trace),
    collective payload dtypes from the compiled text. A dtype KEY
    appearing here that is absent from the committed baseline is a
    precision regression (`scripts/ds_gate.py numerics --check`)."""
    ledger: Dict[str, Dict] = {"reduce": {}, "dot": {}, "convert": {},
                               "collectives": {}}
    pre = preopt_hlo_text(lowered) if lowered is not None else None
    if pre:
        for r in parse_hlo_dtype_ops(pre):
            if _accumulating_reduce(r):
                ledger["reduce"][r["dtype"]] = \
                    ledger["reduce"].get(r["dtype"], 0) + 1
            elif r["op"] == "dot":
                ledger["dot"][r["dtype"]] = \
                    ledger["dot"].get(r["dtype"], 0) + 1
            elif r["op"] == "convert" and r["operands"]:
                src = r["operands"][0][0]
                key = f"{src}->{r['dtype']}"
                ledger["convert"][key] = ledger["convert"].get(key, 0) + 1
    if compiled is not None:
        try:
            text = compiled.as_text()
        except Exception:
            text = None
        if text:
            for r in parse_hlo_dtype_ops(text):
                if r["op"] in ("all-reduce", "reduce-scatter",
                               "all-gather", "all-to-all"):
                    slot = ledger["collectives"].setdefault(r["op"], {})
                    slot[r["dtype"]] = slot.get(r["dtype"], 0) + 1
    return ledger


def diff_ledgers(
    current: Dict, baseline: Dict, program: str,
) -> List[Finding]:
    """Ledger regression diff: a dtype key present now but absent from
    the baseline is an ERROR (a new low-precision op class appeared —
    or any dtype drift at all: the ledger is exact); count drift on an
    existing key is a warning (re-capture when intended)."""
    out: List[Finding] = []

    def walk(cur: Dict, base: Dict, where: str):
        for key, val in sorted(cur.items()):
            if isinstance(val, dict):
                walk(val, base.get(key, {}), f"{where}.{key}")
                continue
            if key not in base:
                out.append(Finding(
                    rule="N001", path=program, line=0, severity="error",
                    message=(
                        f"dtype regression in {where}: {key!r} "
                        f"(x{val}) is not in the committed "
                        "NUMERICS.json baseline"),
                    fix_hint="inspect the new op's precision; "
                             "re-capture (scripts/ds_gate.py numerics "
                             "--capture) only if intended",
                ))
            elif base[key] != val:
                out.append(Finding(
                    rule="N001", path=program, line=0,
                    severity="warning",
                    message=(
                        f"dtype-ledger count drift in {where}.{key}: "
                        f"{base[key]} -> {val}"),
                    fix_hint="re-capture the ledger if the new op "
                             "count is intended",
                ))

    walk(current, baseline, program)
    return out
