"""Share of its roofline the expert pass of an SDAR-MoE stage reaches:
what the PUBLISHED weights need, every expert's read once a layer plus
the rows in and out over the HBM peak, or the operations the rows' own
top-k experts need over the bf16 peak, whichever is larger, over the
device time of scope `moe_experts` per shared-table program, all
layers (`kernels/sdar_moe.py`; `moe_experts_roofline` reads OLMoE's
`intermediate_size`, which here is the family's unused dense width).
Rows an iteration are the scheduler's (`batched_tokens` / steps). It
prints which bound it names, and beside it the MXU's time for the pass
as the program runs it, every expert against every row: where that is
the larger, the pass is held by its own design and not by the weights.
None unless the configuration is an `sdar_moe`."""

import pathlib

from benchmarks import harness

_here = pathlib.Path(__file__).resolve()
_moe = harness.load_module(_here.with_name("moe_ms_per_step.py"))
_shapes = harness.load_module(_here.parents[1] / "kernels" / "sdar_moe.py")


def read(obs):
    ms = _moe.per_program_ms(obs, ("moe_experts",))
    d = obs.get("counters_delta") or {}
    hf = obs.get("hf") or {}
    if ms is None or not obs.get("peaks") or not d.get("steps") \
            or not d.get("batched_tokens") \
            or hf.get("model_type") != "sdar_moe":
        return None
    rows = d["batched_tokens"] / d["steps"]
    need, L, peaks = _shapes.expert_pass(hf, rows), obs["n_layers"], obs["peaks"]
    by_bytes = 1e3 * L * need["bytes"] / peaks["hbm_bytes_per_s"]
    by_flops = 1e3 * L * need["needed_flops"] / peaks["bf16_flops_per_s"]
    as_run = 1e3 * L * need["flops"] / peaks["bf16_flops_per_s"]
    print(f"[bench] sdar experts: "
          f"{'memory' if by_bytes >= by_flops else 'compute'}-bound; "
          f"{rows:.1f} rows an iteration in {L} layers, "
          f"{max(by_bytes, by_flops):.3f} ms needed (bytes {by_bytes:.3f}, "
          f"operations of the rows' own experts {by_flops:.3f}) vs {ms:.3f} "
          f"ms taken; every expert against every row is {as_run:.3f} ms of "
          f"the MXU: the pass is held by "
          f"{'its all-expert design' if as_run > by_bytes else 'the weights'}",
          flush=True)
    return 100.0 * max(by_bytes, by_flops) / ms
