"""Numerics sanitizer tests (analysis/numerics.py, N001-N004).

Same contract as the sanitizer/cost-model suites: every N-series check
fires EXACTLY ONCE on a deliberately seeded violation (forced bf16
accumulation, donated-then-downcast master weight, dropped loss-scale
inf-check, misaligned qgZ groups) and stays silent on the real
fused/fp16/serving step programs. The ds_numerics gate is exercised
through its CLI against the committed NUMERICS.json and an injected
dtype regression.
"""

import json
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.analysis.numerics import (
    check_accumulation_dtypes,
    check_loss_scale,
    check_master_integrity,
    check_program_numerics,
    check_quantized_groups,
    diff_ledgers,
    dtype_ledger,
    grad_elem_counts,
)
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.profiling.hlo import (
    parse_hlo_collectives,
    parse_hlo_dtype_ops,
    preopt_hlo_text,
)
from deepspeed_tpu.runtime.precision import (
    PrecisionPolicy,
    found_inf_in_grads,
    hlo_dtype_name,
    precision_policy,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 128


def model_cfg(**kw):
    base = dict(vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=64,
                max_seq=32, variant="llama", use_flash=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def bf16_policy(**kw):
    base = dict(compute="bf16", master="f32", grad_accum="f32",
                grad_comm="bf16", loss_scaled=False)
    base.update(kw)
    return PrecisionPolicy(**base)


# ----------------------------------------------------------------------
# the declared policy (runtime/precision.py)
# ----------------------------------------------------------------------

class TestPrecisionPolicy:
    def _cfg(self, **kw):
        from deepspeed_tpu.config.config import DeepSpeedTPUConfig

        base = {"train_batch_size": 8}
        base.update(kw)
        return DeepSpeedTPUConfig(**base)

    def test_bf16_defaults(self):
        p = precision_policy(self._cfg(bf16={"enabled": True}))
        assert p == PrecisionPolicy("bf16", "f32", "f32", "bf16", False)

    def test_fp16_is_loss_scaled(self):
        p = precision_policy(self._cfg(fp16={"enabled": True}))
        assert p.compute == "f16" and p.loss_scaled
        assert p.grad_comm == "f16"  # reference default: comm at compute

    def test_fp32_has_no_master(self):
        p = precision_policy(self._cfg())
        assert p.compute == "f32" and p.master is None

    def test_declared_comm_and_accum_dtypes(self):
        p = precision_policy(self._cfg(
            bf16={"enabled": True}, communication_data_type="fp32",
            data_types={"grad_accum_dtype": "bf16"}))
        assert p.grad_comm == "f32" and p.grad_accum == "bf16"

    def test_no_master_weights(self):
        p = precision_policy(self._cfg(
            bf16={"enabled": True, "master_weights": False}))
        assert p.master is None

    def test_bad_accum_dtype_rejected(self):
        with pytest.raises(Exception):
            self._cfg(data_types={"grad_accum_dtype": "int8"})

    def test_hlo_dtype_names(self):
        assert hlo_dtype_name(jnp.bfloat16) == "bf16"
        assert hlo_dtype_name(np.float32) == "f32"
        assert hlo_dtype_name(np.int8) == "s8"
        assert hlo_dtype_name(np.bool_) == "pred"


# ----------------------------------------------------------------------
# hlo.py dtype-flow parsing (+ the collective-parser hardening)
# ----------------------------------------------------------------------

class TestHloDtypeOps:
    def test_compiled_form_with_inline_operands(self):
        hlo = ("%dot.4 = f32[4,4]{1,0} dot(bf16[4,8]{1,0} %a, "
               "bf16[8,4]{1,0} %b), lhs_contracting_dims={1}")
        recs = parse_hlo_dtype_ops(hlo)
        assert len(recs) == 1
        r = recs[0]
        assert r["op"] == "dot" and r["dtype"] == "f32"
        assert r["operands"] == [("bf16", 32), ("bf16", 32)]

    def test_preopt_form_resolves_operands_by_name(self):
        lo = jax.jit(lambda x: jnp.sum(x)).lower(
            jnp.zeros((8, 8), jnp.float32))
        recs = [r for r in parse_hlo_dtype_ops(preopt_hlo_text(lo))
                if r["op"] == "reduce"]
        assert len(recs) == 1
        assert recs[0]["reduce_kind"] == "add"
        assert recs[0]["operands"][0] == ("f32", 64)

    def test_max_reduce_classified_as_selection(self):
        lo = jax.jit(lambda x: jnp.max(x)).lower(
            jnp.zeros((8, 8), jnp.float32))
        recs = [r for r in parse_hlo_dtype_ops(preopt_hlo_text(lo))
                if r["op"] == "reduce"]
        assert recs and recs[0]["reduce_kind"] == "maximum"

    def test_tuple_typed_reduce_result(self):
        hlo = ("%r = (f32[8]{0}, s32[8]{0}) reduce(f32[8,4] %x, "
               "s32[8,4] %i, f32[] %c0, s32[] %c1), dimensions={1}, "
               "to_apply=%argmax")
        recs = parse_hlo_dtype_ops(hlo)
        assert len(recs) == 1
        assert recs[0]["dtype"] == "f32" and recs[0]["elems"] == 16

    def test_pred_reduce_and_token_operands_no_crash(self):
        hlo = ("%all = pred[] reduce(pred[64] %flags, pred[] %true), "
               "dimensions={0}, to_apply=%and_region\n"
               "%ar = f32[4]{0} all-reduce(f32[4]{0} %x, token[] %t), "
               "replica_groups={}\n")
        recs = parse_hlo_dtype_ops(hlo)
        assert {r["op"] for r in recs} == {"reduce", "all-reduce"}
        # the collective parser shares the shape machinery — no crash,
        # token payload contributes zero bytes
        coll = parse_hlo_collectives(hlo)
        assert coll and coll[0]["op"] == "all-reduce"
        assert coll[0]["bytes"] == 16

    def test_convert_chain_records_src_and_dst(self):
        lo = jax.jit(lambda x: x.astype(jnp.bfloat16).astype(
            jnp.float32)).lower(jnp.zeros((4,), jnp.float32))
        recs = [r for r in parse_hlo_dtype_ops(preopt_hlo_text(lo))
                if r["op"] == "convert"]
        pairs = {(r["operands"][0][0] if r["operands"] else None,
                  r["dtype"]) for r in recs}
        assert ("f32", "bf16") in pairs and ("bf16", "f32") in pairs

    def test_reduce_scatter_not_shadowed_by_reduce(self):
        hlo = ("%rs = f32[2,8]{1,0} reduce-scatter(f32[8,8]{1,0} %x), "
               "replica_groups=[2,4]<=[8], dimensions={0}, "
               "to_apply=%add.1")
        recs = parse_hlo_dtype_ops(hlo)
        assert [r["op"] for r in recs] == ["reduce-scatter"]


# ----------------------------------------------------------------------
# N001: low-precision accumulation
# ----------------------------------------------------------------------

class TestN001Accumulation:
    def test_seeded_bf16_reduce_fires_exactly_once(self):
        """The forced-bf16-accumulation seed: an explicit lax.reduce
        with a bf16 carry (jnp reductions upcast by default, so this
        only appears when someone overrides the accumulator dtype)."""
        lo = jax.jit(lambda x: jax.lax.reduce(
            x, jnp.bfloat16(0), jax.lax.add, (0,))).lower(
            jnp.zeros((64, 64), jnp.bfloat16))
        out = check_accumulation_dtypes(
            bf16_policy(), preopt_text=preopt_hlo_text(lo))
        assert len(out.findings) == 1
        f = out.findings[0]
        assert f.rule == "N001" and f.severity == "error"
        assert "bf16" in f.message

    def test_jnp_sum_upcast_is_silent(self):
        lo = jax.jit(lambda x: jnp.sum(x)).lower(
            jnp.zeros((64, 64), jnp.bfloat16))
        assert check_accumulation_dtypes(
            bf16_policy(), preopt_text=preopt_hlo_text(lo)).ok

    def test_bf16_max_reduce_is_silent(self):
        """Selection reduces don't accumulate — softmax max-subtraction
        in bf16 is fine."""
        lo = jax.jit(lambda x: jnp.max(x, axis=0)).lower(
            jnp.zeros((64, 64), jnp.bfloat16))
        assert check_accumulation_dtypes(
            bf16_policy(), preopt_text=preopt_hlo_text(lo)).ok

    def test_identity_reduce_over_size1_dim_is_silent(self):
        """shard_map's manual-axis machinery emits reduces over size-1
        worker dims — nothing is accumulated."""
        lo = jax.jit(lambda x: jnp.sum(x, axis=0)).lower(
            jnp.zeros((1, 64), jnp.bfloat16))
        assert check_accumulation_dtypes(
            bf16_policy(), preopt_text=preopt_hlo_text(lo)).ok

    def test_declared_fp32_program_with_bf16_dot_fires(self):
        """A downcast snuck into a config-declared-fp32 program."""
        def f(x, y):
            return (x.astype(jnp.bfloat16)
                    @ y.astype(jnp.bfloat16)).astype(jnp.float32)

        lo = jax.jit(f).lower(jnp.zeros((4, 8), jnp.float32),
                              jnp.zeros((8, 4), jnp.float32))
        policy = PrecisionPolicy("f32", None, "f32", "f32", False)
        out = check_accumulation_dtypes(
            policy, preopt_text=preopt_hlo_text(lo))
        assert len(out.findings) == 1
        assert "dot" in out.findings[0].message

    def test_declared_bf16_compute_dots_are_silent(self):
        lo = jax.jit(lambda x, y: x @ y).lower(
            jnp.zeros((4, 8), jnp.bfloat16), jnp.zeros((8, 4), jnp.bfloat16))
        assert check_accumulation_dtypes(
            bf16_policy(), preopt_text=preopt_hlo_text(lo)).ok

    # -- the collective (communication_data_type) leg ------------------

    _GRAD_RS = ("%rs = bf16[512]{0} reduce-scatter(bf16[4096]{0} %g), "
                "replica_groups=[1,8]<=[8], dimensions={0}, "
                "to_apply=%add.1\n")

    def test_grad_sized_low_precision_collective_fires(self):
        out = check_accumulation_dtypes(
            bf16_policy(grad_comm="f32"), compiled_text=self._GRAD_RS,
            grad_elem_counts={4096})
        assert len(out.findings) == 1
        assert "communication_data_type" in out.findings[0].message

    def test_collective_at_declared_comm_dtype_is_silent(self):
        # grad_comm=bf16 (the reference default) tolerates the bf16 psum
        out = check_accumulation_dtypes(
            bf16_policy(), compiled_text=self._GRAD_RS,
            grad_elem_counts={4096})
        assert out.ok

    def test_activation_sized_collective_is_silent(self):
        # payload matches no gradient leaf -> TP activation partial sum
        out = check_accumulation_dtypes(
            bf16_policy(grad_comm="f32"), compiled_text=self._GRAD_RS,
            grad_elem_counts={8192, 64})
        assert out.ok


# ----------------------------------------------------------------------
# N002: fp32 master-weight integrity
# ----------------------------------------------------------------------

class TestN002MasterIntegrity:
    def _compile(self, fn, *args, donate=(0,)):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return jax.jit(fn, donate_argnums=donate).lower(
                *args).compile()

    def test_seeded_donated_downcast_master_fires_once(self):
        """The donated-master-weight seed: the update chain returns the
        master in bf16, so the donated fp32 buffer cannot alias — the
        S001 table shows the break, N002 names the precision story."""
        master = {"w": jnp.ones((64, 64), jnp.float32)}

        def step(m, g):
            return {"w": (m["w"] - 0.1 * g).astype(jnp.bfloat16)}

        c = self._compile(step, master, jnp.ones((64, 64), jnp.float32))
        out = check_master_integrity(c, master=master, argnames=("m",))
        assert len(out.findings) == 1
        f = out.findings[0]
        assert f.rule == "N002" and "input_output_alias" in f.message

    def test_fp32_in_place_update_is_clean(self):
        master = {"w": jnp.ones((64, 64), jnp.float32)}

        def step(m, g):
            return {"w": m["w"] - 0.1 * g}

        c = self._compile(step, master, jnp.ones((64, 64), jnp.float32))
        assert check_master_integrity(c, master=master,
                                      argnames=("m",)).ok

    def test_master_stored_below_fp32_fires_tree_only(self):
        master = {"w": jnp.ones((8,), jnp.bfloat16)}
        out = check_master_integrity(master=master)
        assert len(out.findings) == 1
        assert "stored as bfloat16" in out.findings[0].message

    def test_integer_and_residual_leaves_skipped(self):
        opt = {"step": jnp.zeros((), jnp.int32),
               "error_w": {"w": jnp.zeros((8,), jnp.bfloat16)}}
        # int leaf: not floating; error_*: N003's territory
        assert check_master_integrity(opt=opt).ok

    def test_unused_leaf_is_dced_not_flagged(self):
        master = {"w": jnp.ones((8,), jnp.float32),
                  "dead": jnp.ones((8,), jnp.float32)}

        def step(m, g):
            return {"w": m["w"] - g, "dead": jnp.zeros((8,), jnp.float32)}

        c = self._compile(step, master, jnp.ones((8,), jnp.float32))
        out = check_master_integrity(c, master=master, argnames=("m",))
        # 'dead' is unused (its output is fresh zeros) — donation of an
        # unused buffer frees it; only never-aliased USED state counts
        assert all("dead" not in f.path for f in out.findings), \
            out.render()


# ----------------------------------------------------------------------
# N003: loss-scale coverage
# ----------------------------------------------------------------------

class TestN003LossScale:
    def test_seeded_dropped_inf_check_fires_once(self):
        """The dropped-loss-scale seed: a scaled step that never
        inf-checks — the backoff path can never trigger."""
        def step(m, g, scale):
            return m - (g / scale)

        c = jax.jit(step).lower(
            jnp.ones((8,), jnp.float32), jnp.ones((8,), jnp.float16),
            jnp.float32(1024.0)).compile()
        policy = PrecisionPolicy("f16", "f32", "f32", "f16", True)
        out = check_loss_scale(policy, compiled_text=c.as_text())
        assert len(out.findings) == 1
        assert "is-finite" in out.findings[0].message

    def test_inf_checked_step_is_silent(self):
        def step(m, g, scale):
            bad = jnp.logical_not(jnp.all(jnp.isfinite(g)))
            return jnp.where(bad, m, m - g / scale)

        c = jax.jit(step).lower(
            jnp.ones((8,), jnp.float32), jnp.ones((8,), jnp.float16),
            jnp.float32(1024.0)).compile()
        policy = PrecisionPolicy("f16", "f32", "f32", "f16", True)
        assert check_loss_scale(policy, compiled_text=c.as_text()).ok

    def test_scaled_grads_into_compressed_path_fires(self):
        policy = PrecisionPolicy("f16", "f32", "f32", "f16", True,
                                 compressed="onebit")
        out = check_loss_scale(policy)
        assert len(out.findings) == 1
        assert "error-feedback" in out.findings[0].message

    def test_residual_below_fp32_fires(self):
        opt = {"error_w": {"w": jnp.zeros((8,), jnp.bfloat16)},
               "error_s": {"w": jnp.zeros((8,), jnp.float32)}}
        out = check_loss_scale(bf16_policy(), opt=opt)
        assert len(out.findings) == 1
        assert "error_w" in out.findings[0].path

    def test_fp32_residuals_silent(self):
        opt = {"error_w": {"w": jnp.zeros((8,), jnp.float32)}}
        assert check_loss_scale(bf16_policy(), opt=opt).ok


# ----------------------------------------------------------------------
# N004: quantized-collective sanity
# ----------------------------------------------------------------------

class TestN004QuantizedGroups:
    def test_seeded_misaligned_groups_fire_once(self):
        params = {"w": jnp.zeros((65,), jnp.float32)}  # 65 % 8 != 0
        out = check_quantized_groups(params, dp=8)
        assert len(out.findings) == 1
        f = out.findings[0]
        assert f.rule == "N004" and "does not divide" in f.message

    def test_degenerate_leaf_smaller_than_groups_fires(self):
        params = {"b": jnp.zeros((4,), jnp.float32)}
        out = check_quantized_groups(params, dp=8)
        assert len(out.findings) == 1
        assert "pure zero-padding" in out.findings[0].message

    def test_aligned_groups_silent(self):
        params = {"w": jnp.zeros((64, 64), jnp.float32),
                  "tok": jnp.zeros((7,), jnp.int32)}  # int leaves skipped
        assert check_quantized_groups(params, dp=8).ok

    def test_qgz_block_misalignment_warns(self):
        params = {"w": jnp.zeros((8, 24), jnp.float32)}  # chunk 24
        out = check_quantized_groups(params, dp=8, block=16)
        assert len(out.findings) == 1
        assert out.findings[0].severity == "warning"

    def test_fp32_leak_on_compressed_wire_fires(self):
        params = {"w": jnp.zeros((64, 64), jnp.float32)}
        hlo = ("%a2a = f32[8,8,64]{2,1,0} all-to-all(f32[8,8,64]{2,1,0} "
               "%codes), replica_groups=[1,8]<=[8], dimensions={0}\n")
        out = check_quantized_groups(params, dp=8, compiled_text=hlo)
        assert len(out.findings) == 1
        assert "full precision went on the wire" in out.findings[0].message

    def test_int8_wire_and_f32_dequant_silent(self):
        params = {"w": jnp.zeros((64, 64), jnp.float32)}
        hlo = ("%a2a = s8[8,8,64]{2,1,0} all-to-all(s8[8,8,64]{2,1,0} "
               "%codes), replica_groups=[1,8]<=[8]\n"
               "%dq = f32[4096]{0} convert(s8[4096]{0} %codes2)\n")
        assert check_quantized_groups(params, dp=8,
                                      compiled_text=hlo).ok

    def test_dequant_below_fp32_fires(self):
        params = {"w": jnp.zeros((64, 64), jnp.float32)}
        hlo = "%dq = bf16[4096]{0} convert(s8[4096]{0} %codes)\n"
        out = check_quantized_groups(params, dp=8, compiled_text=hlo)
        assert len(out.findings) == 1
        assert "land fp32" in out.findings[0].message


# ----------------------------------------------------------------------
# found_inf_in_grads hardening (runtime/precision.py satellite)
# ----------------------------------------------------------------------

class TestFoundInfHardening:
    def test_integer_leaves_skipped(self):
        grads = {"w": jnp.array([1.0, jnp.inf]),
                 "count": jnp.zeros((3,), jnp.int32)}
        assert bool(found_inf_in_grads(grads))
        assert not bool(found_inf_in_grads(
            {"count": jnp.zeros((3,), jnp.int32)}))

    def test_empty_pytree_reports_no_overflow(self):
        assert not bool(found_inf_in_grads({}))
        assert not bool(found_inf_in_grads(None))

    def test_all_float_behavior_unchanged(self):
        assert not bool(found_inf_in_grads({"a": jnp.ones(3)}))
        assert bool(found_inf_in_grads({"a": jnp.array([jnp.nan])}))


# ----------------------------------------------------------------------
# the real programs stay silent (engine + serving integration)
# ----------------------------------------------------------------------

class TestEngineNumerics:
    def _engine(self, **kw):
        mcfg = model_cfg()
        base = {"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "steps_per_print": 1000}
        base.update(kw)
        return ds.initialize(
            base, loss_fn=T.make_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg))

    def test_fp16_step_sanitizes_clean_and_fp32_comm_declared_fires(self):
        """One engine, two policies: the real fp16 step is clean under
        the default policy (comm at compute dtype, the reference
        behavior), and the SAME program violates a declared-fp32
        communication_data_type — the policy cross-check, end to end."""
        engine = self._engine(fp16={"enabled": True}, mesh={"data": 8})
        batch = {"tokens": np.zeros(
            (engine.config.train_batch_size, 33), np.int32)}
        rep = engine.sanitize(batch)
        assert rep.ok, rep.render()

        engine.config.communication_data_type = "fp32"
        rep2 = engine.sanitize(batch)
        n001 = [f for f in rep2.findings if f.rule == "N001"]
        assert len(n001) == 1, rep2.render()
        assert "communication_data_type" in n001[0].message


class TestServingNumerics:
    def test_decode_buckets_sanitize_clean(self):
        from deepspeed_tpu.inference import init_inference

        mcfg = model_cfg(max_seq=64)
        eng = init_inference(
            T.init(mcfg, jax.random.PRNGKey(0)), mcfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=8),
            dtype=jnp.float32)
        rep = eng.sanitize_numerics(widths=[8])
        assert rep.ok, rep.render()
        assert "serving_decode[w8]" in rep.render() or rep.ok


# ----------------------------------------------------------------------
# comm/compressed.py error-feedback residuals (satellite coverage)
# ----------------------------------------------------------------------

class TestErrorFeedbackResiduals:
    def _mesh(self, dp=8):
        from jax.sharding import Mesh

        devs = np.array(jax.devices()[:dp]).reshape(1, dp, 1, 1, 1, 1)
        return Mesh(devs, ("pipe", "data", "zero", "expert", "seq",
                           "model"))

    def test_residual_dtype_stays_fp32_under_bf16_inputs(self):
        """bf16 gradients through the 1-bit hop must NOT drag the error
        memories down to bf16 — the compensation buffer carries the
        sub-quantization error bf16 cannot represent."""
        from deepspeed_tpu.comm.compressed import (
            compressed_mean,
            padded_cols,
        )

        mesh = self._mesh()
        dp, n = 8, 64
        grads_bf16 = jax.random.normal(
            jax.random.PRNGKey(0), (dp, n)).astype(jnp.bfloat16)
        ew = jnp.zeros((dp, padded_cols(n, dp)), jnp.float32)
        es = jnp.zeros((dp, padded_cols(n, dp) // dp), jnp.float32)
        with jax.sharding.set_mesh(mesh):
            out, ew2, es2 = jax.jit(
                lambda p, a, b: compressed_mean(
                    p.astype(jnp.float32), a, b, mesh))(grads_bf16, ew, es)
        assert ew2.dtype == jnp.float32 and es2.dtype == jnp.float32
        assert out.dtype == jnp.float32
        # and the N003 residual check agrees with the real buffers
        assert check_loss_scale(
            bf16_policy(), opt={"error_w": ew2, "error_s": es2}).ok

    def test_round_trip_error_bounded_under_bf16_inputs(self):
        """Error feedback over repeated rounds: the cumulative
        compressed mean tracks the true mean within one step's
        compression residual, even when inputs arrive as bf16."""
        from deepspeed_tpu.comm.compressed import (
            compressed_mean,
            padded_cols,
        )

        mesh = self._mesh()
        dp, n = 8, 64
        key = jax.random.PRNGKey(1)
        ew = jnp.zeros((dp, padded_cols(n, dp)), jnp.float32)
        es = jnp.zeros((dp, padded_cols(n, dp) // dp), jnp.float32)
        total_true = jnp.zeros((n,), jnp.float32)
        total_comp = jnp.zeros((n,), jnp.float32)
        with jax.sharding.set_mesh(mesh):
            f = jax.jit(lambda p, a, b: compressed_mean(
                p.astype(jnp.float32), a, b, mesh))
            for t in range(20):
                parts = jax.random.normal(
                    jax.random.fold_in(key, t), (dp, n)).astype(
                    jnp.bfloat16)
                out, ew, es = f(parts, ew, es)
                total_true += jnp.mean(parts.astype(jnp.float32), axis=0)
                total_comp += out
        rel = float(jnp.linalg.norm(total_comp - total_true)
                    / (jnp.linalg.norm(total_true) + 1e-6))
        assert rel < 0.25, rel

    def test_qgz_group_geometry_matches_n004_contract(self):
        """The geometry quantized_mean actually pads is exactly what
        N004 calls misaligned: a 65-element leaf over 8 workers."""
        from deepspeed_tpu.comm.compressed import padded_cols

        assert padded_cols(65, 8) == 72  # 7 padded zeros -> diluted scale
        out = check_quantized_groups({"w": jnp.zeros((65,), jnp.float32)},
                                     dp=8)
        assert len(out.findings) == 1 and "65" in out.findings[0].message


# ----------------------------------------------------------------------
# the dtype ledger + ds_numerics CLI gate
# ----------------------------------------------------------------------

class TestDtypeLedger:
    def test_ledger_shape_and_determinism(self):
        lo = jax.jit(lambda x, y: jnp.sum(x @ y)).lower(
            jnp.zeros((8, 8), jnp.bfloat16), jnp.zeros((8, 8), jnp.bfloat16))
        c = lo.compile()
        led = dtype_ledger(c, lo)
        assert led["dot"] == {"bf16": 1}
        assert "f32" in led["reduce"]
        assert led == dtype_ledger(c, lo)  # deterministic

    def test_diff_flags_new_dtype_as_error(self):
        cur = {"reduce": {"f32": 3, "bf16": 1}, "dot": {}}
        base = {"reduce": {"f32": 3}, "dot": {}}
        fs = diff_ledgers(cur, base, "p")
        assert len(fs) == 1 and fs[0].severity == "error"
        assert "bf16" in fs[0].message

    def test_diff_flags_count_drift_as_warning(self):
        cur = {"reduce": {"f32": 4}}
        base = {"reduce": {"f32": 3}}
        fs = diff_ledgers(cur, base, "p")
        assert len(fs) == 1 and fs[0].severity == "warning"

    def test_identical_ledgers_clean(self):
        led = {"reduce": {"f32": 3}, "collectives": {"all-gather":
                                                     {"bf16": 2}}}
        assert diff_ledgers(led, json.loads(json.dumps(led)), "p") == []


class TestDsNumericsScript:
    def _run(self, *args):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # the script sets its own device count
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "ds_gate.py"),
             "numerics", *args],
            capture_output=True, text=True, env=env, cwd=REPO,
            timeout=600)

    def test_check_passes_on_committed_tree(self):
        # filtered to the cheapest canonical program; the full
        # four-program sweep runs in the slow lane below
        r = self._run("--check", "--strict", "--programs",
                      "serving_decode_w8")
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(r.stdout.strip().splitlines()[-1])
        assert doc["ok"] and doc["findings"] == []

    def test_check_fails_on_injected_dtype_regression(self, tmp_path):
        base = json.load(open(os.path.join(REPO, "NUMERICS.json")))
        # erase the recorded f32 dots: the (unchanged) tree now reads
        # as "a new dtype appeared in serving_decode_w8.dot"
        prog = base["programs"]["serving_decode_w8"]
        prog["dot"] = {k: v for k, v in prog["dot"].items()
                       if k != "f32"}
        injected = tmp_path / "numerics.json"
        injected.write_text(json.dumps(base))
        r = self._run("--check", "--baseline", str(injected),
                      "--programs", "serving_decode_w8")
        assert r.returncode != 0, r.stdout + r.stderr
        doc = json.loads(r.stdout.strip().splitlines()[-1])
        assert not doc["ok"]
        assert any(f["rule"] == "N001" and "regression" in f["message"]
                   for f in doc["findings"])

    @pytest.mark.slow
    def test_full_sweep_passes_on_committed_tree(self):
        r = self._run("--check", "--strict")
        assert r.returncode == 0, r.stdout + r.stderr
        doc = json.loads(r.stdout.strip().splitlines()[-1])
        assert doc["ok"], doc
