"""Graph sanitizer + ds-lint tests (analysis/).

Strategy: every sanitizer check must BOTH fire on a deliberately broken
program (exactly one finding per seeded violation) and stay silent on
the real training/inference step functions — a check that never fires is
dead weight, one that fires on healthy code is noise. Lint rules are
driven over synthetic sources plus the live tree (which must be clean —
the `scripts/ds_gate.py lint --strict` gate).
"""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.analysis import (
    RecompileTracker,
    check_donation,
    check_sharding,
    lint_paths,
    lint_source,
)
from deepspeed_tpu.models import transformer as T

VOCAB = 128


def model_cfg(**kw):
    base = dict(vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=64,
                max_seq=32, variant="llama", use_flash=False)
    base.update(kw)
    return T.TransformerConfig(**base)


def build_engine(**cfg_kw):
    mcfg = model_cfg()
    base = {
        "train_micro_batch_size_per_gpu": 1,
        "gradient_accumulation_steps": 2,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "seed": 7,
        "steps_per_print": 1000,
    }
    base.update(cfg_kw)
    return ds.initialize(
        base,
        loss_fn=T.make_loss_fn(mcfg),
        param_init_fn=lambda k: T.init(mcfg, k),
        param_logical_specs=T.logical_specs(mcfg),
    )


def data(batch, seq=33, seed=0):
    r = np.random.default_rng(seed)
    return {"tokens": r.integers(0, VOCAB, (batch, seq)).astype(np.int32)}


# ----------------------------------------------------------------------
# hlo.py parser hardening (dynamic dims, nested tuples, entry params)
# ----------------------------------------------------------------------

class TestHloParserHardening:
    def test_dynamic_dim_shapes(self):
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        hlo = "%x = bf16[<=128,64]{1,0} all-gather(bf16[<=32,64]{1,0} %a)"
        recs = parse_hlo_collectives(hlo)
        assert len(recs) == 1
        assert recs[0]["op"] == "all-gather"
        assert recs[0]["bytes"] == 128 * 64 * 2  # bound counts as the dim

    def test_tuple_of_tuple_start_result(self):
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        hlo = ("%ag = ((bf16[4,128]{1,0}, bf16[8,128]{1,0}), "
               "(bf16[16,128]{1,0}, bf16[32,128]{1,0})) "
               "all-gather-start(bf16[4,128]{1,0} %x, bf16[8,128]{1,0} %y)")
        recs = parse_hlo_collectives(hlo)
        assert len(recs) == 1
        # -start result is ((operands), (outputs), aux...): the wire
        # payload is the OUTPUT group summed, not the max member (see
        # tests/test_profiling.py::TestHloAccounting for the sugared
        # reduce-scatter/permute cases this fixes)
        assert recs[0]["bytes"] == (16 + 32) * 128 * 2

    def test_scalar_and_spaced_dims(self):
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        hlo = "%r = f32[] all-reduce(f32[] %x)"
        recs = parse_hlo_collectives(hlo)
        assert recs and recs[0]["bytes"] == 4

    def test_entry_parameter_parsing(self):
        from deepspeed_tpu.profiling.hlo import parse_entry_parameters

        hlo = textwrap.dedent("""\
        HloModule jit_f, num_partitions=8

        %fused (param_0: f32[4,2]) -> f32[4,2] {
          %param_0 = f32[4,2]{1,0} parameter(0)
        }

        ENTRY %main.42 (p0: f32[2,32], p1: s32[]) -> f32[2,32] {
          %p0 = f32[2,32]{1,0} parameter(0), sharding={devices=[4,2]<=[8]}, metadata={op_name="state[\\'params\\'][\\'w\\']"}
          %p1 = s32[] parameter(1), sharding={replicated}
          %dyn = bf16[<=16,8]{1,0} parameter(2)
        }
        """)
        recs = parse_entry_parameters(hlo)
        # the fusion's parameter(0) must NOT leak into the entry list
        assert [r["index"] for r in recs] == [0, 1, 2]
        assert recs[0]["dims"] == (2, 32)
        assert recs[0]["sharding"] == "devices=[4,2]<=[8]"
        assert recs[0]["op_name"] == "state['params']['w']"
        assert recs[1]["sharding"] == "replicated"
        assert recs[2]["dims"] == (16, 8)  # dynamic bound

    def test_real_compiled_entry_params(self):
        from deepspeed_tpu.profiling.hlo import entry_parameter_shardings

        mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("d", "m"))
        w = jax.device_put(
            jnp.zeros((8, 64)), NamedSharding(mesh, P("d", "m")))
        c = jax.jit(lambda s: s["w"] * 2).lower({"w": w}).compile()
        recs = entry_parameter_shardings(c)
        assert "s['w']" in recs
        assert recs["s['w']"]["dims"] == (2, 32)  # per-shard
        assert "devices" in recs["s['w']"]["sharding"]


# ----------------------------------------------------------------------
# sanitizer check (a): donation aliasing
# ----------------------------------------------------------------------

class TestDonationCheck:
    def test_donated_but_unaliased_fires_once(self):
        # output is a scalar; the donated [4, 8] buffer can never alias
        rep = check_donation(
            lambda x: x.sum(), (jnp.zeros((4, 8)),),
            donate_argnums=(0,), argnames=("x",), label="bad")
        assert len(rep.findings) == 1
        f = rep.findings[0]
        assert f.rule == "S001" and f.severity == "error" and f.path == "x"
        assert "copied" in f.message

    def test_aliased_donation_is_clean(self):
        rep = check_donation(
            lambda x: x + 1, (jnp.zeros((4, 8)),),
            donate_argnums=(0,), argnames=("x",))
        assert rep.ok

    def test_unused_donated_leaf_is_freed_not_flagged(self):
        # y is donated but unused: it is deleted, not copied — no finding
        rep = check_donation(
            lambda s: {"x": s["x"] + 1},
            ({"x": jnp.zeros((4, 8)), "y": jnp.zeros((3,))},),
            donate_argnums=(0,), argnames=("s",))
        assert rep.ok

    def test_argnames_default_from_signature(self):
        def step(buf):
            return buf.sum()

        rep = check_donation(step, (jnp.zeros((4, 8)),), donate_argnums=(0,))
        assert len(rep.findings) == 1 and rep.findings[0].path == "buf"

    def test_sharded_donation_resolved_from_compiled_table(self):
        # sharded args defer donation to XLA (jax.buffer_donor); ground
        # truth must come from the compiled input_output_alias table
        mesh = Mesh(np.array(jax.devices()[:8]).reshape(8), ("d",))
        x = jax.device_put(jnp.zeros((8, 64)), NamedSharding(mesh, P("d")))
        ok = check_donation(lambda v: v * 2, (x,), donate_argnums=(0,),
                            argnames=("v",))
        assert ok.ok
        bad = check_donation(lambda v: v.sum(), (x,), donate_argnums=(0,),
                             argnames=("v",))
        assert len(bad.findings) == 1 and bad.findings[0].rule == "S001"


# ----------------------------------------------------------------------
# sanitizer check (b): PartitionSpec survival
# ----------------------------------------------------------------------

class TestShardingCheck:
    def _mesh(self):
        return Mesh(np.array(jax.devices()[:8]).reshape(4, 2),
                    ("data", "model"))

    def test_dropped_spec_fires_once(self):
        mesh = self._mesh()
        aval = {"w": jax.ShapeDtypeStruct((8, 64), jnp.float32)}

        def f(state):
            # an in-program replicated constraint overrides the spec
            return jax.lax.with_sharding_constraint(
                state["w"], NamedSharding(mesh, P())) * 2.0

        c = jax.jit(f).lower(aval).compile()
        rep = check_sharding(c, {"w": P("model", None)}, aval, mesh,
                             argname="state")
        assert len(rep.findings) == 1
        f0 = rep.findings[0]
        assert f0.rule == "S002" and f0.severity == "error"
        assert "did not survive" in f0.message
        assert "state['w']" in f0.path

    def test_surviving_spec_is_clean(self):
        mesh = self._mesh()
        aval = {"w": jax.ShapeDtypeStruct((8, 64), jnp.float32)}

        def f(state):
            return jax.lax.with_sharding_constraint(
                state["w"], NamedSharding(mesh, P("model", None))) * 2.0

        c = jax.jit(f).lower(aval).compile()
        rep = check_sharding(c, {"w": P("model", None)}, aval, mesh,
                             argname="state")
        assert rep.ok

    def test_size1_axes_have_nothing_to_survive(self):
        mesh = self._mesh()
        aval = {"w": jax.ShapeDtypeStruct((8, 64), jnp.float32)}
        c = jax.jit(lambda s: s["w"] * 1.0).lower(aval).compile()
        # 'seq' is not even in this mesh: factor 1 -> skip, clean
        rep = check_sharding(c, {"w": P("seq", None)}, aval, mesh,
                             argname="state")
        assert rep.ok

    def test_structure_mismatch_is_reported_not_crashed(self):
        mesh = self._mesh()
        aval = {"w": jax.ShapeDtypeStruct((8, 64), jnp.float32)}
        c = jax.jit(lambda s: s["w"] * 1.0).lower(aval).compile()
        rep = check_sharding(c, {"w": P(), "extra": P()}, aval, mesh)
        assert len(rep.findings) == 1
        assert rep.findings[0].severity == "warning"


# ----------------------------------------------------------------------
# sanitizer check (c): recompilation hazards
# ----------------------------------------------------------------------

class TestRecompileTracker:
    def test_weak_type_drift_fires_once(self):
        t = RecompileTracker()
        assert t.record("step", (jnp.float32(1.0),)) is False  # baseline
        assert t.record("step", (1.0,)) is False  # miss
        assert len(t.findings) == 1
        f = t.findings[0]
        assert f.rule == "S003"
        assert "promotion" in f.message or "weak-type" in f.message

    def test_cache_hit_is_silent(self):
        t = RecompileTracker()
        t.record("step", (jnp.zeros((4,)),))
        assert t.record("step", (jnp.ones((4,)),)) is True  # same signature
        assert not t.findings

    def test_weak_type_drift_on_arrays(self):
        t = RecompileTracker()
        t.record("f", (jnp.float32(2.0) * 1,))           # strong f32 scalar
        t.record("f", (jnp.asarray(1.0) * 1.0,))
        # whichever direction the weak types land, a second distinct
        # signature must classify as weak-type/promotion, not shape churn
        if t.findings:
            assert "weak" in t.findings[0].message or \
                "promotion" in t.findings[0].message

    def test_shape_churn_classified(self):
        t = RecompileTracker()
        t.record("step", ({"tokens": np.zeros((4, 33), np.int32)},))
        t.record("step", ({"tokens": np.zeros((4, 17), np.int32)},))
        assert len(t.findings) == 1
        assert "shape churn" in t.findings[0].message
        assert "bucket" in t.findings[0].fix_hint

    def test_structure_churn_classified(self):
        t = RecompileTracker()
        t.record("step", ({"a": np.zeros(3)},))
        t.record("step", ({"a": np.zeros(3), "b": np.zeros(3)},))
        assert len(t.findings) == 1
        assert "STRUCTURE" in t.findings[0].message

    def test_report_and_reset(self):
        t = RecompileTracker()
        t.record("s", (np.zeros((2,)),))
        t.record("s", (np.zeros((3,)),))
        rep = t.report()
        assert not rep.ok and rep.by_rule() == {"S003": 1}
        t.reset()
        assert t.report().ok and t.n_signatures("s") == 0


# ----------------------------------------------------------------------
# the real step functions stay silent
# ----------------------------------------------------------------------

class TestEngineSanitize:
    def test_train_step_sanitizes_clean(self):
        engine = build_engine(
            zero_optimization={"stage": 3, "param_persistence_threshold": 64},
            bf16={"enabled": True},
            mesh={"data": 4, "model": 2},
        )
        batch = data(engine.config.train_batch_size)
        engine.train_batch(batch)
        rep = engine.sanitize(batch)
        assert rep.ok, rep.render()

    def test_recompile_hazard_surfaces_in_report(self):
        engine = build_engine(mesh={"data": 8})
        b = engine.config.train_batch_size
        engine.train_batch(data(b, seq=33))
        engine.train_batch(data(b, seq=17))  # deliberate shape churn
        rep = engine.sanitize(data(b, seq=33))
        assert any(f.rule == "S003" and "shape churn" in f.message
                   for f in rep.findings), rep.render()

    def test_inference_decode_step_sanitizes_clean(self):
        from deepspeed_tpu.inference import model as M

        mcfg = model_cfg(max_seq=64)
        params = jax.jit(
            lambda k: M.prepare(T.init(mcfg, k), mcfg))(jax.random.PRNGKey(0))
        cache = M.init_cache(mcfg, 16, 16, jnp.float32)
        S, NB = 4, 4
        tables = jnp.asarray(
            (np.arange(S * NB).reshape(S, NB) % 16).astype(np.int32))
        toks = jnp.zeros((S,), jnp.int32)
        ctx = jnp.full((S,), 5, jnp.int32)

        def step(params, cache, tokens, tables, ctx):
            return M.decode_step(params, cache, tokens, tables, ctx, mcfg,
                                 use_kernel=False)

        rep = check_donation(
            step, (params, cache, toks, tables, ctx), donate_argnums=(1,),
            argnames=("params", "cache", "tokens", "tables", "ctx"),
            label="decode_step")
        assert rep.ok, rep.render()


# ----------------------------------------------------------------------
# ds-lint rules
# ----------------------------------------------------------------------

def _findings(src, relpath="pkg/mod.py"):
    found, suppressed = lint_source(textwrap.dedent(src), relpath)
    return found, suppressed


class TestLintR001:
    def test_jit_decorated_conversion_fires(self):
        src = """
        import jax
        @jax.jit
        def f(x):
            y = x * 2
            return float(y)
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R001"]

    def test_jit_by_name_and_nested_def(self):
        src = """
        import jax, numpy as np
        def f(x):
            def inner(z):
                return np.asarray(z)
            return inner(x)
        g = jax.jit(f)
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R001"]

    def test_static_metadata_access_is_clean(self):
        src = """
        import jax
        @jax.jit
        def f(x):
            n = int(x.shape[0]) + int(x.ndim)
            m = len(x)
            return x * (n + m)
        """
        found, _ = _findings(src)
        assert not found

    def test_callback_body_is_host_code(self):
        src = """
        import jax
        @jax.jit
        def f(x):
            jax.experimental.io_callback(lambda v: print(int(v)), None, x)
            return x
        """
        found, _ = _findings(src)
        assert not found

    def test_unjitted_function_is_clean(self):
        src = """
        def host(x):
            return float(x)
        """
        found, _ = _findings(src)
        assert not found


class TestLintR002:
    HOT = "deepspeed_tpu/runtime/engine.py"

    def test_sync_in_hot_path_fires(self):
        src = """
        import jax
        class E:
            def train_batch(self, batch):
                out = self._step(batch)
                return jax.device_get(out)
        """
        found, _ = _findings(src, self.HOT)
        assert [f.rule for f in found] == ["R002"]

    def test_helper_is_allowlisted(self):
        src = """
        from deepspeed_tpu.utils.sync import host_sync
        class E:
            def train_batch(self, batch):
                return host_sync(self._step(batch))
        """
        found, _ = _findings(src, self.HOT)
        assert not found

    def test_cold_file_not_in_scope(self):
        src = """
        import jax
        def train_batch(batch):
            return jax.device_get(batch)
        """
        found, _ = _findings(src, "deepspeed_tpu/utils/timers.py")
        assert not found

    def test_cold_function_in_hot_file_is_clean(self):
        src = """
        import jax
        class E:
            def save_checkpoint(self, d):
                return jax.device_get(self.state)
        """
        found, _ = _findings(src, self.HOT)
        assert not found


class TestLintR003:
    def test_unlocked_mutation_fires(self):
        src = """
        import threading
        class Store:
            def __init__(self):
                self._inflight = {}
                self._lock = threading.Lock()
            def submit(self, l, v):
                self._inflight[l] = v
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R003"]

    def test_locked_mutation_is_clean(self):
        src = """
        import threading
        class Store:
            def __init__(self):
                self._inflight = {}
                self._lock = threading.Lock()
            def submit(self, l, v):
                with self._lock:
                    self._inflight[l] = v
        """
        found, _ = _findings(src)
        assert not found

    def test_locked_suffix_convention(self):
        src = """
        import threading
        class Store:
            def __init__(self):
                self._inflight = {}
                self._lock = threading.Lock()
            def _submit_locked(self, l, v):
                self._inflight[l] = v
        """
        found, _ = _findings(src)
        assert not found

    def test_unthreaded_class_is_clean(self):
        src = """
        class Cache:
            def __init__(self):
                self._d = {}
            def put(self, k, v):
                self._d[k] = v
        """
        found, _ = _findings(src)
        assert not found

    def test_mutating_method_call_fires(self):
        src = """
        import threading
        class Store:
            def __init__(self):
                self._q = []
                self._lock = threading.Lock()
            def push(self, v):
                self._q.append(v)
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R003"]


class TestLintR004:
    def test_undocumented_donation_fires(self):
        src = """
        import jax
        def build(step):
            return jax.jit(step, donate_argnums=(0,))
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R004"]

    def test_donation_comment_satisfies(self):
        src = """
        import jax
        def build(step):
            # donated: state aliases the returned state
            return jax.jit(step, donate_argnums=(0,))
        """
        found, _ = _findings(src)
        assert not found

    def test_plain_jit_not_in_scope(self):
        src = """
        import jax
        def build(step):
            return jax.jit(step)
        """
        found, _ = _findings(src)
        assert not found


class TestLintR005:
    def test_weak_literal_array_fires(self):
        src = """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            scale = jnp.array(0.5)
            return x * scale
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R005"]
        assert found[0].severity == "warning"

    def test_list_literal_and_full_fire(self):
        src = """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            mask = jnp.asarray([1, 0, 1])
            fill = jnp.full((4,), 7)
            return x * mask[0] + fill[0]
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R005", "R005"]

    def test_explicit_dtype_is_clean(self):
        src = """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            scale = jnp.array(0.5, dtype=jnp.float32)
            fill = jnp.full((4,), 7, dtype=jnp.int32)
            return x * scale + fill[0]
        """
        found, _ = _findings(src)
        assert not found

    def test_non_literal_value_is_clean(self):
        src = """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            return jnp.asarray(x) * jnp.array(x.shape[0] * [0])
        """
        # neither a bare literal value: traced x, computed list
        found, _ = _findings(src)
        assert not found

    def test_outside_jit_is_clean(self):
        src = """
        import jax.numpy as jnp
        def host():
            return jnp.array(0.5)
        """
        found, _ = _findings(src)
        assert not found

    def test_negated_literal_fires(self):
        src = """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            return x + jnp.array(-1.0)
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R005"]

    def test_pragma_suppresses(self):
        src = """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            s = jnp.array(0.5)  # ds-lint: ok R005 promotion is intended here
            return x * s
        """
        found, suppressed = _findings(src)
        assert not found and len(suppressed) == 1


class TestLintR006:
    def test_float64_mention_fires(self):
        src = """
        import jax
        import numpy as np
        @jax.jit
        def f(x):
            return x.astype(np.float64)
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R006"]
        assert "f64" in found[0].message

    def test_dtypeless_zeros_and_arange_fire(self):
        src = """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            acc = jnp.zeros((4, 4))
            idx = jnp.arange(4)
            return acc + x[idx]
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R006", "R006"]
        assert all(f.severity == "warning" for f in found)

    def test_pinned_dtypes_are_clean(self):
        src = """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            acc = jnp.zeros((4, 4), jnp.float32)
            acc2 = jnp.ones((4,), dtype=x.dtype)
            idx = jnp.arange(4, dtype=jnp.int32)
            return acc + acc2[idx]
        """
        found, _ = _findings(src)
        assert not found

    def test_astype_python_float_fires(self):
        src = """
        import jax
        @jax.jit
        def f(x):
            return x.astype(float)
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R006"]

    def test_astype_explicit_jnp_dtype_is_clean(self):
        src = """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            return x.astype(jnp.float32)
        """
        found, _ = _findings(src)
        assert not found

    def test_outside_jit_is_clean(self):
        src = """
        import numpy as np
        import jax.numpy as jnp
        def host():
            return jnp.zeros((4,)) + np.float64(1.0)
        """
        found, _ = _findings(src)
        assert not found

    def test_pragma_suppresses(self):
        src = """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def f(x):
            acc = jnp.zeros(x.shape)  # ds-lint: ok R006 inherits x64 policy deliberately
            return acc + x
        """
        found, suppressed = _findings(src)
        assert not found and len(suppressed) == 1


class TestLintR007:
    def test_psum_in_for_loop_fires(self):
        src = """
        import jax
        @jax.jit
        def f(xs):
            total = 0.0
            for x in xs:
                total = total + jax.lax.psum(x, "data")
            return total
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R007"]
        assert "unrolls the loop" in found[0].message
        assert found[0].severity == "warning"

    def test_all_gather_in_while_loop_fires(self):
        src = """
        import jax
        from jax import lax
        @jax.jit
        def f(x):
            i = 0
            while i < 4:
                x = lax.all_gather(x, "model")
                i += 1
            return x
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R007"]

    def test_comm_wrapper_names_fire_too(self):
        """The comm/ wrappers share the lax collective names — the
        unrolled-volume class does not care which module spelled it."""
        src = """
        import jax
        from deepspeed_tpu import comm
        @jax.jit
        def f(xs):
            out = []
            for x in xs:
                out.append(comm.psum_scatter(x, "data"))
            return out
        """
        found, _ = _findings(src)
        assert [f.rule for f in found] == ["R007"]

    def test_scan_and_fori_loop_are_clean(self):
        """The carried-loop forms compile ONE collective in the body —
        exactly the fix the rule suggests."""
        src = """
        import jax
        from jax import lax
        @jax.jit
        def f(x):
            def body(c, _):
                return c + lax.psum(c, "data"), None
            y, _ = lax.scan(body, x, None, length=4)
            return lax.fori_loop(0, 4, lambda i, c: c * 2, y)
        """
        found, _ = _findings(src)
        assert not found

    def test_collective_outside_loop_is_clean(self):
        src = """
        import jax
        @jax.jit
        def f(x):
            return jax.lax.psum(x, "data")
        """
        found, _ = _findings(src)
        assert not found

    def test_loop_outside_jit_is_clean(self):
        src = """
        import jax
        def host(xs):
            return [jax.lax.psum(x, "data") for x in xs]
        """
        found, _ = _findings(src)
        assert not found

    def test_pragma_suppresses(self):
        src = """
        import jax
        @jax.jit
        def f(xs):
            total = 0.0
            for x in xs:
                total = total + jax.lax.psum(x, "data")  # ds-lint: ok R007 2-hop unrolled ring, bounded by mesh axis
            return total
        """
        found, suppressed = _findings(src)
        assert not found and len(suppressed) == 1


class TestMergeReports:
    def _f(self, rule, path="p"):
        from deepspeed_tpu.analysis import Finding

        return Finding(rule=rule, path=path, line=0, severity="error",
                       message="m", fix_hint="")

    def test_folds_reports_and_raw_lists(self):
        from deepspeed_tpu.analysis import SanitizerReport, merge_reports

        a = SanitizerReport(findings=[self._f("S001")], label="a")
        b = SanitizerReport(findings=[self._f("S002"), self._f("S002")],
                            label="b")
        merged = merge_reports("all", a, b, [self._f("S003")])
        assert merged.label == "all"
        assert merged.by_rule() == {"S001": 1, "S002": 2, "S003": 1}
        assert not merged.ok

    def test_empty_merge_is_ok(self):
        from deepspeed_tpu.analysis import SanitizerReport, merge_reports

        merged = merge_reports("none", SanitizerReport(), SanitizerReport())
        assert merged.ok and merged.by_rule() == {}
        assert "clean" in merged.render()

    def test_merge_preserves_finding_order(self):
        from deepspeed_tpu.analysis import SanitizerReport, merge_reports

        a = SanitizerReport(findings=[self._f("S001", "first")])
        b = SanitizerReport(findings=[self._f("S002", "second")])
        merged = merge_reports("ordered", a, b)
        assert [f.path for f in merged.findings] == ["first", "second"]

    def test_merge_with_cost_attachment_renders(self):
        from deepspeed_tpu.analysis import (
            CostReport,
            SanitizerReport,
            merge_reports,
        )

        merged = merge_reports("c", SanitizerReport())
        merged.cost = CostReport(label="step", arg_bytes=2**20)
        assert "cost[step]" in merged.render()


class TestLintPragma:
    def test_same_line_pragma_suppresses(self):
        src = """
        import jax
        class E:
            def train_batch(self, b):
                return jax.device_get(b)  # ds-lint: ok R002 one deliberate sync
        """
        found, suppressed = _findings(src, TestLintR002.HOT)
        assert not found and len(suppressed) == 1

    def test_rule_scoped_pragma_only_matches_its_rule(self):
        src = """
        import jax
        class E:
            def train_batch(self, b):
                return jax.device_get(b)  # ds-lint: ok R001 wrong rule
        """
        found, suppressed = _findings(src, TestLintR002.HOT)
        assert len(found) == 1 and not suppressed

    def test_bare_pragma_suppresses_all(self):
        src = """
        import jax
        class E:
            def train_batch(self, b):
                return jax.device_get(b)  # ds-lint: ok
        """
        found, suppressed = _findings(src, TestLintR002.HOT)
        assert not found and len(suppressed) == 1

    def test_pragma_line_above(self):
        src = """
        import jax
        class E:
            def train_batch(self, b):
                # ds-lint: ok R002 metrics sync
                return jax.device_get(b)
        """
        found, suppressed = _findings(src, TestLintR002.HOT)
        assert not found and len(suppressed) == 1

    def test_multi_rule_pragma(self):
        """One pragma naming several rules suppresses exactly those:
        the R001+R002 double finding collapses, nothing else rides."""
        src = """
        import jax
        @jax.jit
        def step(x):
            return float(x) + int(x)  # ds-lint: ok R001 R002 both host reads intended
        """
        found, suppressed = _findings(src, TestLintR002.HOT)
        assert not [f for f in found if f.rule == "R001"]
        assert all(s.rule in ("R001", "R002") for s in suppressed)
        assert len(suppressed) >= 1

    def test_malformed_reason_with_rule_like_tokens(self):
        """Rule ids are harvested from the WHOLE pragma tail — a reason
        that mentions another rule id widens the suppression. Documented
        greedy behavior: keep rule ids out of prose reasons."""
        src = """
        import jax
        class E:
            def train_batch(self, b):
                return jax.device_get(b)  # ds-lint: ok R001 relates to R002 cleanup
        """
        found, suppressed = _findings(src, TestLintR002.HOT)
        # R002 appears in the tail (even as prose), so the R002 finding
        # is suppressed despite R001 being the "named" rule
        assert not found and len(suppressed) == 1

    def test_unknown_rule_number_suppresses_nothing_named(self):
        """A pragma naming only a non-existent 2-digit token has no
        R\\d{3} ids at all — it degrades to a bare `ok` and suppresses
        the line's findings (documented fallback)."""
        src = """
        import jax
        class E:
            def train_batch(self, b):
                return jax.device_get(b)  # ds-lint: ok R99 typo'd rule id
        """
        found, suppressed = _findings(src, TestLintR002.HOT)
        assert not found and len(suppressed) == 1

    def test_stale_pragma_on_clean_line_is_inert(self):
        """A pragma left behind after the offending code was fixed
        suppresses nothing and breaks nothing — zero findings, zero
        suppressed entries."""
        src = """
        import jax
        class E:
            def train_batch(self, b):
                out = self._step(b)  # ds-lint: ok R002 stale note
                return out
        """
        found, suppressed = _findings(src, TestLintR002.HOT)
        assert not found and not suppressed

    def test_pragma_two_lines_above_does_not_reach(self):
        """The pragma scope is one line (same line or directly above) —
        a distant pragma must NOT bless later findings."""
        src = """
        import jax
        class E:
            def train_batch(self, b):
                # ds-lint: ok R002 only covers the next line
                x = 1
                return jax.device_get(b)
        """
        found, suppressed = _findings(src, TestLintR002.HOT)
        assert len(found) == 1 and not suppressed


class TestTreeIsClean:
    def test_package_lints_clean(self):
        """The merged tree must stay lint-clean — the same gate as
        `python scripts/ds_gate.py lint --strict`."""
        import os

        pkg = os.path.dirname(os.path.abspath(ds.__file__))
        report = lint_paths([pkg], base=os.path.dirname(pkg))
        assert report.findings == [], report.render()
        assert report.files_checked > 50


class TestSyncHelpers:
    def test_host_sync_roundtrip(self):
        from deepspeed_tpu.utils.sync import host_sync, serving_readback

        x = jnp.arange(8.0)
        assert host_sync(x) is x
        rb = serving_readback(x[:1])
        assert rb.shape == (1,) and float(rb[0]) == 0.0
