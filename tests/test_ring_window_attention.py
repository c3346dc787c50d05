"""Ring attention, sliding-window attention and per-layer window
patterns.

Ring attention vs full causal attention (exact algorithm -> exact
match), its flash-tiled hops included; windowed attention vs a masked
reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu as ds
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops.attention import causal_attention

pytestmark = pytest.mark.usefixtures("pallas_interpret_module")

VOCAB = 128


class TestRingAttention:
    def _mesh(self, seq=4):
        devs = np.array(jax.devices()[: seq * 2]).reshape(1, 2, 1, 1, seq, 1)
        return Mesh(devs, ("pipe", "data", "zero", "expert", "seq", "model"))

    @pytest.mark.parametrize("kv_heads", [4, 2])
    def test_matches_full_causal(self, kv_heads):
        from deepspeed_tpu.parallel.ring_attention import ring_causal_attention

        mesh = self._mesh()
        B, S, H, D = 2, 64, 4, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, kv_heads, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, kv_heads, D), jnp.float32)

        want = causal_attention(q, k, v, use_flash=False)
        with jax.sharding.set_mesh(mesh):
            spec = NamedSharding(mesh, P(None, "seq"))
            qs, ksh, vs = (jax.device_put(x, spec) for x in (q, k, v))
            got = jax.jit(ring_causal_attention)(qs, ksh, vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)

    def test_uses_collective_permute(self):
        from deepspeed_tpu.parallel.ring_attention import ring_causal_attention
        from deepspeed_tpu.profiling.hlo import parse_hlo_collectives

        mesh = self._mesh()
        B, S, H, D = 1, 32, 4, 8
        x = jnp.zeros((B, S, H, D))
        with jax.sharding.set_mesh(mesh):
            spec = NamedSharding(mesh, P(None, "seq"))
            xs = jax.device_put(x, spec)
            compiled = jax.jit(ring_causal_attention).lower(xs, xs, xs).compile()
        ops = {r["op"] for r in parse_hlo_collectives(compiled.as_text())}
        assert "collective-permute" in ops, ops

    def test_engine_ring_matches_ulysses_trajectory(self):
        def build(impl):
            mcfg = T.TransformerConfig(
                vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=64,
                max_seq=32, variant="llama", use_flash=False,
                attention_impl=impl)
            return ds.initialize(
                {"train_micro_batch_size_per_gpu": 4,
                 "gradient_accumulation_steps": 1,
                 "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                 "mesh": {"data": 4, "seq": 2},
                 "seed": 7, "steps_per_print": 1000},
                loss_fn=T.make_loss_fn(mcfg),
                param_init_fn=lambda k: T.init(mcfg, k),
                param_logical_specs=T.logical_specs(mcfg))

        r = np.random.default_rng(0)
        batches = [{"tokens": r.integers(0, VOCAB, (16, 33)).astype(np.int32)}
                   for _ in range(3)]
        lu = [build("ulysses").train_batch(b)["loss"] for b in [batches[0]]]
        ring_engine = build("ring")
        lr_ = [ring_engine.train_batch(b)["loss"] for b in [batches[0]]]
        np.testing.assert_allclose(lr_, lu, rtol=2e-4)


class TestSlidingWindow:
    """Token-exact sliding window (Mistral-class) on the training path."""

    def test_windowed_attention_matches_reference(self):
        import numpy as np
        from deepspeed_tpu.ops.attention import causal_attention, _xla_attention

        r = np.random.default_rng(0)
        q = jnp.asarray(r.normal(size=(2, 16, 4, 8)), jnp.float32)
        k = jnp.asarray(r.normal(size=(2, 16, 4, 8)), jnp.float32)
        v = jnp.asarray(r.normal(size=(2, 16, 4, 8)), jnp.float32)
        got = causal_attention(q, k, v, use_flash=False, window=4)
        # handmade mask reference
        S = 16
        scale = 1.0 / np.sqrt(8)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        i = jnp.arange(S)[:, None]
        j = jnp.arange(S)[None, :]
        mask = (j <= i) & (j > i - 4)
        logits = jnp.where(mask[None, None], logits, -1e30)
        ref = jnp.einsum("bhqk,bkhd->bqhd",
                         jax.nn.softmax(logits, axis=-1), v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)

    def test_window_model_trains(self):
        import numpy as np
        import deepspeed_tpu as ds
        from deepspeed_tpu.models import transformer as T

        cfg = T.TransformerConfig(
            vocab_size=128, n_layers=2, n_heads=4, d_model=64, max_seq=64,
            variant="llama", use_flash=False, sliding_window=8)
        engine = ds.initialize(
            {"train_micro_batch_size_per_gpu": 2,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "seed": 7, "steps_per_print": 1000},
            loss_fn=T.make_loss_fn(cfg),
            param_init_fn=lambda k: T.init(cfg, k),
            param_logical_specs=T.logical_specs(cfg))
        r = np.random.default_rng(0)
        b = {"tokens": r.integers(0, 128, (16, 33)).astype(np.int32)}
        ls = [engine.train_batch(b)["loss"] for _ in range(4)]
        assert ls[-1] < ls[0]

    def test_window_requires_ulysses(self):
        from deepspeed_tpu.models import transformer as T

        with pytest.raises(ValueError, match="sliding_window"):
            T.TransformerConfig(
                vocab_size=64, n_layers=1, n_heads=2, d_model=32, max_seq=32,
                attention_impl="ring", sliding_window=4)


class TestRingFlashHops:
    """Round-5 flash-tiled ring hops: each hop runs the Pallas kernels
    (flash_attention_with_lse) and partials merge by logsumexp — the
    dense [Sl, Sl] f32 per-hop logits never materialize. Must match the
    full causal oracle exactly, GQA consumed in place (never repeated
    through the ICI hops), gradients included."""

    def _mesh(self, seq=4):
        devs = np.array(jax.devices()[: seq * 2]).reshape(1, 2, 1, 1, seq, 1)
        return Mesh(devs, ("pipe", "data", "zero", "expert", "seq", "model"))

    def test_with_lse_matches_softmax(self):
        from deepspeed_tpu.ops.pallas.flash_attention import (
            flash_attention_with_lse)

        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        B, S, H, KV, D = 2, 128, 4, 2, 64
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
        with jax.default_matmul_precision("highest"):
            o, lse = flash_attention_with_lse(q, k, v, causal=False,
                                              block_q=64, block_k=64)
            kr = jnp.repeat(k, 2, axis=2)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / np.sqrt(D)
            want_lse = jax.scipy.special.logsumexp(logits, axis=-1)
            p = jax.nn.softmax(logits, axis=-1)
            want_o = jnp.einsum("bhqk,bkhd->bqhd", p,
                                jnp.repeat(v, 2, axis=2))
        np.testing.assert_allclose(np.asarray(o), np.asarray(want_o),
                                   rtol=3e-5, atol=3e-5)
        np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                                   rtol=3e-5, atol=3e-5)

    def test_with_lse_grads_including_lse_cotangent(self):
        """The lse cotangent folds into the bwd kernels as a delta
        adjustment — check against jax.grad of the jnp reference for a
        loss that consumes BOTH outputs."""
        from deepspeed_tpu.ops.pallas.flash_attention import (
            flash_attention_with_lse)

        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        B, S, H, D = 1, 64, 2, 64
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)

        def loss_flash(q, k, v):
            o, lse = flash_attention_with_lse(q, k, v, causal=True,
                                              block_q=64, block_k=64)
            return jnp.sum(o ** 2) + 0.3 * jnp.sum(jnp.sin(lse))

        def loss_ref(q, k, v):
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
            mask = jnp.tril(jnp.ones((S, S), bool))
            logits = jnp.where(mask[None, None], logits, -jnp.inf)
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            p = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
            return jnp.sum(o ** 2) + 0.3 * jnp.sum(jnp.sin(lse))

        with jax.default_matmul_precision("highest"):
            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("kv_heads", [4, 2])
    def test_flash_hops_match_full_causal(self, kv_heads):
        from deepspeed_tpu.parallel.ring_attention import (
            ring_causal_attention)

        mesh = self._mesh()
        B, S, H, D = 1, 256, 4, 64
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, kv_heads, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, kv_heads, D), jnp.float32)
        want = causal_attention(q, k, v, use_flash=False)
        with jax.sharding.set_mesh(mesh):
            spec = NamedSharding(mesh, P(None, "seq"))
            qs, ksh, vs = (jax.device_put(x, spec) for x in (q, k, v))
            with jax.default_matmul_precision("highest"):
                got = jax.jit(lambda a, b, c: ring_causal_attention(
                    a, b, c, use_flash=True, block_q=64, block_k=64,
                ))(qs, ksh, vs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.parametrize("kv_heads", [2, 1])
    def test_flash_hops_grads_match_dense_ring(self, kv_heads):
        """GQA grads included: _ring_bwd's own head flattening (B*H vs
        B*KV) only the grouped case stresses."""
        from deepspeed_tpu.parallel.ring_attention import (
            ring_causal_attention)

        mesh = self._mesh()
        B, S, H, D = 1, 256, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(4), 4)
        q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, kv_heads, D), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, kv_heads, D), jnp.float32)
        do = jax.random.normal(ks[3], (B, S, H, D), jnp.float32)
        with jax.sharding.set_mesh(mesh):
            spec = NamedSharding(mesh, P(None, "seq"))
            qs, ksh, vs = (jax.device_put(x, spec) for x in (q, k, v))
            with jax.default_matmul_precision("highest"):
                # jit like the training path does (eager partial-auto
                # shard_map cannot execute the custom_vjp route)
                gfl = jax.jit(jax.grad(lambda a, b, c: jnp.sum(
                    ring_causal_attention(a, b, c, use_flash=True,
                                          block_q=64, block_k=64) * do),
                    argnums=(0, 1, 2)))(qs, ksh, vs)
                gdn = jax.jit(jax.grad(lambda a, b, c: jnp.sum(
                    ring_causal_attention(a, b, c) * do),
                    argnums=(0, 1, 2)))(qs, ksh, vs)
        for a, b in zip(gfl, gdn):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-3, atol=3e-3)


class TestWindowPattern:
    """Per-layer attention windows (GPT-Neo class,
    attention_window_pattern): the scan groups layers by pattern
    period; training must run the distinct static windows per
    sublayer."""

    def test_pattern_forward_matches_manual(self):
        cfg = T.TransformerConfig(
            vocab_size=64, n_layers=4, n_heads=2, d_model=32, max_seq=64,
            variant="gpt2", use_flash=False,
            attention_window_pattern=(0, 8))
        assert [cfg.window_for_layer(i) for i in range(4)] == [0, 8, 0, 8]
        params = T.init(cfg, jax.random.PRNGKey(0))
        toks = jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (2, 33)), jnp.int32)
        out = T.forward(params, toks, cfg)
        assert np.isfinite(np.asarray(out)).all()
        # a uniform-window config must NOT equal the pattern (the local
        # layers actually cut context)
        cfg_g = T.TransformerConfig(
            vocab_size=64, n_layers=4, n_heads=2, d_model=32, max_seq=64,
            variant="gpt2", use_flash=False)
        out_g = T.forward(params, toks, cfg_g)
        assert not np.allclose(np.asarray(out), np.asarray(out_g))

    def test_pattern_model_trains(self):
        cfg = T.TransformerConfig(
            vocab_size=64, n_layers=4, n_heads=2, d_model=32, max_seq=64,
            variant="gpt2", use_flash=False,
            attention_window_pattern=(0, 8))
        engine = ds.initialize(
            {"train_micro_batch_size_per_gpu": 2,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "steps_per_print": 10**9},
            loss_fn=T.make_loss_fn(cfg),
            param_init_fn=lambda k: T.init(cfg, k),
            param_logical_specs=T.logical_specs(cfg))
        r = np.random.default_rng(0)
        batch = {"tokens": r.integers(
            0, 64, (engine.config.train_batch_size, 33)).astype(np.int32)}
        losses = [float(engine.train_batch(batch)["loss"]) for _ in range(6)]
        assert losses[-1] < losses[0], losses

    def test_pattern_validation(self):
        with pytest.raises(ValueError, match="divide"):
            T.TransformerConfig(n_layers=3,
                                attention_window_pattern=(0, 8))
        with pytest.raises(ValueError, match="ulysses"):
            T.TransformerConfig(n_layers=4, attention_impl="ring",
                                attention_window_pattern=(0, 8))
