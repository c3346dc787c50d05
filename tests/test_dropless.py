"""Dropless MoE tests (moe/dropless.py + the train/serve integration).

Contract being pinned (docs/moe.md):
- NO token is ever dropped: every top-k assignment routes (counts sum
  to T*k exactly), regardless of routing skew.
- Dropless matches the capacity-factor path's math wherever that path
  would not drop (same selection, same combine weights, same l_aux).
- EP is a layout, never the math: the a2a frame (EP=N) equals the
  sorted ragged wire (EP=1), and the noisy-gate rng is a pure function
  of (seed, step, layer) — byte-identical across mesh layouts.
- Serving reuses the same gating authority: the dropless grouped path,
  the scan path, and the training forward agree; expert stacks ride
  the groupwise-int8 QuantizedWeight machinery; the census reaches
  scheduler.metrics().
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as ds
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.moe import (
    chosen_scores,
    compute_capacity,
    dropless_apply,
    dropless_moe_ffn,
    dropless_topk_gating,
    expert_counts,
    grouped_mm,
    router_z_loss,
    rows_of,
    sigmoid_topk_gating,
    sort_by_expert,
    sort_pairs,
    sum_to_tokens,
    token_order,
    topk_gating,
)
from deepspeed_tpu.ops.pallas import interpret_kernels, token_sum

VOCAB = 128


def _logits(T_=64, X=4, seed=0, skew=0.0):
    r = np.random.default_rng(seed)
    base = r.normal(size=(T_, X))
    base[:, 0] += skew
    return jnp.asarray(base, jnp.float32)


def _weights(E=16, F=32, X=4, seed=1, scale=0.1):
    r = np.random.default_rng(seed)
    return {
        "router": jnp.asarray(r.normal(size=(E, X)), jnp.float32),
        "w_in": jnp.asarray(r.normal(size=(X, E, F)), jnp.float32) * scale,
        "w_gate": jnp.asarray(r.normal(size=(X, E, F)), jnp.float32) * scale,
        "w_out": jnp.asarray(r.normal(size=(X, F, E)), jnp.float32) * scale,
        "b_in": jnp.asarray(r.normal(size=(X, F)), jnp.float32) * scale,
        "b_out": jnp.asarray(r.normal(size=(X, E)), jnp.float32) * scale,
    }


class TestDroplessGating:
    def test_zero_drops_pinned_under_extreme_skew(self):
        # every token wants expert 0: capacity routing would drop almost
        # everything; dropless routes every assignment, always
        logits = _logits(T_=128, skew=10.0)
        idx, w, _, _ = dropless_topk_gating(logits, 2)
        counts = expert_counts(idx, 4)
        assert int(counts.sum()) == 128 * 2  # nothing lost
        assert int(counts[0]) == 128  # the hot expert holds every token
        # the capacity path on the same logits measurably drops
        _, disp, _ = topk_gating(logits, 2, capacity_factor=0.25,
                                 min_capacity=1)
        assert int(jnp.sum(disp)) < 128 * 2

    @pytest.mark.parametrize("k", [1, 2, 3])
    def test_matches_capacity_path_where_nothing_drops(self, k):
        """Same selection, same combine weights, same l_aux as
        topk_gating with ample capacity (the no-drop regime)."""
        logits = _logits()
        comb, disp, aux = topk_gating(logits, k, capacity_factor=4.0)
        idx, w, aux_d, _ = dropless_topk_gating(logits, k)
        T_, X = logits.shape
        cap_w = np.asarray(jnp.sum(comb, axis=-1))  # [T, X]
        drop_w = np.zeros((T_, X), np.float32)
        for t in range(T_):
            for j in range(k):
                drop_w[t, int(idx[t, j])] += float(w[t, j])
        np.testing.assert_allclose(drop_w, cap_w, atol=1e-6)
        np.testing.assert_allclose(float(aux_d), float(aux), rtol=1e-6)

    def test_topk_bounds_validated(self):
        logits = _logits(X=4)
        with pytest.raises(ValueError):
            dropless_topk_gating(logits, 0)
        with pytest.raises(ValueError):
            dropless_topk_gating(logits, 5)

    def test_z_loss_uniform_logits(self):
        # logits == 0 -> logsumexp == log(X) exactly
        z = router_z_loss(jnp.zeros((8, 4), jnp.float32))
        np.testing.assert_allclose(float(z), float(np.log(4.0) ** 2),
                                   rtol=1e-6)

    def test_gate_math_fp32_under_bf16_tokens(self):
        w = _weights()
        toks = jnp.asarray(np.random.default_rng(2).normal(size=(32, 16)),
                           jnp.bfloat16)
        res = dropless_moe_ffn(toks, w["router"], w["w_in"], w["w_out"],
                               w_gate=w["w_gate"], act=jax.nn.silu,
                               top_k=2)
        assert res.l_aux.dtype == jnp.float32
        assert res.z_loss.dtype == jnp.float32
        assert res.out.dtype == jnp.bfloat16


class TestGenericCapacityTopK:
    """Satellite: topk_gating generalized past the k in {1, 2} limit,
    with second-and-later choice queues offset by KEPT tokens only."""

    def test_k3_capacity_enforced_no_slot_reuse(self):
        logits = _logits(T_=64, X=8)
        comb, disp, _ = topk_gating(logits, 3, capacity_factor=1.0,
                                    min_capacity=1)
        C = compute_capacity(64, 8, 3.0, 1)
        assert disp.shape == (64, 8, C)
        assert int(jnp.sum(disp, axis=0).max()) <= 1  # no slot reused
        assert int(jnp.sum(disp, axis=(0, 2)).max()) <= C

    def test_k3_renormalized_with_ample_capacity(self):
        comb, disp, _ = topk_gating(_logits(X=8), 3, capacity_factor=8.0)
        per_token = jnp.sum(comb, axis=(1, 2))
        np.testing.assert_allclose(np.asarray(per_token), 1.0, atol=1e-5)
        assert int(jnp.sum(disp, axis=(1, 2)).min()) == 3

    def test_typed_error_retired(self):
        # k=4 of 8 experts routes; out-of-range k still raises
        comb, disp, _ = topk_gating(_logits(X=8), 4, capacity_factor=8.0)
        assert int(jnp.sum(disp, axis=(1, 2)).min()) == 4
        with pytest.raises(ValueError):
            topk_gating(_logits(X=4), 5)

    def test_second_choice_queue_counts_only_kept_tokens(self):
        """All tokens first-choose expert 0 (overflows capacity) and
        second-choose expert 1 (plenty of room): the kept-count offset
        must admit second choices into expert 1's free slots."""
        T_ = 16
        logits = jnp.tile(
            jnp.asarray([[10.0, 5.0, 0.0, -50.0]], jnp.float32), (T_, 1))
        comb, disp, _ = topk_gating(logits, 2, capacity_factor=0.5,
                                    min_capacity=1)
        C = compute_capacity(T_, 4, 1.0, 1)
        per_expert = np.asarray(jnp.sum(disp, axis=(0, 2)))
        assert per_expert[0] == C  # first choices capped at capacity
        assert per_expert[1] == C  # second choices fill their own queue

    def test_wrapper_parity(self):
        from deepspeed_tpu.moe import top1_gating, top2_gating

        logits = _logits()
        for wrapped, k in ((top1_gating, 1), (top2_gating, 2)):
            cw, dw, aw = wrapped(logits, capacity_factor=2.0)
            cg, dg, ag = topk_gating(logits, k, capacity_factor=2.0)
            np.testing.assert_array_equal(np.asarray(cw), np.asarray(cg))
            np.testing.assert_array_equal(np.asarray(dw), np.asarray(dg))


class TestDroplessWires:
    def test_sort_is_stable_and_complete(self):
        idx, _, _, _ = dropless_topk_gating(_logits(skew=3.0), 2)
        order, src, sorted_e = sort_by_expert(idx)
        # expert ids non-decreasing; every assignment appears once
        se = np.asarray(sorted_e)
        assert (np.diff(se) >= 0).all()
        assert sorted(np.asarray(order).tolist()) == list(range(idx.size))
        # stability: within one expert run, source slots stay ascending
        flat = np.asarray(idx).reshape(-1)
        for e in range(4):
            slots = np.asarray(order)[se == e]
            assert (np.diff(slots) > 0).all()
            assert (flat[slots] == e).all()

    def test_ragged_equals_dense_oracle(self):
        r = np.random.default_rng(3)
        xs = jnp.asarray(r.normal(size=(24, 8)), jnp.float32)
        w = jnp.asarray(r.normal(size=(3, 8, 16)), jnp.float32)
        counts = jnp.asarray([10, 3, 11], jnp.int32)
        np.testing.assert_allclose(
            np.asarray(grouped_mm(xs, w, counts, impl="ragged")),
            np.asarray(grouped_mm(xs, w, counts, impl="dense")),
            atol=1e-6)
        with pytest.raises(ValueError):
            grouped_mm(xs, w, counts, impl="bogus")

    @pytest.mark.parametrize("gated", [True, False])
    def test_a2a_frame_equals_ragged_wire(self, gated):
        """The EP frame (ep_size=2; pure reshape math without a mesh)
        and the sorted ragged wire compute the same token mixes."""
        w = _weights()
        toks = jnp.asarray(np.random.default_rng(4).normal(size=(64, 16)),
                           jnp.float32)
        kw = dict(act=jax.nn.silu if gated else jax.nn.gelu, top_k=2)
        if gated:
            kw["w_gate"] = w["w_gate"]
        else:
            kw.update(b_in=w["b_in"], b_out=w["b_out"])
        r1 = dropless_moe_ffn(toks, w["router"], w["w_in"], w["w_out"],
                              ep_size=1, **kw)
        r2 = dropless_moe_ffn(toks, w["router"], w["w_in"], w["w_out"],
                              ep_size=2, **kw)
        np.testing.assert_allclose(np.asarray(r1.out), np.asarray(r2.out),
                                   atol=1e-5)
        np.testing.assert_array_equal(np.asarray(r1.counts),
                                      np.asarray(r2.counts))

    def test_indivisible_token_count_falls_back_to_ragged(self):
        w = _weights()
        toks = jnp.asarray(np.random.default_rng(5).normal(size=(63, 16)),
                           jnp.float32)
        res = dropless_moe_ffn(toks, w["router"], w["w_in"], w["w_out"],
                               w_gate=w["w_gate"], act=jax.nn.silu,
                               top_k=2, ep_size=2)  # 63 % 2 != 0
        assert res.out.shape == (63, 16)
        assert int(res.counts.sum()) == 63 * 2

    def test_dropless_apply_matches_ffn(self):
        """The serving entry point (pre-computed routing) equals the
        full ffn on the same decisions."""
        w = _weights()
        toks = jnp.asarray(np.random.default_rng(6).normal(size=(32, 16)),
                           jnp.float32)
        logits = toks @ w["router"]
        idx, wts, _, _ = dropless_topk_gating(logits, 2)
        out = dropless_apply(toks, idx, wts, expert_counts(idx, 4),
                             w["w_in"], w["w_out"], w_gate=w["w_gate"],
                             act=jax.nn.silu)
        ref = dropless_moe_ffn(toks, w["router"], w["w_in"], w["w_out"],
                               w_gate=w["w_gate"], act=jax.nn.silu,
                               top_k=2)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref.out),
                                   atol=1e-6)


def _eqns(jaxpr):
    """Every equation of `jaxpr`, the bodies of cond / scan / checkpoint
    / custom rules included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _element_indexed(jaxpr, n_pairs):
    """The gathers and scatters of `jaxpr` that move ONE element an
    index over n_pairs indices or more: (primitive, indices, scope)."""
    found = []
    for eqn in _eqns(jaxpr):
        name = eqn.primitive.name
        if name == "gather" or name.startswith("scatter"):
            n_idx = int(np.prod(eqn.invars[1].aval.shape[:-1]))
            moved = eqn.outvars[0].aval if name == "gather" \
                else eqn.invars[2].aval
            if moved.size == n_idx and n_idx >= n_pairs:
                found.append((name, n_idx, str(eqn.source_info.name_stack)))
    return found


def _row_scatters(jaxpr, n_rows):
    """The scatters of `jaxpr` whose updates are n_rows ROWS or more:
    (primitive, updates' shape, scope)."""
    return [(eqn.primitive.name, eqn.invars[2].aval.shape,
             str(eqn.source_info.name_stack))
            for eqn in _eqns(jaxpr)
            if eqn.primitive.name.startswith("scatter")
            and eqn.invars[2].aval.ndim > 1
            and eqn.invars[2].aval.shape[0] >= n_rows]


class TestPairsRideTheSort:
    """A per-pair value is never fetched or put back by an element-
    indexed gather or scatter over the T x K pairs (~8.7 ns an element
    on a v5e whatever it moves, PERF.md §6, PR 59): it rides the sort,
    or comes from a comparison. Every replaced value bit for bit."""

    T_, K, X = 64, 4, 8

    def _routing(self, seed=0):
        r = np.random.default_rng(seed)
        idx = jnp.asarray(np.argsort(r.normal(size=(self.T_, self.X)),
                                     axis=-1)[:, :self.K], jnp.int32)
        w = jnp.asarray(r.uniform(size=(self.T_, self.K)), jnp.float32)
        return idx, w

    # count < K: the list is a PREFIX of the sorted pairs; count >= K:
    # all of them
    @pytest.mark.parametrize("held", [(2, 2), (5, 3), (0, 6), (0, 8)])
    def test_held_list_equals_argsort_and_three_gathers(self, held):
        start, count = held
        idx, w = self._routing()
        bound = self.T_ * min(self.K, count)
        local = idx.reshape(-1) - start
        is_held = (local >= 0) & (local < count)
        key = jnp.where(is_held, local, count)
        order = jnp.argsort(key, stable=True)
        sk, so, sw = sort_pairs(key, w.reshape(-1))
        np.testing.assert_array_equal(so, order)
        np.testing.assert_array_equal(sk, key[order])
        np.testing.assert_array_equal(sw, w.reshape(-1)[order])
        np.testing.assert_array_equal(sk[:bound] < count,
                                      is_held[order[:bound]])
        # every held pair is inside the list, whatever the bound cut
        assert int(jnp.sum(sk[:bound] < count)) == int(jnp.sum(is_held))

        cot = jnp.asarray(np.random.default_rng(1).normal(size=bound),
                          jnp.float32)
        by_sort = jax.grad(lambda v: jnp.sum(
            sort_pairs(key, v.reshape(-1))[2][:bound] * cot))(w)
        by_gather = jax.grad(lambda v: jnp.sum(
            v.reshape(-1)[order[:bound]] * cot))(w)
        np.testing.assert_array_equal(by_sort, by_gather)

    def test_sort_by_expert_is_the_same_sort(self):
        idx, w = self._routing(2)
        order, src, sorted_e = sort_by_expert(idx)
        sk, so, _ = sort_pairs(idx.reshape(-1), w.reshape(-1))
        np.testing.assert_array_equal(order, so)
        np.testing.assert_array_equal(sorted_e, sk)
        np.testing.assert_array_equal(src, so // self.K)

    @pytest.mark.parametrize("shape", ["pairs", "flat"])
    def test_census_equals_scatter_add(self, shape):
        idx, _ = self._routing(3)
        idx = idx.at[:, 0].set(1)  # a crowded expert
        ids = idx if shape == "pairs" else idx.reshape(-1)
        want = jnp.zeros((self.X,), jnp.int32).at[idx.reshape(-1)].add(1)
        got = expert_counts(ids, self.X)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("bias", [False, True])
    def test_chosen_scores_equal_take_along_axis(self, bias):
        r = np.random.default_rng(4)
        logits = r.normal(size=(self.T_, self.X)).astype(np.float32)
        logits[:, 5] = logits[:, 2]  # ties: the lowest index is chosen
        logits[::3] = 0.25           # a whole row of ties
        logits = jnp.asarray(logits)
        b = jnp.asarray(r.normal(size=self.X), jnp.float32) if bias else None
        scores = jax.nn.sigmoid(logits)
        _, want_idx = jax.lax.top_k(scores + b if bias else scores, self.K)
        want = jnp.take_along_axis(scores, want_idx, axis=-1)
        np.testing.assert_array_equal(chosen_scores(scores, want_idx), want)
        idx, wts = sigmoid_topk_gating(logits, self.K, b, renormalize=True,
                                       scale=2.5)
        np.testing.assert_array_equal(idx, want_idx)
        np.testing.assert_array_equal(
            wts, want / (jnp.sum(want, -1, keepdims=True) + 1e-20) * 2.5)
        # the softmax gate's weights, and both gradients
        sidx, sw, _, _ = dropless_topk_gating(logits, self.K,
                                              renormalize=False)
        np.testing.assert_array_equal(sw, jnp.take_along_axis(
            jax.nn.softmax(logits, axis=-1), sidx, axis=-1))
        cot = jnp.asarray(r.normal(size=(self.T_, self.K)), jnp.float32)
        np.testing.assert_array_equal(
            jax.grad(lambda s: jnp.sum(chosen_scores(s, want_idx) * cot))(
                scores),
            jax.grad(lambda s: jnp.sum(jnp.take_along_axis(
                s, want_idx, axis=-1) * cot))(scores))

    @pytest.mark.parametrize("scoring,bias", [
        ("softmax", False), ("sigmoid", True), ("sigmoid", False)])
    @pytest.mark.parametrize("held", [None, (2, 2), (0, 6)])
    def test_no_element_indexed_op_over_the_pairs(self, held, scoring, bias):
        """What stands for the counter a static mechanism cannot have:
        the jaxpr of a routed block's value and gradient holds no
        gather or scatter of one element an index over T x K indices
        (the row gathers and row scatter-adds of E elements an index
        stay). Whole through _ragged_wire, held through _held_wire."""
        Xh = self.X if held is None else held[1]
        w = _weights(X=Xh)
        router = _weights(X=self.X)["router"]
        toks = jnp.asarray(
            np.random.default_rng(5).normal(size=(self.T_, 16)), jnp.float32)

        def loss(toks, router, w_in, w_out, w_gate):
            res = dropless_moe_ffn(
                toks, router, w_in, w_out, w_gate, act=jax.nn.silu,
                top_k=self.K, held=held, scoring=scoring, renormalize=True,
                choice_bias=jnp.zeros(self.X) if bias else None)
            return jnp.sum(res.out ** 2) + res.l_aux + res.z_loss, res.counts

        # (traced as where kernels run: elsewhere sum_to_tokens falls
        # back to XLA's sorted segment sum)
        with interpret_kernels():
            jaxpr = jax.make_jaxpr(jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(
                    toks, router, w["w_in"], w["w_out"], w["w_gate"])
        assert _element_indexed(jaxpr.jaxpr, self.T_ * self.K) == []
        # and inside a held chunk no ROW is moved by a scatter either
        # (2.6-2.9 ms for 32,768 rows of 2,048 on a v5e, twelve times
        # their gather: PERF.md §6, PR 61); the whole wire keeps its
        # segment sum, which the same walk sees
        assert (_row_scatters(jaxpr.jaxpr, self.T_) == []) == (
            held is not None)
        # (the walk does see such an op where there is one)
        probe = jax.make_jaxpr(jax.grad(lambda v: jnp.sum(
            v[jnp.arange(self.T_ * self.K) % self.T_])))(toks[:, 0])
        assert _element_indexed(probe.jaxpr, self.T_ * self.K)


class TestRowsGoBackInTokenOrder:
    """Inside a chunk of the held wire no row is moved by a scatter:
    rows_of and sum_to_tokens are each the other's transpose, and the
    sum is a sort, a row gather and a banded one-hot product
    (ops/pallas/token_sum.py) that equals the segment sum it replaced,
    dead rows left out whatever they hold."""

    @staticmethod
    def _chunk(name):
        """(src, live, rows, n_tokens) of a chunk that crosses the
        edges of 16-row blocks and 8-token tiles."""
        r = np.random.default_rng(7)
        T_, C = 37, 100  # neither a multiple of a tile
        src = r.integers(0, T_, C)
        live = r.random(C) < 0.6
        if name == "a_token_holds_many_rows":
            src[10:18] = 21
            live[10:18] = True
        elif name == "a_run_straddles_two_blocks":
            # tokens 8..15 (one tile) own rows 12..19 of the sorted list:
            # 12 rows under them, 8 of theirs, across the edge at 16
            src = np.concatenate([np.repeat(np.arange(6), 2),
                                  np.arange(8, 16), np.full(C - 20, 30)])
            live = np.arange(C) < 24
        elif name == "most_tokens_hold_no_row":
            live &= src > 30
        elif name == "every_row_dead":
            live[:] = False
        elif name == "every_row_live":
            live[:] = True
        elif name == "tiles_divide_it":
            T_, C = 32, 96
            src, live = src[:C] % T_, live[:C]
        rows = r.normal(size=(C, 24)).astype(np.float32)
        if name == "dead_rows_hold_inf_and_nan":
            rows[~live] = np.where(r.random((int((~live).sum()), 24)) < 0.5,
                                   np.inf, np.nan)
        return (jnp.asarray(src, jnp.int32), jnp.asarray(live),
                jnp.asarray(rows), T_)

    @pytest.mark.parametrize("lane", ["no_kernel", "kernel"])
    @pytest.mark.parametrize("name", [
        "edges", "a_token_holds_many_rows", "a_run_straddles_two_blocks",
        "most_tokens_hold_no_row", "every_row_dead", "every_row_live",
        "tiles_divide_it", "dead_rows_hold_inf_and_nan"])
    def test_the_banded_sum_is_the_segment_sum(self, name, lane):
        src, live, rows, T_ = self._chunk(name)
        want = jax.ops.segment_sum(jnp.where(live[:, None], rows, 0), src,
                                   num_segments=T_)
        o = token_order(src, live, T_)
        np.testing.assert_array_equal(o.ids[:-1] <= o.ids[1:], True)
        np.testing.assert_array_equal(o.ids, jnp.where(live, src, T_)[o.perm])
        if lane == "no_kernel":  # what a backend without Mosaic runs
            assert not token_sum.kernels_runnable()
            got = token_sum.token_tile_sum(rows[o.perm], o.ids, T_)
        else:  # the Pallas body, interpreted
            got = token_sum._tile_sum(rows[o.perm], o.ids, T_, 16, 8, True)
        assert got.shape == want.shape and got.dtype == rows.dtype
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)
        # at the package's own tiles, through the public entry
        np.testing.assert_allclose(sum_to_tokens(rows, o), want, rtol=0,
                                   atol=2e-6)

    def test_bf16_rows_are_summed_in_float32_and_rounded_once(self):
        r = np.random.default_rng(8)
        T_, C = 16, 128  # eight rows a token: a bf16 running sum drifts
        src = jnp.asarray(np.arange(C) % T_, jnp.int32)
        rows = jnp.asarray(r.normal(size=(C, 128)), jnp.bfloat16)
        o = token_order(src, jnp.ones(C, bool), T_)
        want = jax.ops.segment_sum(rows.astype(jnp.float32), src,
                                   num_segments=T_).astype(jnp.bfloat16)
        for got in (token_sum.token_tile_sum(rows[o.perm], o.ids, T_),
                    token_sum._tile_sum(rows[o.perm], o.ids, T_, 32, 8,
                                        True)):
            assert got.dtype == jnp.bfloat16
            np.testing.assert_array_equal(got, want)

    def test_a_width_over_a_slab_is_walked_in_slabs(self, monkeypatch):
        src, live, rows, T_ = self._chunk("edges")
        rows = jnp.tile(rows, (1, 16))  # E 384: three slabs of 128
        monkeypatch.setattr(token_sum, "_E_TILE", 128)
        o = token_order(src, live, T_)
        got = token_sum._tile_sum.__wrapped__(rows[o.perm], o.ids, T_, 16, 8,
                                              True)
        np.testing.assert_allclose(got, jax.ops.segment_sum(
            jnp.where(live[:, None], rows, 0), src, num_segments=T_),
            rtol=0, atol=2e-6)

    @pytest.mark.parametrize("n_rows,n_tokens,want", [
        (32768, 16384, 191), (100, 37, 1), (257, 256, 2), (16, 8, 1)])
    def test_pairs_bound(self, n_rows, n_tokens, want):
        assert token_sum.pairs_bound(n_rows, n_tokens) == want

    @pytest.mark.parametrize("name", ["edges", "every_row_live",
                                      "a_run_straddles_two_blocks"])
    def test_the_schedule_visits_every_tile_and_every_live_pair(self, name):
        src, live, _, T_ = self._chunk(name)
        o = token_order(src, live, T_)
        rb, tb = 16, 8
        nb, nt = -(-src.shape[0] // rb), -(-T_ // tb)
        ids = np.pad(np.asarray(o.ids), (0, nb * rb - src.shape[0]),
                     constant_values=T_)
        blk, tile, flags, n_live = map(np.asarray, token_sum._schedule(
            jnp.asarray(ids), T_, nb, nt, rb, tb))
        assert len(blk) == nb + nt - 1 and n_live[0] == int(live.sum())
        real = flags != 0
        # every tile once first and once last, in order; a step with
        # rows for every (block, tile) pair that shares a live row
        assert list(tile[(flags & 1) != 0]) == list(range(nt))
        assert list(tile[(flags & 2) != 0]) == list(range(nt))
        assert (np.diff(tile[real]) >= 0).all()
        pairs = {(p // rb, t // tb) for p, t in enumerate(ids) if t < T_}
        assert pairs <= {(b, t) for b, t, f in zip(blk, tile, flags)
                         if f & 4}
        # the steps past the last pair repeat it: nothing is fetched
        assert (blk[~real] == blk[real][-1]).all()
        assert (tile[~real] == nt - 1).all()

    def test_each_mover_is_the_others_transpose(self):
        src, live, rows, T_ = self._chunk("dead_rows_hold_inf_and_nan")
        o = token_order(src, live, T_)
        tokens = jnp.asarray(
            np.random.default_rng(9).normal(size=(T_, rows.shape[1])),
            jnp.float32)
        got_rows, back = jax.vjp(lambda t: rows_of(t, o), tokens)
        # a live row is its token's; a dead row is nobody's (a constant)
        live_rows = jnp.where(live[:, None], tokens[src], 0)
        np.testing.assert_array_equal(
            jnp.where(live[:, None], got_rows, 0), live_rows)
        np.testing.assert_array_equal(back(rows)[0], sum_to_tokens(rows, o))
        _, back = jax.vjp(lambda r: sum_to_tokens(r, o), rows)
        np.testing.assert_array_equal(back(tokens)[0], live_rows)
        # and what jax derives for the plain forms agrees
        finite = jnp.where(live[:, None], rows, 1.0)
        np.testing.assert_allclose(
            jax.vjp(lambda t: jnp.where(live[:, None], t[src], 0),
                    tokens)[1](finite)[0],
            sum_to_tokens(finite, o), rtol=0, atol=2e-6)

    @staticmethod
    def _held_value_and_grads(held, K=4, X=8, T_=64):
        w = _weights(X=held[1])
        router = _weights(X=X)["router"]
        toks = jnp.asarray(
            np.random.default_rng(5).normal(size=(T_, 16)), jnp.float32)

        def loss(toks, router, w_in, w_out, w_gate):
            res = dropless_moe_ffn(
                toks, router, w_in, w_out, w_gate, act=jax.nn.silu,
                top_k=K, held=held, scoring="sigmoid", renormalize=True)
            n_held = jnp.sum(res.counts[held[0]:held[0] + held[1]])
            # a chunk is 2 T rows (T where min(k, count) is odd): the
            # first always runs, a later one where the held pairs reach
            C = dropless.held_chunk_rows(T_, K, held[1])
            return jnp.sum(res.out ** 2), (
                res.out, res.dropped, res.chunks_run - jnp.maximum(1, (n_held + C - 1) // C))

        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4),
                                  has_aux=True)(
            toks, router, w["w_in"], w["w_out"], w["w_gate"])

    # count < K: the list is a prefix of the pairs; count >= K: all
    @pytest.mark.parametrize("held", [(2, 2), (5, 3), (0, 6)])
    def test_held_gradients_match_the_scatter_form(self, held, monkeypatch):
        """The oracle is the wire as it stood before PR 61: the same
        chunk with a plain row gather and the segment sum, and the
        transposes jax derives for them (two row scatter-adds)."""
        (_, (out, dropped, chunks_off)), grads = self._held_value_and_grads(
            held)
        assert int(dropped) == 0 and int(chunks_off) == 0
        monkeypatch.setattr(
            dropless, "rows_of",
            lambda t, o: jnp.where(o.live[:, None], t[o.src], 0))
        monkeypatch.setattr(
            dropless, "sum_to_tokens",
            lambda r, o: jax.ops.segment_sum(
                jnp.where(o.live[:, None], r, 0), o.src,
                num_segments=o.n_tokens))
        (_, (want_out, _, _)), want = self._held_value_and_grads(held)
        np.testing.assert_allclose(out, want_out, rtol=0, atol=1e-5)
        for g, w in zip(grads, want):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)

    def test_the_kernel_runs_the_held_wire_interpreted(self,
                                                       pallas_interpret):
        held = (0, 6)
        (_, (out, dropped, _)), grads = self._held_value_and_grads(held)
        assert int(dropped) == 0
        with interpret_kernels(False):
            (_, (want_out, _, _)), want = self._held_value_and_grads(held)
        np.testing.assert_allclose(out, want_out, rtol=0, atol=1e-5)
        for g, w in zip(grads, want):
            np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


class TestGatingRngDeterminism:
    """Satellite: the per-step gating rng is a pure function of
    (seed, step, layer) — the engine folds PRNGKey(seed) by step and
    splits per layer — and the draw is byte-identical across mesh
    layouts (keys never depend on sharding)."""

    def _routing(self, seed, step, layer, n_layers=4):
        base = jax.random.fold_in(jax.random.PRNGKey(seed), step)
        layer_rng = jax.random.split(base, n_layers)[layer]
        _, gate_rng = jax.random.split(jax.random.split(layer_rng)[1])
        idx, _, _, _ = dropless_topk_gating(
            _logits(), 2, rng=gate_rng, noisy_gate_policy="RSample")
        return np.asarray(idx)

    def test_same_seed_step_layer_same_routing(self):
        np.testing.assert_array_equal(self._routing(7, 3, 1),
                                      self._routing(7, 3, 1))

    def test_distinct_steps_and_layers_decorrelate(self):
        a = self._routing(7, 3, 1)
        assert not np.array_equal(a, self._routing(7, 4, 1))
        assert not np.array_equal(a, self._routing(7, 3, 2))

    def test_noise_byte_identical_across_layouts(self):
        """The same key produces the same routing decision whether the
        gate runs unjitted, jitted, or jitted under a device mesh."""
        key = jax.random.fold_in(jax.random.PRNGKey(7), 3)
        logits = _logits()

        def route(lg):
            idx, w, _, _ = dropless_topk_gating(
                lg, 2, rng=key, noisy_gate_policy="RSample")
            return idx, w

        eager_idx, eager_w = route(logits)
        jit_idx, jit_w = jax.jit(route)(logits)
        np.testing.assert_array_equal(np.asarray(eager_idx),
                                      np.asarray(jit_idx))
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        n = min(4, jax.device_count())
        mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
        sharded = jax.device_put(
            logits, NamedSharding(mesh, P("data", None)))
        with mesh:
            mesh_idx, mesh_w = jax.jit(route)(sharded)
        np.testing.assert_array_equal(np.asarray(eager_idx),
                                      np.asarray(mesh_idx))
        np.testing.assert_array_equal(np.asarray(eager_w),
                                      np.asarray(mesh_w))


class TestServingUnits:
    """_mlp-level serving units: dropless vs scan parity, groupwise
    quantized expert stacks, the census callback."""

    def _layer(self, cfg, seed=1, dtype=jnp.float32):
        r = np.random.default_rng(seed)
        E, F, X = cfg.d_model, cfg.ff_dim, cfg.n_experts
        lp = {
            "w_router": jnp.asarray(r.normal(size=(E, X)), jnp.float32),
            "w_in": jnp.asarray(r.normal(size=(X, E, F)) * 0.1, dtype),
            "w_gate": jnp.asarray(r.normal(size=(X, E, F)) * 0.1, dtype),
            "w_out": jnp.asarray(r.normal(size=(X, F, E)) * 0.1, dtype),
        }
        return lp

    def _cfg(self, **kw):
        base = dict(vocab_size=VOCAB, n_layers=1, n_heads=4, d_model=32,
                    max_seq=32, variant="llama", use_flash=False,
                    n_experts=4, moe_top_k=2)
        base.update(kw)
        return T.TransformerConfig(**base)

    # serving picks its expert path from the call's shape
    # (inference/model.py expert_path); these force every expert (the
    # scan; where kernels run on 16-bit stacks of whole lanes, the
    # streamed pass) or the ragged wire
    SCAN, RAGGED = (0.0, float("inf")), (float("inf"),) * 2

    def test_dropless_mlp_equals_scan_mlp(self, monkeypatch):
        from deepspeed_tpu.inference import model as M

        cfg = self._cfg()
        lp = self._layer(cfg)
        h = jnp.asarray(np.random.default_rng(2).normal(size=(16, 32)),
                        jnp.float32)
        monkeypatch.setattr(M, "_SCAN_ROWS_PER_EXPERT", self.RAGGED)
        ragged = np.asarray(M._mlp(h, lp, cfg))
        monkeypatch.setattr(M, "_SCAN_ROWS_PER_EXPERT", self.SCAN)
        np.testing.assert_allclose(ragged, np.asarray(M._mlp(h, lp, cfg)),
                                   atol=1e-5)
        # the training flag does not reach serving
        np.testing.assert_array_equal(
            np.asarray(M._mlp(h, lp, self._cfg(moe_dropless=True))),
            np.asarray(M._mlp(h, lp, cfg)))

    @pytest.mark.parametrize("path", ["scan", "ragged", "stream", "grouped"])
    def test_census_counts_assignments(self, monkeypatch, pallas_interpret,
                                       path):
        from deepspeed_tpu.inference import model as M

        rows = self.RAGGED if path == "ragged" else self.SCAN
        monkeypatch.setattr(M, "_SCAN_ROWS_PER_EXPERT", rows)
        monkeypatch.setattr(M, "_STREAM_ROWS_PER_EXPERT", rows)
        monkeypatch.setattr(M, "_STREAM_RIDGE_TOKENS",
                            0 if path == "grouped" else float("inf"))
        # the pass, either entry: bf16 stacks of whole lanes
        wide = path in ("stream", "grouped")
        cfg = self._cfg(**(dict(d_model=128, d_ff=128) if wide else {}))
        dtype = jnp.bfloat16 if wide else jnp.float32
        lp = self._layer(cfg, dtype=dtype)
        h = jnp.asarray(np.random.default_rng(2).normal(
            size=(16, cfg.d_model)), dtype)
        assert M.expert_path(16, cfg, lp, wide) == path
        seen = []
        jax.block_until_ready(M._mlp(h, lp, cfg, seen.append, wide))  # ds-lint: ok R002 test asserts the callback landed
        assert len(seen) == 1
        counts = np.asarray(seen[0])
        assert counts.shape == (4,)
        assert int(counts.sum()) == 16 * 2  # every assignment counted

    def test_expert_stacks_quantize_groupwise(self, monkeypatch):
        from deepspeed_tpu.inference import model as M
        from deepspeed_tpu.inference.quantization import QuantizedWeight

        monkeypatch.setattr(M, "_SCAN_ROWS_PER_EXPERT", self.RAGGED)
        cfg = self._cfg()
        lp = self._layer(cfg)
        qlp = M.quantize_layer(dict(lp), cfg)
        for name in ("w_in", "w_gate", "w_out"):
            assert isinstance(qlp[name], QuantizedWeight), name
            assert qlp[name].q.dtype == jnp.int8
        assert not isinstance(qlp["w_router"], QuantizedWeight)
        h = jnp.asarray(np.random.default_rng(2).normal(size=(16, 32)),
                        jnp.float32)
        # int8 grouped codes reproduce the fp experts within PTQ error
        ragged = np.asarray(M._mlp(h, qlp, cfg))
        np.testing.assert_allclose(ragged, np.asarray(M._mlp(h, lp, cfg)),
                                   atol=0.05)
        # the scan path consumes the same quantized stacks
        monkeypatch.setattr(M, "_SCAN_ROWS_PER_EXPERT", self.SCAN)
        np.testing.assert_allclose(np.asarray(M._mlp(h, qlp, cfg)), ragged,
                                   atol=1e-5)


@pytest.mark.slow
class TestDroplessEngines:
    """Engine-level integration (compile-heavy — slow lane; the ds_moe
    gate exercises the same machinery pre-test)."""

    def _mcfg(self, **kw):
        base = dict(vocab_size=VOCAB, n_layers=2, n_heads=4, d_model=64,
                    max_seq=32, variant="llama", use_flash=False,
                    n_experts=4, moe_top_k=2, moe_dropless=True,
                    moe_z_loss_coef=1e-3)
        base.update(kw)
        return T.TransformerConfig(**base)

    def _engine(self, mcfg, mesh):
        return ds.initialize(
            {"train_micro_batch_size_per_gpu": 2, "train_batch_size": 16,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "seed": 7, "steps_per_print": 10**9, "mesh": mesh},
            loss_fn=T.make_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg))

    def _data(self, n=3):
        r = np.random.default_rng(0)
        return [{"tokens": r.integers(0, VOCAB, (16, 33)).astype(np.int32)}
                for _ in range(n)]

    @pytest.mark.parametrize("policy", [None, "RSample"])
    def test_ep_layout_equivalence_dropless(self, policy):
        """EP=1 == EP=2 dropless trajectories — BITWISE, noisy gating
        included (the rng never depends on the layout)."""
        mcfg = self._mcfg(moe_noisy_gate_policy=policy)
        data = self._data()
        base_eng = self._engine(mcfg, {"data": -1})
        base = [base_eng.train_batch(b)["loss"] for b in data]
        # fresh engine per layout; same seed -> same init
        ep_eng = self._engine(mcfg, {"data": 4, "expert": 2})
        ep = [ep_eng.train_batch(b)["loss"] for b in data]
        # the first step is bitwise in BOTH cases — in particular the
        # noisy-gate draw is byte-identical across layouts (the
        # _replicated_draw contract); later steps accumulate only
        # backward-pass float reassociation
        assert base[0] == ep[0]
        if policy is None:
            assert base == ep  # bitwise: layout is never the math
        else:
            np.testing.assert_allclose(base, ep, rtol=1e-5)

    def test_z_loss_contributes(self):
        b = self._data(1)[0]
        on = self._engine(self._mcfg(moe_z_loss_coef=1.0),
                          {"data": -1}).train_batch(b)["loss"]
        off = self._engine(self._mcfg(moe_z_loss_coef=0.0),
                           {"data": -1}).train_batch(b)["loss"]
        assert on > off

    def test_dropless_loss_decreases(self):
        eng = self._engine(self._mcfg(), {"data": 4, "expert": 2})
        b = self._data(1)[0]
        ls = [eng.train_batch(b)["loss"] for _ in range(8)]
        assert ls[-1] < ls[0]

    def test_moe_sanitize_clean_with_cost(self):
        """engine.sanitize on the dropless zero3+EP+TP program: S001-
        S009 silent, the cost report attributes the expert all-to-all
        pair (ds_budget's canonical-program contract)."""
        mcfg = self._mcfg()
        eng = ds.initialize(
            {"train_micro_batch_size_per_gpu": 1,
             "gradient_accumulation_steps": 2,
             "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 3,
                                   "param_persistence_threshold": 64},
             "bf16": {"enabled": True},
             "mesh": {"data": 2, "expert": 2, "model": 2},
             "steps_per_print": 10**9},
            loss_fn=T.make_loss_fn(mcfg),
            param_init_fn=lambda k: T.init(mcfg, k),
            param_logical_specs=T.logical_specs(mcfg))
        batch = {"tokens": np.zeros((eng.config.train_batch_size, 33),
                                    np.int32)}
        san = eng.sanitize(batch)
        assert san.ok, [f.render() for f in san.findings]
        assert san.cost is not None

    def test_scheduler_census_metrics(self):
        from deepspeed_tpu.inference import ServingScheduler, init_inference

        mcfg = self._mcfg()
        params = T.init(mcfg, jax.random.PRNGKey(1))
        eng = init_inference(
            params, mcfg,
            dict(max_seq_len=64, kv_block_size=8, num_kv_blocks=32,
                 min_prefill_bucket=8, max_batch_size=4, moe_census=True),
            dtype=jnp.float32)
        sched = ServingScheduler(
            eng, {"max_num_batched_tokens": 32, "prefill_chunk": 8,
                  "warmup": False}, seed=0)
        r = np.random.default_rng(0)
        rids = [sched.submit(list(r.integers(0, VOCAB, 9)), 4, stream=i)
                for i in range(3)]
        sched.run()
        assert all(sched.finished[rid].output for rid in rids)
        m = sched.metrics()
        assert m["moe_census_tokens"] > 0
        assert m["moe_imbalance"] >= 1.0
        shares = [v for k, v in m.items()
                  if k.startswith("moe_expert_") and k.endswith("_share")]
        assert len(shares) == 4
        np.testing.assert_allclose(sum(shares), 1.0, rtol=1e-6)
