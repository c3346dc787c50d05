"""Loss-scaler state machine tests (ref model: tests/unit/runtime/
half_precision — DynamicLossScaler dynamics)."""

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.config.config import FP16Config
from deepspeed_tpu.runtime.precision import (

    clip_grads_by_global_norm,
    found_inf_in_grads,
    global_grad_norm,
    init_loss_scale,
    update_loss_scale,
)

# interpreter-/compile-heavy: excluded from the fast lane (-m 'not slow')
import pytest  # noqa: E402


def cfg(**kw):
    return FP16Config(enabled=True, **kw)


def test_initial_scale():
    s = init_loss_scale(cfg(initial_scale_power=8))
    assert float(s.scale) == 256.0


def test_backoff_on_overflow():
    c = cfg(initial_scale_power=8, hysteresis=1)
    s = init_loss_scale(c)
    s = update_loss_scale(s, jnp.bool_(True), c)
    assert float(s.scale) == 128.0


def test_hysteresis_delays_backoff():
    c = cfg(initial_scale_power=8, hysteresis=2)
    s = init_loss_scale(c)
    s = update_loss_scale(s, jnp.bool_(True), c)
    assert float(s.scale) == 256.0  # first overflow burns hysteresis
    s = update_loss_scale(s, jnp.bool_(True), c)
    assert float(s.scale) == 128.0


def test_growth_after_window():
    c = cfg(initial_scale_power=8, loss_scale_window=3, hysteresis=1)
    s = init_loss_scale(c)
    for _ in range(3):
        s = update_loss_scale(s, jnp.bool_(False), c)
    assert float(s.scale) == 512.0


def test_static_scale_never_moves():
    c = cfg(loss_scale=1024.0)
    s = init_loss_scale(c)
    s = update_loss_scale(s, jnp.bool_(True), c)
    assert float(s.scale) == 1024.0


def test_min_loss_scale_floor():
    c = cfg(initial_scale_power=1, hysteresis=1, min_loss_scale=1.0)
    s = init_loss_scale(c)
    for _ in range(5):
        s = update_loss_scale(s, jnp.bool_(True), c)
    assert float(s.scale) == 1.0


def test_found_inf():
    good = {"a": jnp.ones(3), "b": jnp.zeros(2)}
    bad = {"a": jnp.array([1.0, jnp.inf]), "b": jnp.zeros(2)}
    assert not bool(found_inf_in_grads(good))
    assert bool(found_inf_in_grads(bad))


def test_global_norm_and_clip():
    grads = {"a": jnp.full((3,), 2.0), "b": jnp.full((4,), 2.0)}
    n = global_grad_norm(grads)
    np.testing.assert_allclose(float(n), (7 * 4.0) ** 0.5, rtol=1e-6)
    clipped = clip_grads_by_global_norm(grads, 1.0, n)
    np.testing.assert_allclose(float(global_grad_norm(clipped)), 1.0, rtol=1e-4)
    # no-op when under the limit
    same = clip_grads_by_global_norm(grads, 100.0, n)
    np.testing.assert_allclose(same["a"], grads["a"], rtol=1e-6)


def test_sustained_overflow_keeps_halving():
    """Reference consecutive_hysteresis=False: once hysteresis is spent,
    EVERY further overflow halves (ADVICE r1: fast divergence recovery)."""
    c = cfg(initial_scale_power=8, hysteresis=2)
    s = init_loss_scale(c)
    scales = []
    for _ in range(4):
        s = update_loss_scale(s, jnp.bool_(True), c)
        scales.append(float(s.scale))
    assert scales == [256.0, 128.0, 64.0, 32.0]


def test_good_steps_do_not_refill_hysteresis():
    c = cfg(initial_scale_power=8, hysteresis=2, loss_scale_window=1000)
    s = update_loss_scale(init_loss_scale(c), jnp.bool_(True), c)  # burn 1
    s = update_loss_scale(s, jnp.bool_(False), c)  # good step: no refill
    s = update_loss_scale(s, jnp.bool_(True), c)
    assert float(s.scale) == 128.0  # halves immediately


def test_consecutive_hysteresis_refills_on_good_steps():
    c = cfg(initial_scale_power=8, hysteresis=2, consecutive_hysteresis=True)
    s = update_loss_scale(init_loss_scale(c), jnp.bool_(True), c)  # burn 1
    s = update_loss_scale(s, jnp.bool_(False), c)  # refill
    s = update_loss_scale(s, jnp.bool_(True), c)  # burns refilled credit
    assert float(s.scale) == 256.0


# --- direct overflow/growth WINDOW dynamics (PR-5 satellite) -----------

def test_overflow_resets_growth_window():
    """good_steps is the growth window's clock: an overflow at step
    window-1 zeroes it, so growth needs a FULL clean window again."""
    c = cfg(initial_scale_power=8, loss_scale_window=3, hysteresis=1)
    s = init_loss_scale(c)
    s = update_loss_scale(s, jnp.bool_(False), c)
    s = update_loss_scale(s, jnp.bool_(False), c)
    s = update_loss_scale(s, jnp.bool_(True), c)  # overflow at window-1
    assert int(s.good_steps) == 0
    assert float(s.scale) == 128.0  # hysteresis=1: immediate backoff
    for _ in range(2):
        s = update_loss_scale(s, jnp.bool_(False), c)
    assert float(s.scale) == 128.0  # window not yet refilled
    s = update_loss_scale(s, jnp.bool_(False), c)
    assert float(s.scale) == 256.0  # full window elapsed -> grow


def test_growth_exactly_at_window_boundary():
    c = cfg(initial_scale_power=8, loss_scale_window=2, hysteresis=1)
    s = init_loss_scale(c)
    s = update_loss_scale(s, jnp.bool_(False), c)
    assert float(s.scale) == 256.0  # 1 < window: no growth yet
    s = update_loss_scale(s, jnp.bool_(False), c)
    assert float(s.scale) == 512.0  # exactly window clean steps
    assert int(s.good_steps) == 0  # window clock restarts after growth


def test_growth_refills_hysteresis():
    """Growth is the ONLY hysteresis refill under the reference default
    (consecutive_hysteresis=False)."""
    c = cfg(initial_scale_power=8, loss_scale_window=2, hysteresis=2)
    s = init_loss_scale(c)
    s = update_loss_scale(s, jnp.bool_(True), c)  # burn one credit
    assert int(s.hysteresis_left) == 1
    s = update_loss_scale(s, jnp.bool_(False), c)
    s = update_loss_scale(s, jnp.bool_(False), c)  # window -> grow
    assert float(s.scale) == 512.0
    assert int(s.hysteresis_left) == 2  # refilled by growth
    s = update_loss_scale(s, jnp.bool_(True), c)
    assert float(s.scale) == 512.0  # credit absorbs the next overflow


def test_found_inf_skips_integer_leaves():
    grads = {"w": jnp.array([1.0, 2.0]),
             "token_count": jnp.array([3], jnp.int32)}
    assert not bool(found_inf_in_grads(grads))
    grads["w"] = jnp.array([1.0, jnp.inf])
    assert bool(found_inf_in_grads(grads))


def test_found_inf_empty_and_integer_only_trees():
    assert not bool(found_inf_in_grads({}))
    assert not bool(found_inf_in_grads(
        {"steps": jnp.zeros((2,), jnp.int32)}))
