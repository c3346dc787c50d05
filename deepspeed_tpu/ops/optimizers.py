"""Optimizers.

TPU-native analogs of the reference fused optimizers
(ref: ops/adam/fused_adam.py FusedAdam:18, csrc/adam/multi_tensor_adam.cu
multi_tensor_adam_cuda:128, csrc/lamb/fused_lamb_cuda_kernel.cu,
csrc/lion/multi_tensor_lion.cu, ops/adagrad). The reference needs
hand-written multi-tensor CUDA kernels to fuse the elementwise update;
on TPU one `tree.map` under jit gives XLA the whole update to fuse onto
the VPU, so the update is bandwidth-bound by construction (~27ms on
update+norm for 350M params ≈ 2.2x the raw HBM read/write time of the
state it touches — measured on an earlier setup; not re-measured).

API shape: functional `init(params) -> state`, `update(grads, state,
params, lr, step) -> (new_params, new_state)` pairs, fp32 throughout —
the engine owns the master-weight dtype policy (ref:
runtime/bf16_optimizer.py) and hands these fns fp32 master params.
"""

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp


class Optimizer(NamedTuple):
    init: Callable[[Any], Any]
    update: Callable[..., Any]  # (grads, state, params, lr, step) -> (params, state)
    name: str


def _tmap(f, *trees, **kw):
    return jax.tree.map(f, *trees, **kw)


def _zeros_like_f32(params):
    return _tmap(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def adam(
    betas=(0.9, 0.999),
    eps: float = 1e-8,
    weight_decay: float = 0.0,
    adam_w_mode: bool = True,
    bias_correction: bool = True,
) -> Optimizer:
    """Adam/AdamW (ref: ops/adam/fused_adam.py:18 — same knob names)."""
    b1, b2 = betas

    def init(params):
        return {"mu": _zeros_like_f32(params), "nu": _zeros_like_f32(params)}

    def update(grads, state, params, lr, step):
        step = step.astype(jnp.float32)
        if bias_correction:
            c1 = 1.0 - b1**step
            c2 = 1.0 - b2**step
        else:
            c1 = c2 = 1.0

        def leaf(g, m, v, p):
            g = g.astype(jnp.float32)
            if weight_decay != 0.0 and not adam_w_mode:
                g = g + weight_decay * p  # L2 mode
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * jnp.square(g)
            upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
            if weight_decay != 0.0 and adam_w_mode:
                upd = upd + weight_decay * p  # decoupled decay
            return p - lr * upd, m, v

        out = _tmap(leaf, grads, state["mu"], state["nu"], params)
        new_params = _tmap(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        mu = _tmap(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        nu = _tmap(lambda o: o[2], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"mu": mu, "nu": nu}

    return Optimizer(init, update, "adamw" if adam_w_mode else "adam")


def lamb(
    betas=(0.9, 0.999),
    eps: float = 1e-6,
    weight_decay: float = 0.0,
    max_trust_ratio: float = 10.0,
) -> Optimizer:
    """LAMB (ref: csrc/lamb/fused_lamb_cuda_kernel.cu) — layerwise trust ratio."""
    b1, b2 = betas

    def init(params):
        return {"mu": _zeros_like_f32(params), "nu": _zeros_like_f32(params)}

    def update(grads, state, params, lr, step):
        step = step.astype(jnp.float32)
        c1 = 1.0 - b1**step
        c2 = 1.0 - b2**step

        def leaf(g, m, v, p):
            g = g.astype(jnp.float32)
            m = b1 * m + (1.0 - b1) * g
            v = b2 * v + (1.0 - b2) * jnp.square(g)
            upd = (m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p
            w_norm = jnp.linalg.norm(p.reshape(-1))
            u_norm = jnp.linalg.norm(upd.reshape(-1))
            trust = jnp.where(
                (w_norm > 0) & (u_norm > 0),
                jnp.clip(w_norm / u_norm, 0.0, max_trust_ratio),
                1.0,
            )
            return p - lr * trust * upd, m, v

        out = _tmap(leaf, grads, state["mu"], state["nu"], params)
        new_params = _tmap(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        mu = _tmap(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        nu = _tmap(lambda o: o[2], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"mu": mu, "nu": nu}

    return Optimizer(init, update, "lamb")


def lion(betas=(0.9, 0.99), weight_decay: float = 0.0) -> Optimizer:
    """Lion (ref: csrc/lion/multi_tensor_lion.cu, ops/lion)."""
    b1, b2 = betas

    def init(params):
        return {"mu": _zeros_like_f32(params)}

    def update(grads, state, params, lr, step):
        def leaf(g, m, p):
            g = g.astype(jnp.float32)
            upd = jnp.sign(b1 * m + (1.0 - b1) * g) + weight_decay * p
            m = b2 * m + (1.0 - b2) * g
            return p - lr * upd, m

        out = _tmap(leaf, grads, state["mu"], params)
        new_params = _tmap(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        mu = _tmap(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"mu": mu}

    return Optimizer(init, update, "lion")


def adagrad(eps: float = 1e-10, weight_decay: float = 0.0) -> Optimizer:
    """Adagrad (ref: csrc/adagrad/cpu_adagrad.cpp)."""

    def init(params):
        return {"acc": _zeros_like_f32(params)}

    def update(grads, state, params, lr, step):
        def leaf(g, a, p):
            g = g.astype(jnp.float32) + weight_decay * p
            a = a + jnp.square(g)
            return p - lr * g / (jnp.sqrt(a) + eps), a

        out = _tmap(leaf, grads, state["acc"], params)
        new_params = _tmap(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        acc = _tmap(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"acc": acc}

    return Optimizer(init, update, "adagrad")


def sgd(momentum: float = 0.0, weight_decay: float = 0.0, nesterov: bool = False) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return {}
        return {"mu": _zeros_like_f32(params)}

    def update(grads, state, params, lr, step):
        if momentum == 0.0:
            new_params = _tmap(
                lambda p, g: p - lr * (g.astype(jnp.float32) + weight_decay * p), params, grads
            )
            return new_params, state

        def leaf(g, m, p):
            g = g.astype(jnp.float32) + weight_decay * p
            m = momentum * m + g
            d = g + momentum * m if nesterov else m
            return p - lr * d, m

        out = _tmap(leaf, grads, state["mu"], params)
        new_params = _tmap(lambda o: o[0], out, is_leaf=lambda x: isinstance(x, tuple))
        mu = _tmap(lambda o: o[1], out, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"mu": mu}

    return Optimizer(init, update, "sgd")


class OnebitAdam:
    """1-bit Adam (ref: runtime/fp16/onebit/adam.py OnebitAdam:14).

    Two phases split at `freeze_step` (the reference's warmup):
      warmup     — exact Adam; variance (nu) still adapting; gradients
                   arrive fully reduced (`update`, the plain engine path).
      compressed — nu FROZEN; each data-parallel worker updates a local
                   momentum with its own partial gradient and the workers'
                   momenta are averaged through the error-feedback 1-bit
                   collective (comm/compressed.py), cutting comm volume
                   ~4x+ (`compressed_update`, fed worker-major grads from
                   the engine's shard_map gradient path).

    State = {mu, nu, error_w, error_s}; error buffers are worker-major
    [dp, ·] leaves sharded over the data axes.
    """

    name = "onebitadam"

    def __init__(self, betas=(0.9, 0.999), eps: float = 1e-8,
                 weight_decay: float = 0.0, freeze_step: int = 100,
                 dp: int = 1):
        self.b1, self.b2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.freeze_step = int(freeze_step)
        self.dp = int(dp)
        self._inner = adam(betas=betas, eps=eps, weight_decay=weight_decay,
                           adam_w_mode=False, bias_correction=True)

    def init(self, params):
        from ..comm.compressed import init_error_buffers

        ew, es = init_error_buffers(params, self.dp)
        return {
            "mu": _zeros_like_f32(params),
            "nu": _zeros_like_f32(params),
            "error_w": ew,
            "error_s": es,
        }

    def update(self, grads, state, params, lr, step):
        """Warmup phase: exact Adam on fully-reduced grads
        (ref: adam.py warmup branch — comm_time==0 standard allreduce)."""
        inner_state = {"mu": state["mu"], "nu": state["nu"]}
        new_params, new_inner = self._inner.update(grads, inner_state, params, lr, step)
        return new_params, {**state, **new_inner}

    def _apply_update(self, m, v, p, lr, c1, c2):
        """Per-leaf parameter update from the (compressed-averaged)
        momentum — the only piece 1-bit variants override."""
        upd = (m / c1) / (jnp.sqrt(v / c2) + self.eps)
        if self.weight_decay != 0.0:
            upd = upd + self.weight_decay * p
        return p - lr * upd

    def compressed_update(self, worker_grads, state, params, lr, step, mesh):
        """Compression phase (ref: adam.py:210 — local momentum update then
        compressed_allreduce; exp_avg_sq frozen)."""
        from ..comm.compressed import compressed_mean_tree

        b1, b2 = self.b1, self.b2
        step_f = step.astype(jnp.float32)
        c1 = 1.0 - b1**step_f
        c2 = 1.0 - b2 ** jnp.float32(self.freeze_step)  # nu frozen here

        m_part = _tmap(
            lambda mu, gw: b1 * mu[None] + (1.0 - b1) * gw.astype(jnp.float32),
            state["mu"], worker_grads,
        )
        mu_new, ew, es = compressed_mean_tree(
            m_part, state["error_w"], state["error_s"], mesh
        )
        new_params = _tmap(
            lambda m, v, p: self._apply_update(m, v, p, lr, c1, c2),
            mu_new, state["nu"], params,
        )
        return new_params, {"mu": mu_new, "nu": state["nu"],
                            "error_w": ew, "error_s": es}


class OnebitLamb(OnebitAdam):
    """1-bit LAMB (ref: runtime/fp16/onebit/lamb.py OnebitLamb) — the
    momentum exchange is the same error-feedback 1-bit collective as
    1-bit Adam; the update applies LAMB's layerwise trust ratio on top.
    Where the reference freezes per-chunk scaling coefficients at
    freeze_step (an artifact of its fused flat buffers), the trust ratio
    here is recomputed exactly per step from local state — no extra comm
    either way."""

    name = "onebitlamb"

    def __init__(self, betas=(0.9, 0.999), eps: float = 1e-6,
                 weight_decay: float = 0.0, freeze_step: int = 100,
                 max_coeff: float = 10.0, min_coeff: float = 0.01,
                 dp: int = 1):
        super().__init__(betas=betas, eps=eps, weight_decay=weight_decay,
                         freeze_step=freeze_step, dp=dp)
        self.max_coeff = float(max_coeff)
        self.min_coeff = float(min_coeff)
        self._inner = lamb(betas=betas, eps=eps, weight_decay=weight_decay,
                           max_trust_ratio=max_coeff)

    def _apply_update(self, m, v, p, lr, c1, c2):
        upd = (m / c1) / (jnp.sqrt(v / c2) + self.eps) + self.weight_decay * p
        w_norm = jnp.linalg.norm(p.reshape(-1))
        u_norm = jnp.linalg.norm(upd.reshape(-1))
        trust = jnp.where(
            (w_norm > 0) & (u_norm > 0),
            jnp.clip(w_norm / u_norm, self.min_coeff, self.max_coeff),
            1.0,
        )
        return p - lr * trust * upd


class ZeroOneSchedule:
    """Host-side replica of 0/1 Adam's deterministic step schedule
    (ref: runtime/fp16/onebit/zoadam.py var_interval/var_counter/
    local_step_interval/local_step_counter bookkeeping :175-181,:265-287).

    Both intervals are pure functions of the step count, so the engine
    keeps this tiny state machine on the host and picks the compiled
    program per step; on checkpoint load it is replayed from step 0."""

    def __init__(self, var_freeze_step: int, var_update_scaler: int,
                 local_step_scaler: int, local_step_clipper: int):
        self.var_freeze_step = int(var_freeze_step)
        self.var_update_scaler = int(var_update_scaler)
        self.local_step_scaler = int(local_step_scaler)
        self.local_step_clipper = int(local_step_clipper)
        self.var_interval = 1
        self.var_counter = 0
        self.local_interval = 1
        self.local_counter = 0

    def kind(self, step: int) -> str:
        """Program for 1-indexed global step `step` (call before advance).

        phase 1 (step <= var_freeze_step + 1):
          'full'   — exact-sync gradient, update mu AND nu
          'onebit' — 1-bit error-feedback gradient sync, update mu only
        phase 2 (later steps):
          'local'  — no communication at all (local step)
          'sync'   — local step + 1-bit momentum reconciliation

        The +1: the reference flips freeze_key only AFTER the step where
        state['step'] exceeds var_freeze_step completes
        (ref: runtime/fp16/onebit/zoadam.py freeze_key flip), so it runs
        one more variance-adapting step than the naive boundary.
        """
        if step <= self.var_freeze_step + 1:
            return "full" if step % self.var_interval == 0 else "onebit"
        return "sync" if step % self.local_interval == 0 else "local"

    def advance(self, step: int) -> None:
        """Post-step interval bookkeeping (exponential growth rules)."""
        if step <= self.var_freeze_step + 1:
            if step % self.var_interval == 0:
                self.var_counter += 1
                if self.var_counter == self.var_update_scaler:
                    self.var_counter = 0
                    self.var_interval *= 2
        else:
            self.local_counter += 1
            if self.local_counter == self.local_step_scaler:
                self.local_counter = 0
                self.local_interval = min(self.local_step_clipper,
                                          self.local_interval * 2)

    def replay(self, n_steps: int) -> None:
        """Rebuild interval state after loading a step-n checkpoint."""
        for s in range(1, n_steps + 1):
            self.advance(s)


class ZeroOneAdam:
    """0/1 Adam (ref: runtime/fp16/onebit/zoadam.py ZeroOneAdam:14,
    arxiv 2202.06009).

    Adaptive-frequency variance updates + adaptive-frequency 1-bit
    synchronization. Update rule is the reference's un-bias-corrected
    `p -= lr * (mu / (sqrt(nu) + eps) + wd*p)`.

    State (engine opt dict; `worker_*`/`error_*` leaves are worker-major,
    dim 0 sharded over the data axes):
      mu         [·]     — replicated momentum, authoritative in phase 1
                           and at sync points (phase-1 updates touch only
                           this copy — no cross-worker traffic)
      worker_mu  [dp, ·] — per-worker momentum, authoritative between
                           phase-2 syncs (tiled from mu at the freeze
                           transition by the engine)
      nu         [·]     — variance, frozen after var_freeze_step
      worker_u   [dp, ·] — accumulated local parameter delta since the
                           last sync (the paper's `u`; the reference's
                           momentum_accumulator). TrainState.params hold
                           the last-SYNCED weights; the live local
                           weights are params + worker_u[w], applied
                           inside the shard_map gradient path.
      worker_lrs [dp]    — sum of lrs since last sync (rows identical)
      error_w/error_s    — 1-bit error-feedback memories
    """

    name = "zerooneadam"

    def __init__(self, betas=(0.9, 0.999), eps: float = 1e-8,
                 weight_decay: float = 0.0,
                 var_freeze_step: int = 100000,
                 var_update_scaler: int = 16,
                 local_step_scaler: int = 32678,
                 local_step_clipper: int = 16,
                 dp: int = 1):
        self.b1, self.b2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.var_freeze_step = int(var_freeze_step)
        self.var_update_scaler = int(var_update_scaler)
        self.local_step_scaler = int(local_step_scaler)
        self.local_step_clipper = int(local_step_clipper)
        self.dp = int(dp)

    def make_schedule(self) -> ZeroOneSchedule:
        return ZeroOneSchedule(self.var_freeze_step, self.var_update_scaler,
                               self.local_step_scaler, self.local_step_clipper)

    def init(self, params):
        from ..comm.compressed import init_error_buffers

        ew, es = init_error_buffers(params, self.dp)
        wz = _tmap(
            lambda p: jnp.zeros((self.dp,) + tuple(p.shape), jnp.float32), params
        )
        return {
            "mu": _zeros_like_f32(params),
            "worker_mu": wz,
            "nu": _zeros_like_f32(params),
            "worker_u": jax.tree.map(jnp.zeros_like, wz),
            "worker_lrs": jnp.zeros((self.dp,), jnp.float32),
            "error_w": ew,
            "error_s": es,
        }

    def _delta(self, mu, nu, p_local, lr):
        """-lr * (mu/(sqrt(nu)+eps) + wd*p): the parameter increment."""
        upd = mu / (jnp.sqrt(nu) + self.eps)
        if self.weight_decay != 0.0:
            upd = upd + self.weight_decay * p_local
        return -lr * upd

    def full_update(self, worker_grads, state, params, lr, mesh):
        """Variance-update step: exact gradient sync, mu AND nu advance
        (ref: zoadam.py:207-209 var_interval branch)."""
        from ..parallel import sharding as shd
        from jax.sharding import PartitionSpec as P

        b1, b2 = self.b1, self.b2

        def leaf(gw, mu, nu, p):
            g = jnp.mean(gw.astype(jnp.float32), axis=0)
            g = shd.constraint(g, P(), mesh)  # exact all-reduce mean
            nu_new = b2 * nu + (1.0 - b2) * jnp.square(g)
            mu_new = b1 * mu + (1.0 - b1) * g
            p_new = p + self._delta(mu_new, nu_new, p, lr)
            return p_new, mu_new, nu_new

        out = _tmap(leaf, worker_grads, state["mu"], state["nu"], params)
        pick = lambda i: _tmap(lambda o: o[i], out,
                               is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), {**state, "mu": pick(1), "nu": pick(2)}

    def onebit_update(self, worker_grads, state, params, lr, mesh):
        """Non-variance phase-1 step: gradient travels through the 1-bit
        error-feedback collective; nu frozen (ref: zoadam.py:210-218)."""
        from ..comm.compressed import compressed_mean_tree

        b1 = self.b1
        g1, ew, es = compressed_mean_tree(
            _tmap(lambda g: g.astype(jnp.float32), worker_grads),
            state["error_w"], state["error_s"], mesh,
        )

        def leaf(g, mu, nu, p):
            mu_new = b1 * mu + (1.0 - b1) * g
            p_new = p + self._delta(mu_new, nu, p, lr)
            return p_new, mu_new

        out = _tmap(leaf, g1, state["mu"], state["nu"], params)
        pick = lambda i: _tmap(lambda o: o[i], out,
                               is_leaf=lambda x: isinstance(x, tuple))
        return pick(0), {**state, "mu": pick(1),
                         "error_w": ew, "error_s": es}

    def local_update(self, worker_grads, state, params, lr, mesh):
        """Phase-2 local step: NO communication — each worker advances its
        momentum and its local delta u (ref: zoadam.py:221-223,:239-243).
        params (the last-synced copy) are returned unchanged."""
        b1 = self.b1

        def leaf(gw, mu, nu, u, p):
            mu_new = b1 * mu + (1.0 - b1) * gw.astype(jnp.float32)
            d = self._delta(mu_new, nu[None], p[None] + u, lr)
            return mu_new, u + d

        out = _tmap(leaf, worker_grads, state["worker_mu"], state["nu"],
                    state["worker_u"], params)
        pick = lambda i: _tmap(lambda o: o[i], out,
                               is_leaf=lambda x: isinstance(x, tuple))
        return params, {**state, "worker_mu": pick(0), "worker_u": pick(1),
                        "worker_lrs": state["worker_lrs"] + lr}

    def sync_update(self, worker_grads, state, params, lr, mesh):
        """Phase-2 sync step: local step, then reconcile — scale u to
        momentum units, 1-bit average it, rebuild mu from the average and
        fold the averaged delta into the synced params
        (ref: zoadam.py:245-260)."""
        from ..comm.compressed import compressed_mean_tree

        params, state = self.local_update(worker_grads, state, params, lr, mesh)
        lrs = jnp.max(state["worker_lrs"])  # rows identical; max is comm-cheap

        u_scaled = _tmap(
            lambda u, nu: u * (jnp.sqrt(nu)[None] + self.eps),
            state["worker_u"], state["nu"],
        )
        u_avg, ew, es = compressed_mean_tree(
            u_scaled, state["error_w"], state["error_s"], mesh
        )

        def leaf(ua, nu, u, p):
            p_new = p + ua / (jnp.sqrt(nu) + self.eps)
            mu_new = -ua / lrs
            wmu_new = jnp.broadcast_to(mu_new[None], u.shape)
            return p_new, mu_new, wmu_new

        out = _tmap(leaf, u_avg, state["nu"], state["worker_u"], params)
        pick = lambda i: _tmap(lambda o: o[i], out,
                               is_leaf=lambda x: isinstance(x, tuple))
        zeros_u = _tmap(jnp.zeros_like, state["worker_u"])
        return pick(0), {**state, "mu": pick(1), "worker_mu": pick(2),
                         "worker_u": zeros_u,
                         "worker_lrs": jnp.zeros_like(state["worker_lrs"]),
                         "error_w": ew, "error_s": es}


_REGISTRY: Dict[str, Callable[..., Optimizer]] = {
    "adam": lambda **kw: adam(adam_w_mode=False, **kw),
    "adamw": lambda **kw: adam(adam_w_mode=True, **kw),
    "fusedadam": lambda **kw: adam(**kw),  # reference name compat
    "lamb": lamb,
    "lion": lion,
    "adagrad": adagrad,
    "sgd": sgd,
    "onebitadam": OnebitAdam,
    "onebitlamb": OnebitLamb,
    "zerooneadam": ZeroOneAdam,
    "zoadam": ZeroOneAdam,
}


def build_optimizer(type_name: str, params: Optional[Dict[str, Any]] = None) -> Optimizer:
    """Build from config block (ref: engine.py:1276 _configure_basic_optimizer).

    The 'lr' key is handled by the scheduler layer, not the optimizer."""
    key = type_name.lower().replace("_", "")
    if key not in _REGISTRY:
        raise ValueError(f"unknown optimizer '{type_name}'; available: {sorted(_REGISTRY)}")
    kwargs = dict(params or {})
    kwargs.pop("lr", None)
    kwargs.pop("torch_adam", None)  # reference-compat noise
    kwargs.pop("cuda_aware", None)  # 1-bit reference knob, no TPU meaning
    kwargs.pop("comm_backend_name", None)
    if "betas" in kwargs:
        kwargs["betas"] = tuple(kwargs["betas"])
    return _REGISTRY[key](**kwargs)
