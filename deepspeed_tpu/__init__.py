"""deepspeed_tpu — a TPU-native distributed training & inference framework.

A ground-up JAX/XLA/Pallas framework with the capabilities of the
reference DeepSpeed (mounted at /root/reference; see SURVEY.md):
config-driven engine, ZeRO-style sharding expressed as NamedShardings,
pipeline/tensor/expert/sequence parallelism on one device mesh, mixed
precision, checkpointing, profiling, and a ragged-batch inference engine.

Top-level API mirrors the reference contract
(ref: deepspeed/__init__.py — initialize():69, init_inference():268).
"""

from typing import Any, Callable, Dict, Optional

from .version import __version__
from .config.config import DeepSpeedTPUConfig, parse_config
from .platform.accelerator import get_accelerator
from .platform.mesh import build_mesh, MESH_AXES
from .runtime.engine import DeepSpeedTPUEngine, TrainState
from . import comm


def initialize(
    config: Any = None,
    *,
    loss_fn: Callable,
    params: Any = None,
    param_init_fn: Optional[Callable] = None,
    param_logical_specs: Any = None,
    mesh=None,
    rules: Optional[Dict[str, Any]] = None,
    has_aux: bool = False,
    init_rng=None,
    pipelined: bool = False,
    pipeline_virtual_stages: Optional[int] = None,
    state_rule=None,
) -> DeepSpeedTPUEngine:
    """Build a training engine (ref: deepspeed/__init__.py:69 initialize).

    The reference takes an nn.Module and wraps it; TPU-first, the engine
    takes a pure `loss_fn(params, batch, rng) -> loss` plus either a
    concrete params pytree or (`param_init_fn`, abstract shapes) so
    parameters can be materialized directly sharded.

    `state_rule` (runtime.engine.StepStateRule, with `has_aux`): leaves
    of the tree that are the step's own state, written after the
    optimizer's update from the loss's aux and kept out of the
    optimizer (models.transformer.step_state_rule makes a routed
    model's).

    Returns the engine; optimizer and lr scheduler are owned by the
    engine and built from the config's optimizer/scheduler blocks.
    """
    from .utils import profiler

    # always-kept set-up spans (docs/tracing.md): train.init, and under
    # it train.init.shapes and train.init.state in the engine
    with profiler.span("train.init", always=True):
        cfg = parse_config(config)
        comm.init_distributed()
        if params is None and param_init_fn is None:
            raise ValueError(
                "initialize() needs `params` or `param_init_fn`")
        return DeepSpeedTPUEngine(
            cfg,
            loss_fn,
            params,
            param_logical_specs=param_logical_specs,
            mesh=mesh,
            rules=rules,
            has_aux=has_aux,
            param_init_fn=param_init_fn,
            init_rng=init_rng,
            pipelined=pipelined,
            pipeline_virtual_stages=pipeline_virtual_stages,
            state_rule=state_rule,
        )


def init_inference(*args, **kwargs):
    from .inference.engine import init_inference as _init_inference

    return _init_inference(*args, **kwargs)


def init_inference_from_hf(*args, **kwargs):
    """Serve an HF-format checkpoint directory (build_hf_engine analog,
    ref: inference/v2/engine_factory.py:67)."""
    from .inference.engine import init_inference_from_hf as _f

    return _f(*args, **kwargs)


def import_external(*args, **kwargs):
    """HF-format checkpoint → (TransformerConfig, host params tree)
    (ref: inference/v2/checkpoint/huggingface_engine.py)."""
    from .utils.hf_checkpoint import import_external as _f

    return _f(*args, **kwargs)
