"""Seeded random weights for serving cells, made ON THE DEVICE in one
jitted call, in the dtype they are served in.

The tree has the names and shapes of the training layout
(`models/transformer.init`'s, read through `jax.eval_shape` — nothing
is materialised in float32): what `init_inference(params, ...)` takes.
Values follow the same recipe (normal x 0.02, output projections
scaled by 1/sqrt(2L), norm scales 1), drawn layer by layer inside a
`lax.map` so the float32 temporaries are one layer's, never the
model's.
"""

from typing import Any, Dict

import jax
import jax.numpy as jnp

STD = 0.02


def _leaf(key, name: str, shape, dtype, n_layers: int):
    if "scale" in name:
        return jnp.ones(shape, dtype)
    if name.startswith("b"):
        return jnp.zeros(shape, dtype)
    std = STD / (2 * n_layers) ** 0.5 if name in ("wo", "w_out") else STD
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def make_params(mcfg, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The whole tree, on the default device, in `dtype`."""
    from deepspeed_tpu.models import transformer as T

    shapes = jax.eval_shape(lambda k: T.init(mcfg, k), jax.random.PRNGKey(0))
    L = mcfg.n_layers
    layer_names = sorted(shapes["layers"])

    def make(key):
        k_top, k_layers = jax.random.split(key)
        out = {}
        for i, name in enumerate(sorted(n for n in shapes if n != "layers")):
            out[name] = _leaf(jax.random.fold_in(k_top, i), name,
                              shapes[name].shape, dtype, L)

        def one_layer(l):
            k = jax.random.fold_in(k_layers, l)
            return {name: _leaf(jax.random.fold_in(k, i), name,
                                shapes["layers"][name].shape[1:], dtype, L)
                    for i, name in enumerate(layer_names)}

        out["layers"] = jax.lax.map(one_layer, jnp.arange(L, dtype=jnp.int32))
        return out

    # the hardware generator: threefry over 3.75 B values is seconds of
    # set-up that every run of every cell would pay
    return jax.jit(make)(jax.random.key(int(seed), impl="rbg"))
