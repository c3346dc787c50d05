"""Device-mesh construction and topology introspection.

TPU-native replacement for the reference's process-group topology
machinery (ref: deepspeed/utils/groups.py, runtime/pipe/topology.py —
ProcessTopology:12, PipeModelDataParallelTopology:244). Where the
reference builds cartesian rank grids plus torch ProcessGroups, here the
whole cluster is one `jax.sharding.Mesh` with named axes; "groups" are
mesh axes and collectives ride ICI/DCN as XLA chooses.

Axis names (fixed vocabulary, any may be size 1):
  pipe    — pipeline stages           (ref: runtime/pipe/)
  data    — data parallel / ZeRO      (ref: groups.py:385)
  zero    — ZeRO sub-group (MiCS/hpZ) (ref: runtime/zero/mics.py:64,
            zero_hpz_partition_size config.py:264): when >1, the data
            dimension is factored data×zero and ZeRO state shards over
            'zero' only, replicating across 'data' groups — sharding
            collectives stay on the fast intra-group links
  expert  — expert parallel for MoE   (ref: groups.py:113-290)
  seq     — Ulysses sequence parallel (ref: deepspeed/sequence/layer.py)
  model   — tensor parallel           (ref: module_inject AutoTP)

Order is outermost→innermost: 'model' is fastest-varying so TP
collectives ride the highest-bandwidth ICI links; 'pipe' is outermost so
stage boundaries may cross DCN; 'zero' sits inside 'data' so sub-group
gathers ride shorter paths than cross-group traffic.
"""

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

MESH_AXES = ("pipe", "data", "zero", "expert", "seq", "model")

# Axes over which a batch is sharded (data-parallel-like axes).
BATCH_AXES = ("data", "zero", "expert")


def resolve_axis_sizes(
    axis_sizes: Dict[str, int], n_devices: Optional[int] = None
) -> Dict[str, int]:
    """Fill in a single -1 axis from the device count and validate the product."""
    if n_devices is None:
        n_devices = len(jax.devices())
    sizes = {ax: int(axis_sizes.get(ax, 1)) for ax in MESH_AXES}
    wildcard = [ax for ax, s in sizes.items() if s == -1]
    if len(wildcard) > 1:
        raise ValueError(f"at most one mesh axis may be -1, got {wildcard}")
    fixed = int(np.prod([s for s in sizes.values() if s != -1]))
    if wildcard:
        if n_devices % fixed != 0:
            raise ValueError(
                f"device count {n_devices} not divisible by fixed axes product {fixed}"
            )
        sizes[wildcard[0]] = n_devices // fixed
        fixed = n_devices
    if fixed != n_devices:
        raise ValueError(
            f"mesh axes {sizes} multiply to {fixed} but there are {n_devices} devices"
        )
    return sizes


def build_mesh(
    axis_sizes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the global Mesh.

    On real TPU slices this uses `mesh_utils.create_device_mesh` so axis
    adjacency maps onto the physical ICI torus — a shape it cannot lay
    out RAISES (an enumeration-order reshape would quietly change which
    links TP rides); on CPU a plain reshape of the device list is used.
    """
    if devices is None:
        devices = jax.devices()
    sizes = resolve_axis_sizes(axis_sizes or {}, n_devices=len(devices))
    shape = tuple(sizes[ax] for ax in MESH_AXES)
    if devices[0].platform == "tpu" and len(devices) > 1:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=list(devices))
    else:
        dev_array = np.array(list(devices)).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def single_device_mesh() -> Mesh:
    return build_mesh({ax: 1 for ax in MESH_AXES})


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def data_parallel_size(mesh: Mesh) -> int:
    """World size of the batch-sharded axes (data × expert).

    Mirrors the reference notion that the expert-parallel group is carved
    out of the data-parallel world (ref: groups.py:113
    _create_expert_and_data_parallel).
    """
    return int(np.prod([mesh.shape[ax] for ax in BATCH_AXES]))


def describe(mesh: Mesh) -> str:
    parts = [f"{ax}={mesh.shape[ax]}" for ax in mesh.axis_names if mesh.shape[ax] > 1]
    return "Mesh(" + (", ".join(parts) or "1 device") + f", {mesh.size} devices)"
