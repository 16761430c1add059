"""Device-mesh construction and multi-host initialization.

TPU-native replacement for the reference's process-group rendezvous
(reference: ``python/ray/train/torch/config.py:65`` builds a torch
``init_process_group``; here the equivalent object is a
``jax.sharding.Mesh`` whose axes name the parallelism dimensions and over
which XLA inserts ICI/DCN collectives).

Axis conventions (any subset may be present, sizes multiply to #devices):

- ``dp``   — data parallel (gradient psum)
- ``fsdp`` — fully-sharded data parallel (params/opt-state sharded, ZeRO-3)
- ``tp``   — tensor parallel (contracting-dim sharding inside matmuls)
- ``sp``   — sequence/context parallel (ring attention / Ulysses)
- ``ep``   — expert parallel (MoE all-to-all)
- ``pp``   — pipeline parallel (collective-permute microbatch schedule)
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")
# tp innermost: tensor-parallel collectives are per-matmul (latency bound),
# so they should ride the fastest/nearest ICI links; dp/fsdp gradient
# reductions are per-step and tolerate the outer (slower) axes.


@dataclass
class MeshConfig:
    """Declarative mesh shape; -1 on one axis means "fill remaining"."""

    axes: Dict[str, int] = field(default_factory=dict)
    devices: Optional[Sequence] = None  # default: jax.devices()

    def resolve(self, n_devices: int) -> Dict[str, int]:
        axes = dict(self.axes)
        if not axes:
            return {"dp": n_devices}
        fill = [k for k, v in axes.items() if v == -1]
        if len(fill) > 1:
            raise ValueError(f"only one axis may be -1, got {fill}")
        fixed = math.prod(v for v in axes.values() if v != -1)
        if fill:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by {fixed}")
            axes[fill[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh axes {axes} use {fixed} devices, have {n_devices}")
        return axes


def create_mesh(axes: Optional[Dict[str, int]] = None, *,
                devices: Optional[Sequence] = None):
    """Build a ``jax.sharding.Mesh`` with named parallelism axes.

    Axes are laid out in ``AXIS_ORDER`` so that ``tp``/``sp`` map to the
    innermost (fastest-wrapping) device dimension — on a TPU slice that is
    the tightest ICI neighborhood, which is where per-matmul collectives
    belong.
    """
    import jax
    import numpy as np

    devs = list(devices if devices is not None else jax.devices())
    shape = MeshConfig(dict(axes or {})).resolve(len(devs))
    names = tuple(sorted(shape, key=lambda a: AXIS_ORDER.index(a)
                         if a in AXIS_ORDER else len(AXIS_ORDER)))
    dims = tuple(shape[n] for n in names)
    arr = np.asarray(devs).reshape(dims)
    return jax.sharding.Mesh(arr, names)


def single_device_mesh(axis: str = "dp"):
    import jax

    return create_mesh({axis: 1}, devices=jax.devices()[:1])


def mesh_shape(mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> None:
    """Join this process into a multi-host JAX runtime (DCN control plane).

    TPU-native analogue of the reference's rank-0 rendezvous
    (``train/torch/config.py:112`` ``dist.init_process_group``): after this
    call ``jax.devices()`` spans every host and a single Mesh covers the
    full slice/pod.
    """
    import jax

    kwargs = {}
    if coordinator_address:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)
