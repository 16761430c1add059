"""The jax names the repo's sharded programs go through.

``shard_map`` is ``jax.shard_map`` of the one installation there is
(jax 0.9.0): call sites import it from here so the spelling lives in
one line.

Also home to :func:`decode_mesh`, the one place a tensor-parallel
DecodeEngine turns ``tp=N`` into a device mesh: every sharded jit
factory in ``models/gpt_decode.py`` and every cache allocator keys off
the mesh built here, so tp=2 on an 8-way forced-host-device CPU run
and tp=4 on a four-chip host go through the identical code path.
"""
import functools

import jax
import numpy as np

shard_map = jax.shard_map


@functools.lru_cache(maxsize=8)
def decode_mesh(tp: int = 1) -> jax.sharding.Mesh:
    """The 1-D ``("tp",)`` mesh a tensor-parallel decode engine shards
    over: the first ``tp`` local devices, cached so every factory and
    cache allocator asking for the same ``tp`` shares one Mesh object
    (Mesh identity is part of shard_map's trace key — a fresh Mesh per
    call would defeat the compiled-program budget).

    On CPU hosts tier-1 forces virtual devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (conftest
    does this before importing jax), so a tp=2 mesh here is a REAL
    2-device mesh, not a stub — the same shard_map programs that run
    on a TPU slice run in the test suite.
    """
    tp = int(tp)
    if tp < 1:
        raise ValueError(f"decode_mesh: tp must be >= 1, got {tp}")
    devs = jax.devices()
    if len(devs) < tp:
        raise RuntimeError(
            f"decode_mesh(tp={tp}) needs {tp} devices but only "
            f"{len(devs)} are visible; on CPU set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={tp} "
            f"(before jax import) to fake a host-platform mesh")
    return jax.sharding.Mesh(np.asarray(devs[:tp]), ("tp",))
