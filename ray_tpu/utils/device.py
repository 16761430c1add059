"""Host↔device staging helpers for TPU (SURVEY §7 "plasma-style
zero-copy into jax.Array").

The object-plane design already gets host-side zero-copy for free:
large values live in shm segments, serialization keeps array bodies as
out-of-band pickle-5 buffers, and ``rt.get`` returns numpy arrays that
ALIAS the (read-only) segment — no host copy at any size. What remains
is the host→device hop, which these helpers make explicit and
measurable.
"""
from __future__ import annotations

import threading
import time
from typing import Any

_stats_lock = threading.Lock()
_stats = {"calls": 0, "bytes": 0, "seconds": 0.0, "copies": 0}


def transfer_stats(reset: bool = False) -> dict:
    """Cumulative host→device staging telemetry for this process:
    calls, bytes, wall seconds (and derived GiB/s), and how many inputs
    needed a contiguity copy before DMA. The replica/bench hot paths
    route through :func:`device_put_shm` to make the host→device hop
    visible."""
    with _stats_lock:
        out = dict(_stats)
        if reset:
            _stats.update({"calls": 0, "bytes": 0, "seconds": 0.0,
                           "copies": 0})
    secs = out["seconds"]
    out["gib_per_s"] = (out["bytes"] / (1 << 30) / secs) if secs else 0.0
    return out


def device_put_shm(x: Any, device=None, sharding=None):
    """Stage a host array (zero-copy shm view or otherwise) on device.

    Non-contiguous or non-native-endian inputs force jax into a hidden
    host copy before the transfer; this chokepoint makes the copy
    explicit (counted in :func:`transfer_stats`) so an shm-aliased
    array that silently lost contiguity shows up in telemetry instead
    of as mystery latency.
    """
    import jax
    import numpy as np

    copied = 0
    if isinstance(x, np.ndarray):
        if x.dtype.byteorder not in ("=", "|", "<"):
            # byteswap to native — ascontiguousarray would keep the
            # foreign byte order and jax would copy AGAIN internally
            x = x.astype(x.dtype.newbyteorder("="))
            copied = 1
        if not x.flags.c_contiguous:
            x = np.ascontiguousarray(x)
            copied = 1
    t0 = time.perf_counter()
    out = jax.device_put(x, sharding if sharding is not None else device)
    dt = time.perf_counter() - t0
    with _stats_lock:
        _stats["calls"] += 1
        _stats["bytes"] += int(getattr(x, "nbytes", 0))
        _stats["seconds"] += dt
        _stats["copies"] += copied
    return out


def donate_wrapper(fn, donate_argnums=(0,), static_argnums=()):
    """``jax.jit`` with donated array arguments: the caller's device
    buffers are reused for the outputs (halves steady-state HBM traffic
    for in-place-shaped loops like optimizer steps or KV-cache
    updates)."""
    import jax

    return jax.jit(fn, donate_argnums=donate_argnums,
                   static_argnums=static_argnums)
