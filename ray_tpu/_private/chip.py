"""Which device this process runs on — every such decision, in one file.

Four questions used to be answered ad hoc, each in several spellings:
how a Pallas kernel runs here, whether a measurement has a chip under
it, what that chip's peak is, and where compiled programs are cached.
Each now has exactly one answer below. Importing this module does not
import jax (the smoke's parent and the launch scripts' drivers must
stay off the chip); the functions that need jax import it lazily.

Host-side chip *counting* and per-worker chip *visibility* need no jax
at all and live in :mod:`ray_tpu._private.accelerators`.
"""
from __future__ import annotations

import os
from typing import Dict, Tuple

#: Peak dense bf16 FLOP/s per chip, keyed by the EXACT
#: ``jax.devices()[0].device_kind`` string, with the source of each
#: figure. A device that is not listed is an error (:func:`peak_flops`),
#: never a default: an MFU computed against a guessed peak is a wrong
#: number with a right name.
PEAK_BF16_FLOPS: Dict[str, Tuple[float, str]] = {
    "TPU v5 lite": (197e12, 'Google Cloud documentation, "TPU v5e"'),
}

#: The one fixed compile-cache location used when the environment names
#: none: inside the checkout (derived from this file's own location, so
#: every process of every run resolves the same path — the path is part
#: of the cache key's lookup), listed in ``.gitignore``.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> str:
    """Place jax's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set it is left untouched;
    where it is not, it is set to the one fixed directory inside the
    checkout. Call it before ``rt.init()`` spawns anything: workers,
    replicas and trainer workers inherit the environment, so this single
    assignment covers every process on the chip path. jax reads the
    variable when it is imported, so a process that already imported
    jax gets the same value pushed into its config as well.
    """
    import sys

    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                 _DEFAULT_CACHE_DIR)
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` as jax reports them — the
    triple every printed result carries."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu() -> dict:
    """The device summary, or an error naming what was found instead.

    For processes that are meant to hold a chip: jax's own behaviour
    with ``JAX_PLATFORMS`` unset is to fall back to the CPU with a
    warning when the TPU fails to initialise, so "no exception" proves
    nothing — the platform has to be read back and checked.
    """
    dev = device_summary()
    if dev["platform"] != "tpu":
        raise RuntimeError(
            f"this process needs a TPU but jax initialised "
            f"platform={dev['platform']!r} kind={dev['kind']!r} "
            f"count={dev['count']} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    return dev


def peak_flops(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of ``device_kind``; an unknown
    device is an error."""
    try:
        return PEAK_BF16_FLOPS[device_kind][0]
    except KeyError:
        raise RuntimeError(
            f"no peak FLOP/s on record for device_kind={device_kind!r}; "
            f"known: {sorted(PEAK_BF16_FLOPS)}. Add it to "
            f"ray_tpu/_private/chip.py with its source.") from None


def pallas_interpret() -> bool:
    """How a Pallas kernel runs in this process — THE one decision:
    ``tpu`` compiles it through Mosaic, ``cpu`` interprets it (tier-1
    exercises the shipping kernel body), anything else is an error. No
    other code decides ``interpret=``."""
    import jax

    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here run compiled on 'tpu' or interpreted on "
        f"'cpu'; this process is on platform {platform!r}")


def compiled_by_mosaic(lowered_text: str) -> bool:
    """Whether a lowered program (``jit(f).lower(..).as_text()``)
    carries a Mosaic-compiled kernel — observed from the program, not
    inferred from the platform."""
    return "tpu_custom_call" in lowered_text
