"""Native codec loader: compile-on-first-use with a pure-Python fallback.

The reference ships its data plane as prebuilt C++ (bazel targets under
``src/ray/``); this runtime compiles its single-file extension lazily with
the system compiler and caches the .so next to the source, keyed by the
python ABI AND the content of ``codec.cpp`` — git ignores the binary, so
a copied tree can carry one built from another commit's source, and an
mtime says nothing about that. If no compiler is available the callers
fall back to the Python implementations in
``_private/serialization.py``.
"""
from __future__ import annotations

import glob
import hashlib
import os
import subprocess
import sysconfig
import threading

_here = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_mod = None
_tried = False


def _build() -> str:
    src = os.path.join(_here, "codec.cpp")
    tag = sysconfig.get_config_var("SOABI") or "generic"
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(_here, f"_rt_native.{tag}.{digest}.so")
    if os.path.exists(out):
        return out
    include = sysconfig.get_paths()["include"]
    cxx = os.environ.get("CXX", "g++")
    tmp = f"{out}.{os.getpid()}.tmp"    # concurrent first users race
    cmd = [cxx, "-O3", "-shared", "-fPIC", "-std=c++17",
           f"-I{include}", src, "-o", tmp]
    subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    os.replace(tmp, out)
    for stale in glob.glob(os.path.join(_here, f"_rt_native.{tag}*.so")):
        if stale != out:
            try:
                os.remove(stale)
            except OSError:     # another first user got there first
                pass
    return out


def load():
    """The native module, or None when unavailable."""
    global _mod, _tried
    if _mod is not None or _tried:
        return _mod
    with _lock:
        if _mod is not None or _tried:
            return _mod
        _tried = True
        if os.environ.get("RT_DISABLE_NATIVE", "") == "1":
            return None
        try:
            so = _build()
            import importlib.util

            spec = importlib.util.spec_from_file_location("_rt_native", so)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _mod = mod
        except Exception:  # noqa: BLE001 - fall back to pure python
            _mod = None
        return _mod
