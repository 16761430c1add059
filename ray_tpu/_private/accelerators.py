"""TPU detection and gang-scheduling resources.

Capability parity with the reference's TPU accelerator manager
(reference: ``python/ray/_private/accelerators/tpu.py:75``
TPUAcceleratorManager; ``:363`` documents the ``TPU-v4-16-head`` gang
pattern): every node advertises its chip count as ``TPU``, and worker 0 of
a slice additionally advertises ``TPU-{pod_type}-head: 1`` so a gang can
anchor itself to exactly one slice and fan out over its hosts.

Zero-egress redesign: the reference polls GCE instance metadata over HTTP;
here detection is purely env-var + device-file based (the same variables
the TPU runtime/GKE injects), with ``RT_TPU_TOPOLOGY`` as an explicit
override for tests and air-gapped machines.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

# Long-form GCE accelerator types → short version names.
_VERSION_ALIASES = {
    "v5litepod": "v5e",
    "v5lite": "v5e",
    "v6litepod": "v6e",
    "v6lite": "v6e",
}

# Chips per host per TPU generation (v5e pods come in 4- and 8-chip host
# shapes; override with RT_TPU_CHIPS_PER_HOST when needed).
_CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5e": 4, "v5p": 4, "v6e": 4}


#: Chips-per-process → the ``x,y,z`` mesh libtpu is told the process
#: owns (the shapes a single host's chips can form).
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1", 8: "2,4,1"}


def local_chip_ids() -> List[str]:
    """Ids of the TPU chips this process may use, without importing jax
    or touching the runtime — THE one chip census (``rt.init`` sizes the
    ``TPU`` resource from it, the head hands these ids out to workers,
    and :func:`ray_tpu.parallel.local_chip_count` is its length).

    A process already confined by libtpu's visibility variable sees only
    that subset. Otherwise the chips are the numbered device nodes the
    TPU driver creates — ``/dev/accelN`` or ``/dev/vfio/N``; the
    ``/dev/vfio/vfio`` control node is not a chip — and a chip's id is
    its ORDINAL among them, which is how libtpu counts: a machine that
    is handed only ``/dev/vfio/3`` has one chip, and it is chip 0
    (established on the v5e: ``TPU_VISIBLE_CHIPS=3`` there finds no
    device)."""
    env = os.environ.get("TPU_VISIBLE_CHIPS")
    if env is not None:
        return [c.strip() for c in env.split(",") if c.strip()]
    for pattern in ("/dev/accel*", "/dev/vfio/*"):
        n = sum(bool(re.fullmatch(r"(?:accel)?\d+", os.path.basename(p)))
                for p in glob.glob(pattern))
        if n:
            return [str(i) for i in range(n)]
    return []


def local_chip_count() -> int:
    return len(local_chip_ids())


def node_chip_ids(tpu_total: float) -> List[str]:
    """The chip ids behind a node's ``TPU: tpu_total`` resource: the
    census when it covers the count, else ``0..n-1`` — a count given by
    hand (``rt.init(num_tpus=4)`` on a machine without chips: tests and
    dry runs) still hands out distinct ids."""
    n = int(tpu_total)
    ids = local_chip_ids()
    return ids[:n] if len(ids) >= n else [str(i) for i in range(n)]


def chip_visibility_env(chip_ids: Sequence[str],
                        node_chips: int) -> Dict[str, str]:
    """The environment that confines a process to exactly ``chip_ids``
    of a node with ``node_chips`` chips — THE one place libtpu's
    visibility variables are spelled. Must be in place before the
    process first imports jax (libtpu reads it once, at start-up).

    - An empty grant hides every chip AND pins jax to the CPU, so a
      controller, proxy or data actor cannot take a chip by importing
      jax.
    - A grant of ALL the node's chips changes nothing: the machine's
      own environment already describes that topology.
    - A part of the node gets ``TPU_VISIBLE_CHIPS`` to select the
      chips, plus the two ``*_BOUNDS`` variables that tell libtpu the
      process is its own single-host topology of that many chips
      instead of a slice of the host's full mesh — without them a
      sub-host process waits for peers that never come.
    """
    ids = [str(c) for c in chip_ids]
    if not ids:
        return {"TPU_VISIBLE_CHIPS": "", "JAX_PLATFORMS": "cpu"}
    if len(ids) == node_chips:
        return {}
    if len(ids) not in _PROCESS_BOUNDS:
        raise ValueError(
            f"cannot confine a process to {len(ids)} chips {ids}: "
            f"libtpu forms a mesh of {sorted(_PROCESS_BOUNDS)} chips")
    return {"TPU_VISIBLE_CHIPS": ",".join(ids),
            "TPU_CHIPS_PER_HOST_BOUNDS": _PROCESS_BOUNDS[len(ids)],
            "TPU_HOST_BOUNDS": "1,1,1"}


def normalize_pod_type(raw: str) -> str:
    """'v5litepod-16' → 'v5e-16'; already-short names pass through."""
    version, _, chips = raw.partition("-")
    version = _VERSION_ALIASES.get(version, version)
    return f"{version}-{chips}" if chips else version


def parse_topology(topology: str) -> Tuple[str, int]:
    """'v5e-16' → ('v5e', 16). Raises ValueError on malformed input."""
    topology = normalize_pod_type(topology)
    version, _, chips = topology.partition("-")
    if not chips or not chips.isdigit():
        raise ValueError(
            f"malformed TPU topology {topology!r}; expected "
            "'<version>-<chips>' like 'v5e-16'")
    return version, int(chips)


def chips_per_host(version: str) -> int:
    env = os.environ.get("RT_TPU_CHIPS_PER_HOST")
    if env:
        return int(env)
    return _CHIPS_PER_HOST.get(version, 4)


def num_hosts(topology: str) -> int:
    version, chips = parse_topology(topology)
    per = chips_per_host(version)
    return max(1, chips // per)


def detect_pod_type() -> Optional[str]:
    """The slice this host belongs to, e.g. 'v5e-16' (None off-TPU)."""
    raw = (os.environ.get("RT_TPU_TOPOLOGY")
           or os.environ.get("TPU_ACCELERATOR_TYPE"))
    return normalize_pod_type(raw) if raw else None


def detect_worker_id() -> int:
    """This host's index within its slice (0 on single-host)."""
    return int(os.environ.get("TPU_WORKER_ID", "0") or 0)


def head_resource_name(pod_type: str) -> str:
    return f"TPU-{normalize_pod_type(pod_type)}-head"


def gang_resources(num_chips: float, pod_type: Optional[str] = None,
                   worker_id: Optional[int] = None) -> Dict[str, float]:
    """Extra node resources advertised alongside ``TPU: num_chips``.

    Worker 0 of a slice gets the ``TPU-{pod}-head`` anchor; every worker
    gets the ``accelerator_type:TPU-{VERSION}`` label-style resource.
    ``pod_type``/``worker_id`` default to env detection (a real TPU VM
    host); explicit values let provisioners (the autoscaler's slice
    provider) mint the same shape for hosts they are about to launch.
    """
    pod = normalize_pod_type(pod_type) if pod_type else detect_pod_type()
    if not pod or not num_chips:
        return {}
    version, _ = parse_topology(pod)
    res: Dict[str, float] = {
        f"accelerator_type:TPU-{version.upper()}": float(num_chips)}
    wid = detect_worker_id() if worker_id is None else worker_id
    if wid == 0:
        res[head_resource_name(pod)] = 1.0
    return res
