"""What every cell shares: finding a cell's files by name, the output
directory, the failure log and the one result line.

Nothing here imports jax: ``run.py``'s own process must stay off the
chip, which belongs to the replica (serving) or the training child.

A cell is data. ``BENCHMARK.json`` names its configuration and traffic
mix; ``configs/<config>.json``, ``traffic/<mix>.json`` and
``layer_metrics/<metric>.py`` are found by those names, and so is the
model: a configuration file's ``"architecture"`` (absent: ``"gpt2"``)
names ``architectures/<name>.py``, the one file that knows the
program's model code and its plain reference (``load_architecture``).
So a later PR adds a cell, a mix, a configuration, a per-layer metric
or a model by adding files and entries and edits nothing that is here.

``correct`` holds a serving run to the reference twice: the logits of
the served arithmetic within the configuration's ``logits_rel_tol``,
and every distinct answer the engine gave to the check request ranked
by the reference within twice that of its best logit at every token,
with a control (the same tokens against another prompt's logits) that
has to fail (``perf_reference_check.py``).
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

#: Stamped when this module is first imported, which ``run.py`` does
#: before anything else: ``setup_s`` is measured from here.
PROCESS_START = time.monotonic()


class BenchError(RuntimeError):
    """The benchmark cannot run as asked (bad name, no chip, a failed
    set-up): exit non-zero, print no result line."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    """The cell with its configuration entry and the metrics it reports:
    a metric without a ``workloads`` key is every cell's."""
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])

    def mine(metrics: List[dict]) -> List[dict]:
        return [m for m in metrics
                if "workloads" not in m or name in m["workloads"]]

    return {"cell": cell, "config": config,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(entry: dict, root: str = ROOT) -> dict:
    return load_json(os.path.join(root, entry["file"]))


def load_mix(name: str, here: str = HERE) -> dict:
    path = os.path.join(here, "traffic", name + ".json")
    if not os.path.exists(path):
        raise BenchError(f"no traffic mix file {path}")
    return load_json(path)


def load_file(path: str, prefix: str):
    """The module in the file at ``path``, under a name of its own."""
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        prefix + stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, here: str = HERE):
    """The per-layer metric's own reader: ``layer_metrics/<name>.py``
    with ``read(run) -> number or None`` and the constants ``LAYER``,
    ``UNIT``, ``SOURCE``, ``MOVES``."""
    path = os.path.join(here, "layer_metrics", name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no reader {path} for per-layer metric {name!r}")
    return load_file(path, "perf_layer_")


def load_architecture(conf: dict, here: str = HERE):
    """The one module that knows the configuration's model:
    ``architectures/<name>.py`` for the configuration file's
    ``"architecture"`` (absent: ``"gpt2"``). ``architectures/gpt2.py``
    says what such a module provides. It imports jax and the program
    inside its functions only: the driver of a serving cell loads it
    too, and stays off the chip."""
    name = conf.get("architecture", "gpt2")
    path = os.path.join(here, "architectures", name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no architecture file {path} for configuration "
                         f"{conf.get('name')!r}")
    return load_file(path, "perf_arch_")


def twin(name: str):
    """For a ``.sat``/``.train`` twin: the plain metric's ``read``."""
    return load_reader(name).read


def peaks(device_kind: str, here: str = HERE) -> dict:
    """The chip's published peaks by exact ``device_kind``; an unknown
    kind is an error, never a default."""
    table = load_json(os.path.join(here, "peaks.json"))
    if device_kind not in table["devices"]:
        raise BenchError(
            f"no peaks on record for device_kind={device_kind!r}; known: "
            f"{sorted(table['devices'])}. Add it to peaks.json with its "
            f"source.")
    return table["devices"][device_kind]


def out_dir(workload: str, seed: int, trace: int, root: str = ROOT) -> str:
    """Where this run writes its tables and logs: inside the checkout,
    listed in ``.gitignore``; under ``chiprun_out/`` so that a builder's
    call brings it back."""
    path = os.path.join(root, "chiprun_out", "perf",
                        f"{workload}-s{seed}-t{trace}")
    os.makedirs(path, exist_ok=True)
    return path


class FailureLog:
    """Every failed request: exception class, time and request, to a
    file in the run's output directory and to stdout (an earlier line
    than the result)."""

    def __init__(self, directory: str):
        self.path = os.path.join(directory, "failures.jsonl")
        self.rows: List[dict] = []
        open(self.path, "w").close()

    def add(self, **row):
        self.rows.append(row)
        line = json.dumps(row, default=str)
        with open(self.path, "a") as f:
            f.write(line + "\n")
        print("FAILURE " + line, flush=True)


def quantile(values: List[float], q: float) -> Optional[float]:
    """Linear-interpolated quantile of all the values (q in [0, 1])."""
    if not values:
        return None
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: dict,
                breakdown: Optional[dict] = None,
                extra: Optional[dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if extra:
        out.update(extra)
    return json.dumps(out)


def device_entry(device: dict, memory_peak_bytes: int, red) -> dict:
    """The result line's ``device``: as JAX reports it, the fullest
    chip's peak and, from a traced run, busy seconds and the slice."""
    dev = dict(device, memory_peak_bytes=memory_peak_bytes)
    if red and red.get("devices"):
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
    return dev


def breakdown_entry(red) -> Optional[dict]:
    if not red or not red.get("devices"):
        return None
    return {"device_ops": red["device_ops"][:10],
            "idle_gaps": red["idle_gaps"][:10]}


def worker_env(root: str = ROOT, here: str = HERE):
    """Make ``ray_tpu`` and the benchmark's modules importable in every
    process the run starts (replicas unpickle the deployment by name)."""
    for p in (here, root):
        if p not in sys.path:
            sys.path.insert(0, p)
    parts = [here, root] + [p for p in os.environ.get(
        "PYTHONPATH", "").split(os.pathsep) if p]
    os.environ["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
