"""Shared by the benchmark's tests: where the benchmark lives, and a
temporary copy of it to which a test adds files (never edits one)."""
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PERF = os.path.join(ROOT, "benchmarks", "perf")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
if PERF not in sys.path:
    sys.path.insert(0, PERF)


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def copy_with_additions(tmp, *, configs=(), mixes=(), readers=(),
                        architectures=(), cells=(), metrics=(),
                        join=None):
    """A copy of BENCHMARK.json and benchmarks/perf under ``tmp``, plus
    new files and new entries only. Returns the copy's root.
    ``architectures``: (file name under ``architectures/``, path of the
    file to copy there): a model and the reference beside it."""
    root = os.path.join(str(tmp), "co")
    shutil.copytree(PERF, os.path.join(root, "benchmarks", "perf"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = benchmark()
    for name, path in configs:
        dst = os.path.join(root, "benchmarks", "perf", "configs",
                           name + ".json")
        assert not os.path.exists(dst)
        shutil.copy(path, dst)
        bench["configs"].append({
            "name": name, "source": "none: a tiny preset for CPU tests",
            "file": f"benchmarks/perf/configs/{name}.json",
            "reduced": [], "why": "test"})
    for name, path in mixes:
        dst = os.path.join(root, "benchmarks", "perf", "traffic",
                           name + ".json")
        assert not os.path.exists(dst)
        shutil.copy(path, dst)
    for name, path in architectures:
        dst = os.path.join(root, "benchmarks", "perf", "architectures",
                           name)
        assert not os.path.exists(dst)
        shutil.copy(path, dst)
    for name, text in readers:
        dst = os.path.join(root, "benchmarks", "perf", "layer_metrics",
                           name + ".py")
        assert not os.path.exists(dst)
        with open(dst, "w") as f:
            f.write(text)
    bench["workloads"].extend(cells)
    for new, like in (join or {}).items():
        # the new cell reports what a cell like it reports: its name
        # is appended to those metrics' lists
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(new)
    for kind, entry in metrics:
        bench[kind].append(entry)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run_copy(root, *args, devices=1, timeout=300):
    """Run the copy's run.py as a rehearsal on the CPU; returns
    (returncode, stdout lines, stderr)."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "perf",
                                      "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True,
        timeout=timeout)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr
