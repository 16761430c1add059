"""Shared by the benchmark's tests: where the benchmark lives, a
temporary copy of it to which a test adds files (never edits one), and
the two structural checks that every root has to pass: the tree, and
the rehearsal's copy that holds a second, CUT configuration whose
architecture file imports the program openly (``rehearsal_copy``)."""
import ast
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def perf_dir(root=ROOT):
    return os.path.join(root, "benchmarks", "perf")


PERF = perf_dir()
if PERF not in sys.path:
    sys.path.insert(0, PERF)


def benchmark(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def fixture(name):
    return os.path.join(FIXTURES, name)


def copy_with_additions(tmp, *, configs=(), mixes=(), readers=(),
                        architectures=(), cells=(), metrics=(),
                        join=None):
    """A copy of BENCHMARK.json and benchmarks/perf under ``tmp``, plus
    new files and new entries only. Returns the copy's root. A
    configuration's entry takes ``source`` and ``reduced`` from the
    configuration's own file, as a PR that adds one writes them.
    ``architectures``: (file name under ``architectures/``, path of the
    file to copy there): a model and the reference beside it."""
    root = os.path.join(str(tmp), "co")
    shutil.copytree(PERF, perf_dir(root),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = benchmark()
    for name, path in configs:
        dst = os.path.join(perf_dir(root), "configs", name + ".json")
        assert not os.path.exists(dst)
        shutil.copy(path, dst)
        with open(path) as f:
            conf = json.load(f)
        bench["configs"].append({
            "name": name, "source": conf["source"]["url"],
            "file": f"benchmarks/perf/configs/{name}.json",
            "reduced": conf["reduced"], "why": "test"})
    for name, path in mixes:
        dst = os.path.join(perf_dir(root), "traffic", name + ".json")
        assert not os.path.exists(dst)
        shutil.copy(path, dst)
    for name, path in architectures:
        dst = os.path.join(perf_dir(root), "architectures", name)
        assert not os.path.exists(dst)
        shutil.copy(path, dst)
    for name, text in readers:
        dst = os.path.join(perf_dir(root), "layer_metrics", name + ".py")
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


READER = '''"""A dummy per-layer metric: requests the window attempted."""
LAYER = "load generator"
UNIT = "1"
SOURCE = "host_clock"
MOVES = "tpot_mean_ms"


def read(run):
    n = run["e2e"].get("attempted")
    return None if n is None else float(n)
'''

#: a second architecture: its module, and its reference beside it
DUMMY_ARCH = [("dummy.py", fixture("dummy_arch.py")),
              ("dummy_reference.py", fixture("dummy_arch_reference.py"))]


def cell(name, config, traffic, chips=1):
    return {"name": name, "config": config, "traffic": traffic,
            "chips": chips, "why": "test"}


def rehearsal_copy(tmp):
    """THE rehearsal: what a ``model_config`` PR brings, as files and
    entries. A gpt2 configuration at ``nano``, and the dummy
    architecture with a CUT configuration (``dummy-serve.json``: keys in
    ``reduced``, published and held values, the deployment), each with
    a chat cell; a mix; a per-layer metric. ``run.py`` runs in it, and
    the benchmark's structural tests run against it as against the
    tree."""
    return copy_with_additions(
        tmp,
        configs=[("nano-serve", fixture("nano-serve.json")),
                 ("dummy-serve", fixture("dummy-serve.json"))],
        mixes=[("nano-chat", fixture("nano-chat.json"))],
        readers=[("dummy_attempted", READER)],
        architectures=DUMMY_ARCH,
        cells=[cell("nano-chat", "nano-serve", "nano-chat"),
               cell("dummy-chat", "dummy-serve", "nano-chat")],
        metrics=[("per_layer", {
            "name": "dummy_attempted", "unit": "1", "better": "higher",
            "source": "host_clock", "layer": "load generator",
            "moves": "tpot_mean_ms", "workloads": ["nano-chat"]})],
        join={"nano-chat": "cgpt1b3-chat-steady",
              "dummy-chat": "cgpt1b3-chat-steady"})


def root_of(kind, tmp_path_factory):
    """The two roots the structural tests run on: ``"tree"``, or a
    fresh ``"rehearsal"`` copy."""
    if kind == "tree":
        return ROOT
    return rehearsal_copy(tmp_path_factory.mktemp("perf_rehearsal"))


# ---- what every configuration file states (the contract is in
# architectures/gpt2.py's docstring)

CEREBRAS_1B3 = ("https://huggingface.co/cerebras/Cerebras-GPT-1.3B/"
                "blob/main/config.json")


def check_configuration(entry, conf, arch=None):
    """A configuration's file against its entry in BENCHMARK.json and
    against its own statement. Raises AssertionError."""
    assert conf["reduced"] == entry["reduced"], (conf["reduced"],
                                                 entry["reduced"])
    assert conf["source"]["url"] == entry["source"]
    assert conf["assumed"] and all(isinstance(a, str) and a
                                   for a in conf["assumed"])
    cut = conf.get("cut", {})
    assert sorted(cut) == sorted(conf["reduced"]), \
        "every key in reduced states its cut, and no other key does"
    for key, c in cut.items():
        held = conf[key] if key in conf else conf["model"][key]
        assert c["held"] == held, (key, c, held)
        assert c["published"] != held, (key, c)
        assert type(c["published"]) is type(held), (key, c)
    if conf["reduced"]:
        stands = conf["cut_stands_for"]
        assert isinstance(stands["chips_sharing_a_layer"], int) \
            and stands["chips_sharing_a_layer"] >= 1
        assert isinstance(stands["how"], str) and stands["how"]
    else:
        assert "cut_stands_for" not in conf
    ck = conf["correct"]
    if arch is not None and hasattr(arch, "decidable"):
        assert "tie_eps" in ck, "decidable needs correct.tie_eps"
    if "tie_eps" in ck:
        assert ck["tie_eps"] > 0 and "tie_eps" in ck["why"]
    assert isinstance(ck.get("rows", 2), int) and ck.get("rows", 2) >= 1
    assert 0 < ck.get("min_compared", 1.0) <= 1
    if conf["source"]["url"] == CEREBRAS_1B3:
        m = conf["model"]
        assert conf["reduced"] == []
        assert (m["n_layer"], m["n_embd"], m["n_head"], m["n_inner"],
                m["n_positions"], m["vocab_size"]) == \
            (24, 2048, 16, 8192, 2048, 50257)


# ---- which files know the model

#: a plain reference's module: ``reference_<arch>.py`` (gpt2's, beside
#: the harness) or ``<arch>_reference.py`` (beside its architecture)
REFERENCE = re.compile(r"^(reference_(\w+)|(\w+)_reference)$")
PROGRAM = ("ray_tpu.models", "ray_tpu.serve.engine")


def names_in_code(path):
    """Every module a file imports and every string it holds outside
    docstrings (``importlib.import_module("ray_tpu.models.x")`` and
    ``load_file(".../x_reference.py")`` name a module too)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    docs = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef, ast.ClassDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and isinstance(
                    body[0].value, ast.Constant):
                docs.add(id(body[0].value))
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out += [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            out += [f"{node.module}.{a.name}" for a in node.names]
        elif isinstance(node, ast.Constant) and isinstance(
                node.value, str) and id(node) not in docs:
            out.append(node.value)
    return out


def who_knows_the_model(perf):
    """(files under ``perf`` that import the program's model or engine
    code or name a reference module, the reference modules found). The
    files all have to lie under ``architectures/``, and
    ``architectures/<name>.py`` may name only its own reference.
    Raises AssertionError."""
    files = sorted(
        os.path.relpath(os.path.join(folder, name), perf)
        for folder, _dirs, names in os.walk(perf) for name in names
        if name.endswith(".py"))

    def stem(rel):
        return os.path.splitext(os.path.basename(rel))[0]

    references = [rel for rel in files if REFERENCE.match(stem(rel))]
    knows = []
    for rel in files:
        if rel in references:
            continue
        names = names_in_code(os.path.join(perf, rel))
        refs = {stem(r) for r in references for n in names
                if re.search(rf"\b{stem(r)}\b", n)}
        if refs or any(p in n for n in names for p in PROGRAM):
            knows.append(rel)
            assert os.path.dirname(rel) == "architectures", \
                f"{rel} knows the model and is no architecture file"
            assert refs <= {"reference_" + stem(rel),
                            stem(rel) + "_reference"}, \
                f"{rel} names a reference that is not its own: {refs}"
    for rel in references:
        assert os.path.dirname(rel) in ("", "architectures"), rel
        assert not any(n.split(".")[0] == "ray_tpu" for n in
                       names_in_code(os.path.join(perf, rel))), \
            f"{rel} shares code with the program"
    return knows, references


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
        [sys.executable, os.path.join(perf_dir(root), "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True,
        timeout=timeout)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr
