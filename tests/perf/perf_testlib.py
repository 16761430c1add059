"""Shared by the benchmark's tests: where the benchmark lives, a
temporary copy of it to which a test adds files (never edits one), and
the structural checks that every root has to pass: the tree, the
rehearsal's copy that holds a second, CUT configuration whose
architecture file imports the program openly (``rehearsal_copy``), and
the PLANTED tree (``planted_tree``, PR 36): a copy of the benchmark AND
of these tests into which an architecture, a cut configuration, a mix,
a reader and a serving cell are planted in place, as a ``model_config``
PR's diff would, and in which the copy's own structural tests then run.
``ROOT`` follows this file's place, so a copy's tests hold the copy."""
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


def copy_with_additions(tmp, **additions):
    """A copy of BENCHMARK.json and benchmarks/perf under ``tmp``, plus
    new files and new entries only (``add_in_place``). Returns the
    copy's root."""
    root = os.path.join(str(tmp), "co")
    shutil.copytree(PERF, perf_dir(root),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return add_in_place(root, **additions)


def add_in_place(root, *, configs=(), mixes=(), readers=(),
                 architectures=(), cells=(), metrics=(), join=None):
    """New files under ``root``'s benchmarks/perf and new entries in its
    BENCHMARK.json (this tree's, with the entries added), as a PR that
    adds and edits nothing writes them. Returns ``root``. A
    configuration's entry takes ``source`` and ``reduced`` from the
    configuration's own file, as a PR that adds one writes them.
    ``architectures``: (file name under ``architectures/``, path of the
    file to copy there): a model and the reference beside it."""
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


# ---- the planted tree (PR 36)

#: the planted architecture, configuration, cell and reader
PLANTED = "planted"
PLANTED_CONFIG, PLANTED_CELL = PLANTED + "-serve", PLANTED + "-batch"
PLANTED_READER = PLANTED + "_attempted"
#: the cell whose metrics the planted cell joins, every list of them
PLANTED_LIKE = "cgpt1b3-batch-offline"

#: what the planted architecture has beyond the dummy fixture: a
#: ``decidable`` (nothing in it chooses, so every position is decided;
#: its configuration then has to state ``correct.tie_eps``), and the
#: bytes of a decode step under key names of its own
PLANTED_MORE = '''

def decidable(cfg, conf):
    assert conf["correct"]["tie_eps"] > 0

    def fn(weights, tokens):
        import jax.numpy as jnp

        return jnp.ones(tokens.shape, bool)

    return fn


def decode_step_bytes(conf, weight_bytes, kv_bytes, live_tokens,
                      stats_delta):
    m = conf["model"]
    d, f, n = m["width"], m["ffn"], m["layers"]
    weights = m["rows"] * d + n * (4 * d * d + 2 * d * f + 2 * d) + d
    return weights * weight_bytes + 2 * n * d * kv_bytes * live_tokens
'''

PLANTED_FAULTS = (
    "the_cell_in_a_list_whose_moves_it_does_not_report",
    "a_cell_taken_out_of_a_counter_entrys_list",
    "a_cell_put_before_the_cells_that_were_there",
    "a_reference_without_its_module",
    "an_architecture_without_its_reference",
    "the_planted_architecture_names_reference_gpt2")


def planted_tree(tmp, fault=None, name=PLANTED):
    """A copy of the tree a PR would change: BENCHMARK.json,
    benchmarks/perf AND tests/perf, with what a ``model_config`` PR
    brings planted IN PLACE, as its diff would (``rehearsal_copy``
    plants beside a copy of benchmarks/perf only, so the tests' own
    lists of what the tree holds never met an addition until PR 36): a
    second architecture with its reference under ``architectures/``
    (the dummy fixtures under another name, with a ``decidable`` and a
    ``decode_step_bytes``), a CUT configuration that names it (with
    ``correct.tie_eps``), a closed-loop mix, a reader with its entry,
    and a serving cell appended to the ``workloads`` list of every
    end-to-end and per-layer metric ``cgpt1b3-batch-offline`` reports.
    The copy's own tests then hold the copy (``run_planted_tests``) and
    its ``run.py`` runs the planted cell (``run_copy``). ``fault``: one
    of ``PLANTED_FAULTS``, each of which the copy's tests must refuse.
    ``name``: the planted architecture's; its configuration, cell and
    reader are named after it. Under another name the copy is a tree
    that has grown by one, in which ALL of the copy's tests/perf pass,
    these plantings among them (PR 36 ran it so; CHANGES.md). Returns
    the copy's root."""
    config, cell_name, reader = name + "-serve", name + "-batch", \
        name + "_attempted"
    assert fault is None or fault in PLANTED_FAULTS, fault
    root = os.path.join(str(tmp), "tree")
    ignore = shutil.ignore_patterns("__pycache__")
    shutil.copytree(PERF, perf_dir(root), ignore=ignore)
    shutil.copytree(os.path.dirname(FIXTURES),
                    os.path.join(root, "tests", "perf"), ignore=ignore)
    made = os.path.join(str(tmp), "planted")
    os.makedirs(made)
    with open(fixture("dummy_arch.py")) as f:
        module = f.read().replace("dummy_reference",
                                  name + "_reference") + PLANTED_MORE
    if fault == "the_planted_architecture_names_reference_gpt2":
        module += ("\n\ndef reference(cfg):\n    import reference_gpt2"
                   "\n    return reference_gpt2\n")
    with open(fixture("dummy-serve.json")) as f:
        conf = json.load(f)
    conf.update(name=config, architecture=name)
    conf["correct"].update(
        tie_eps=1e-3, why=conf["correct"]["why"] + "; tie_eps: the "
        "planted module has a decidable, so the file states one (the "
        "model chooses nothing and every position is decided)")
    for file, text in ((name + ".py", module),
                       (config + ".json", json.dumps(conf))):
        with open(os.path.join(made, file), "w") as f:
            f.write(text)
    architectures = [
        (name + ".py", os.path.join(made, name + ".py")),
        (name + "_reference.py", fixture("dummy_arch_reference.py"))]
    if fault == "a_reference_without_its_module":
        architectures.append(("orphan_reference.py",
                              fixture("dummy_arch_reference.py")))
    if fault == "an_architecture_without_its_reference":
        architectures.pop()
    add_in_place(
        root,
        configs=[(config, os.path.join(made, config + ".json"))],
        mixes=[(cell_name, fixture("nano-batch.json"))],
        readers=[(reader, READER.replace(
            "tpot_mean_ms", "out_tokens_per_s"))],
        architectures=architectures,
        cells=[cell(cell_name, config, cell_name)],
        metrics=[("per_layer", {
            "name": reader, "unit": "1", "better": "higher",
            "source": "host_clock", "layer": "load generator",
            "moves": "out_tokens_per_s",
            "workloads": [cell_name]})],
        join={cell_name: PLANTED_LIKE})
    in_a_list = {
        "the_cell_in_a_list_whose_moves_it_does_not_report":
            lambda ls: ls["admit_queue_mean_ms"].append(cell_name),
        "a_cell_taken_out_of_a_counter_entrys_list":
            lambda ls: ls["decode_stall_pct.sat"].remove(PLANTED_LIKE),
        "a_cell_put_before_the_cells_that_were_there":
            lambda ls: ls["driver_host_pct.sat"].reverse()}
    if fault in in_a_list:
        bench = benchmark(root)
        in_a_list[fault]({m["name"]: m["workloads"]
                          for m in bench["per_layer"]})
        with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f)
    return root


#: the copy's own structural tests: BENCHMARK.json against the contract
#: and its files, who knows the model, the counter entries' contract
PLANTED_TESTS = (
    "test_perf_benchmark_json.py",
    "test_perf_reference.py"
    "::test_only_the_architecture_file_knows_the_model",
    "test_perf_reference.py"
    "::test_a_planted_file_that_knows_the_model_is_refused",
    "test_perf_engine_counters.py::test_entries_keep_the_contract")


def run_planted_tests(root, timeout=300):
    """The structural tests OF THE COPY at ``root``, on the copy, in a
    process of their own; returns (returncode, stdout)."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "no:xdist", "-rf",
         *(os.path.join("tests", "perf", t) for t in PLANTED_TESTS)],
        cwd=root, env=_cpu_env(1), capture_output=True, text=True,
        timeout=timeout)
    return proc.returncode, proc.stdout + proc.stderr


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


def architectures_and_references(perf):
    """The names of the architectures under ``perf``, by the rule that
    holds for any number of them: the files that know the model
    (``who_knows_the_model``) are exactly the modules under
    ``architectures/`` that are no reference, and the references are
    ``reference_gpt2.py`` plus exactly ``architectures/<name>_
    reference.py`` for every ``architectures/<name>.py`` but gpt2's: no
    module without its reference, no reference without its module.
    Raises AssertionError."""
    knows, references = who_knows_the_model(perf)
    arch = os.path.join("architectures", "")
    names = sorted(
        os.path.splitext(n)[0]
        for n in os.listdir(os.path.join(perf, "architectures"))
        if n.endswith(".py")
        and not REFERENCE.match(os.path.splitext(n)[0]))
    assert knows == [f"{arch}{n}.py" for n in names], \
        "every architecture file knows the model and nothing else does"
    assert references == [f"{arch}{n}_reference.py" for n in names
                          if n != "gpt2"] + ["reference_gpt2.py"], \
        "one reference beside every architecture file, and no other"
    return names


def _cpu_env(devices):
    """A child's environment: the CPU with ``devices`` virtual devices,
    and ``ray_tpu`` found from this tree (a planted copy holds the
    benchmark and its tests, not the program: its children find the
    program through the path they inherit)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    env["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={devices}"
    return env


def run_copy(root, *args, devices=1, timeout=300):
    """Run the copy's run.py as a rehearsal on the CPU; returns
    (returncode, stdout lines, stderr)."""
    import subprocess

    proc = subprocess.run(
        [sys.executable, os.path.join(perf_dir(root), "run.py"), *args],
        cwd=root, env=_cpu_env(devices), capture_output=True, text=True,
        timeout=timeout)
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr
