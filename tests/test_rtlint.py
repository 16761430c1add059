"""rtlint (tools/rtlint): the repo-native static analyzer.

Three layers of coverage:

- fixture files under ``tests/rtlint_fixtures/`` assert every rule
  RT101-RT107 both FIRES (lines tagged ``# FIRES RTxxx``, or
  ``# FIRES-BELOW RTxxx`` when a same-line comment would read as a
  justification) and respects inline suppressions — the expectation set
  is derived from the tags, so the fixtures are self-describing;
- the baseline mechanism is proven on a real finding (grandfathered
  entries filtered, stale entries reported);
- the CI gate: ``python -m tools.rtlint ray_tpu/ --check`` must exit 0
  against the checked-in baseline (this is the tier-1 hook — a new
  finding in ray_tpu/ fails this test), and two runs must be
  byte-identical (determinism).
"""
import json
import os
import re
import subprocess
import sys

import pytest

from tools.rtlint import (DEFAULT_BASELINE, RULE_TABLE, lint_metric_name,
                          run_paths, write_baseline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "rtlint_fixtures")

_MARKER = re.compile(r"#\s*FIRES(-BELOW)?\s+(RT\d{3})")


def _expected_from_markers(path):
    """(line, rule) pairs a fixture file declares it must produce."""
    out = set()
    with open(path) as f:
        lines = f.readlines()
    for i, text in enumerate(lines, 1):
        m = _MARKER.search(text)
        if not m:
            continue
        line = i
        if m.group(1):  # FIRES-BELOW: next non-blank, non-comment line
            j = i
            while j < len(lines) and (
                    not lines[j].strip()
                    or lines[j].lstrip().startswith("#")):
                j += 1
            line = j + 1
        out.add((line, m.group(2)))
    return out


def _fixture_findings():
    report = run_paths([FIXTURES])
    return report, {(f.line, f.rule) for f in report.findings
                    if f.rule != "RT999"}


def test_fixtures_fire_exactly_as_marked():
    """Every tagged line fires its rule; nothing else fires — which
    proves, per rule, the positive, the negative, AND the suppressed
    cases in one comparison."""
    report, got = _fixture_findings()
    expected = set()
    by_file = {}
    for root, _dirs, files in os.walk(FIXTURES):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            p = os.path.join(root, fn)
            rel = os.path.relpath(p, REPO).replace(os.sep, "/")
            marks = _expected_from_markers(p)
            by_file[rel] = marks
            expected |= marks
    # Findings are repo-relative only when cwd == repo root; compare on
    # (line, rule) per file to stay cwd-independent.
    got_pairs = {(f.path.split("rtlint_fixtures/")[-1], f.line, f.rule)
                 for f in report.findings}
    exp_pairs = {(rel.split("rtlint_fixtures/")[-1], line, rule)
                 for rel, marks in by_file.items()
                 for (line, rule) in marks}
    assert got_pairs == exp_pairs, (
        f"unexpected: {sorted(got_pairs - exp_pairs)}\n"
        f"missing: {sorted(exp_pairs - got_pairs)}")


def test_every_rule_has_fire_and_suppression_coverage():
    """The fixture set exercises each rule's fire path (a tagged line)
    and its suppression path (a ``# rtlint: disable=`` for the same
    rule somewhere in the fixtures)."""
    tagged, suppressed = set(), set()
    for root, _dirs, files in os.walk(FIXTURES):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            src = open(os.path.join(root, fn)).read()
            tagged |= {m.group(2) for m in _MARKER.finditer(src)}
            suppressed |= set(
                re.findall(r"rtlint:\s*disable=(RT\d{3})", src))
    rules = set(RULE_TABLE)
    assert tagged == rules, f"no fire fixture for {rules - tagged}"
    assert suppressed == rules, \
        f"no suppression fixture for {rules - suppressed}"


def test_baseline_grandfathers_and_reports_stale(tmp_path):
    report, _ = _fixture_findings()
    assert report.findings, "fixtures must produce findings"
    # One grandfathered finding PER RULE: the baseline must silence
    # each rule's findings individually, not just wholesale.
    grandfathered = {}
    for f in report.findings:
        grandfathered.setdefault(f.rule, f)
    assert set(grandfathered) == set(RULE_TABLE)
    baseline = tmp_path / "baseline.json"
    write_baseline(str(baseline), list(grandfathered.values()))
    data = json.loads(baseline.read_text())
    assert sorted(data["findings"]) == sorted(
        f.key for f in grandfathered.values())

    again = run_paths([FIXTURES], baseline_path=str(baseline))
    assert {f.key for f in again.baselined} == \
        {f.key for f in grandfathered.values()}
    assert not {f.key for f in again.new} & set(data["findings"])
    assert len(again.new) == len(report.findings) - len(grandfathered)
    grandfather = report.findings[0]

    # A stale entry (finding since fixed) is surfaced, not silently kept.
    baseline.write_text(json.dumps(
        {"findings": [grandfather.key, "RT101:gone.py:Gone.fixed.attr"]}))
    stale = run_paths([FIXTURES], baseline_path=str(baseline))
    assert stale.stale_baseline == ["RT101:gone.py:Gone.fixed.attr"]


def test_baseline_keys_are_line_number_free(tmp_path):
    """Inserting lines above a finding must not churn its baseline key
    (the whole point of symbol-keyed entries)."""
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def a(self):\n"
        "        with self._lock:\n"
        "            self._n = 1\n"
        "    def b(self):\n"
        "        self._n = 2\n")
    p = tmp_path / "mod.py"
    p.write_text(src)
    key1 = run_paths([str(p)]).findings[0].key
    p.write_text("# a new header comment\n# another\n" + src)
    moved = run_paths([str(p)]).findings[0]
    assert moved.key == key1 and moved.line > 10 - 1


def test_parse_error_is_a_finding(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    report = run_paths([str(p)])
    assert [f.rule for f in report.findings] == ["RT999"]
    assert report.new, "a broken file must fail the gate"


def test_parse_errors_are_never_grandfatherable(tmp_path):
    """A baseline must not greenlight a file that escapes every rule:
    write_baseline drops RT999 keys, and even a hand-edited baseline
    carrying one still fails the gate."""
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    report = run_paths([str(p)])
    baseline = tmp_path / "baseline.json"
    write_baseline(str(baseline), report.findings)
    assert json.loads(baseline.read_text())["findings"] == []
    baseline.write_text(json.dumps(
        {"findings": [report.findings[0].key]}))  # hand-edited in
    again = run_paths([str(p)], baseline_path=str(baseline))
    assert again.new and not again.baselined


def test_rt106_shares_the_runtime_implementation():
    """The satellite contract: MetricsRegistry.register and the static
    RT106 rule run ONE source of truth, so they cannot drift. The
    runtime loads metrics_names.py by FILE PATH (a package import
    would drag the whole analyzer into every ray_tpu process), so the
    pin is source-file identity, not function-object identity."""
    from ray_tpu._private import metrics
    from tools.rtlint import metrics_names

    assert os.path.samefile(
        metrics.lint_metric_name.__code__.co_filename,
        metrics_names.__file__)
    # And ray_tpu's import must NOT pull the analyzer package in.
    proc = subprocess.run(
        [sys.executable, "-c",
         "import ray_tpu._private.metrics as m, sys; "
         "assert not any(k.startswith('tools') for k in sys.modules), "
         "sorted(k for k in sys.modules if k.startswith('tools')); "
         "assert m.lint_metric_name('x', 'counter')"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    # RT_METRICS_STRICT semantics unchanged: strict registries raise on
    # the same problems the static rule reports.
    reg = metrics.MetricsRegistry(strict=True)
    with pytest.raises(ValueError, match="_total"):
        metrics.Counter("requests_shed", registry=reg)
    reg_warn = metrics.MetricsRegistry(strict=False)
    with pytest.warns(UserWarning, match="_seconds"):
        metrics.Histogram("decode_latency", registry=reg_warn)


def test_rtlint_is_clean_on_itself():
    report = run_paths([os.path.join(REPO, "tools")])
    assert not report.findings, [f.render() for f in report.findings]


def test_determinism_two_runs_byte_identical():
    """Two analyses of ray_tpu/ must render byte-identical JSON (no
    timestamps, no dict-order leakage, stable sort)."""
    target = os.path.join(REPO, "ray_tpu")
    a = run_paths([target]).to_json()
    b = run_paths([target]).to_json()
    assert a == b


def test_ci_gate_ray_tpu_is_clean():
    """THE tier-1 hook: the analyzer over ray_tpu/ must exit 0 against
    the checked-in baseline — a new finding fails this test, which
    fails the suite, which fails the existing verify command. Runs the
    real CLI so the exit-code contract is what's pinned."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.rtlint", "ray_tpu/", "--check"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (
        f"rtlint found new findings (fix them or, if genuinely "
        f"grandfathered, add them to {DEFAULT_BASELINE}):\n"
        f"{proc.stdout}\n{proc.stderr}")


def test_ci_gate_fails_on_new_findings(tmp_path):
    """--check exits non-zero on a non-baselined finding."""
    p = tmp_path / "serve"
    p.mkdir()
    bad = p / "controller.py"
    bad.write_text(
        "def loop(work):\n"
        "    try:\n"
        "        work()\n"
        "    except Exception:\n"
        "        pass\n")
    proc = subprocess.run(
        [sys.executable, "-m", "tools.rtlint", str(bad), "--check",
         "--no-baseline"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "RT107" in proc.stdout


def test_cli_json_output_and_rule_filter(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-m", "tools.rtlint",
         "tests/rtlint_fixtures/rt104_async.py", "--json",
         "--no-baseline", "--rules", "RT104"],
        cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    rules = {f["rule"] for f in data["findings"]}
    assert rules == {"RT104"}
    assert data["files_checked"] == 1


def test_shared_lint_rules_agree_with_register():
    """Spot-check the shared function directly (the same strings the
    runtime warns/raises about are what RT106 reports)."""
    assert lint_metric_name("x_total", "counter") == []
    assert any("_total" in p
               for p in lint_metric_name("x", "counter"))
    assert any("_seconds" in p
               for p in lint_metric_name("wait_ms", "histogram"))
    assert any("regex" in p
               for p in lint_metric_name("1bad", "gauge"))


# ---------------------------------------------------------------- rtflow
# ISSUE 15: interprocedural dataflow (tools/rtlint/flow.py +
# callgraph.py) and the RT109/RT110/RT111 rules built on it.

def test_new_rules_registered():
    assert {"RT109", "RT110", "RT111"} <= set(RULE_TABLE)


def test_determinism_covers_rtflow_rules():
    """Two analyses of the fixture tree — where RT109-RT111 actually
    produce findings — must render byte-identical JSON, extending the
    determinism pin to the interprocedural rules (their fixpoint and
    call-graph iteration order must not leak)."""
    a = run_paths([FIXTURES]).to_json()
    b = run_paths([FIXTURES]).to_json()
    assert a == b
    rules = {f["rule"] for f in json.loads(a)["findings"]}
    assert {"RT109", "RT110", "RT111"} <= rules


def test_parse_budget_grammar():
    from tools.rtlint import parse_budget

    c = parse_budget("len(prompt_buckets) + 3")
    assert c.evaluate({"len(prompt_buckets)": 2}) == 5
    assert parse_budget("1").evaluate({}) == 1
    assert parse_budget("2 * len(buckets) + 1").evaluate(
        {"len(buckets)": 4}) == 9
    for bad in ("len(prompt_buckets) - 1", "foo", "1.5", "len(a, b)"):
        with pytest.raises(ValueError):
            parse_budget(bad)


def test_card_leq_assumes_atoms_at_least_one():
    from tools.rtlint import Card, parse_budget

    atom = parse_budget("len(prompt_buckets)")
    assert Card.const(1).leq(atom)           # len >= 1 covers a const
    assert atom.leq(parse_budget("len(prompt_buckets) + 2"))
    assert not parse_budget("len(prompt_buckets) + 1").leq(atom)
    assert not Card.unbounded().leq(parse_budget("len(prompt_buckets)"))
    assert Card.unbounded().leq(Card.unbounded())


def _run_engine_scoped(tmp_path, src):
    """Analyze ``src`` under a path RT109's budget scope matches."""
    p = tmp_path / "serve"
    p.mkdir(exist_ok=True)
    f = p / "engine.py"
    f.write_text(src)
    return run_paths([str(f)])


def test_rt109_unbounded_fails_then_bounded_passes(tmp_path):
    """THE acceptance-criteria pin: a request-varying value laundered
    through a helper reaches a trace key -> RT109 fires (RT103 stays
    blind: no len() at the flagged site); re-bounding it through the
    bucket discipline makes the same code clean."""
    unbounded = (
        "import numpy as np\n"
        "# rtlint: program-budget: 1\n"
        "def jit_step(cfg):\n"
        "    return lambda *a: a\n"
        "class Eng:\n"
        "    # rtlint: program-budget: 1\n"
        "    def _build(self, cfg):\n"
        "        self._prog = jit_step(cfg)\n"
        "    def _width(self, prompt):\n"
        "        return len(prompt)\n"
        "    def admit(self, prompt):\n"
        "        n = self._width(prompt)\n"
        "        padded = np.zeros((1, n), np.int32)\n"
        "        return self._prog(padded)\n")
    report = _run_engine_scoped(tmp_path, unbounded)
    assert [f.rule for f in report.findings] == ["RT109"]
    assert "request-varying" in report.findings[0].message
    assert report.new, "an unbounded trace key must fail the gate"

    bounded = unbounded.replace(
        "        n = self._width(prompt)\n"
        "        padded = np.zeros((1, n), np.int32)\n",
        "        n = self._width(prompt)\n"
        "        b = next(x for x in self.prompt_buckets if x >= n)\n"
        "        padded = np.zeros((1, b), np.int32)\n").replace(
        "    # rtlint: program-budget: 1\n"
        "    def _build",
        "    # rtlint: program-budget: len(prompt_buckets)\n"
        "    def _build").replace(
        "# rtlint: program-budget: 1\n"
        "def jit_step",
        "# rtlint: program-budget: len(prompt_buckets)\n"
        "def jit_step")
    report = _run_engine_scoped(tmp_path, bounded)
    assert not report.findings, [f.render() for f in report.findings]


def test_rt109_budget_exceeded_then_raised(tmp_path):
    over = (
        "# rtlint: program-budget: 1\n"
        "def jit_p(cfg, k=0):\n"
        "    return lambda *a: a\n"
        "class Eng:\n"
        "    # rtlint: program-budget: 1\n"
        "    def _build(self, cfg):\n"
        "        self._a = jit_p(cfg)\n"
        "        self._b = jit_p(cfg, 1)\n")
    report = _run_engine_scoped(tmp_path, over)
    assert [f.rule for f in report.findings] == ["RT109"]
    assert "budget_exceeded" in report.findings[0].key
    fixed = over.replace("    # rtlint: program-budget: 1\n",
                         "    # rtlint: program-budget: 2\n")
    assert not _run_engine_scoped(tmp_path, fixed).findings


def test_descriptions_are_found_by_rule_and_the_frame_is_in_scope(
        tmp_path):
    """A module of ``ray_tpu/models`` that defines ``cache_spec`` is a
    description: rtflow's alias resolution and budget scope and rtsan's
    dispatch wrap find all seven by that one rule (``scmoe``,
    ``ssm_hybrid`` and ``ssm_moe``, which no list named, among them);
    the engine's ``self._model.jit_x(...)``
    resolves to the FRAME's def of a factory a description only binds;
    and RT109 fires on a factory in the frame without its declaration."""
    from tools.rtlint.callgraph import (CallGraph, description_names,
                                        is_description)
    from tools.rtlint.core import Module, collect_files
    from tools.rtlint.flow import in_budget_scope

    models = os.path.join(REPO, "ray_tpu", "models")
    assert description_names(models) == [
        "dsa_moe", "gpt_decode", "kda_moe", "mla_moe", "scmoe",
        "ssm_hybrid", "ssm_moe"]
    mods = [Module(ap, os.path.relpath(ap, REPO), open(ap).read())
            for ap, _ in collect_files([os.path.join(REPO, "ray_tpu")])]
    by_name = {os.path.basename(m.relpath): m for m in mods
               if "/models/" in m.relpath}
    assert [n for n, m in sorted(by_name.items()) if is_description(m)] \
        == ["dsa_moe.py", "gpt_decode.py", "kda_moe.py", "mla_moe.py",
            "scmoe.py", "ssm_hybrid.py", "ssm_moe.py"]
    assert all(in_budget_scope(by_name[n]) for n in (
        "ssm_moe.py", "ssm_hybrid.py", "scmoe.py", "kda_moe.py",
        "mla_moe.py", "dsa_moe.py",
        "gpt_decode.py", "serving.py"))
    assert not in_budget_scope(by_name["gpt.py"])
    g = CallGraph.build(mods)
    built = [e.callee for e in g.edges if e.caller
             == "ray_tpu/serve/engine.py::DecodeEngine._build_pool"]
    for factory in ("jit_prefill_into_slot_paged",
                    "jit_decode_chunk_slots_paged"):
        assert f"ray_tpu/models/serving.py::{factory}" in built

    # the sanitizer wraps the same four, each under its own names
    # (conftest's session sanitizer already has, unless RT_SAN=0)
    import importlib

    from tools.rtsan.core import Sanitizer
    san = Sanitizer()
    try:
        san._wrap_jit_factories()
        for name in description_names(models):
            desc = importlib.import_module(f"ray_tpu.models.{name}")
            for factory in ("jit_prefill_into_slot_paged",
                            "jit_decode_chunk_slots_paged"):
                assert getattr(getattr(desc, factory), "__rtsan__", False)
    finally:
        for module, name, orig in reversed(san._factory_patches):
            setattr(module, name, orig)

    frame = tmp_path / "models"
    frame.mkdir()
    (frame / "serving.py").write_text(
        "import jax\n"
        "# rtlint: program-budget: 1\n"
        "def jit_decode_chunk_slots_paged(cfg, *, model):\n"
        "    return jax.jit(model.decode_chunk_slots_paged)\n"
        "def jit_prefill_into_slot_paged(cfg, *, model):\n"
        "    return jax.jit(model.prefill_into_slot_paged)\n")
    report = run_paths([str(frame / "serving.py")])
    assert [(f.rule, f.key.rsplit(":", 1)[-1]) for f in report.findings] \
        == [("RT109", "jit_prefill_into_slot_paged.budget_missing")], \
        [f.render() for f in report.findings]


def test_rt110_holds_checked_at_edges(tmp_path):
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def _bump(self):  # rtlint: holds=_lock\n"
        "        self._n += 1\n"
        "    def good(self):\n"
        "        with self._lock:\n"
        "            self._bump()\n"
        "    def bad(self):\n"
        "        self._bump()\n")
    p = tmp_path / "m.py"
    p.write_text(src)
    report = run_paths([str(p)])
    assert [f.rule for f in report.findings] == ["RT110"]
    assert "C.bad->C._bump" in report.findings[0].key


def test_callgraph_resolves_repo_idioms(tmp_path):
    """Self methods, base-class methods, thread registration, nested
    with-lock context, and manual-acquire credit all resolve."""
    from tools.rtlint.callgraph import CallGraph
    from tools.rtlint.core import Module

    src = (
        "import threading\n"
        "class Base:\n"
        "    def shared(self):\n"
        "        return 1\n"
        "class C(Base):\n"
        "    def _run(self):\n"
        "        self.helper()\n"
        "    def helper(self):\n"
        "        return self.shared()\n"
        "    def start(self):\n"
        "        t = threading.Thread(target=self._run)\n"
        "        return t\n"
        "    def locked_call(self):\n"
        "        with self._big_lock:\n"
        "            with self._small_lock:\n"
        "                self.helper()\n")
    p = tmp_path / "cg.py"
    p.write_text(src)
    mod = Module(str(p), str(p), src)
    g = CallGraph.build([mod])
    edges = {(e.caller or "<mod>", e.callee, e.kind): e for e in g.edges}
    rel = mod.relpath
    assert (f"{rel}::C._run", f"{rel}::C.helper", "call") in edges
    assert (f"{rel}::C.helper", f"{rel}::Base.shared", "call") in edges
    assert (f"{rel}::C.start", f"{rel}::C._run", "thread") in edges
    nested = edges[(f"{rel}::C.locked_call", f"{rel}::C.helper", "call")]
    assert nested.locks == frozenset({"_big_lock", "_small_lock"})


def test_decorator_line_directives_attach(tmp_path):
    """The shared loader attaches directives on ANY decorator line of a
    def (and the line above the stack) — the rtlint suppression and the
    rtsan contract read the same placement (fixture coverage lives in
    rt101_locks.py; this pins the loader directly, multi-line decorator
    included)."""
    from tools.rtlint.annotations import directive_map, func_directives

    src = (
        "import functools\n"
        "# rtlint: owner=driver\n"
        "@functools.lru_cache(\n"
        "    maxsize=64)\n"
        "@staticmethod  # rtlint: holds=_lock\n"
        "def f():\n"
        "    pass\n")
    import ast as _ast
    fn = _ast.parse(src).body[1]
    d = func_directives(directive_map(src), fn)
    assert d == {"owner": "driver", "holds": "_lock"}


def test_update_baseline_refuses_growth(tmp_path):
    """--update-baseline is a burn-down tool: shrinking is free, adding
    entries needs --allow-growth (ISSUE 15 satellite)."""
    bad = tmp_path / "serve"
    bad.mkdir()
    f = bad / "controller.py"
    one = ("def loop(work):\n"
           "    try:\n"
           "        work()\n"
           "    except Exception:\n"
           "        pass\n")
    two = one + ("def loop2(work):\n"
                 "    try:\n"
                 "        work()\n"
                 "    except Exception:\n"
                 "        pass\n")
    baseline = tmp_path / "baseline.json"

    def cli(*args):
        return subprocess.run(
            [sys.executable, "-m", "tools.rtlint", *args],
            cwd=REPO, capture_output=True, text=True, timeout=120)

    f.write_text(one)
    proc = cli(str(f), "--update-baseline", "--baseline", str(baseline))
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "refusing to grow" in proc.stderr
    assert not baseline.exists()

    proc = cli(str(f), "--update-baseline", "--baseline", str(baseline),
               "--allow-growth")
    assert proc.returncode == 0, proc.stderr
    assert len(json.loads(baseline.read_text())["findings"]) == 1

    # Growing an EXISTING baseline refuses the same way...
    f.write_text(two)
    proc = cli(str(f), "--update-baseline", "--baseline", str(baseline))
    assert proc.returncode == 2 and "refusing" in proc.stderr
    assert len(json.loads(baseline.read_text())["findings"]) == 1
    # ...while shrinking (the burn-down direction) never needs a flag.
    f.write_text("def loop(work):\n    return work()\n")
    proc = cli(str(f), "--update-baseline", "--baseline", str(baseline))
    assert proc.returncode == 0, proc.stderr
    assert json.loads(baseline.read_text())["findings"] == []


def test_ci_gate_rtflow_rules_clean_on_ray_tpu():
    """The tier-1 budget/contract gate, rule-filtered: even under
    --rules RT109,RT110,RT111 the engine tree must be clean — every
    factory entrypoint declares its budget, every contract edge holds,
    every sync point is justified."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.rtlint", "ray_tpu/", "--check",
         "--rules", "RT109,RT110,RT111"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"


def test_engine_declared_budget_matches_actual_nano():
    """The declared budgets in serve/engine.py are the engine's REAL
    compiled-program count (ISSUE 15 satellite): exercise every prompt
    bucket plus a full handoff round-trip on nano CPU and compare the
    jit cache growth against the parsed program-budget declarations."""
    import jax
    import numpy as np

    from ray_tpu.models import gpt
    from ray_tpu.models import gpt_decode as gd
    from ray_tpu.serve.engine import DecodeEngine
    from tools.rtlint import declared_budgets, parse_budget
    from tools.rtlint.core import Module

    cfg = gpt.CONFIGS["nano"]
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    buckets = (8, 16)
    eng = DecodeEngine(params, cfg, slots=3, chunk=4, max_len=40,
                       prompt_buckets=buckets, eos_token=-1)
    try:
        wrappers = {"_prefill": eng._prefill, "_step": eng._step,
                    "_export": eng._export, "_import": eng._import}
        pre = {k: w._cache_size() for k, w in wrappers.items()}
        rng = np.random.default_rng(3)
        # warm_up() runs the whole set once: each bucket's program for
        # one prompt, each PAIR of buckets' for the two prompts one
        # chunk boundary admits (widest first: n (n + 1) / 2 of them),
        # and the chunk program; then every bucket decodes...
        assert set(eng.warm_up()["programs"]) == {
            "prefill_8", "prefill_16", "prefill_8+8", "prefill_16+8",
            "prefill_16+16", "chunk"}
        for n in (5, 8, 11, 16):
            prompt = rng.integers(0, cfg.vocab_size, (n,)).astype(
                np.int32)
            assert len(list(eng.stream(prompt, 6))) >= 1
        # ...and the handoff path exports AND imports.
        prompt = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)
        desc = eng.handoff(prompt, max_new=5)
        out = np.concatenate(list(eng.stream(prompt, 5)))
        resumed = eng.admit_prefilled(desc)
        from ray_tpu.serve.batching import _EngineStream
        got = np.concatenate(list(_EngineStream(resumed)))
        assert np.array_equal(out, got)
        actual = sum(w._cache_size() - pre[k]
                     for k, w in wrappers.items())

        src = open(os.path.join(REPO, "ray_tpu", "serve",
                                "engine.py")).read()
        mod = Module("engine.py", "serve/engine.py", src)
        decls = declared_budgets(mod)
        declared = parse_budget(decls["DecodeEngine._build_pool"][1])
        env = {"len(prompt_buckets)": len(buckets)}
        # the declaration bounds the pairs by n * n (the grammar has no
        # division); what is built is n (n + 1) / 2 of them
        n = len(buckets)
        assert declared.evaluate(env) == n * n + n + 3
        assert actual == n * (n + 1) // 2 + n + 3 <= declared.evaluate(env)
        # The verify budget is declared separately (spec engines).
        assert parse_budget(
            decls["DecodeEngine._bind_verify"][1]).evaluate(env) == 1
        # And the factory-level declarations in the frame, where the
        # two factories every model binds are defined, parse and cover
        # the factories' per-site bounds.
        gsrc = open(os.path.join(REPO, "ray_tpu", "models",
                                 "serving.py")).read()
        gdecls = declared_budgets(
            Module("serving.py", "models/serving.py", gsrc))
        assert parse_budget(gdecls["jit_prefill_into_slot_paged"][1]
                            ).evaluate(env) == n * n + n
        assert parse_budget(gdecls["jit_decode_chunk_slots_paged"][1]
                            ).evaluate(env) == 1
    finally:
        eng.shutdown()
