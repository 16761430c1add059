"""rtflow call graph: a project-wide, AST-derived call graph over the
analyzed file set (ISSUE 15).

rtlint's per-module rules stop at function boundaries — a ``holds=``
contract, a driver-ownership annotation, or a config-derived value
evaporates the moment it crosses a call. This module builds the graph
those checks propagate over. Resolution is *lexical*, like every other
rtlint analysis, and resolves exactly the idioms this repo uses:

- **module functions**: bare-name calls to defs in the same module, and
  through ``from x import f`` / ``import x as m`` → ``m.f(...)``
  (relative imports resolved against the module's own dotted path; only
  modules inside the analyzed set resolve);
- **methods through self**: ``self.m(...)`` against the enclosing class
  and its bases (bases matched by terminal name across the analyzed
  set, first definition wins — the same convention RT105 uses);
- **module aliases on self**: ``self._gd.f(...)`` where some method
  assigned ``self._gd = <imported module>`` (the drafter's
  ``self._gd = gpt_decode`` idiom), and ``self._model.f(...)`` where
  it assigned ``self._model = <x>.decode_programs(cfg)``: the served
  model's DESCRIPTION (``models/serving.py``), which every model
  answers with a module of the same factory names and budgets, so the
  call resolves against the first description of the analyzed set
  (:func:`is_description`: found by rule, not listed), and a name the
  description only BINDS (``jit_x = serving.bind(serving.jit_x, ...)``)
  against the frame's def of it (:data:`FRAME_MODULE`);
- **constructors**: ``Cls(...)`` → ``Cls.__init__``;
- **driver registration**: ``threading.Thread(target=self._run)`` (and
  any ``*Thread(target=...)``) becomes an edge of ``kind="thread"`` —
  the repo's driver-thread registration idiom, which RT110 treats as
  the legitimate entry into ``owner=driver`` code.

Every edge records the **lock context** at the call site: the
``self.<lock>`` attributes (names matching ``lock|cond|mutex``) whose
``with`` blocks lexically enclose the call, plus the caller's own
``holds=`` contract and any lock it manually ``.acquire()``s — the
exact leniencies RT101 already grants, made transitive.

What does NOT resolve (and is deliberately skipped, never guessed):
calls through arbitrary objects (``self._drafter.propose(...)`` where
``_drafter``'s type is a runtime choice), calls through containers, and
anything behind ``getattr``. Unresolved calls produce no edges; rules
built on this graph check only what resolved, so precision errs toward
false negatives, not noise.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

#: LOCKISH_RE is the shared lock-naming convention (RT101's) — one
#: definition in annotations so rtflow and rtsan can never disagree.
from .annotations import LOCKISH_RE
from .core import Module


#: The serving frame: where the factories every description binds are
#: DEFINED, with their ``program-budget`` declarations.
FRAME_MODULE = "models/serving.py"


def is_description(mod: Module) -> bool:
    """THE rule, stated once: a module of ``ray_tpu/models`` that
    defines ``cache_spec`` is a model DESCRIPTION, what a config
    object's ``decode_programs()`` may answer with
    (``models/serving.py``). rtflow's alias resolution and budget scope
    and rtsan's dispatch wrap all read it, so a new model is seen by
    all three the day its module exists."""
    return os.path.basename(os.path.dirname(mod.relpath)) == "models" \
        and any(isinstance(n, ast.FunctionDef) and n.name == "cache_spec"
                for n in mod.tree.body)


def description_names(models_dir: str) -> List[str]:
    """Module names of the descriptions among ``models_dir/*.py``
    (:func:`is_description`), sorted: for a caller that imports them
    (rtsan) instead of analyzing them."""
    out = []
    for fn in sorted(os.listdir(models_dir)):
        path = os.path.join(models_dir, fn)
        if fn.endswith(".py") and os.path.isfile(path):
            with open(path) as f:
                mod = Module(path, f"models/{fn}", f.read())
            if is_description(mod):
                out.append(fn[:-3])
    return out


def self_attr(node) -> Optional[str]:
    """``self.X`` -> ``'X'`` (else None)."""
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


def terminal_name(func) -> Optional[str]:
    """Rightmost name of a call target: ``a.b.c(...)`` -> ``'c'``."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


@dataclass
class FuncNode:
    """One function/method in the analyzed set."""

    key: str                      # "<relpath>::<Qual.name>"
    mod: Module
    node: ast.AST                 # FunctionDef / AsyncFunctionDef
    cls: Optional[str]            # enclosing class qualname, or None
    name: str
    directives: Dict[str, str] = field(default_factory=dict)

    @property
    def qualname(self) -> str:
        return f"{self.cls}.{self.name}" if self.cls else self.name


@dataclass
class ClassNode:
    key: str                      # "<relpath>::<Qual>"
    mod: Module
    node: ast.ClassDef
    bases: Tuple[str, ...]        # terminal base names
    methods: Dict[str, str] = field(default_factory=dict)  # name -> fkey
    #: self.<attr> = <value> assignment sites: attr -> [(fkey, value)]
    attr_assigns: Dict[str, List[Tuple[str, ast.AST]]] = \
        field(default_factory=dict)
    #: self.<attr> = <imported module> aliases: attr -> module relpath
    module_aliases: Dict[str, str] = field(default_factory=dict)


@dataclass
class CallEdge:
    """One resolved call site. ``locks`` is the caller-side lock
    context: lexical ``with self.<lock>`` blocks enclosing the site,
    the caller's own ``holds=``, and locks the caller manually
    acquires anywhere in its body (RT101's leniency, transitive)."""

    caller: Optional[str]         # FuncNode key; None = module level
    callee: str                   # FuncNode key
    mod: Module                   # the CALLER's module (finding anchor)
    line: int
    call: ast.Call
    locks: frozenset = frozenset()
    kind: str = "call"            # "call" | "thread"


def _dotted(relpath: str) -> str:
    """``ray_tpu/serve/engine.py`` -> ``ray_tpu.serve.engine`` (and
    ``pkg/__init__.py`` -> ``pkg``)."""
    p = relpath[:-3] if relpath.endswith(".py") else relpath
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


class CallGraph:
    """Build with :meth:`build`; query via the indexes below."""

    def __init__(self):
        self.funcs: Dict[str, FuncNode] = {}
        self.classes: Dict[str, ClassNode] = {}     # by key
        self.class_by_name: Dict[str, ClassNode] = {}  # terminal, 1st wins
        self.edges: List[CallEdge] = []
        self.edges_to: Dict[str, List[CallEdge]] = {}
        self.edges_from: Dict[str, List[CallEdge]] = {}
        #: module relpath -> {local name -> ("mod", relpath) |
        #:                    ("obj", relpath, objname)}
        self.imports: Dict[str, Dict[str, Tuple]] = {}
        self._by_dotted: Dict[str, str] = {}        # dotted -> relpath
        #: relpaths of the model descriptions in the set, sorted, and
        #: of the frame whose factories they bind (None: not in the set)
        self.descriptions: List[str] = []
        self.frame: Optional[str] = None

    # ------------------------------------------------------------- build
    @classmethod
    def build(cls, mods: Sequence[Module]) -> "CallGraph":
        g = cls()
        for m in mods:
            g._by_dotted[_dotted(m.relpath)] = m.relpath
        g.descriptions = sorted(m.relpath for m in mods
                                if is_description(m))
        g.frame = next((m.relpath for m in mods
                        if m.relpath.endswith(FRAME_MODULE)), None)
        for m in mods:
            g._index_module(m)
        for m in mods:
            g._collect_imports(m)
        for m in mods:
            g._collect_aliases(m)
        for m in mods:
            g._collect_edges(m)
        for e in g.edges:
            g.edges_to.setdefault(e.callee, []).append(e)
            if e.caller:
                g.edges_from.setdefault(e.caller, []).append(e)
        return g

    def _index_module(self, mod: Module):
        def rec(node, cls_path: Optional[str], cnode: Optional[ClassNode]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    qual = (f"{cls_path}.{child.name}" if cls_path
                            else child.name)
                    ck = f"{mod.relpath}::{qual}"
                    cn = ClassNode(
                        key=ck, mod=mod, node=child,
                        bases=tuple(b for b in
                                    (terminal_name(x) for x in child.bases)
                                    if b))
                    self.classes[ck] = cn
                    self.class_by_name.setdefault(child.name, cn)
                    rec(child, qual, cn)
                    continue
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    qual = (f"{cls_path}.{child.name}" if cls_path
                            else child.name)
                    fk = f"{mod.relpath}::{qual}"
                    fn = FuncNode(key=fk, mod=mod, node=child,
                                  cls=cls_path, name=child.name,
                                  directives=mod.func_directives(child))
                    # A nested def shadowing its enclosing method's
                    # name keeps the method (indexed first) as the key.
                    self.funcs.setdefault(fk, fn)
                    if cnode is not None:
                        cnode.methods.setdefault(child.name, fk)
                        self._collect_attr_assigns(cnode, fk, child)
                    # Nested defs keep the class path (same convention
                    # as the annotations loader).
                    rec(child, cls_path, cnode)
                    continue
                rec(child, cls_path, cnode)

        rec(mod.tree, None, None)

    @staticmethod
    def _collect_attr_assigns(cnode: ClassNode, fkey: str, method):
        for node in ast.walk(method):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            value = node.value
            if value is None:
                continue
            for t in targets:
                a = self_attr(t)
                if a:
                    cnode.attr_assigns.setdefault(a, []).append(
                        (fkey, value))

    def _collect_imports(self, mod: Module):
        table: Dict[str, Tuple] = {}
        own_pkg = _dotted(mod.relpath).rsplit(".", 1)[0] \
            if "." in _dotted(mod.relpath) else ""
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    rel = self._by_dotted.get(a.name)
                    if rel and (a.asname or "." not in a.name):
                        # Without an alias, "import a.b" binds "a", not
                        # "a.b" — only top-level imports resolve bare.
                        table[a.asname or a.name] = ("mod", rel)
            elif isinstance(node, ast.ImportFrom):
                base = node.module or ""
                if node.level:
                    parts = own_pkg.split(".") if own_pkg else []
                    parts = parts[: len(parts) - (node.level - 1)]
                    base = ".".join(parts + ([node.module]
                                             if node.module else []))
                for a in node.names:
                    # "from m import x": x is a submodule OR an object.
                    sub = self._by_dotted.get(f"{base}.{a.name}"
                                              if base else a.name)
                    if sub:
                        table[a.asname or a.name] = ("mod", sub)
                        continue
                    rel = self._by_dotted.get(base)
                    if rel:
                        table[a.asname or a.name] = ("obj", rel, a.name)
        self.imports[mod.relpath] = table

    def _collect_aliases(self, mod: Module):
        """``self.X = <imported module>`` assignments (the engine's
        ``self._gd = gpt_decode``): X becomes a module alias for
        ``self.X.f(...)`` resolution."""
        table = self.imports.get(mod.relpath, {})
        for cn in self.classes.values():
            if cn.mod is not mod:
                continue
            for attr, sites in cn.attr_assigns.items():
                for _fk, value in sites:
                    if isinstance(value, ast.Name):
                        ent = table.get(value.id)
                        if ent and ent[0] == "mod":
                            cn.module_aliases[attr] = ent[1]
                    elif isinstance(value, ast.Call) and terminal_name(
                            value.func) == "decode_programs" and \
                            self.descriptions:
                        cn.module_aliases[attr] = self.descriptions[0]

    # --------------------------------------------------------- resolution
    def _module_func(self, relpath: str, name: str) -> Optional[str]:
        key = f"{relpath}::{name}"
        if key in self.funcs:
            return key
        if relpath in self.descriptions and \
                f"{self.frame}::{name}" in self.funcs:
            # a description binds the frame's factories under its own
            # names: the def (and its budget) is the frame's
            return f"{self.frame}::{name}"
        ck = f"{relpath}::{name}"
        cn = self.classes.get(ck)
        if cn is not None:
            return cn.methods.get("__init__")
        return None

    def method_of(self, cnode: Optional[ClassNode],
                  name: str, _seen=None) -> Optional[str]:
        """Method lookup through the class and its bases (terminal-name
        matched across the analyzed set)."""
        if cnode is None:
            return None
        _seen = _seen or set()
        if cnode.key in _seen:
            return None
        _seen.add(cnode.key)
        got = cnode.methods.get(name)
        if got:
            return got
        for b in cnode.bases:
            got = self.method_of(self.class_by_name.get(b), name, _seen)
            if got:
                return got
        return None

    def resolve_call(self, mod: Module, cnode: Optional[ClassNode],
                     call: ast.Call) -> Optional[str]:
        f = call.func
        table = self.imports.get(mod.relpath, {})
        if isinstance(f, ast.Name):
            got = self._module_func(mod.relpath, f.id)
            if got:
                return got
            ent = table.get(f.id)
            if ent and ent[0] == "obj":
                return self._module_func(ent[1], ent[2])
            if ent and ent[0] == "mod":
                return None
            cn = self.class_by_name.get(f.id)
            if cn is not None and f.id[:1].isupper():
                return cn.methods.get("__init__")
            return None
        if isinstance(f, ast.Attribute):
            a = self_attr(f.value)
            if a is not None and cnode is not None:
                alias = cnode.module_aliases.get(a)
                if alias:
                    return self._module_func(alias, f.attr)
                return None
            a = self_attr(f)
            if a is not None:
                return self.method_of(cnode, a)
            if isinstance(f.value, ast.Name):
                ent = table.get(f.value.id)
                if ent and ent[0] == "mod":
                    return self._module_func(ent[1], f.attr)
        return None

    # ------------------------------------------------------ edge collection
    @staticmethod
    def _acquired_locks(fn) -> frozenset:
        out = set()
        for w in ast.walk(fn):
            if isinstance(w, ast.Call) and \
                    isinstance(w.func, ast.Attribute) and \
                    w.func.attr == "acquire":
                a = self_attr(w.func.value)
                if a and LOCKISH_RE.search(a):
                    out.add(a)
        return frozenset(out)

    def _collect_edges(self, mod: Module):
        def visit(node, caller: Optional[FuncNode],
                  cnode: Optional[ClassNode], cls_qual: Optional[str],
                  locks: frozenset):
            if isinstance(node, ast.ClassDef):
                qual = f"{cls_qual}.{node.name}" if cls_qual \
                    else node.name
                cn = self.classes.get(f"{mod.relpath}::{qual}")
                for c in ast.iter_child_nodes(node):
                    visit(c, None, cn, qual, frozenset())
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = (f"{cls_qual}.{node.name}" if cls_qual
                        else node.name)
                fn = self.funcs.get(f"{mod.relpath}::{qual}")
                base = frozenset(
                    h.strip() for h in
                    (fn.directives.get("holds", "") if fn else ""
                     ).split(",") if h.strip()) \
                    | self._acquired_locks(node)
                for c in ast.iter_child_nodes(node):
                    visit(c, fn or caller, cnode, cls_qual,
                          frozenset(base))
                return
            if isinstance(node, ast.With):
                held = set(locks)
                for item in node.items:
                    a = self_attr(item.context_expr)
                    if a and LOCKISH_RE.search(a):
                        held.add(a)
                    visit(item.context_expr, caller, cnode, cls_qual,
                          locks)
                for c in node.body:
                    visit(c, caller, cnode, cls_qual, frozenset(held))
                return
            if isinstance(node, ast.Call):
                self._edge_for_call(mod, caller, cnode, node, locks)
            for c in ast.iter_child_nodes(node):
                visit(c, caller, cnode, cls_qual, locks)

        for top in ast.iter_child_nodes(mod.tree):
            visit(top, None, None, None, frozenset())

    def _edge_for_call(self, mod: Module, caller: Optional[FuncNode],
                       cnode: Optional[ClassNode], call: ast.Call,
                       locks: frozenset):
        tname = terminal_name(call.func)
        if tname and tname.endswith("Thread"):
            for kw in call.keywords:
                if kw.arg != "target":
                    continue
                target = None
                a = self_attr(kw.value)
                if a is not None:
                    target = self.method_of(cnode, a)
                elif isinstance(kw.value, ast.Name):
                    target = self._module_func(mod.relpath, kw.value.id)
                if target:
                    self.edges.append(CallEdge(
                        caller=caller.key if caller else None,
                        callee=target, mod=mod, line=call.lineno,
                        call=call, locks=locks, kind="thread"))
            return
        callee = self.resolve_call(mod, cnode, call)
        if callee:
            self.edges.append(CallEdge(
                caller=caller.key if caller else None, callee=callee,
                mod=mod, line=call.lineno, call=call, locks=locks))
