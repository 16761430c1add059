"""rtflow: interprocedural dataflow over the rtlint call graph, and the
three rules built on it (ISSUE 15 tentpole).

Every engine PR since the continuous-batching engine has hand-audited
one invariant — the compiled-program set stays bounded
(``len(prompt_buckets) + 1`` base, ``+1`` spec-decode verify, ``+2``
KV-handoff export/import) — because one stray request-varying Python
value reaching a jit trace key silently multiplies XLA compiles. RT103
checks the hazard intra-procedurally; the contracts evaporate at the
first helper boundary. rtflow makes three of them machine-checked
project-wide:

RT109  **static compiled-program-budget audit.** Factory entrypoints
       declare ``# rtlint: program-budget: <expr>``; rtflow computes an
       upper bound on the distinct trace keys reachable from all call
       sites and fails when the bound exceeds the declaration or is
       unbounded (a request-varying value reaches a static factory
       argument or a dispatch-time array shape).
RT110  **interprocedural lock/driver contracts.** ``holds=`` /
       ``owner=driver`` annotations are checked at every resolved call
       EDGE: a ``holds=L`` method entered on an edge that does not hold
       ``L``, a ``*_locked`` method entered with no lock at all, or an
       ``owner=driver`` method called from non-driver code (thread
       registration and ``entry=driver`` excepted) — the static twin of
       rtsan's RS102/RS103, one hop earlier.
RT111  **host-device sync points.** In the driver-dispatch files, every
       synchronizing use of a dispatch result (``np.asarray`` /
       ``np.array`` / ``.item()`` / implicit ``bool()`` on a value that
       came out of a bound jit program — tracked through helper calls —
       plus ``jax.device_get`` / ``.block_until_ready()`` anywhere)
       must carry a ``# rtlint: sync-ok=<tag> <why>`` justification, so
       the complete sync-point inventory of the dispatch loop is
       explicit and a stray ``.item()`` fails the gate.

The cardinality lattice
-----------------------

Values are classified by how many DISTINCT runtime values they can
take, as a symbolic linear expression over ``len(<collection>)`` atoms:

- config default — ``1``: literals, function parameters with no
  analyzed caller (a deployment fixes them once), ``self.<attr>``
  unless some assignment taints it. The budget is per engine INSTANCE,
  so per-instance-fixed values cost one trace key.
- bounded — ``len(X)``: an element of a collection whose terminal name
  matches ``buckets`` (``self.prompt_buckets``, the repo's compile-
  shape discipline) or the mesh-shape discipline (``tps``/``meshes``,
  ISSUE 20), extracted via ``for``/``next(...)``/subscript. ``len(X)``
  of such a collection is itself a config scalar (``1``).
- unbounded: ``len(...)``, ``.shape``, ``.size`` of anything else —
  one compiled program per distinct value — and anything arithmetic
  derives from one.

Cardinalities propagate through assignments, arithmetic (``|A·B|``
bounds; a product of two symbolic factors distributes into product
atoms — ``len(buckets)·len(tps)`` keys, the mesh-keyed factory-table
bound — never collapsing to unbounded), returned values,
and function parameters (a small fixpoint over the call graph), so
``len(prompt)`` laundered through a helper still arrives unbounded at
the trace key — the blind spot RT103 cannot see. Array SHAPES propagate
separately: ``np.zeros((1, bucket))`` is an array whose trace-key
multiplicity is ``card(bucket)``; dispatching it through a bound
program multiplies that binding's program count.

Deliberate approximations (all err toward the config default, so
precision failures are false NEGATIVES — rtflow never guesses a value
is request-varying): attribute reads off unknown objects use a
project-wide per-field-name summary (every ``x.f = v`` and
``Ctor(f=v)`` joined); branch-exclusive rebinds of one ``self.<attr>``
join by max (one engine takes one config branch); arrays not built by a
recognized constructor (``zeros``/``ones``/``full``/``empty``/
``reshape``) have shape multiplicity 1.

Budget grammar: integers, ``len(<name>)`` atoms, ``+``, and products
of the above — ``int * len(<name>)`` or ``len(<a>) * len(<b>)`` (a
per-mesh-shape budget: ``len(prompt_buckets) * len(tps)``) — e.g.
``len(prompt_buckets) + 3``. For a
BINDING method (one that assigns ``self.X = <factory>(...)``) the
declaration bounds the method's total across everything it binds; for
a factory DEF it bounds the programs any single call site can create.
Comparisons assume every atom is >= 1 (an engine has at least one
prompt bucket).
"""
from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .callgraph import (FRAME_MODULE, CallGraph, ClassNode, FuncNode,
                        is_description, self_attr, terminal_name)
from .core import Finding, Module, ProjectRule

#: Collections whose elements are compile-shape knobs: the repo's
#: bucket discipline (prompt_buckets, default_buckets, ...) plus the
#: mesh-shape discipline (ISSUE 20: ``tps`` / ``meshes`` collections —
#: a sharded factory keyed by (bucket, tp) compiles one program per
#: element of each, never per request).
BUCKETS_RE = re.compile(r"(buckets|tps|meshes)$")

#: Files under the compiled-program-budget discipline: factory defs and
#: binding methods here MUST declare budgets (RT109), and dispatch
#: results here are sync-audited (RT111, minus the models, whose host
#: loops are the library surface, not the engine driver). The frame,
#: where the two factories every model has are defined, and beside
#: these every model DESCRIPTION, found by rule (:func:`in_budget_scope`).
BUDGET_SCOPE = (FRAME_MODULE, "serve/engine.py", "serve/draft.py",
                "serve/handoff.py", "data/llm.py")
SYNC_SCOPE = ("serve/engine.py", "serve/draft.py", "serve/handoff.py",
              "data/llm.py")



def in_budget_scope(mod: Module) -> bool:
    """:data:`BUDGET_SCOPE`, or a model description
    (:func:`~tools.rtlint.callgraph.is_description`: never a list)."""
    return mod.relpath.endswith(BUDGET_SCOPE) or is_description(mod)


#: Array constructors whose first argument is the shape.
_SHAPE_CTORS = ("zeros", "ones", "full", "empty")
#: Host-converting calls that synchronize on a device value.
_SYNC_CONVERTERS = ("asarray", "array")
#: Pure-ish passthroughs: card of result = product of arg cards.
_PASSTHROUGH = ("int", "float", "bool", "abs", "round", "min", "max",
                "sorted", "tuple", "list", "set", "frozenset", "str",
                "int32", "int64", "float32", "uint32", "asarray",
                "array")

_FIXPOINT_ROUNDS = 4


# ------------------------------------------------------------------ Card
def _compose_atoms(a: str, b: str) -> str:
    """Product-atom name: the sorted ``*``-join of both factor lists
    (``"" `` is the constant term and contributes no factor), so
    ``len(x)*len(y)`` names one atom regardless of operand order or
    association."""
    if not a:
        return b
    if not b:
        return a
    return "*".join(sorted(a.split("*") + b.split("*")))


class Card:
    """A symbolic upper bound on distinct values: ``terms`` maps atom
    name -> coefficient, with the constant under ``""``; ``terms is
    None`` means unbounded. Immutable."""

    __slots__ = ("terms",)

    def __init__(self, terms: Optional[Dict[str, int]]):
        self.terms = None if terms is None else dict(terms)

    @staticmethod
    def const(n: int = 1) -> "Card":
        return Card({"": int(n)})

    @staticmethod
    def atom(name: str) -> "Card":
        return Card({name: 1})

    @staticmethod
    def unbounded() -> "Card":
        return Card(None)

    @property
    def is_unbounded(self) -> bool:
        return self.terms is None

    def _const_only(self) -> Optional[int]:
        if self.terms is None:
            return None
        if all(k == "" for k in self.terms):
            return self.terms.get("", 0)
        return None

    def add(self, other: "Card") -> "Card":
        if self.is_unbounded or other.is_unbounded:
            return Card.unbounded()
        out = dict(self.terms)
        for k, v in other.terms.items():
            out[k] = out.get(k, 0) + v
        return Card(out)

    def mul(self, other: "Card") -> "Card":
        if self.is_unbounded or other.is_unbounded:
            return Card.unbounded()
        a, b = self._const_only(), other._const_only()
        if a is not None:
            return Card({k: v * max(a, 1) for k, v in other.terms.items()})
        if b is not None:
            return Card({k: v * max(b, 1) for k, v in self.terms.items()})
        # Two symbolic factors: distribute into product atoms (ISSUE 20
        # — a mesh-keyed factory table is len(buckets)*len(tps) programs,
        # a REAL bound, not "give up"). Atom names compose as the sorted
        # "*"-join of their factors so `a*b` and `b*a` meet in leq/join.
        out: Dict[str, int] = {}
        for ka, va in self.terms.items():
            for kb, vb in other.terms.items():
                k = _compose_atoms(ka, kb)
                out[k] = out.get(k, 0) + va * vb
        return Card(out)

    def join(self, other: "Card") -> "Card":
        """Branch join: per-atom max (branch-exclusive configs — one
        instance takes one branch). A unit constant (the ubiquitous
        config default, e.g. a ``next(gen, <default>)`` fallback) is
        absorbed into an atom-bearing side: the default is assumed to
        coincide with one of the bounded values, keeping budgets tight
        (``len(prompt_buckets)``, not ``len(prompt_buckets) + 1``)."""
        if self.is_unbounded or other.is_unbounded:
            return Card.unbounded()
        a, b = self._const_only(), other._const_only()
        if a is not None and a <= 1 and b is None:
            return Card(other.terms)
        if b is not None and b <= 1 and a is None:
            return Card(self.terms)
        out = dict(self.terms)
        for k, v in other.terms.items():
            out[k] = max(out.get(k, 0), v)
        return Card(out)

    def leq(self, declared: "Card") -> bool:
        """``self <= declared`` assuming every atom >= 1."""
        if declared.is_unbounded:
            return True
        if self.is_unbounded:
            return False
        slack = 0
        for k in set(self.terms) | set(declared.terms):
            if k == "":
                continue
            d = declared.terms.get(k, 0) - self.terms.get(k, 0)
            if d < 0:
                return False
            slack += d               # each atom is worth >= 1
        return self.terms.get("", 0) <= declared.terms.get("", 0) + slack

    def render(self) -> str:
        if self.is_unbounded:
            return "unbounded"
        parts = []
        for k in sorted(t for t in self.terms if t and self.terms[t]):
            c = self.terms[k]
            parts.append(k if c == 1 else f"{c}*{k}")
        c0 = self.terms.get("", 0)
        if c0 or not parts:
            parts.append(str(c0))
        return " + ".join(parts)

    def evaluate(self, atoms: Dict[str, int]) -> int:
        """Numeric value given concrete atom sizes (raises KeyError on
        a missing atom; ValueError when unbounded). Product atoms
        (``len(x)*len(y)``) evaluate as the product of their factors."""
        if self.is_unbounded:
            raise ValueError("unbounded budget has no numeric value")

        def val(k: str) -> int:
            out = 1
            for f in k.split("*"):
                out *= atoms[f]
            return out

        return sum(v * (1 if k == "" else val(k))
                   for k, v in self.terms.items())

    def __eq__(self, other):
        return isinstance(other, Card) and self.terms == other.terms

    def __repr__(self):
        return f"Card<{self.render()}>"


def parse_budget(expr: str) -> Card:
    """``len(prompt_buckets) + 3`` -> :class:`Card`. Grammar: integer
    literals, ``len(<name>)`` / ``len(<obj>.<name>)`` atoms, ``+``, and
    products — with an integer, or of two atoms (a mesh-keyed budget:
    ``len(prompt_buckets) * len(tps)``). Raises ValueError on anything
    else."""
    try:
        tree = ast.parse(expr.strip(), mode="eval").body
    except SyntaxError as e:
        raise ValueError(f"unparseable budget expression {expr!r}: "
                         f"{e.msg}") from None

    def ev(node) -> Card:
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and not isinstance(node.value, bool):
            return Card.const(node.value)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
            return ev(node.left).add(ev(node.right))
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult):
            return ev(node.left).mul(ev(node.right))
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Name) and \
                node.func.id == "len" and len(node.args) == 1:
            t = terminal_name(node.args[0])
            if t:
                return Card.atom(f"len({t})")
        raise ValueError(
            f"budget expression {expr!r} must be built from integers, "
            f"len(<name>) atoms, '+', and products ('int * atom' or "
            f"'atom * atom')")

    return ev(tree)


def declared_budgets(mod: Module) -> Dict[str, Tuple[int, str]]:
    """``qualname -> (def lineno, raw budget expr)`` for every function
    in ``mod`` carrying a ``program-budget:`` declaration (the helper
    the budget-vs-actual test reads the engine's contract through)."""
    out: Dict[str, Tuple[int, str]] = {}

    def rec(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                rec(child, f"{prefix}{child.name}.")
            elif isinstance(child, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                d = mod.func_directives(child)
                if "program-budget" in d:
                    out[f"{prefix}{child.name}"] = \
                        (child.lineno, d["program-budget"])
                rec(child, prefix)

    rec(mod.tree, "")
    return out


# ------------------------------------------------------------- analysis
def _is_factory(fn: FuncNode) -> bool:
    """A jit/pjit program factory: named ``jit_*``/``pjit_*``, or a def
    that directly calls ``jax.jit`` / ``pjit``."""
    if fn.name.startswith(("jit_", "pjit_")):
        return True
    for w in ast.walk(fn.node):
        if isinstance(w, ast.Call):
            t = terminal_name(w.func)
            if t in ("jit", "pjit"):
                return True
    return False


def _rt103_visible(arg) -> bool:
    """True when RT103's intra-procedural classifier reports this
    argument (unhashable literal, or len()/.shape/.size directly in
    the expression) — rtflow then stays quiet to keep one finding per
    hazard; RT109 adds only what RT103 cannot see. Callers must ALSO
    check that RT103 covers the call site at all: its classifier is
    name-based (``jit_*`` callees), so a structurally-recognized
    factory's sites are rtflow's to report even when the len() is
    right there in the argument."""
    if isinstance(arg, (ast.List, ast.Set, ast.Dict, ast.ListComp,
                        ast.SetComp, ast.DictComp, ast.GeneratorExp)):
        return True
    for w in ast.walk(arg):
        if isinstance(w, ast.Call) and isinstance(w.func, ast.Name) \
                and w.func.id == "len":
            return True
        if isinstance(w, ast.Attribute) and w.attr in ("shape", "size"):
            return True
    return False


def _bucketish(expr) -> Optional[str]:
    """Terminal name of a bucket-convention collection expression."""
    t = self_attr(expr)
    if t is None and isinstance(expr, ast.Name):
        t = expr.id
    if t is None and isinstance(expr, ast.Attribute):
        t = expr.attr
    if t is not None and BUCKETS_RE.search(t):
        return t
    return None


@dataclass
class _FactoryCallSite:
    factory: str                  # factory FuncNode key
    caller: Optional[str]
    mod: Module
    call: ast.Call
    args_card: Card               # product over static args
    bound_attr: Optional[str]     # self.<attr> the result binds to
    bound_local: Optional[str]    # local name it binds to
    unbounded_arg: Optional[ast.AST]  # first non-RT103-visible offender


@dataclass
class _DispatchSite:
    mod: Module
    call: ast.Call
    caller: Optional[str]
    cls_key: Optional[str]
    attr: Optional[str]           # self.<attr> dispatch
    local: Optional[str]          # local-binding dispatch
    shape_card: Card


class FlowAnalysis:
    """One pass over the analyzed set: call graph + cardinality/device
    fixpoints + the per-site audit tables the rules read."""

    def __init__(self, mods: Sequence[Module]):
        self.mods = list(mods)
        self.graph = CallGraph.build(mods)
        g = self.graph
        self.factories: Dict[str, FuncNode] = {
            k: f for k, f in g.funcs.items() if _is_factory(f)}
        #: class key -> {attr: True} attrs ever bound from a factory
        self.bound_attrs: Dict[str, Set[str]] = {}
        for ck, cn in g.classes.items():
            for attr, sites in cn.attr_assigns.items():
                for _fk, value in sites:
                    if isinstance(value, ast.Call) and \
                            self._factory_of(cn.mod, cn, value):
                        self.bound_attrs.setdefault(ck, set()).add(attr)
        # Fixpoint state.
        self.param_cards: Dict[Tuple[str, str], Card] = {}
        self.ret_cards: Dict[str, Card] = {}
        #: Element-wise cards for functions whose every return is a
        #: tuple literal of one length — tuple-unpacking call sites
        #: read these instead of the (product) whole-value card, which
        #: would compound through fixpoint feedback loops. None marks
        #: incompatible return shapes.
        self.ret_tuple_cards: Dict[str, Optional[List[Card]]] = {}
        self.attr_cards: Dict[Tuple[str, str], Card] = {}
        self.field_cards: Dict[str, Card] = {}
        self.param_taint: Set[Tuple[str, str]] = set()
        self.ret_taint: Set[str] = set()
        # Audit tables (rebuilt on the final round).
        self.factory_sites: List[_FactoryCallSite] = []
        self.dispatch_sites: List[_DispatchSite] = []
        self.sync_sites: List[Tuple[Module, int, str, Optional[str]]] = []
        self._run_fixpoint()

    # ------------------------------------------------------------ plumbing
    def _factory_of(self, mod: Module, cnode: Optional[ClassNode],
                    call: ast.Call) -> Optional[str]:
        key = self.graph.resolve_call(mod, cnode, call)
        if key and key in self.factories:
            return key
        return None

    def _class_of(self, fn: FuncNode) -> Optional[ClassNode]:
        if fn.cls is None:
            return None
        return self.graph.classes.get(f"{fn.mod.relpath}::{fn.cls}")

    def _run_fixpoint(self):
        self._seed_field_cards()
        for rnd in range(_FIXPOINT_ROUNDS):
            final = rnd == _FIXPOINT_ROUNDS - 1
            if final:
                self.factory_sites = []
                self.dispatch_sites = []
                self.sync_sites = []
            changed = False
            for key in sorted(self.graph.funcs):
                fn = self.graph.funcs[key]
                flow = _FuncFlow(self, fn, record=final)
                flow.run()
                changed |= flow.changed
            if not changed and not final:
                # Converged early: one more pass with recording on.
                for key in sorted(self.graph.funcs):
                    _FuncFlow(self, self.graph.funcs[key],
                              record=True).run()
                break

    def _seed_field_cards(self):
        """Project-wide per-field-name summaries from constructor
        keywords (``_EngineRequest(bucket=...)``): the data-carrier
        idiom request state flows through. Non-constructor keyword args
        are excluded (a ``Capitalized`` callee is the convention)."""
        for mod in self.mods:
            for node in ast.walk(mod.tree):
                if not isinstance(node, ast.Call):
                    continue
                t = terminal_name(node.func)
                if not t or not t.lstrip("_")[:1].isupper():
                    continue
                for kw in node.keywords:
                    if kw.arg:
                        self.field_cards[kw.arg] = Card.const(1)
        # Values are joined in during the fixpoint (via _FuncFlow).

    # Fixpoint update helpers (monotone joins; report change).
    def _join_into(self, table, key, card: Card) -> bool:
        cur = table.get(key)
        new = card if cur is None else cur.join(card)
        if cur is None or new.terms != cur.terms:
            table[key] = new
            return True
        return False


class _FuncFlow:
    """One function's forward pass: evaluates local cardinalities and
    shapes, propagates summaries outward, and (on the recording round)
    emits the audit sites."""

    def __init__(self, an: FlowAnalysis, fn: FuncNode, record: bool):
        self.an = an
        self.fn = fn
        self.record = record
        self.changed = False
        self.cnode = an._class_of(fn)
        self.cls_key = self.cnode.key if self.cnode else None
        self.env: Dict[str, Card] = {}
        self.shapes: Dict[str, Card] = {}
        self.taint: Set[str] = set()
        self.local_factories: Set[str] = set()
        self._recording = False
        args = fn.node.args
        all_args = list(getattr(args, "posonlyargs", [])) + args.args + \
            ([args.vararg] if args.vararg else []) + args.kwonlyargs + \
            ([args.kwarg] if args.kwarg else [])
        for a in all_args:
            if a.arg in ("self", "cls"):
                continue
            self.env[a.arg] = an.param_cards.get((fn.key, a.arg),
                                                 Card.const(1))
            if (fn.key, a.arg) in an.param_taint:
                self.taint.add(a.arg)

    # ------------------------------------------------------------- driving
    def run(self):
        # Two passes over the body approximate loop-carried joins (the
        # lattice is shallow; cards only grow); audit sites are emitted
        # on the SECOND pass only, with the env fully converged.
        self._recording = False
        self._walk_body(self.fn.node.body)
        self._recording = self.record
        self._walk_body(self.fn.node.body)

    def _walk_body(self, stmts):
        for s in stmts:
            self._stmt(s)

    def _stmt(self, node):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return                          # separate flow unit
        if isinstance(node, ast.Assign):
            self._visit_expr(node.value)
            card = self._eval(node.value)
            tainted = self._is_device(node.value)
            shape = self._shape_of(node.value)
            for t in node.targets:
                self._assign(t, node.value, card, tainted, shape)
            self._note_summaries(node.targets, node.value, card)
            return
        if isinstance(node, (ast.AnnAssign, ast.AugAssign)):
            if node.value is not None:
                self._visit_expr(node.value)
                card = self._eval(node.value)
                if isinstance(node, ast.AugAssign) and \
                        isinstance(node.target, ast.Name):
                    card = card.mul(self.env.get(node.target.id,
                                                 Card.const(1)))
                self._assign(node.target, node.value, card,
                             self._is_device(node.value), None)
                self._note_summaries([node.target], node.value, card)
            return
        if isinstance(node, ast.Return):
            if node.value is not None:
                self._visit_expr(node.value)
                self.changed |= self.an._join_into(
                    self.an.ret_cards, self.fn.key,
                    self._eval(node.value))
                self._note_ret_tuple(node.value)
                if self._is_device(node.value):
                    if self.fn.key not in self.an.ret_taint:
                        self.an.ret_taint.add(self.fn.key)
                        self.changed = True
            return
        if isinstance(node, ast.For):
            self._visit_expr(node.iter)
            card = self._element_card(node.iter)
            self._assign(node.target, None, card, False, None)
            self._walk_body(node.body)
            self._walk_body(node.orelse)
            return
        if isinstance(node, (ast.If, ast.While)):
            self._visit_expr(node.test)
            self._check_bool_sync(node.test)
            self._walk_body(node.body)
            self._walk_body(node.orelse)
            return
        if isinstance(node, ast.With):
            for item in node.items:
                self._visit_expr(item.context_expr)
            self._walk_body(node.body)
            return
        if isinstance(node, ast.Try):
            self._walk_body(node.body)
            for h in node.handlers:
                self._walk_body(h.body)
            self._walk_body(node.orelse)
            self._walk_body(node.finalbody)
            return
        if isinstance(node, ast.Expr):
            self._visit_expr(node.value)
            return
        # Everything else: visit any embedded expressions generically.
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._visit_expr(child)
            elif isinstance(child, ast.stmt):
                self._stmt(child)

    def _note_ret_tuple(self, value):
        tbl = self.an.ret_tuple_cards
        if not isinstance(value, ast.Tuple):
            if self.fn.key in tbl and tbl[self.fn.key] is not None:
                tbl[self.fn.key] = None
                self.changed = True
            elif self.fn.key not in tbl:
                tbl[self.fn.key] = None
            return
        cards = [self._eval(e) for e in value.elts]
        cur = tbl.get(self.fn.key)
        if self.fn.key in tbl and (cur is None or len(cur) != len(cards)):
            if cur is not None:
                tbl[self.fn.key] = None
                self.changed = True
            return
        if cur is None:
            tbl[self.fn.key] = cards
            self.changed = True
            return
        out = [a.join(b) for a, b in zip(cur, cards)]
        if any(a.terms != b.terms for a, b in zip(out, cur)):
            tbl[self.fn.key] = out
            self.changed = True

    def _assign(self, target, value, card: Card, tainted: bool,
                shape: Optional[Card]):
        if isinstance(target, (ast.Tuple, ast.List)):
            if isinstance(value, (ast.Tuple, ast.List)) and \
                    len(value.elts) == len(target.elts):
                for t, v in zip(target.elts, value.elts):
                    self._assign(t, v, self._eval(v),
                                 self._is_device(v), self._shape_of(v))
                return
            if isinstance(value, ast.Call):
                callee = self.an.graph.resolve_call(
                    self.fn.mod, self.cnode, value)
                elems = self.an.ret_tuple_cards.get(callee) \
                    if callee else None
                if elems is not None and len(elems) == len(target.elts):
                    for t, c in zip(target.elts, elems):
                        self._assign(t, None, c, tainted, None)
                    return
            for t in target.elts:
                self._assign(t, None, card, tainted, None)
            return
        if isinstance(target, ast.Starred):
            self._assign(target.value, None, card, tainted, None)
            return
        if isinstance(target, ast.Name):
            old = self.env.get(target.id)
            self.env[target.id] = card if old is None else old.join(card)
            if tainted:
                self.taint.add(target.id)
            if shape is not None:
                self.shapes[target.id] = shape
            if isinstance(value, ast.Call) and \
                    self.an._factory_of(self.fn.mod, self.cnode, value):
                self.local_factories.add(target.id)
            elif isinstance(value, ast.Name) and \
                    value.id in self.local_factories:
                self.local_factories.add(target.id)

    def _note_summaries(self, targets, value, card: Card):
        """Feed self-attr and field-name summaries."""
        for t in targets:
            a = self_attr(t)
            if a is not None and self.cls_key:
                self.changed |= self.an._join_into(
                    self.an.attr_cards, (self.cls_key, a), card)
                continue
            if isinstance(t, ast.Attribute):    # x.f = v (field summary)
                self.changed |= self.an._join_into(
                    self.an.field_cards, t.attr, card)

    # --------------------------------------------------------- expressions
    def _visit_expr(self, expr):
        """Walk an expression, producing param-summary updates for
        resolved calls and (on the recording round) audit sites."""
        for node in ast.walk(expr):
            if isinstance(node, ast.Call):
                self._visit_call(node)
            elif isinstance(node, (ast.GeneratorExp, ast.ListComp,
                                   ast.SetComp, ast.DictComp)):
                for gen in node.generators:
                    card = self._element_card(gen.iter)
                    self._assign(gen.target, None, card, False, None)

    def _visit_call(self, call: ast.Call):
        callee = self.an.graph.resolve_call(self.fn.mod, self.cnode, call)
        if callee is not None:
            self._propagate_params(callee, call)
        fkey = callee if callee in self.an.factories else None
        if fkey is not None and self._recording:
            self._note_factory_call(fkey, call)
        if self._recording:
            self._note_dispatch(call)
            self._note_sync(call)
        # Constructor keywords feed the field summaries.
        t = terminal_name(call.func)
        if t and t.lstrip("_")[:1].isupper():
            for kw in call.keywords:
                if kw.arg:
                    self.changed |= self.an._join_into(
                        self.an.field_cards, kw.arg, self._eval(kw.value))

    def _propagate_params(self, callee: str, call: ast.Call):
        cf = self.an.graph.funcs.get(callee)
        if cf is None:
            return
        args = cf.node.args
        names = [a.arg for a in
                 list(getattr(args, "posonlyargs", [])) + args.args]
        if names and names[0] in ("self", "cls"):
            names = names[1:]
        for i, a in enumerate(call.args):
            if isinstance(a, ast.Starred) or i >= len(names):
                break
            self._feed_param(callee, names[i], a)
        for kw in call.keywords:
            if kw.arg:
                self._feed_param(callee, kw.arg, kw.value)

    def _feed_param(self, callee: str, name: str, value):
        self.changed |= self.an._join_into(
            self.an.param_cards, (callee, name), self._eval(value))
        if self._is_device(value) and (callee, name) not in \
                self.an.param_taint:
            self.an.param_taint.add((callee, name))
            self.changed = True

    # ---------------------------------------------------------- audit sites
    def _binding_of(self, call: ast.Call) -> Tuple[Optional[str],
                                                   Optional[str]]:
        """(self_attr, local_name) this call's result is assigned to,
        found via the enclosing statement (best-effort: direct assign)."""
        parent = getattr(call, "_rtflow_parent", None)
        if isinstance(parent, ast.Assign):
            for t in parent.targets:
                a = self_attr(t)
                if a:
                    return a, None
                if isinstance(t, ast.Name):
                    return None, t.id
        return None, None

    def _note_factory_call(self, fkey: str, call: ast.Call):
        cards = []
        offender = None
        # RT103 only classifies jit_*-named call sites; a factory
        # recognized structurally (jax.jit in its body) is invisible
        # to it, so rtflow owns even the argument-local hazards there.
        callee = terminal_name(call.func) or ""
        rt103_site = callee.startswith(("jit_", "pjit_"))
        for a in list(call.args) + [kw.value for kw in call.keywords]:
            c = self._eval(a)
            if c.is_unbounded:
                if offender is None and not (rt103_site
                                             and _rt103_visible(a)):
                    offender = a
                continue             # reported (here or by RT103)
            cards.append(c)
        total = Card.const(1)
        for c in cards:
            total = total.mul(c)
        attr, local = self._binding_of(call)
        self.an.factory_sites.append(_FactoryCallSite(
            factory=fkey, caller=self.fn.key, mod=self.fn.mod, call=call,
            args_card=total, bound_attr=attr, bound_local=local,
            unbounded_arg=offender))

    def _dispatch_target(self, call: ast.Call) -> Tuple[Optional[str],
                                                        Optional[str]]:
        """(attr, local) when this call dispatches a bound program."""
        a = self_attr(call.func)
        if a is not None and self.cls_key and \
                a in self.an.bound_attrs.get(self.cls_key, ()):
            return a, None
        if isinstance(call.func, ast.Name):
            # Local binding: f = jit_x(...); f(...)
            if call.func.id in self.local_factories:
                return None, call.func.id
        if isinstance(call.func, ast.Call):
            inner = self.an._factory_of(self.fn.mod, self.cnode,
                                        call.func)
            if inner:
                return None, "<immediate>"
        return None, None

    def _note_dispatch(self, call: ast.Call):
        attr, local = self._dispatch_target(call)
        if attr is None and local is None:
            return
        mult = Card.const(1)
        for a in call.args:
            mult = mult.mul(self._shape_card(a))
        self.an.dispatch_sites.append(_DispatchSite(
            mod=self.fn.mod, call=call, caller=self.fn.key,
            cls_key=self.cls_key, attr=attr, local=local,
            shape_card=mult))

    def _note_sync(self, call: ast.Call):
        if not self.fn.mod.relpath.endswith(SYNC_SCOPE):
            return
        t = terminal_name(call.func)
        line = call.lineno
        if t in ("device_get", "block_until_ready"):
            self.an.sync_sites.append(
                (self.fn.mod, line, f"{t}(...)", self.fn.qualname))
            return
        if t == "item" and isinstance(call.func, ast.Attribute) and \
                self._is_device(call.func.value):
            self.an.sync_sites.append(
                (self.fn.mod, line, ".item() on a dispatch result",
                 self.fn.qualname))
            return
        if t in _SYNC_CONVERTERS and call.args and \
                self._is_device(call.args[0]):
            self.an.sync_sites.append(
                (self.fn.mod, line,
                 f"np.{t}(...) on a dispatch result", self.fn.qualname))

    def _check_bool_sync(self, test):
        if not (getattr(self, "_recording", False) and
                self.fn.mod.relpath.endswith(SYNC_SCOPE)):
            return
        expr = test
        if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.Not):
            expr = expr.operand
        if isinstance(expr, ast.Name) and expr.id in self.taint:
            self.an.sync_sites.append(
                (self.fn.mod, test.lineno,
                 f"implicit bool() on dispatch result {expr.id!r}",
                 self.fn.qualname))

    # ------------------------------------------------------------- taint
    def _is_device(self, expr) -> bool:
        """Did this value come out of a bound jit program? Tracked
        through locals, tuple unpacking, params, and returns; a host
        conversion (np.asarray/.item()) strips the taint."""
        if isinstance(expr, ast.Name):
            return expr.id in self.taint
        if isinstance(expr, ast.Call):
            attr, local = self._dispatch_target(expr)
            if attr is not None or local is not None:
                return True
            callee = self.an.graph.resolve_call(self.fn.mod, self.cnode,
                                                expr)
            return callee in self.an.ret_taint
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(self._is_device(e) for e in expr.elts)
        if isinstance(expr, ast.Subscript):
            return self._is_device(expr.value)
        return False

    # -------------------------------------------------------------- shapes
    def _shape_of(self, expr) -> Optional[Card]:
        """Shape multiplicity of a recognized array construction."""
        if not isinstance(expr, ast.Call):
            return None
        t = terminal_name(expr.func)
        if t in _SHAPE_CTORS and expr.args:
            return self._dims_card(expr.args[0])
        if t == "reshape" and expr.args:
            dims = expr.args[0] if len(expr.args) == 1 else None
            if dims is not None:
                return self._dims_card(dims)
            out = Card.const(1)
            for a in expr.args:
                out = out.mul(self._eval(a))
            return out
        return None

    def _dims_card(self, dims) -> Card:
        if isinstance(dims, (ast.Tuple, ast.List)):
            out = Card.const(1)
            for d in dims.elts:
                out = out.mul(self._eval(d))
            return out
        return self._eval(dims)

    def _shape_card(self, arg) -> Card:
        if isinstance(arg, ast.Name):
            return self.shapes.get(arg.id, Card.const(1))
        got = self._shape_of(arg)
        return got if got is not None else Card.const(1)

    # --------------------------------------------------------------- cards
    def _eval(self, expr) -> Card:
        if expr is None:
            return Card.const(1)
        if isinstance(expr, ast.Constant):
            return Card.const(1)
        if isinstance(expr, ast.Name):
            return self.env.get(expr.id, Card.const(1))
        if isinstance(expr, ast.Attribute):
            if expr.attr in ("shape", "size"):
                return Card.unbounded()
            a = self_attr(expr)
            if a is not None:
                if self.cls_key:
                    got = self.an.attr_cards.get((self.cls_key, a))
                    if got is not None:
                        return got
                return Card.const(1)
            return self.an.field_cards.get(expr.attr, Card.const(1))
        if isinstance(expr, ast.Subscript):
            b = _bucketish(expr.value)
            if b:
                return Card.atom(f"len({b})")
            return self._eval(expr.value)
        if isinstance(expr, ast.Call):
            return self._eval_call(expr)
        if isinstance(expr, ast.BinOp):
            return self._eval(expr.left).mul(self._eval(expr.right))
        if isinstance(expr, ast.BoolOp):
            out = Card.const(1)
            for v in expr.values:
                out = out.mul(self._eval(v))
            return out
        if isinstance(expr, ast.UnaryOp):
            return self._eval(expr.operand)
        if isinstance(expr, ast.Compare):
            return Card.const(2)
        if isinstance(expr, ast.IfExp):
            return self._eval(expr.body).join(self._eval(expr.orelse))
        if isinstance(expr, (ast.Tuple, ast.List)):
            out = Card.const(1)
            for e in expr.elts:
                out = out.mul(self._eval(e))
            return out
        if isinstance(expr, ast.Starred):
            return self._eval(expr.value)
        return Card.const(1)

    def _eval_call(self, call: ast.Call) -> Card:
        t = terminal_name(call.func)
        if t == "len" and len(call.args) == 1:
            b = _bucketish(call.args[0])
            if b:
                return Card.const(1)     # len of a config tuple: fixed
            return Card.unbounded()
        if t == "next" and call.args:
            card = self._element_card_of_gen(call.args[0])
            if len(call.args) > 1:
                card = card.join(self._eval(call.args[1]))
            return card
        if t == "range":
            out = Card.const(1)
            for a in call.args:
                out = out.mul(self._eval(a))
            return out
        callee = self.an.graph.resolve_call(self.fn.mod, self.cnode, call)
        if callee is not None:
            got = self.an.ret_cards.get(callee)
            if got is not None:
                return got
            return Card.const(1)
        if t in _PASSTHROUGH:
            out = Card.const(1)
            for a in call.args:
                out = out.mul(self._eval(a))
            return out
        return Card.const(1)

    def _element_card_of_gen(self, expr) -> Card:
        if isinstance(expr, ast.GeneratorExp) and expr.generators:
            return self._element_card(expr.generators[0].iter)
        return self._element_card(expr)

    def _element_card(self, it) -> Card:
        b = _bucketish(it)
        if b:
            return Card.atom(f"len({b})")
        if isinstance(it, ast.Call) and terminal_name(it.func) == "range":
            out = Card.const(1)
            for a in it.args:
                out = out.mul(self._eval(a))
            return out
        card = self._eval(it)
        if card.is_unbounded:
            return Card.unbounded()
        return Card.const(1)


# Parent links for _binding_of: set once per module tree.
def _link_parents(mods: Sequence[Module]):
    for mod in mods:
        if getattr(mod, "_rtflow_linked", False):
            continue
        for node in ast.walk(mod.tree):
            for child in ast.iter_child_nodes(node):
                child._rtflow_parent = node
        mod._rtflow_linked = True


_ANALYSIS_CACHE: Dict[tuple, FlowAnalysis] = {}


def get_analysis(mods: Sequence[Module]) -> FlowAnalysis:
    key = tuple(id(m) for m in mods)
    got = _ANALYSIS_CACHE.get(key)
    if got is None:
        _ANALYSIS_CACHE.clear()          # one live analysis at a time
        _link_parents(mods)
        got = FlowAnalysis(mods)
        _ANALYSIS_CACHE[key] = got
    return got


# ----------------------------------------------------------------- RT109
class ProgramBudgetRule(ProjectRule):
    """RT109: static compiled-program-budget audit (see the module
    docstring for the lattice and the grammar). Three checks:

    - a factory def (``jit_*``/``pjit_*`` or direct ``jax.jit``) or a
      method binding one to ``self`` in the budget-scope files without
      a ``# rtlint: program-budget:`` declaration;
    - an UNBOUNDED value reaching a trace key: a request-varying factory
      argument RT103 cannot see at the site (it arrived through a
      helper/variable), or a dispatch of an array whose shape derives
      from one — each compiled program's cache grows per distinct value;
    - a declared budget the computed bound exceeds (binding methods:
      total over everything the method binds, each binding multiplied
      by the worst dispatch-shape multiplicity of its attribute;
      factory defs: the worst single call site).
    """

    id = "RT109"
    summary = "compiled-program budget missing, exceeded, or unbounded"

    def check_project(self, mods: Sequence[Module]) -> Iterable[Finding]:
        an = get_analysis(mods)
        g = an.graph
        budgets: Dict[str, Tuple[FuncNode, Optional[Card], str]] = {}
        for key, fn in sorted(g.funcs.items()):
            raw = fn.directives.get("program-budget")
            if raw is None:
                continue
            try:
                budgets[key] = (fn, parse_budget(raw), raw)
            except ValueError as e:
                budgets[key] = (fn, None, raw)
                yield Finding(
                    fn.mod.relpath, fn.node.lineno, self.id,
                    f"{fn.qualname}: {e}", f"{fn.qualname}.budget_syntax")

        # Binding methods: which functions assign self.<attr> from a
        # factory call (collected from the recorded factory sites).
        binds_by_fn: Dict[str, List[_FactoryCallSite]] = {}
        sites_by_factory: Dict[str, List[_FactoryCallSite]] = {}
        for s in an.factory_sites:
            sites_by_factory.setdefault(s.factory, []).append(s)
            if s.caller:
                binds_by_fn.setdefault(s.caller, []).append(s)

        # Check 1: missing declarations in the budget-scope files.
        for key, fn in sorted(g.funcs.items()):
            if not in_budget_scope(fn.mod):
                continue
            if key in budgets:
                continue
            if key in an.factories:
                yield Finding(
                    fn.mod.relpath, fn.node.lineno, self.id,
                    f"jit factory {fn.qualname} has no "
                    f"'# rtlint: program-budget: <expr>' declaration — "
                    f"every factory entrypoint must state how many "
                    f"compiled programs it can create per call site",
                    f"{fn.qualname}.budget_missing")
                continue
            if any(s.bound_attr for s in binds_by_fn.get(key, ())):
                yield Finding(
                    fn.mod.relpath, fn.node.lineno, self.id,
                    f"{fn.qualname} binds jit programs to self but has "
                    f"no '# rtlint: program-budget: <expr>' declaration "
                    f"— the engine's compiled-program set must be a "
                    f"declared, machine-checked budget",
                    f"{fn.qualname}.budget_missing")

        # Check 2a: unbounded factory arguments (RT103-invisible).
        for s in an.factory_sites:
            if s.unbounded_arg is None:
                continue
            fac = g.funcs[s.factory]
            yield Finding(
                s.mod.relpath, s.unbounded_arg.lineno, self.id,
                f"argument {ast.unparse(s.unbounded_arg)!r} of "
                f"{fac.name}(...) is request-varying (unbounded "
                f"cardinality, established interprocedurally) — every "
                f"distinct value compiles and caches a fresh XLA "
                f"program; thread a bucketed config value instead",
                f"{_caller_qual(g, s.caller)}.{fac.name}.unbounded")

        # Check 2b: unbounded dispatch shapes.
        attr_mult: Dict[Tuple[Optional[str], str], Card] = {}
        local_mult: Dict[Tuple[Optional[str], str], Card] = {}
        for d in an.dispatch_sites:
            if d.shape_card.is_unbounded:
                what = f"self.{d.attr}" if d.attr else "the bound program"
                yield Finding(
                    d.mod.relpath, d.call.lineno, self.id,
                    f"dispatch of {what} with an array whose shape "
                    f"derives from a request-varying value — every "
                    f"distinct shape is a fresh trace key (one compiled "
                    f"program per value); pad to a prompt bucket first",
                    f"{_caller_qual(g, d.caller)}.{what}.unbounded_shape")
                continue
            if d.attr is not None:
                k = (d.cls_key, d.attr)
                attr_mult[k] = attr_mult.get(k, Card.const(1)).join(
                    d.shape_card)
            elif d.local not in (None, "<immediate>"):
                k = (d.caller, d.local)
                local_mult[k] = local_mult.get(k, Card.const(1)).join(
                    d.shape_card)

        # Check 3: computed bound vs declaration.
        for key in sorted(budgets):
            fn, declared, raw = budgets[key]
            if declared is None:
                continue
            if key in an.factories:
                computed = Card.const(0)
                for s in sites_by_factory.get(key, ()):
                    computed = computed.join(
                        self._site_card(s, attr_mult, local_mult, g))
                kind = "worst call site"
            else:
                computed = Card.const(0)
                per_attr: Dict[str, Card] = {}
                for s in binds_by_fn.get(key, ()):
                    c = self._site_card(s, attr_mult, local_mult, g)
                    if s.bound_attr:
                        per_attr[s.bound_attr] = per_attr.get(
                            s.bound_attr, Card.const(0)).join(c)
                    else:
                        computed = computed.add(c)
                for a in sorted(per_attr):
                    computed = computed.add(per_attr[a])
                kind = "total bound programs"
            if not computed.leq(declared):
                yield Finding(
                    fn.mod.relpath, fn.node.lineno, self.id,
                    f"{fn.qualname} declares 'program-budget: {raw}' "
                    f"but rtflow bounds its {kind} at "
                    f"{computed.render()} — raise the declaration only "
                    f"if the extra programs are intended, otherwise "
                    f"find the knob that multiplied the trace keys",
                    f"{fn.qualname}.budget_exceeded")

    @staticmethod
    def _site_card(s: _FactoryCallSite, attr_mult, local_mult,
                   g: CallGraph) -> Card:
        mult = Card.const(1)
        caller = g.funcs.get(s.caller) if s.caller else None
        if s.bound_attr and caller is not None and caller.cls:
            k = (f"{caller.mod.relpath}::{caller.cls}", s.bound_attr)
            mult = attr_mult.get(k, Card.const(1))
        elif s.bound_local:
            mult = local_mult.get((s.caller, s.bound_local),
                                  Card.const(1))
        return s.args_card.mul(mult)


def _caller_qual(g: CallGraph, caller: Optional[str]) -> str:
    fn = g.funcs.get(caller) if caller else None
    return fn.qualname if fn else "<module>"


# ----------------------------------------------------------------- RT110
class InterprocContractRule(ProjectRule):
    """RT110: lock/driver contracts checked at call EDGES — the
    interprocedural completion of RT101/RT102/RT108 and the static twin
    of rtsan's RS102/RS103. For every resolved call:

    - callee annotated ``holds=L``: the edge must hold ``L`` (lexical
      ``with self.L``, caller's own ``holds=``, a manual ``acquire()``
      in the caller, or a ``*_locked`` caller — RT101's leniencies,
      made transitive);
    - callee named ``*_locked``: the edge must hold at least one lock;
    - callee annotated ``owner=driver``: the caller must be driver code
      (``owner=`` / ``entry=driver``), the edge a thread registration
      (``Thread(target=...)``), or the callee itself an ``entry=driver``
      rebinding point. Anything else runs device-owning code off the
      driver thread; suppress with a justification only where ownership
      is deliberately transferred (e.g. failing a confirmed-dead
      driver's lanes)."""

    id = "RT110"
    summary = "holds=/owner= contract broken at a resolved call edge"

    def check_project(self, mods: Sequence[Module]) -> Iterable[Finding]:
        an = get_analysis(mods)
        g = an.graph
        for e in an.graph.edges:
            callee = g.funcs.get(e.callee)
            if callee is None:
                continue
            caller = g.funcs.get(e.caller) if e.caller else None
            cd = caller.directives if caller else {}
            caller_qual = caller.qualname if caller else "<module>"
            caller_locked = bool(caller and
                                 caller.name.endswith("_locked"))
            holds = tuple(h.strip() for h in
                          callee.directives.get("holds", "").split(",")
                          if h.strip())
            for lock in holds:
                if lock in e.locks or caller_locked:
                    continue
                yield Finding(
                    e.mod.relpath, e.line, self.id,
                    f"{caller_qual} calls {callee.qualname} without "
                    f"self.{lock} held — the callee's 'holds={lock}' "
                    f"contract promises every caller locks first "
                    f"(rtsan raises RS102 for this at runtime)",
                    f"{caller_qual}->{callee.qualname}.holds.{lock}")
            if callee.cls and callee.name.endswith("_locked") \
                    and not holds and e.kind == "call":
                if not e.locks and not caller_locked:
                    yield Finding(
                        e.mod.relpath, e.line, self.id,
                        f"{caller_qual} calls {callee.qualname} with no "
                        f"lock held — the *_locked naming convention "
                        f"promises callers hold the guarding lock",
                        f"{caller_qual}->{callee.qualname}.locked")
            if callee.directives.get("owner") == "driver":
                if e.kind == "thread":
                    continue
                if callee.directives.get("entry") == "driver":
                    continue         # the call itself (re)binds the owner
                if cd.get("owner") == "driver" or \
                        cd.get("entry") == "driver":
                    continue
                yield Finding(
                    e.mod.relpath, e.line, self.id,
                    f"{caller_qual} calls {callee.qualname}, which is "
                    f"'owner=driver', from non-driver code — only the "
                    f"driver thread may run it (rtsan raises RS103 at "
                    f"runtime); annotate the caller, register a thread "
                    f"entry, or suppress with the ownership-transfer "
                    f"justification",
                    f"{caller_qual}->{callee.qualname}.owner")


# ----------------------------------------------------------------- RT111
class SyncPointRule(ProjectRule):
    """RT111: every host-device sync point reachable in the driver
    dispatch path must be JUSTIFIED — ``# rtlint: sync-ok=<tag> <why>``
    on the line (or the line above), or a ``disable=RT111`` suppression.
    Dispatch results are tracked through locals, tuple unpacking,
    helper parameters, and returns (the interprocedural part RT102's
    lexical scope cannot see), so the justified sites ARE the complete
    sync inventory of the dispatch loop: a new stray ``.item()`` or
    ``np.asarray`` on a device value — each one a device-queue stall —
    fails the gate instead of quietly riding a PR. ``jax.device_get``
    and ``.block_until_ready()`` are flagged unconditionally."""

    id = "RT111"
    summary = "unjustified host-device sync point in the dispatch path"

    def check_project(self, mods: Sequence[Module]) -> Iterable[Finding]:
        an = get_analysis(mods)
        seen = set()
        for mod, line, what, qual in an.sync_sites:
            key = (mod.relpath, line, what)
            if key in seen:
                continue
            seen.add(key)
            if "sync-ok" in mod.line_directives(line):
                continue
            yield Finding(
                mod.relpath, line, self.id,
                f"{what} in {qual} synchronizes the host with the "
                f"device inside the driver dispatch path; if the sync "
                f"is deliberate (chunk-boundary transfer, TTFT token), "
                f"annotate it '# rtlint: sync-ok=<tag> <why>' — "
                f"otherwise hoist it out of the loop",
                f"{qual}.sync.{what.split('(')[0].strip('.')}")
