"""Partition-rule based parameter sharding (GSPMD-style).

The reference delegates sharded data parallelism to torch FSDP
(``python/ray/train/train_loop_utils.py:175`` ``parallel_strategy="fsdp"``);
on TPU the same capability is native to XLA: annotate every parameter with a
``NamedSharding`` and the compiler moves the data. These helpers map pytree
paths → ``PartitionSpec`` via ordered regex rules (the t5x-style approach,
rebuilt fresh).

The rules say where a parameter is STORED. Where it is USED is the model's
to say: the partitioner keeps a weight where it lies and moves activations
instead whenever that looks cheaper to it, so a step that wants the ZeRO-3
schedule (gather a layer's weights where the layer runs, reduce-scatter
its gradient) asks for the gather by name
(``models/gpt.py _gather_layer``). :func:`compiled_collectives` reads what
the compiler made of both.
"""
from __future__ import annotations

import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

# A rule's tuple names the mesh axes of the leaf's dimensions from dimension
# 0. A tuple that starts with ``...`` describes the leaf's LAST dimensions,
# a matrix's own: whatever dimensions lie in front of them (the layer
# dimension of a stack that ``lax.scan`` slices) are never sharded.
PartitionRule = Tuple[str, Tuple[Any, ...]]


def path_str(path) -> str:
    """Render a jax tree path as 'a/b/0/c'."""
    parts = []
    for p in path:
        name = getattr(p, "name", None)
        if name is None:
            name = getattr(p, "key", None)
        if name is None:
            name = getattr(p, "idx", None)
        parts.append(str(name))
    return "/".join(parts)


def spec_for(path: str, shape: Sequence[int],
             rules: Sequence[PartitionRule], mesh) -> "Any":
    """First matching rule wins; axes absent from the mesh degrade to None."""
    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)
    for pattern, spec in rules:
        if re.search(pattern, path):
            if spec and spec[0] is Ellipsis:
                own = spec[1:][max(0, len(spec) - 1 - len(shape)):]
                spec = (None,) * (len(shape) - len(own)) + tuple(own)
            out = []
            for dim, ax in enumerate(spec):
                if ax is None or dim >= len(shape):
                    out.append(None)
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                axes = tuple(a for a in axes if a in names)
                if not axes:
                    out.append(None)
                    continue
                size = math.prod(mesh.devices.shape[
                    mesh.axis_names.index(a)] for a in axes)
                if shape[dim] % size != 0:
                    out.append(None)  # indivisible → replicate this dim
                    continue
                out.append(axes if len(axes) > 1 else axes[0])
            while out and out[-1] is None:
                out.pop()
            return P(*out)
    return P()


def tree_shardings(params, mesh, rules: Sequence[PartitionRule]):
    """NamedSharding pytree matching ``params`` under ``rules``."""
    import jax
    from jax.sharding import NamedSharding

    def one(path, leaf):
        p = path_str(path)
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, spec_for(p, shape, rules, mesh))

    return jax.tree_util.tree_map_with_path(one, params)


def replicated(tree, mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def shard_tree(tree, shardings):
    """Device-put every leaf to its sharding (host → mesh scatter)."""
    import jax

    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)


_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast",
                "ragged-all-to-all")
# asynchronous starts whose result is (operands, results[, contexts])
_OPERANDS_IN_RESULT = ("all-gather-start", "collective-permute-start")
_ITEMSIZE = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2,
             "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
             "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}
_HLO_HEAD = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_HLO_CALLEE = re.compile(
    r"\b(body|condition|calls|to_apply|branch_computations|"
    r"called_computations)=(?:\{([^}]*)\}|(%?[\w.\-]+))")
_HLO_ARRAY = re.compile(r"\b([a-z]+\d+(?:e\d+m\d+\w*)?|pred)\[([\d,]*)\]")


def _hlo_result(line: str):
    """``(name, result type, opcode)`` of one instruction line of HLO
    text, or None. The type may be a tuple, nested, with comments."""
    m = re.match(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*", line)
    if not m:
        return None
    rest = line[m.end():]
    end = 0
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    else:
        end = rest.find(" ")
    op = re.match(r"\s*([\w\-]+)\(", rest[end:])
    return (m.group(1), rest[:end], op.group(1)) if op else None


def _tuple_parts(rtype: str) -> List[str]:
    """The top-level elements of a tuple type's text."""
    parts, depth, cut = [], 0, 1
    for i, ch in enumerate(rtype):
        depth += (ch in "([{") - (ch in ")]}")
        if (ch == "," and depth == 1) or (ch == ")" and depth == 0):
            parts.append(rtype[cut:i])
            cut = i + 1
    return parts


def compiled_collectives(compiled) -> List[Dict[str, Any]]:
    """The collectives of a compiled program, read from its text: the
    plan the compiler MADE, which the rules above only ask for.
    ``compiled`` is what ``jit(...).lower(...).compile()`` returns, or
    its ``as_text()``. One entry an operation, in the text's order:

    - ``computation``, ``name``: where it stands and what it is called;
    - ``op``: ``all-gather``, ``all-reduce``, ``reduce-scatter``,
      ``all-to-all``, ``collective-permute``, ... (an asynchronous
      pair counts once, at its ``-start``, under the plain name; so
      do the copies of one operation that a TPU's asynchronous
      fusions hold, which share its ``channel_id``);
    - ``shapes``: ``(dtype, dims)`` of every array of the RESULT (a
      combined collective has several; of an ``all-gather-start``'s
      ``(operands, results)`` pair the results), per device;
    - ``bytes``: of those arrays;
    - ``in_loop``: whether the computation is a ``while`` body or is
      called from one: a gather of one layer's weights stands inside
      the layer scan's body, a whole-stack gather in front of it.
    """
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    calls: Dict[str, set] = {}
    bodies, found, comp, channels = set(), [], None, set()
    for line in text.splitlines():
        head = _HLO_HEAD.match(line)
        if head:
            comp = head.group(1)
            calls.setdefault(comp, set())
            continue
        got = _hlo_result(line)
        if got is None or comp is None:
            continue
        name, rtype, op = got
        for kind, several, one in _HLO_CALLEE.findall(line):
            callees = {n.strip().lstrip("%")
                       for n in (several or one).split(",")}
            calls[comp] |= callees
            if kind == "body":
                bodies |= callees
        base = op[:-6] if op.endswith("-start") else op
        if base not in _COLLECTIVES:
            continue
        channel = re.search(r"\bchannel_id=(\d+)", line)
        if channel:
            if channel.group(1) in channels:
                continue
            channels.add(channel.group(1))
        if op in _OPERANDS_IN_RESULT:
            rtype = _tuple_parts(rtype)[1]
        shapes = [(dt, tuple(int(x) for x in dims.split(",") if x))
                  for dt, dims in _HLO_ARRAY.findall(rtype)]
        found.append({
            "computation": comp, "name": name, "op": base,
            "shapes": shapes,
            "bytes": sum(math.prod(dims) * _ITEMSIZE.get(dt, 1)
                         for dt, dims in shapes)})
    inside, todo = set(), list(bodies)
    while todo:
        c = todo.pop()
        if c not in inside:
            inside.add(c)
            todo.extend(calls.get(c, ()))
    for e in found:
        e["in_loop"] = e["computation"] in inside
    return found


# Default rule set for transformer LMs: where each parameter is stored.
# The models/ GPT pytree STACKS a block's matrices for ``lax.scan``
# (``block/wq/kernel`` is ``[L, d, h]``), so the rules of the matrices the
# scan slices start with ``...``: ``fsdp`` and ``tp`` shard a matrix's own
# two dimensions, every chip holds its part of EVERY layer, and the scan's
# slice of layer ``l`` is local. (Sharded on ``L``, each chip owns whole
# layers, a slice at a loop-carried index is not local, and the
# partitioner all-gathers the WHOLE stack: in front of each pass on the
# CPU, and once a LAYER on a TPU, whose compiler sinks it into the body.)
# ``fsdp`` lies on a column matrix's input and a row matrix's output, the
# dimension ``tp`` does not take; the step gathers it away where the layer
# runs. The expert rules below name ``L`` and shard it on purpose; they
# share that flaw, and no cell trains an expert layer yet (ROADMAP T1).
LM_RULES: List[PartitionRule] = [
    (r"embed/kernel", (("fsdp",), "tp")),          # [vocab, d] row-shard
    (r"(wq|wk|wv)/kernel", (..., ("fsdp",), "tp")),    # [L, d, heads*hd]
    (r"wo/kernel", (..., "tp", ("fsdp",))),            # [L, heads*hd, d]
    (r"router/kernel", (("fsdp",),)),              # [L, d, E] small, L-shard
    (r"w_up/kernel", (("fsdp",), "ep", None, "tp")),   # [L, E, d, f]
    (r"w_down/kernel", (("fsdp",), "ep", "tp")),       # [L, E, f, d]
    (r"(w1|wi|up|gate)/kernel", (..., ("fsdp",), "tp")),   # [L, d, f]
    (r"(w2|wo_ff|down)/kernel", (..., "tp", ("fsdp",))),   # [L, f, d]
    (r"head/kernel", (("fsdp",), "tp")),
    (r"pos_embed", (None, ("fsdp",))),
    (r"(bias|scale|norm)", (None,)),
    (r".*", ()),                                   # replicate the rest
]

# Pipeline parallel: stacked block layers sharded over pp on the layer
# (leading) dim, everything else replicated (or dp-replicated). Matches
# pipeline_apply's stage ownership.
PP_LM_RULES: List[PartitionRule] = [
    (r"block/", ("pp",)),
    (r".*", ()),
]

# Pure data-parallel: everything replicated.
DP_RULES: List[PartitionRule] = [(r".*", ())]

# Activation/batch sharding rules used by train steps.
BATCH_SPEC = ("dp", "fsdp")  # batch dim sharded over dp×fsdp


def batch_sharding(mesh, extra_seq_axis: Optional[str] = None):
    """NamedSharding for [batch, seq, ...] activations."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    names = set(mesh.axis_names)
    b = tuple(a for a in BATCH_SPEC if a in names)
    s = extra_seq_axis if (extra_seq_axis in names) else None
    spec = P(b if b else None, s)
    return NamedSharding(mesh, spec)
