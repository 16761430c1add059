"""The serving frame (``ray_tpu/models/serving.py``) and the seven
model descriptions around it.

- no module under ``ray_tpu/models`` imports, or reads off another
  module of the package, an underscore name: what two models share has a
  public name in the frame or beside the expert layer (``models/moe.py``);
- the chunk program is ONE scan, the frame's, bound by each description
  to its own step: its outputs, its counters and the EOS mask-and-carry
  are judged through every description alike;
- every description answers ``decode_attention_fused``, and the engine
  asks nothing else;
- what a second model imports of another has a PUBLIC name there
  (``ssm_hybrid``'s Mamba-2 mixer and ``kda_moe``'s attention, for
  ``ssm_moe``), and making it public changed no program: the lowered
  text of every older description's three programs is the parent's.
"""
import ast
import functools
import glob
import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import (dsa_moe, gpt, gpt_decode, kda_moe, mla_moe,
                            scmoe, serving, ssm_hybrid, ssm_moe)
from ray_tpu.serve.engine import DecodeEngine

MODELS = os.path.dirname(os.path.abspath(serving.__file__))
FILES = sorted(os.path.basename(p)
               for p in glob.glob(os.path.join(MODELS, "*.py")))
SIBLINGS = {f[:-3] for f in FILES if f != "__init__.py"}
#: The one exemption: the training step's own helpers, which
#: ``gpt_decode`` and ``mla_moe`` multiply with (ROADMAP S9 guards
#: ``models/gpt.py``; renaming them would touch the training cell's
#: module for nothing).
EXEMPT = {("gpt", "_mm"), ("gpt", "_rmsnorm"), ("gpt", "_project_vocab")}
#: Names a description object goes by where it is an argument, not an
#: import (the frame's ``model=``).
DESCRIPTION_ARGS = {"model", "desc"}


def _private(name: str) -> bool:
    return name.startswith("_") and not name.startswith("__")


def _sibling(module, level: int):
    """The sibling a ``from <module> import`` names, or None:
    ``.x`` / ``ray_tpu.models.x`` -> ``x``; the package itself -> ``""``."""
    module = module or ""
    if level == 0:
        if module == "ray_tpu.models":
            return ""
        if not module.startswith("ray_tpu.models."):
            return None
        module = module[len("ray_tpu.models."):]
    elif level != 1:
        return None
    return module if module == "" or module in SIBLINGS else None


def _underscore_reads(path: str):
    """``(line, sibling, name)`` for every underscore name the module at
    ``path`` imports from, or reads off, another module of the package."""
    this = os.path.basename(path)[:-3]
    tree = ast.parse(open(path).read())
    bound = {}                  # local name -> sibling module
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            sib = _sibling(node.module, node.level)
            if sib is None:
                continue
            for a in node.names:
                if sib == "":               # from . import x
                    if a.name in SIBLINGS:
                        bound[a.asname or a.name] = a.name
                elif _private(a.name):
                    found.append((node.lineno, sib, a.name))
        elif isinstance(node, ast.Import):
            for a in node.names:
                sib = _sibling(a.name, 0)
                if sib and a.asname:
                    bound[a.asname] = sib
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and _private(node.attr) \
                and isinstance(node.value, ast.Name):
            sib = bound.get(node.value.id) or (
                "<description>" if node.value.id in DESCRIPTION_ARGS
                else None)
            if sib and sib != this:
                found.append((node.lineno, sib, node.attr))
    return [f for f in found if (f[1], f[2]) not in EXEMPT]


@pytest.mark.parametrize("name", FILES)
def test_no_module_reads_an_underscore_name_of_another(name):
    assert _underscore_reads(os.path.join(MODELS, name)) == []


def test_the_hygiene_walk_sees_what_it_must(tmp_path):
    """The walker itself: every spelling of a private read is found,
    the exemption and a module's own names are not."""
    p = tmp_path / "kda_moe.py"
    p.write_text(
        "from . import mla_moe, moe as m\n"
        "from .gpt import _mm, _other\n"
        "from .mla_moe import _ffn, public\n"
        "from ray_tpu.models.gpt_decode import _sample\n"
        "import ray_tpu.models.scmoe as sc\n"
        "def f(model):\n"
        "    from .serving import _hidden\n"
        "    return mla_moe._flat, m._router_logits, sc._layer, \\\n"
        "        model._slot_step, mla_moe.public, self._x, m.__name__\n")
    assert sorted(_underscore_reads(str(p))) == [
        (2, "gpt", "_other"), (3, "mla_moe", "_ffn"),
        (4, "gpt_decode", "_sample"), (7, "serving", "_hidden"),
        (8, "mla_moe", "_flat"), (8, "moe", "_router_logits"),
        (8, "scmoe", "_layer"), (9, "<description>", "_slot_step")]


# ------------------------------------------------------ the chunk program
#: description -> (slots, n_pages, page_size, max_pages) at ``nano``
DESCRIPTIONS = {
    gpt_decode: (3, 15, 8, 5),
    mla_moe: (4, 32, 4, 8),
    kda_moe: (4, 32, 4, 8),
    scmoe: (4, 32, 4, 8),
    ssm_hybrid: (4, 32, 4, 8),
    ssm_moe: (4, 32, 4, 8),
    dsa_moe: (4, 32, 4, 8),
}


@functools.lru_cache(maxsize=None)
def _model(desc):
    if desc is gpt_decode:
        cfg = gpt.CONFIGS["nano"]
        return cfg, gpt.init_params(jax.random.PRNGKey(0), cfg)
    cfg = desc.CONFIGS["nano"]
    return cfg, desc.init_params(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("desc", DESCRIPTIONS,
                         ids=lambda d: d.__name__.rsplit(".", 1)[1])
def test_the_chunk_program_is_the_frames_around_the_models_step(desc):
    """``4 + bool(STEP_COUNTERS)`` outputs, ``len(STEP_COUNTERS)``
    counters, and a lane fed ``eos_token`` masked and carried, through
    the ONE scan of ``serving.decode_chunk_slots_paged``."""
    cfg, params = _model(desc)
    slots, n_pages, ps, max_pages = DESCRIPTIONS[desc]
    k, eos = 3, 7
    # the module's chunk program is the frame's, bound to its own step
    chunk = desc.decode_chunk_slots_paged
    assert chunk.func is serving.decode_chunk_slots_paged
    assert chunk.keywords == dict(
        step=desc._slot_decode_step_paged,
        counters=len(desc.STEP_COUNTERS))
    step = desc.jit_decode_chunk_slots_paged(cfg, k, ps, 0.0, eos)
    assert step.__wrapped__.__name__ == "decode_chunk_slots_paged"
    pt = np.full((slots, max_pages), serving.PT_SENTINEL, np.int32)
    pt[:, :2] = np.arange(slots * 2).reshape(slots, 2)
    # lane 0 was fed EOS, lane 1 decodes, lane 2 was fed EOS but is idle
    token = jnp.asarray([eos, 1, eos] + [2] * (slots - 3), jnp.int32)
    active = jnp.asarray([True, True, False] + [True] * (slots - 3))
    out = step(params, desc.init_paged_cache(cfg, slots, n_pages, ps),
               token, jnp.zeros((slots, 2), jnp.uint32), active,
               jnp.asarray(pt))
    assert len(out) == 4 + bool(desc.STEP_COUNTERS)
    toks, cache, done, rngs = (np.asarray(o) if i != 1 else o
                               for i, o in enumerate(out[:4]))
    assert toks.shape == (slots, k) and done.shape == (slots,)
    assert rngs.shape == (slots, 2)
    for counts in out[4:]:
        assert counts.shape == (len(desc.STEP_COUNTERS),)
        assert counts.dtype == jnp.int32
        assert int(counts[0]) > 0       # every counting model's first
    # masked: the lane fed EOS emits EOS and nothing else; carried: it
    # is done, and any lane is done iff it emitted EOS, after which it
    # emits nothing else either
    assert (toks[0] == eos).all() and done[0]
    for lane in np.flatnonzero(np.asarray(active)):
        hit = toks[lane] == eos
        assert done[lane] == hit.any()
        assert hit[np.argmax(hit):].all() or not hit.any()
    assert not done[2]                  # an idle lane is nobody's end
    # only active lanes advanced
    assert np.array_equal(np.asarray(cache["pos"]),
                          k * np.asarray(active, np.int32))


def test_eos_off_carries_no_mask():
    """``eos_token < 0``: no lane is ever done (the static branch)."""
    cfg, params = _model(mla_moe)
    slots, n_pages, ps, max_pages = DESCRIPTIONS[mla_moe]
    pt = np.arange(slots * max_pages, dtype=np.int32).reshape(
        slots, max_pages) % n_pages
    out = mla_moe.jit_decode_chunk_slots_paged(cfg, 2, ps)(
        params, mla_moe.init_paged_cache(cfg, slots, n_pages, ps),
        jnp.zeros((slots,), jnp.int32), jnp.zeros((slots, 2), jnp.uint32),
        jnp.ones((slots,), bool), jnp.asarray(pt))
    assert not np.asarray(out[2]).any()


# --------------------------------------------- what follows from the spec
@pytest.mark.parametrize("desc", DESCRIPTIONS,
                         ids=lambda d: d.__name__.rsplit(".", 1)[1])
def test_the_frame_serves_every_description_under_its_own_names(desc):
    """The pool and its page cost follow from ``cache_spec``; the knobs
    are checked against the description; one jit wrapper a key, and the
    key holds the description."""
    cfg, _ = _model(desc)
    slots, n_pages, ps, _ = DESCRIPTIONS[desc]
    spec = desc.cache_spec(cfg)
    cache = desc.init_paged_cache(cfg, slots, n_pages, ps)
    assert set(cache) == {e.name for e in spec.entries} | {"pos"}
    assert desc.kv_bytes_per_page(cfg, ps) == spec.bytes_per_page(ps)
    with pytest.raises(ValueError, match="kv_dtype must be one of"):
        desc.cache_spec(cfg, "int4")
    with pytest.raises(ValueError, match="kv_dtype must be one of"):
        desc.jit_prefill_into_slot_paged(cfg, ps, 0.0, "int4")
    with pytest.raises(ValueError, match="attn_kernel must be one of"):
        desc.jit_decode_chunk_slots_paged(cfg, 2, ps, attn_kernel="fused")
    if "tp" in desc.UNSUPPORTED:
        with pytest.raises(ValueError, match=desc.UNSUPPORTED["tp"][:20]):
            desc.check_tp(cfg, 2)
        with pytest.raises(ValueError, match="tp=2"):
            desc.jit_prefill_into_slot_paged(cfg, ps, tp=2)
        assert desc.check_tp(cfg, 1) is None
        params = object()
        assert desc.shard_params(params, cfg, 1) is params
    # the spellings of one key share one wrapper; another description's
    # same knobs do not
    a = desc.jit_prefill_into_slot_paged(cfg, ps)
    assert a is desc.jit_prefill_into_slot_paged(cfg, ps, 0.0, "fp", tp=1)
    other = mla_moe if desc is scmoe else scmoe
    assert a is not other.jit_prefill_into_slot_paged(
        _model(other)[0], ps)


# ------------------------------------------------ decode_attention_fused
def test_every_description_says_whether_its_chunk_program_holds_a_kernel():
    """The engine asks ``decode_attention_fused`` and nothing else:
    ``gpt_decode`` answers by its knob's name, the others by shape
    (interpreted here: any page is addressable)."""
    for desc in DESCRIPTIONS:
        cfg, _ = _model(desc)
        assert isinstance(desc.decode_attention_fused(
            cfg, 4, desc.ATTN_KERNELS[0]), bool)
    nano = gpt.CONFIGS["nano"]
    assert [gpt_decode.decode_attention_fused(nano, 8, kernel)
            for kernel in gpt_decode.ATTN_KERNELS] == [False, True]
    for desc in (mla_moe, kda_moe, scmoe, ssm_hybrid, ssm_moe):
        assert desc.decode_attention_fused(_model(desc)[0], 4, "gather")
    assert scmoe.decode_attention_fused is mla_moe.decode_attention_fused


@pytest.mark.parametrize("kernel,mode", [("gather", None),
                                         ("pallas", "interpret")])
def test_the_gpt_engine_reads_its_kernel_as_before(kernel, mode):
    """``warm_up()["attn_kernel_mode"]`` and
    ``stats()["attn_kernel_dispatches"]`` under both of the GPT block's
    kernels, by the description's word."""
    cfg, params = _model(gpt_decode)
    eng = DecodeEngine(params, cfg, slots=2, chunk=2, max_len=32,
                       prompt_buckets=(8,), page_size=8,
                       attn_kernel=kernel)
    try:
        assert eng._attn_fused is (kernel == "pallas")
        assert eng.warm_up()["attn_kernel_mode"] == mode
        assert len(np.concatenate(list(eng.stream(
            np.arange(1, 6, dtype=np.int32), 5)))) == 5
        st = eng.stats()
        assert st["dispatches"] > 0
        assert st["attn_kernel_dispatches"] == (
            st["dispatches"] if kernel == "pallas" else 0)
    finally:
        eng.shutdown()


# ------------------------- what went public changed no program (ISSUE 55)
#: sha256 (16 hex) of the lowered text of each older description's THREE
#: programs at ``nano`` on the CPU (page 4, 4 slots of 24 pages): one
#: prompt (``[1, 8]``), a group of two (``[1, 16]`` and ``[1, 8]``) and
#: the chunk program (k 3, EOS 7), as commit 1791fd5 (the parent of
#: ISSUE 55) lowers them: before ``ssm_hybrid``'s Mamba-2 mixer went
#: public by sizes (``Mamba2Sizes``: ``ssm_proj`` ... ``ssm_decode``,
#: ``slot_entries``, ``put_slot``) and ``kda_moe.gqa_causal_attention``
#: by head counts. Whoever changes a program's arithmetic on purpose
#: reads the new values off this test's failure.
PARENT_TEXT = {
    "gpt_decode": ("631a6f1c7deb4552", "63fcf7fc7ca55395",
                   "c7045c705baca87e"),
    "mla_moe": ("c643aca1dc411603", "001ebc98072190ad",
                "722df5bf130357b5"),
    "scmoe": ("aad294ac66cca770", "7eb5f1cae1ef6639", "9d0e356957ce6c41"),
    "kda_moe": ("92ae57f1de5ced9c", "855abf4908ee3f2f",
                "cf3802afa63a7310"),
    "ssm_hybrid": ("31a6d34e04b5d0fb", "5381337d2af13972",
                   "ed13fa45b4726bd6"),
    # as PR 63 measured it on the chip (its own first values; the chunk
    # program's with the selection as one kernel, after the review)
    "dsa_moe": ("ffed2d3086b3faf2", "1e772c0440ee7efa",
                "723f89fa4d853c84"),
}


@pytest.mark.parametrize("name", list(PARENT_TEXT))
def test_the_older_descriptions_lower_to_the_parents_text(name):
    """Falcon-H1's three programs before and after its mixer went
    public, and the other four's beside them: equal to the character
    (their hashes)."""
    desc = {d.__name__.rsplit(".", 1)[1]: d for d in DESCRIPTIONS}[name]
    cfg, params = _model(desc)
    sentinel = serving.PT_SENTINEL
    cache = desc.init_paged_cache(cfg, 4, 96, 4)
    prefill = desc.jit_prefill_into_slot_paged(cfg, 4, 0.0)
    lone = prefill.lower(
        params, cache, np.zeros((1, 8), np.int32), np.int32(1), np.int32(0),
        np.full((24,), sentinel, np.int32), np.int32(sentinel),
        np.int32(0), jax.random.PRNGKey(0))
    group = prefill.lower(
        params, cache,
        (np.zeros((1, 16), np.int32), np.zeros((1, 8), np.int32)),
        np.ones((2,), np.int32), np.zeros((2,), np.int32),
        np.full((2, 24), sentinel, np.int32),
        np.full((2,), sentinel, np.int32), np.arange(2, dtype=np.int32),
        np.zeros((2, 2), np.uint32))
    chunk = desc.jit_decode_chunk_slots_paged(cfg, 3, 4, 0.0, 7).lower(
        params, cache, np.zeros((4,), np.int32),
        np.zeros((4, 2), np.uint32), np.ones((4,), bool),
        np.full((4, 24), sentinel, np.int32))
    assert tuple(hashlib.sha256(p.as_text().encode()).hexdigest()[:16]
                 for p in (lone, group, chunk)) == PARENT_TEXT[name]


def test_the_shared_mixer_is_imported_under_public_names():
    """``ssm_moe`` reads of ``ssm_hybrid`` and ``kda_moe`` public names
    alone (the hygiene walk above holds it for every module); the names
    it takes exist, and both configs are ``Mamba2Sizes``."""
    for name in ("Mamba2Sizes", "ssm_proj", "ssm_conv", "ssm_out",
                 "ssm_step", "ssm_step_pallas", "state_kernel",
                 "ssd_chunked", "ssm_mix", "ssm_sequence", "ssm_decode",
                 "slot_entry", "slot_entries", "put_slot"):
        assert callable(getattr(ssm_hybrid, name)), name
    for name in ("gqa_kernel", "gqa_decode_reads", "gqa_decode_attention",
                 "gqa_causal_attention"):
        assert callable(getattr(kda_moe, name)), name
    for desc in (ssm_hybrid, ssm_moe):
        assert isinstance(_model(desc)[0], ssm_hybrid.Mamba2Sizes)
    falcon, granite = _model(ssm_hybrid)[0], _model(ssm_moe)[0]
    assert len(falcon.ssm_col_mults) == 5 and granite.ssm_col_mults is None
    assert ssm_moe.STEP_COUNTERS is kda_moe.STEP_COUNTERS
