"""The GPT-2-shaped decoder of ``ray_tpu/models/gpt.py``, and the
contract of an architecture module. Under ``benchmarks/perf`` only the
files of ``architectures/`` import the program's model or engine code,
and each names no plain reference but its own (this one's is
``reference_gpt2.py``, beside the harness since PR 23; a new one lies
beside its module as ``<name>_reference.py``). ``tests/perf`` holds the
tree, a rehearsal copy with a second architecture and, since PR 36, a
copy of the tree with a third planted IN it (``perf_testlib.
planted_tree``, held by the copy's own tests) to all of it.

An architecture module is found by the configuration file's
``"architecture"`` (``perf_harness.load_architecture``; absent means
this one). A PR that adds a model adds such a file, its reference
beside it, and configurations that name it; it edits nothing here. What
a module provides, all of it functions of the configuration file
``conf`` and of the config object ``cfg`` that ``model_cfg`` makes:

``vocab(conf)``
    (ids a prompt may hold, rows of the table the program holds), plain
    Python: a serving cell's driver calls it and never imports jax.
``model_cfg(conf)``
    the program's config object at the file's sizes.
``param_shapes(cfg)``
    the program's parameter tree as shapes and types.
``leaf_std(cfg, init, name, shape)``
    the standard deviation the leaf called ``name`` is drawn with, or
    None for a leaf of ones. The seeded fill itself is
    ``perf_deployment.seeded_params``.
``make_engine(params, cfg, conf)``
    the serving engine for ``conf["engine"]``: ``stats()``, and what
    ``@serve.batch(continuous=True)`` asks of an engine.
``served_logits(engine, cfg, seqs, n_prompt, n_steps)``
    the served arithmetic, for the logits comparison: what it computed,
    always. It leaves nothing out and replaces nothing.
``reference(cfg)``
    ``(from_program, forward, loss)`` of the plain reference at
    ``cfg``'s sizes: ``forward(from_program(params), tokens[B, S])`` is
    float32 logits ``[B, S, rows]``, ``loss(from_program(params),
    tokens[B, S + 1])`` the mean next-token cross-entropy.
``decidable(cfg, conf)``  (optional; this module has none)
    for a model that makes discrete choices (the experts it holds among
    a layer's top k): a function ``(from_program(params), tokens[B, S])
    -> bool[B, S]`` of the REFERENCE's parameters and the token rows
    alone, float32 at precision ``highest``, that says for every row
    and position whether every such choice the reference makes for the
    logits there, in every layer, clears the edge of its selection by
    ``conf["correct"]["tie_eps"]``. A bfloat16 program and the float32
    reference choose differently at a near-tie and then differ by a
    whole expert's part; the harness (``perf_reference_check``), never
    this module and never the program, leaves the logit vectors and
    served tokens at undecidable positions out of ``correct``, counts
    them (``compared``, ``left_out``) and refuses a run that compared
    too few. A module without it leaves nothing out.
``decode_step_bytes(conf, weight_bytes, kv_bytes, live_tokens, stats_delta)``  (optional)
    plain Python, as ``vocab`` is (the reader runs in ``run.py``'s own
    process, which stays off the chip): the numerator of the whole
    serving step's share of the chip's bandwidth, ``decode_roofline_pct``
    (``.sat``), which asks the configuration's architecture and has no
    other source since PR 36. THE RULE (PR 33): the FEWEST bytes ANY
    program with the configuration's numerics moves in one decode
    step. Every weight the step multiplies by, once, at
    ``weight_bytes`` a parameter (the reader's table of
    ``numerics.compute_dtype``); for routed experts, those that at
    least one live token was routed to, from a counter the engine
    keeps (``stats_delta``: ``engine.stats()`` differenced over the
    window), never all of them by assumption; and the live cache
    (``live_tokens`` summed over the active lanes, whatever the model
    keeps a token a layer: keys and values, a latent) at ``kv_bytes``
    a value (the reader's table of ``engine.kv_dtype``). Never how a
    program HOLDS either: float32 masters, a cast once a launch,
    padding and copies are overhead and read as distance from 100. A
    count that over-reckons reads over 100, which no chip does: the
    driver refuses it as ``impossible_reading``. A module without the
    function has no such count: the reader returns nothing and the
    metric is not listed for that architecture's cells. This module's
    is ``kernel_costs.decode_step_bytes`` on the ``model`` block.
``train_program(cfg, conf, devices)``
    a training configuration's mesh, jitted ``init(key) -> state`` with
    ``state["params"]``, ``step(state, {"tokens": t}) -> (state,
    metrics)`` with ``metrics["loss"]``, the batch's sharding and the
    program's ``loss(params, tokens)``.

jax and the program are imported inside the functions.

What a configuration's file states (``tests/perf/perf_testlib.py:
check_configuration`` holds every file to it):

``source.url``  the entry's ``source`` in ``BENCHMARK.json``.
``reduced``     the entry's ``reduced``: every key whose value differs
                from the source's, never a width.
``assumed``     what the source does not give and was set here.
``cut``         for each key in ``reduced`` and no other: ``{"published":
                the source's value, "held": the value this file holds
                under that key}`` (the key at the file's top level or
                in its ``model`` block).
``cut_stands_for``  where ``reduced`` is not empty: the deployment the
                cut stands for, ``{"chips_sharing_a_layer": n, "how":
                "what one of the n holds of a layer (experts, heads,
                vocabulary rows) and where the layers left out lie"}``.
``correct``     the check's sizes and limits, with ``why``. Beside
                ``prompt_tokens``, ``decode_steps``, ``repeat_prompt``,
                ``repeat_answer`` and ``logits_rel_tol``: ``rows`` (the
                seeded sequences of the logits comparison, default 2),
                ``tie_eps`` (required where the module has
                ``decidable``; its reason in ``why``) and
                ``min_compared`` (the share of the logit vectors, and
                of each answer's tokens, that has to be compared;
                default 1, all).
"""
from __future__ import annotations


def vocab(conf: dict):
    m = conf["model"]
    return m["vocab_size"], m["embedding_rows_held"]


def model_cfg(conf: dict):
    """The program's ``GPTConfig`` at the sizes of a configuration file
    (the one place that maps published names to the program's)."""
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    m = conf["model"]
    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    return gpt.GPTConfig(
        vocab_size=m["embedding_rows_held"], n_layer=m["n_layer"],
        n_head=m["n_head"], d_model=m["n_embd"], d_ff=m["n_inner"],
        max_seq=m["n_positions"],
        dtype=dtypes[conf["numerics"]["compute_dtype"]],
        param_dtype=dtypes[conf["numerics"]["param_dtype"]],
        remat=conf.get("train", {}).get("remat", "dots"),
        loss_chunk=conf.get("train", {}).get("loss_chunk", 0))


def param_shapes(cfg):
    """The tree (names, shapes, types) is the program's own:
    ``eval_shape`` of its ``init_params``."""
    import jax

    from ray_tpu.models import gpt

    return jax.eval_shape(lambda k: gpt.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def leaf_std(cfg, init: dict, name: str, shape):
    """The GPT-2 initialisation the configuration file states
    (``init["std"]``: the standard deviation by kind of leaf)."""
    import math

    if "scale" in name:
        return None
    named = {"resid": 1.0 / math.sqrt(2 * cfg.n_layer * cfg.d_model)}
    for part, val in init["std"].items():
        if part in name:
            return named.get(val, val) if isinstance(val, str) \
                else float(val)
    return 1.0 / math.sqrt(shape[-2])         # fan-in of a matrix


def make_engine(params, cfg, conf: dict):
    from ray_tpu.serve.engine import DecodeEngine

    eng = conf["engine"]
    return DecodeEngine(
        params, cfg, slots=eng["slots"], chunk=eng["chunk"],
        max_len=eng["max_len"],
        prompt_buckets=tuple(eng["prompt_buckets"]),
        paged=True, page_size=eng["page_size"],
        n_pages=eng["n_pages"], prefix_cache=eng["prefix_cache"],
        attn_kernel=eng["attn_kernel"], kv_dtype=eng["kv_dtype"])


def served_logits(engine, cfg, seqs, n_prompt: int, n_steps: int) -> dict:
    """``seqs`` [B, n_prompt + n_steps + 1] through the SERVED
    arithmetic — the paged prefill program, then single decode steps
    through the paged cache with the engine's attention kernel, on a
    small pool of its own: the logits right after prefill (key 0) and
    after ``n_steps`` cached decode steps (key ``n_steps``), float32
    ``[B, rows]``.

    The paged prefill returns a token, not logits, so it is given the
    prompt less its last token, and the first decode step (fed that
    last token, reading the keys and values prefill wrote) yields the
    logits "after prefill"; the tokens fed afterwards are the
    sequence's own (teacher forcing), so both sides see the same
    inputs. ``_slot_decode_step_paged`` is the program's step function
    that the chunk program scans; it is read here because no public
    entry returns logits."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt_decode as gd

    ps = engine.page_size
    B = len(seqs)
    total = n_prompt + n_steps
    max_pages = -(-(total + 1) // ps)
    bucket = next(b for b in engine.prompt_buckets if b >= n_prompt - 1)
    cache = gd.init_paged_cache(cfg, B, B * max_pages, ps,
                                engine.kv_dtype)
    pt = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    prefill = gd.jit_prefill_into_slot_paged(cfg, ps, 0.0,
                                             engine.kv_dtype)
    step = jax.jit(functools.partial(
        gd._slot_decode_step_paged, cfg=cfg, page_size=ps,
        kv_dtype=engine.kv_dtype, attn_kernel=engine.attn_kernel))
    params = engine.params
    for b in range(B):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n_prompt - 1] = seqs[b, :n_prompt - 1]
        _tok, cache, _key = prefill(
            params, cache, padded, np.int32(n_prompt - 1), np.int32(0),
            pt[b], np.int32(gd.PT_SENTINEL), np.int32(b),
            jax.random.PRNGKey(0))
    active = np.ones((B,), bool)
    got = {}
    for i in range(n_steps + 1):
        pos = n_prompt - 1 + i
        logits, cache = step(params, cache, jnp.asarray(seqs[:, pos]),
                             active, jnp.asarray(pt))
        if i in (0, n_steps):
            got[i] = np.asarray(logits, np.float32)
    return got


def decode_step_bytes(conf: dict, weight_bytes: int, kv_bytes: int,
                      live_tokens: float, stats_delta: dict) -> float:
    import kernel_costs

    return kernel_costs.decode_step_bytes(conf["model"], weight_bytes,
                                          kv_bytes, live_tokens)


def reference(cfg):
    import functools

    import reference_gpt2

    return (reference_gpt2.from_program,
            functools.partial(reference_gpt2.forward, n_head=cfg.n_head),
            functools.partial(reference_gpt2.loss, n_head=cfg.n_head))


def train_program(cfg, conf: dict, devices) -> dict:
    from ray_tpu.models import gpt
    from ray_tpu.parallel import create_mesh

    mesh = create_mesh({conf["train"]["mesh_axis"]: len(devices)},
                       devices=devices)
    init, step, _state_sh, batch_sh = gpt.make_train_step(cfg, mesh)
    return {"mesh": mesh, "init": init, "step": step,
            "batch_sharding": batch_sh,
            "loss": lambda p, t: gpt.loss_fn(p, {"tokens": t}, cfg,
                                             mesh)[0]}
