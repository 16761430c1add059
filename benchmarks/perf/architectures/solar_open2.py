"""Solar-Open2-250B (``model_type`` ``solar_open2``): gated delta-rule
linear-attention (KDA) layers whose state lives per slot, one gated
NoPE GQA layer in four over key/value pages, sigmoid-routed experts
beside a shared one in every layer; served by
``ray_tpu/models/kda_moe.py`` through the same ``DecodeEngine`` as the
other two blocks. The contract of an architecture module is in
``gpt2.py``'s docstring; this module's plain reference is
``solar_open2_reference.py``, beside it.

A configuration file of this architecture holds the published
``config.json`` keys at its top level under their own names (the cut
ones as held: ``num_hidden_layers``, ``n_routed_experts``,
``vocab_size``; ``gqa_layers`` whole, of which the layers held are
those below ``num_hidden_layers``), and beside them ``router_width``
(the router keeps its published width whatever is held),
``expert_offset`` (the first expert held), ``kda_low_rank`` (the width
of the decay and gate projections' low rank, assumed) and the usual
blocks (``numerics`` with ``state_dtype``, ``engine``, ``deployment``,
``correct``, ``init``).

``init`` has ``std`` (by kind of leaf, as ``axk1``'s) and ``mean``:
``perf_deployment.seeded_params`` draws every leaf around zero, and
``dt_bias`` has to sit where ``softplus`` is small or every channel
forgets in a token or two; ``with_init_means`` adds the means, and
``make_engine`` (and every test that seeds weights) goes through it.

What the rooflines' numerators count is here too (``decode_step_bytes``
for the whole step; ``kda_state_cost``, ``gqa_attention_cost`` and
``moe_experts_cost`` for three scopes), plain Python from shapes and
from the engine's counters, in the types the configuration STATES
(``numerics``), never in how the program holds anything.
"""
from __future__ import annotations

import os

import perf_harness as H

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = {"float32": 4, "bfloat16": 2}


def plain_reference():
    """This architecture's plain reference, the module beside it."""
    return H.load_file(os.path.join(_HERE, "solar_open2_reference.py"),
                       "perf_arch_")


def vocab(conf: dict):
    return conf["vocab_size"], conf["vocab_size"]


def model_cfg(conf: dict):
    """The program's ``KDAMoEConfig`` at the sizes of a configuration
    file (the one place that maps published names to the program's)."""
    import jax.numpy as jnp

    from ray_tpu.models import kda_moe

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    lin, num = conf["linear_attn_config"], conf["numerics"]
    if conf["use_rope"] or conf["first_k_dense_replace"] != 0 \
            or conf["kda_use_full_proj"] \
            or lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise ValueError(
            "the program has no rotary, no dense layer, low-rank decay "
            "and gate projections and as many KDA key heads as value "
            "heads")
    return kda_moe.KDAMoEConfig(
        vocab_size=conf["vocab_size"], n_layer=conf["num_hidden_layers"],
        gqa_layers=tuple(l for l in conf["gqa_layers"]
                         if l < conf["num_hidden_layers"]),
        d_model=conf["hidden_size"], n_head=conf["num_attention_heads"],
        n_kv_head=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        gqa_gate=conf["use_gqa_gate"], kda_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        conv_size=lin["short_conv_kernel_size"],
        kda_rank=conf["kda_low_rank"],
        neg_eigval=conf["kda_allow_neg_eigval"],
        d_expert=conf["moe_intermediate_size"],
        n_routed=conf["router_width"],
        experts_held=conf["n_routed_experts"],
        expert_offset=conf["expert_offset"],
        top_k=conf["num_experts_per_tok"],
        norm_topk=conf["norm_topk_prob"],
        route_scale=float(conf["routed_scaling_factor"]),
        shared_expert=conf["n_shared_experts"] > 0,
        max_seq=conf["max_position_embeddings"],
        eps=conf["rms_norm_eps"], dtype=dtypes[num["compute_dtype"]],
        param_dtype=dtypes[num["param_dtype"]],
        state_dtype=dtypes[num["state_dtype"]],
        moe_block_rows=conf["engine"].get("moe_block_rows", 32))


def hyper(cfg) -> dict:
    """The reference's ``hp``: the program's config object as the
    plain dict ``solar_open2_reference`` reads."""
    return {"heads": cfg.n_head, "kv_heads": cfg.n_kv_head,
            "head_dim": cfg.head_dim, "kda_heads": cfg.kda_heads,
            "kda_dim": cfg.kda_head_dim, "conv": cfg.conv_size,
            "gqa_layers": tuple(cfg.gqa_layers), "eps": cfg.eps,
            "top_k": cfg.top_k, "norm_topk": cfg.norm_topk,
            "route_scale": cfg.route_scale,
            "experts_held": cfg.experts_held,
            "expert_offset": cfg.expert_offset}


def param_shapes(cfg):
    """The tree (names, shapes, types) is the program's own:
    ``eval_shape`` of its ``init_params``."""
    import jax

    from ray_tpu.models import kda_moe

    return jax.eval_shape(lambda k: kda_moe.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def leaf_std(cfg, init: dict, name: str, shape):
    """``init["std"]``: the standard deviation by kind of leaf (the
    first key that is part of the leaf's path), else 1/sqrt(fan-in);
    norm scales are ones."""
    import math

    if "scale" in name:
        return None
    for part, val in init["std"].items():
        if part in name:
            return float(val)
    return 1.0 / math.sqrt(shape[-2])


def with_init_means(params, init: dict):
    """``init["mean"]`` added to the leaves it names (by the same rule
    as ``leaf_std``'s): the seeded fill draws around zero. The other
    leaves are passed on as they are, not copied."""
    import jax

    def shift(path, leaf):
        name = jax.tree_util.keystr(path)
        for part, val in init.get("mean", {}).items():
            if part in name:
                return (leaf.astype("float32") + val).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(shift, params)


def make_engine(params, cfg, conf: dict):
    from ray_tpu.serve.engine import DecodeEngine

    eng = conf["engine"]
    return DecodeEngine(
        with_init_means(params, conf["init"]), cfg, slots=eng["slots"],
        chunk=eng["chunk"], max_len=eng["max_len"],
        prompt_buckets=tuple(eng["prompt_buckets"]),
        page_size=eng["page_size"], n_pages=eng["n_pages"],
        prefix_cache=eng["prefix_cache"],
        attn_kernel=eng["attn_kernel"], kv_dtype=eng["kv_dtype"])


def served_logits(engine, cfg, seqs, n_prompt: int, n_steps: int) -> dict:
    """``seqs`` [B, n_prompt + n_steps + 1] through the SERVED
    arithmetic: the paged prefill program (keys and values into pages,
    the chunked KDA form into the slot's state and convolution tail),
    then single decode steps (attention over the pages, the recurrence
    on the state), on a small pool of its own: the logits right after
    prefill (key 0) and after ``n_steps`` cached decode steps (key
    ``n_steps``), float32 ``[B, rows]``.

    As in ``gpt2.served_logits``: the prefill is given the prompt less
    its last token and the first decode step yields the logits "after
    prefill"; the tokens fed afterwards are the sequence's own.
    ``_slot_decode_step_paged`` is the step function that the chunk
    program scans."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import kda_moe as km

    ps = engine.page_size
    B = len(seqs)
    total = n_prompt + n_steps
    max_pages = -(-(total + 1) // ps)
    bucket = next(b for b in engine.prompt_buckets if b >= n_prompt - 1)
    cache = km.init_paged_cache(cfg, B, B * max_pages, ps,
                                engine.kv_dtype)
    pt = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    prefill = km.jit_prefill_into_slot_paged(cfg, ps, 0.0,
                                             engine.kv_dtype)
    step = jax.jit(functools.partial(
        km._slot_decode_step_paged, cfg=cfg, page_size=ps,
        kv_dtype=engine.kv_dtype, attn_kernel=engine.attn_kernel),
        donate_argnums=(1,))
    params = engine.params
    for b in range(B):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n_prompt - 1] = seqs[b, :n_prompt - 1]
        _tok, cache, _key = prefill(
            params, cache, padded, np.int32(n_prompt - 1), np.int32(0),
            pt[b], np.int32(km.PT_SENTINEL), np.int32(b),
            jax.random.PRNGKey(0))
    active = np.ones((B,), bool)
    got = {}
    for i in range(n_steps + 1):
        pos = n_prompt - 1 + i
        logits, cache, _counts = step(
            params, cache, jnp.asarray(seqs[:, pos]), active,
            jnp.asarray(pt))
        if i in (0, n_steps):
            got[i] = np.asarray(logits, np.float32)
    return got


def reference(cfg):
    import functools

    ref = plain_reference()
    hp = hyper(cfg)
    return (ref.from_program, functools.partial(ref.forward, hp=hp),
            functools.partial(ref.loss, hp=hp))


def decidable(cfg, conf: dict):
    """[rows, positions]: in EVERY layer the reference's top
    ``num_experts_per_tok`` of the ``router_width`` scores at that
    position keeps every expert HELD here ``correct.tie_eps`` (in the
    sigmoid score) from crossing its edge
    (``solar_open2_reference.select``: one absent expert for another of
    the same score is no jump). A position's own choices only: an
    earlier position's flipped expert reaches this one through the
    state and the attention alone, and stays inside the tolerance (the
    configuration's ``correct.why``)."""
    ref = plain_reference()
    eps = float(conf["correct"]["tie_eps"])
    hp = hyper(cfg)

    def fn(weights, tokens):
        return ref.forward(weights, tokens, hp, margins=True)[1] > eps

    return fn


# ---- operations and bytes, from shapes and the engine's counters

def _sizes(conf: dict) -> dict:
    h = conf["hidden_size"]
    lin = conf["linear_attn_config"]
    H, D, r = lin["num_heads"], lin["head_dim"], conf["kda_low_rank"]
    W = H * D
    hq = conf["num_attention_heads"] * conf["head_dim"]
    hkv = conf["num_key_value_heads"] * conf["head_dim"]
    layers = conf["num_hidden_layers"]
    n_gqa = sum(l < layers for l in conf["gqa_layers"])
    fe = conf["moe_intermediate_size"]
    return {
        "layers": layers, "n_gqa": n_gqa, "n_kda": layers - n_gqa,
        # every leaf of a layer's mixer and its two norms
        "kda": 4 * h * W + 3 * lin["short_conv_kernel_size"] * W + H + W
        + 2 * (h * r + r * W) + h * H + D + 2 * h,
        "gqa": (3 if conf["use_gqa_gate"] else 2) * h * hq + 2 * h * hkv
        + 2 * h,
        "expert": 3 * h * fe, "router": h * conf["router_width"],
        "shared": conf["n_shared_experts"],
        "head": h * conf["vocab_size"] + h,
        "state": H * D * D,                 # values a lane a KDA layer
        "kv_token": 2 * hkv,                # values a token a GQA layer
        "heads": conf["num_attention_heads"], "head_dim": conf["head_dim"],
        "kda_heads": H, "kda_dim": D,
        "state_bytes": _BYTES[conf["numerics"]["state_dtype"]]}


def experts_touched_per_layer(stats_delta: dict):
    """Held experts with at least one token, a step a layer, from the
    engine's counters; None where the program has none."""
    steps = stats_delta.get("moe_steps")
    if not steps:
        return None
    return stats_delta["moe_experts_touched_sum"] / steps


def state_lanes_per_step(conf: dict, stats_delta: dict):
    """Lanes whose state a decode step read and wrote, FROM THE
    COUNTER: ``state_lanes_sum`` over the decode steps (``moe_steps``
    over the expert layers, which are all layers); None where the
    program has no such counter."""
    steps = stats_delta.get("moe_steps")
    if not steps or "state_lanes_sum" not in stats_delta:
        return None
    return stats_delta["state_lanes_sum"] \
        / (steps / conf["num_hidden_layers"])


def decode_step_bytes(conf: dict, weight_bytes: int, kv_bytes: int,
                      live_tokens: float, stats_delta: dict):
    """Fewest bytes ANY program with these numerics moves in one
    decode step (``gpt2.py``'s docstring has the rule). At
    ``weight_bytes``: every layer's mixer, norms, router and shared
    expert and the head once; of the routed experts those that at least
    one token was routed to, FROM THE COUNTER. In
    ``numerics.state_dtype``: every LIVE lane's state in every KDA
    layer, read once and written once (the recurrence changes all of
    it), the lanes FROM THE COUNTER ``state_lanes_sum``. At
    ``kv_bytes``: the live tokens' keys and values in the GQA layers.
    Not the embedding table (a row a lane) and not the convolution's
    tail (0.3 MB a lane a layer against 8.4 MB of state: left out, so
    the count stays a lower bound). Without the counters neither routed
    expert nor state is counted (a lower bound still, never an
    assumption)."""
    z = _sizes(conf)
    touched = experts_touched_per_layer(stats_delta) or 0.0
    lanes = state_lanes_per_step(conf, stats_delta) or 0.0
    weights = z["n_kda"] * z["kda"] + z["n_gqa"] * z["gqa"] \
        + z["layers"] * (z["router"] + z["shared"] * z["expert"]
                         + touched * z["expert"]) + z["head"]
    return weights * weight_bytes \
        + lanes * z["n_kda"] * z["state"] * z["state_bytes"] * 2 \
        + live_tokens * z["n_gqa"] * z["kv_token"] * kv_bytes


def moe_experts_cost(conf: dict, weight_bytes: int, stats_delta: dict):
    """(bytes, FLOPs) the scope ``moe.experts`` needs in ONE decode
    step, all layers: the touched experts' three matrices once, and 2 x
    3 x h x f operations a token-choice that landed here. None without
    the counters."""
    touched = experts_touched_per_layer(stats_delta)
    if touched is None:
        return None
    z = _sizes(conf)
    here = stats_delta["moe_tokens_here_sum"] / stats_delta["moe_steps"]
    return (z["layers"] * touched * z["expert"] * weight_bytes,
            z["layers"] * here * 2 * z["expert"])


def kda_state_cost(conf: dict, stats_delta: dict):
    """(bytes, FLOPs) the scope ``kda.state`` needs in ONE decode step,
    all KDA layers: every live lane's state read once and written once
    in ``numerics.state_dtype``; a head's recurrence is two
    matrix-vector products with the state (``S^T (alpha k)``, ``S'^T
    q``: 2 x 2 x dk x dv), the decay of every element and the rank-one
    update (3 x dk x dv). None without the counter."""
    lanes = state_lanes_per_step(conf, stats_delta)
    if lanes is None:
        return None
    z = _sizes(conf)
    return (lanes * z["n_kda"] * z["state"] * z["state_bytes"] * 2,
            lanes * z["n_kda"] * z["state"] * 7)


def gqa_attention_cost(conf: dict, kv_bytes: int, live_tokens: float):
    """(bytes, FLOPs) the scope ``gqa.attention`` needs in ONE decode
    step, all GQA layers: every live token's keys and values once, and
    for each query head a score and a weighted sum over ``head_dim`` a
    live token."""
    z = _sizes(conf)
    return (z["n_gqa"] * live_tokens * z["kv_token"] * kv_bytes,
            z["n_gqa"] * live_tokens * z["heads"] * 2 * 2 * z["head_dim"])
