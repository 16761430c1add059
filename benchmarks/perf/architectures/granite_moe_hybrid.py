"""granite-4.0-h-small (``model_type`` ``granitemoehybrid``): Mamba-2
layers whose state lives per slot and NoPE grouped-query attention
layers over key/value pages IN TURN (9 : 1, by ``layer_types``), each
followed by a softmax-routed expert layer (the top 10 LOGITS of 72)
beside a shared MLP, one multiplier on both residual branches and a
tied head; served by ``ray_tpu/models/ssm_moe.py`` through the same
``DecodeEngine`` as the other blocks. The contract of an architecture
module is in ``gpt2.py``'s docstring; this module's plain reference is
``granite_moe_hybrid_reference.py``, beside it.

A configuration file of this architecture holds the published
``config.json`` keys at its top level under their own names (the cut
ones as held: ``num_hidden_layers``, ``num_local_experts``,
``vocab_size``; ``layer_types`` whole, of which the layers held are the
first ``num_hidden_layers``), and beside them ``router_width`` (the
router keeps its published width whatever is held), ``expert_offset``
(the first expert held) and the usual blocks (``numerics`` with
``state_dtype``, ``engine``, ``deployment``, ``correct``, ``init``).
``head_dim`` is not a published key: it is ``hidden_size /
num_attention_heads`` (``assumed``).

``init`` has ``std`` and ``mean`` BY LEAF NAME (``wq``, ``down``; the
shared MLP's leaves as ``shared_down``): ``perf_deployment.
seeded_params`` draws every leaf around zero; ``with_init_means`` adds
the means (``dt_bias``, ``A_log``, the skip ``D`` and the gated norm's
weight), and ``make_engine`` (and every test that seeds weights) goes
through it.

What the rooflines' numerators count is here too (``decode_step_bytes``
for the whole step; ``ssm_state_cost``, ``moe_experts_cost`` and
``gqa_attention_cost`` for three scopes), plain Python from shapes and
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
    return H.load_file(
        os.path.join(_HERE, "granite_moe_hybrid_reference.py"),
        "perf_arch_")


def vocab(conf: dict):
    return conf["vocab_size"], conf["vocab_size"]


def layer_types(conf: dict):
    """The mixers of the layers HELD: the first ``num_hidden_layers``
    of the published pattern."""
    return tuple(conf["layer_types"][:conf["num_hidden_layers"]])


def model_cfg(conf: dict):
    """The program's ``SSMMoEConfig`` at the sizes of a configuration
    file (the one place that maps published names to the program's)."""
    import jax.numpy as jnp

    from ray_tpu.models import ssm_moe

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    num = conf["numerics"]
    if conf["position_embedding_type"] != "nope" or conf["rope_scaling"] \
            or not conf["tie_word_embeddings"] \
            or not conf["mamba_conv_bias"] or conf["mamba_proj_bias"] \
            or conf["attention_bias"] or conf["hidden_act"] != "silu" \
            or conf["normalization_function"] != "rmsnorm" \
            or conf["mamba_n_heads"] * conf["mamba_d_head"] \
            != conf["mamba_expand"] * conf["hidden_size"]:
        raise ValueError(
            "the program has no positions, a tied head, RMSNorm, SiLU, a "
            "Mamba-2 mixer mamba_expand times the hidden size wide and "
            "no bias but the convolution's")
    return ssm_moe.SSMMoEConfig(
        vocab_size=conf["vocab_size"], layer_types=layer_types(conf),
        d_model=conf["hidden_size"], n_head=conf["num_attention_heads"],
        n_kv_head=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        attn_mult=conf["attention_multiplier"],
        ssm_heads=conf["mamba_n_heads"], ssm_head_dim=conf["mamba_d_head"],
        ssm_state=conf["mamba_d_state"], ssm_groups=conf["mamba_n_groups"],
        conv_size=conf["mamba_d_conv"], ssm_chunk=conf["mamba_chunk_size"],
        d_expert=conf["intermediate_size"], n_routed=conf["router_width"],
        experts_held=conf["num_local_experts"],
        expert_offset=conf["expert_offset"],
        top_k=conf["num_experts_per_tok"],
        d_shared=conf["shared_intermediate_size"],
        embed_mult=float(conf["embedding_multiplier"]),
        resid_mult=conf["residual_multiplier"],
        logits_scale=float(conf["logits_scaling"]),
        max_seq=conf["max_position_embeddings"], eps=conf["rms_norm_eps"],
        dtype=dtypes[num["compute_dtype"]],
        param_dtype=dtypes[num["param_dtype"]],
        state_dtype=dtypes[num["state_dtype"]],
        moe_block_rows=conf["engine"].get("moe_block_rows", 32))


def hyper(cfg) -> dict:
    """The reference's ``hp``: the program's config object as the plain
    dict ``granite_moe_hybrid_reference`` reads, the constants under
    their published names."""
    return {"heads": cfg.n_head, "kv_heads": cfg.n_kv_head,
            "head_dim": cfg.head_dim, "ssm_heads": cfg.ssm_heads,
            "ssm_head_dim": cfg.ssm_head_dim, "ssm_state": cfg.ssm_state,
            "ssm_groups": cfg.ssm_groups, "conv": cfg.conv_size,
            "eps": cfg.eps, "layer_types": tuple(cfg.layer_types),
            "top_k": cfg.top_k, "experts_held": cfg.experts_held,
            "expert_offset": cfg.expert_offset,
            "embedding_multiplier": cfg.embed_mult,
            "residual_multiplier": cfg.resid_mult,
            "attention_multiplier": cfg.attn_mult,
            "logits_scaling": cfg.logits_scale}


def param_shapes(cfg):
    """The tree (names, shapes, types) is the program's own:
    ``eval_shape`` of its ``init_params``."""
    import jax

    from ray_tpu.models import ssm_moe

    return jax.eval_shape(lambda k: ssm_moe.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def _leaf(name: str) -> str:
    """A leaf's kind from its path (``jax.tree_util.keystr``): its own
    name, its parent's where it is a ``kernel``, and ``shared_<name>``
    for the shared MLP's three."""
    import re

    parts = re.findall(r"'(\w+)'", name)
    leaf = parts[-2] if parts[-1] == "kernel" else parts[-1]
    return "shared_" + leaf if "shared" in parts else leaf


def leaf_std(cfg, init: dict, name: str, shape):
    """``init["std"]`` by the leaf's kind (``_leaf``), else
    1/sqrt(fan-in); the norms' scales are ones."""
    import math

    kind = _leaf(name)
    if kind.endswith("scale"):
        return None
    std = init["std"].get(kind)
    return float(std) if std is not None else 1.0 / math.sqrt(shape[-2])


def with_init_means(params, init: dict):
    """``init["mean"]`` added to the leaves it names (by ``_leaf``): the
    seeded fill draws around zero. The other leaves are passed on as
    they are, not copied."""
    import jax

    def shift(path, leaf):
        mean = init.get("mean", {}).get(_leaf(jax.tree_util.keystr(path)))
        if mean is None:
            return leaf
        return (leaf.astype("float32") + mean).astype(leaf.dtype)

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
    arithmetic: the paged prefill program (the attention layers' keys
    and values into pages, the Mamba layers' chunked form into the
    slot's state and convolution tail), then single decode steps
    (attention over the pages, the recurrence on the state, the expert
    layer a row a lane), on a small pool of its own: the logits right
    after prefill (key 0) and after ``n_steps`` cached decode steps
    (key ``n_steps``), float32 ``[B, rows]``.

    As in ``gpt2.served_logits``: the prefill is given the prompt less
    its last token and the first decode step yields the logits "after
    prefill"; the tokens fed afterwards are the sequence's own.
    ``_slot_decode_step_paged`` is the step function that the chunk
    program scans."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import ssm_moe as sm

    ps = engine.page_size
    B = len(seqs)
    total = n_prompt + n_steps
    max_pages = -(-(total + 1) // ps)
    bucket = next(b for b in engine.prompt_buckets if b >= n_prompt - 1)
    cache = sm.init_paged_cache(cfg, B, B * max_pages, ps,
                                engine.kv_dtype)
    pt = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    prefill = sm.jit_prefill_into_slot_paged(cfg, ps, 0.0,
                                             engine.kv_dtype)
    step = jax.jit(functools.partial(
        sm._slot_decode_step_paged, cfg=cfg, page_size=ps,
        kv_dtype=engine.kv_dtype, attn_kernel=engine.attn_kernel),
        donate_argnums=(1,))
    params = engine.params
    for b in range(B):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n_prompt - 1] = seqs[b, :n_prompt - 1]
        _tok, cache, _key = prefill(
            params, cache, padded, np.int32(n_prompt - 1), np.int32(0),
            pt[b], np.int32(sm.PT_SENTINEL), np.int32(b),
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
    ``num_experts_per_tok`` of the ``router_width`` LOGITS at that
    position keeps every expert HELD here ``correct.tie_eps`` (in the
    logit) from crossing its edge
    (``granite_moe_hybrid_reference.select``: one absent expert for
    another of the same logit is no jump). A position's own choices
    only: an earlier position's flipped expert reaches this one through
    the state and the attention alone, and stays inside the tolerance
    (the configuration's ``correct.why``)."""
    ref = plain_reference()
    eps = float(conf["correct"]["tie_eps"])
    hp = hyper(cfg)

    def fn(weights, tokens):
        return ref.forward(weights, tokens, hp, margins=True)[1] > eps

    return fn


# ---- operations and bytes, from shapes and the engine's counters

def _sizes(conf: dict) -> dict:
    h = conf["hidden_size"]
    H, P, N = conf["mamba_n_heads"], conf["mamba_d_head"], \
        conf["mamba_d_state"]
    W, bc = H * P, conf["mamba_n_groups"] * N
    hd = h // conf["num_attention_heads"]
    hq, hkv = h, conf["num_key_value_heads"] * hd
    kinds = layer_types(conf)
    return {
        "layers": len(kinds), "n_attn": kinds.count("attention"),
        "n_ssm": kinds.count("mamba"),
        # every leaf of a mixer
        "attn": 2 * h * hq + 2 * h * hkv,
        "ssm": h * (2 * W + 2 * bc + H) + W * h
        + (conf["mamba_d_conv"] + 1) * (W + 2 * bc) + 3 * H + W,
        # what every layer has beside its mixer and its routed experts
        "rest": 2 * h + h * conf["router_width"]
        + 3 * h * conf["shared_intermediate_size"],
        "expert": 3 * h * conf["intermediate_size"],
        "head": h * conf["vocab_size"] + h,
        "state": H * P * N,                 # values a lane a Mamba layer
        "kv_token": 2 * hkv,                # values a token an attn layer
        "heads": conf["num_attention_heads"], "head_dim": hd,
        "state_bytes": _BYTES[conf["numerics"]["state_dtype"]]}


def _per_step(conf: dict, stats_delta: dict, key: str):
    """A counter a decode STEP: ``moe_steps`` counts expert layers run,
    ``num_hidden_layers`` a step. None where the program has not the
    counter."""
    layers_run = stats_delta.get("moe_steps")
    if not layers_run or key not in stats_delta:
        return None
    return stats_delta[key] / (layers_run / conf["num_hidden_layers"])


def experts_touched_per_layer(conf: dict, stats_delta: dict):
    """Held experts with at least one token, a step a layer."""
    touched = _per_step(conf, stats_delta, "moe_experts_touched_sum")
    return None if touched is None else touched / conf["num_hidden_layers"]


def state_lanes_per_step(conf: dict, stats_delta: dict):
    """Lanes whose state a decode step read and wrote, FROM THE
    COUNTER ``state_lanes_sum``."""
    return _per_step(conf, stats_delta, "state_lanes_sum")


def decode_step_bytes(conf: dict, weight_bytes: int, kv_bytes: int,
                      live_tokens: float, stats_delta: dict):
    """Fewest bytes ANY program with these numerics moves in one
    decode step (``gpt2.py``'s docstring has the rule). At
    ``weight_bytes``: every layer's mixer, norms, router and shared MLP
    and the table once (as the HEAD: tied; the embedding's gather of a
    row a lane is not counted); of the routed experts those that at
    least one token was routed to, FROM THE COUNTER. In
    ``numerics.state_dtype``: every LIVE lane's state in every Mamba
    layer, read once and written once (the recurrence changes all of
    it), the lanes FROM THE COUNTER ``state_lanes_sum``. At
    ``kv_bytes``: the live tokens' keys and values in the attention
    layers. Not the convolution's tail (51 KB a lane a layer against
    4.2 MB of state: left out, so the count stays a lower bound).
    Without the counters neither routed expert nor state is counted (a
    lower bound still, never an assumption)."""
    z = _sizes(conf)
    touched = experts_touched_per_layer(conf, stats_delta) or 0.0
    lanes = state_lanes_per_step(conf, stats_delta) or 0.0
    weights = z["n_ssm"] * z["ssm"] + z["n_attn"] * z["attn"] \
        + z["layers"] * (z["rest"] + touched * z["expert"]) + z["head"]
    return weights * weight_bytes \
        + lanes * z["n_ssm"] * z["state"] * z["state_bytes"] * 2 \
        + live_tokens * z["n_attn"] * z["kv_token"] * kv_bytes


def ssm_state_cost(conf: dict, stats_delta: dict):
    """(bytes, FLOPs) the scope ``ssm.state`` needs in ONE decode step,
    all Mamba layers: every live lane's state read once and written
    once in ``numerics.state_dtype``; an element of it takes the decay
    (1), the rank-one term and its addition (2) and its part of ``S C``
    (2). None without the counter."""
    lanes = state_lanes_per_step(conf, stats_delta)
    if lanes is None:
        return None
    z = _sizes(conf)
    return (lanes * z["n_ssm"] * z["state"] * z["state_bytes"] * 2,
            lanes * z["n_ssm"] * z["state"] * 5)


def moe_experts_cost(conf: dict, weight_bytes: int, stats_delta: dict):
    """(bytes, FLOPs) the scope ``moe.experts`` needs in ONE decode
    step, all layers: the touched experts' three matrices once, and 2 x
    3 x h x f operations a token-choice that landed here. None without
    the counters."""
    touched = experts_touched_per_layer(conf, stats_delta)
    here = _per_step(conf, stats_delta, "moe_tokens_here_sum")
    if touched is None or here is None:
        return None
    z = _sizes(conf)
    return (z["layers"] * touched * z["expert"] * weight_bytes,
            here * 2 * z["expert"])


def gqa_attention_cost(conf: dict, kv_bytes: int, live_tokens: float):
    """(bytes, FLOPs) the scope ``smoe.attention`` needs in ONE decode
    step, all attention layers: every live token's keys and values
    once, and for each query head a score and a weighted sum over
    ``head_dim`` a live token, whatever implements the scope."""
    z = _sizes(conf)
    return (z["n_attn"] * live_tokens * z["kv_token"] * kv_bytes,
            z["n_attn"] * live_tokens * z["heads"] * 2 * 2 * z["head_dim"])
