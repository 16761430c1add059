"""LongCat-Flash-Chat (``model_type`` ``longcat_flash``): two latent
attentions and two dense FFNs a layer beside a shortcut-connected
expert layer whose 768-wide softmax router has a selection bias and 256
identity experts, served by ``ray_tpu/models/scmoe.py`` through the
same ``DecodeEngine`` as the other three blocks. The contract of an
architecture module is in ``gpt2.py``'s docstring; this module's plain
reference is ``longcat_flash_reference.py``, beside it.

A configuration file of this architecture holds the published
``config.json`` keys at its top level under their own names (the cut
ones as held: ``num_layers``, ``n_routed_experts``, ``vocab_size``),
and beside them ``router_width`` (the router keeps its published width
whatever is held: 512 routed experts' scores and ``zero_expert_num``
identity experts' after them), ``expert_offset`` (the first expert held) and the
usual blocks (``numerics``, ``engine``, ``deployment``, ``correct``,
``init``).

What the rooflines' numerators count is here too (``decode_step_bytes``
for the whole step, ``mla_attention_cost`` and ``moe_experts_cost`` for
the two scopes), plain Python from shapes and from the engine's
counters, so whoever changes the program cannot change the yardstick.
"""
from __future__ import annotations

import os

import perf_harness as H

_HERE = os.path.dirname(os.path.abspath(__file__))


def plain_reference():
    """This architecture's plain reference, the module beside it."""
    return H.load_file(os.path.join(_HERE, "longcat_flash_reference.py"),
                       "perf_arch_")


def vocab(conf: dict):
    return conf["vocab_size"], conf["vocab_size"]


def model_cfg(conf: dict):
    """The program's ``ScMoEConfig`` at the sizes of a configuration
    file (the one place that maps published names to the program's)."""
    import jax.numpy as jnp

    from ray_tpu.models import scmoe

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    assert conf["attention_method"] == "MLA" \
        and conf["zero_expert_type"] == "identity" \
        and conf["mla_scale_q_lora"] is True \
        and conf["mla_scale_kv_lora"] is True, conf["name"]
    return scmoe.ScMoEConfig(
        vocab_size=conf["vocab_size"], n_layer=conf["num_layers"],
        d_model=conf["hidden_size"], n_head=conf["num_attention_heads"],
        q_rank=conf["q_lora_rank"], kv_rank=conf["kv_lora_rank"],
        nope_dim=conf["qk_nope_head_dim"],
        rope_dim=conf["qk_rope_head_dim"], v_dim=conf["v_head_dim"],
        d_ff=conf["ffn_hidden_size"],
        d_expert=conf["expert_ffn_hidden_size"],
        n_routed=conf["router_width"] - conf["zero_expert_num"],
        n_zero=conf["zero_expert_num"],
        experts_held=conf["n_routed_experts"],
        expert_offset=conf["expert_offset"], top_k=conf["moe_topk"],
        route_scale=float(conf["routed_scaling_factor"]),
        rope_theta=float(conf["rope_theta"]),
        max_seq=conf["max_position_embeddings"],
        eps=conf["rms_norm_eps"],
        dtype=dtypes[conf["numerics"]["compute_dtype"]],
        param_dtype=dtypes[conf["numerics"]["param_dtype"]],
        moe_block_rows=conf["engine"].get("moe_block_rows", 32))


def hyper(cfg) -> dict:
    """The reference's ``hp``: the program's config object as the
    plain dict ``longcat_flash_reference`` reads."""
    return {"heads": cfg.n_head, "nope": cfg.nope_dim,
            "rope": cfg.rope_dim, "v": cfg.v_dim, "kv_rank": cfg.kv_rank,
            "eps": cfg.eps, "theta": cfg.rope_theta, "top_k": cfg.top_k,
            "route_scale": cfg.route_scale, "n_routed": cfg.n_routed,
            "experts_held": cfg.experts_held,
            "expert_offset": cfg.expert_offset}


def param_shapes(cfg):
    """The tree (names, shapes, types) is the program's own:
    ``eval_shape`` of its ``init_params``."""
    import jax

    from ray_tpu.models import scmoe

    return jax.eval_shape(lambda k: scmoe.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def leaf_std(cfg, init: dict, name: str, shape):
    """``init["std"]``: the standard deviation by kind of leaf (the
    LAST key of ``init["std"]`` that is part of the leaf's path, so
    ``bias`` beats ``router``), else 1/sqrt(fan-in); norm scales are
    ones."""
    import math

    if "scale" in name:
        return None
    found = [float(val) for part, val in init["std"].items()
             if part in name]
    return found[-1] if found else 1.0 / math.sqrt(shape[-2])


def make_engine(params, cfg, conf: dict):
    from ray_tpu.serve.engine import DecodeEngine

    eng = conf["engine"]
    return DecodeEngine(
        params, cfg, slots=eng["slots"], chunk=eng["chunk"],
        max_len=eng["max_len"],
        prompt_buckets=tuple(eng["prompt_buckets"]),
        page_size=eng["page_size"], n_pages=eng["n_pages"],
        prefix_cache=eng["prefix_cache"],
        attn_kernel=eng["attn_kernel"], kv_dtype=eng["kv_dtype"])


def served_logits(engine, cfg, seqs, n_prompt: int, n_steps: int) -> dict:
    """``seqs`` [B, n_prompt + n_steps + 1] through the SERVED
    arithmetic: the paged prefill program (both attentions' latents
    into pages, keys and values materialised), then single decode steps
    through the latent pages with the up-projections absorbed, on a
    small pool of its own: the logits right after prefill (key 0) and
    after ``n_steps`` cached decode steps (key ``n_steps``), float32
    ``[B, rows]``. As ``axk1.served_logits``: the prefill is given the
    prompt less its last token and the first decode step yields the
    logits "after prefill"; ``_slot_decode_step_paged`` is the step
    function that the chunk program scans."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import scmoe as mm

    ps = engine.page_size
    B = len(seqs)
    total = n_prompt + n_steps
    max_pages = -(-(total + 1) // ps)
    bucket = next(b for b in engine.prompt_buckets if b >= n_prompt - 1)
    cache = mm.init_paged_cache(cfg, B, B * max_pages, ps,
                                engine.kv_dtype)
    pt = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    prefill = mm.jit_prefill_into_slot_paged(cfg, ps, 0.0,
                                             engine.kv_dtype)
    step = jax.jit(functools.partial(
        mm._slot_decode_step_paged, cfg=cfg, page_size=ps,
        kv_dtype=engine.kv_dtype, attn_kernel=engine.attn_kernel),
        donate_argnums=(1,))
    params = engine.params
    for b in range(B):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n_prompt - 1] = seqs[b, :n_prompt - 1]
        _tok, cache, _key = prefill(
            params, cache, padded, np.int32(n_prompt - 1), np.int32(0),
            pt[b], np.int32(mm.PT_SENTINEL), np.int32(b),
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
    """[rows, positions]: in EVERY expert layer the reference's top
    ``moe_topk`` of ``p + b`` for that position clears its edge by
    ``correct.tie_eps`` for every expert that counts here: each routed
    expert HELD and each of the identity experts is that far from
    crossing (``longcat_flash_reference.select``; an absent routed
    expert going for another is no jump). A position's own choices
    only, as ``axk1.decidable``."""
    ref = plain_reference()
    eps = float(conf["correct"]["tie_eps"])
    hp = hyper(cfg)

    def fn(weights, tokens):
        return ref.forward(weights, tokens, hp, margins=True)[1] > eps

    return fn


# ---- operations and bytes, from shapes and the engine's counters

def _sizes(conf: dict) -> dict:
    h, H = conf["hidden_size"], conf["num_attention_heads"]
    rq, rkv = conf["q_lora_rank"], conf["kv_lora_rank"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    attention = h * rq + rq * H * (dn + dr) + h * (rkv + dr) \
        + rkv * H * (dn + dv) + H * dv * h + rq + rkv
    width = conf["router_width"]
    return {"h": h, "H": H, "latent": rkv + dr, "kv_rank": rkv,
            "attention": attention,
            "expert": 3 * h * conf["expert_ffn_hidden_size"],
            "router": h * width + width,
            "dense_ffn": 3 * h * conf["ffn_hidden_size"],
            "head": h * conf["vocab_size"] + h,
            "layers": conf["num_layers"], "top_k": conf["moe_topk"]}


def experts_touched_per_layer(stats_delta: dict):
    """Held experts with at least one token, a step a layer, from the
    engine's counters; None where the program has none."""
    steps = stats_delta.get("moe_steps")
    if not steps:
        return None
    return stats_delta["moe_experts_touched_sum"] / steps


def decode_step_bytes(conf: dict, weight_bytes: int, kv_bytes: int,
                      live_tokens: float, stats_delta: dict):
    """Fewest bytes ANY program with these numerics moves in one
    decode step (``gpt2.py``'s docstring has the rule). At
    ``weight_bytes``: every layer's two attentions, two dense FFNs,
    router and norms once, and the head; of the routed experts those
    that at least one token was routed to, FROM THE COUNTER
    (``moe_experts_touched_sum / moe_steps`` a layer, never all held by
    assumption; an identity expert holds nothing and counts nothing).
    At ``kv_bytes``: the live tokens' latents in both attentions of
    every layer. Not the embedding table: a step reads one row of it a
    lane. Without the counters no routed expert is counted at all (a
    lower bound still, and never an assumption)."""
    touched = experts_touched_per_layer(stats_delta) or 0.0
    z = _sizes(conf)
    weights = z["layers"] * (2 * z["attention"] + 2 * z["dense_ffn"]
                             + z["router"] + 4 * z["h"]
                             + touched * z["expert"]) + z["head"]
    return weights * weight_bytes \
        + live_tokens * 2 * z["layers"] * z["latent"] * kv_bytes


def moe_experts_cost(conf: dict, weight_bytes: int, stats_delta: dict):
    """(bytes, FLOPs) the scope ``moe.experts`` needs in ONE decode
    step, all expert layers: the touched experts' three matrices once,
    and 2 x 3 x h x f operations a token-choice that landed on a held
    routed expert (a choice of an identity expert costs none and is no
    part of that scope). None without the counters."""
    touched = experts_touched_per_layer(stats_delta)
    if touched is None:
        return None
    z = _sizes(conf)
    here = stats_delta["moe_tokens_here_sum"] / stats_delta["moe_steps"]
    return (z["layers"] * touched * z["expert"] * weight_bytes,
            z["layers"] * here * 2 * z["expert"])


def mla_attention_cost(conf: dict, kv_bytes: int, live_tokens: float):
    """(bytes, FLOPs) the scope ``mla.attention`` needs in ONE decode
    step, both attentions of every layer: every live token's latent
    row once an attention, and for each of the H heads a 576-wide score
    and a 512-wide weighted sum a live token an attention."""
    z = _sizes(conf)
    n = 2 * z["layers"]
    return (n * live_tokens * z["latent"] * kv_bytes,
            n * live_tokens * z["H"] * 2 * (z["latent"] + z["kv_rank"]))
