"""DeepSeek-V3.2-Exp (``model_type`` ``deepseek_v32``): A.X-K1's family
of block (latent attention, leading dense layers, sigmoid-routed expert
layers with a shared expert) with a selection bias on the router and,
in every layer, a lightning INDEXER whose top ``index_topk`` cached
tokens are all the attention reads; served by
``ray_tpu/models/dsa_moe.py`` through the same ``DecodeEngine`` as
every serving cell. The contract of an architecture module is in
``gpt2.py``'s docstring; this module's plain reference is
``deepseek_v32_reference.py``, beside it.

A configuration file of this architecture holds the published
``config.json`` keys at its top level under their own names (the cut
ones as held: ``num_hidden_layers``, ``n_routed_experts``,
``vocab_size``), and beside them ``dense_layers_held`` (how many of the
``first_k_dense_replace`` leading dense layers are among the held
ones: that key stays as published), ``router_width`` (the router keeps
its published width whatever is held), ``expert_offset`` (the first
expert held) and the usual blocks
(``numerics``, ``engine``, ``deployment``, ``correct``, ``init``).

What the rooflines' numerators count is here too (``decode_step_bytes``
for the whole step, ``dsa_attention_cost`` for the three scopes of the
sparse attention, ``moe_experts_cost`` for the experts), plain Python
from shapes and from the engine's counters, so whoever changes the
program cannot change the yardstick.
"""
from __future__ import annotations

import os

import perf_harness as H

_HERE = os.path.dirname(os.path.abspath(__file__))


def plain_reference():
    """This architecture's plain reference, the module beside it."""
    return H.load_file(os.path.join(_HERE, "deepseek_v32_reference.py"),
                       "perf_arch_")


def vocab(conf: dict):
    return conf["vocab_size"], conf["vocab_size"]


def model_cfg(conf: dict):
    """The program's ``DSAMoEConfig`` at the sizes of a configuration
    file (the one place that maps published names to the program's)."""
    import jax.numpy as jnp

    from ray_tpu.models import dsa_moe

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    rs = conf["rope_scaling"]
    return dsa_moe.DSAMoEConfig(
        vocab_size=conf["vocab_size"], n_layer=conf["num_hidden_layers"],
        n_dense=conf["dense_layers_held"], d_model=conf["hidden_size"],
        n_head=conf["num_attention_heads"], q_rank=conf["q_lora_rank"],
        kv_rank=conf["kv_lora_rank"], nope_dim=conf["qk_nope_head_dim"],
        rope_dim=conf["qk_rope_head_dim"], v_dim=conf["v_head_dim"],
        d_ff=conf["intermediate_size"],
        d_expert=conf["moe_intermediate_size"],
        n_routed=conf["router_width"],
        experts_held=conf["n_routed_experts"],
        expert_offset=conf["expert_offset"], n_group=conf["n_group"],
        topk_group=conf["topk_group"], top_k=conf["num_experts_per_tok"],
        norm_topk=conf["norm_topk_prob"],
        route_scale=conf["routed_scaling_factor"],
        shared_expert=conf["n_shared_experts"] > 0,
        rope_theta=float(conf["rope_theta"]),
        rope_factor=float(rs["factor"]),
        rope_orig_max=rs["original_max_position_embeddings"],
        rope_beta_fast=float(rs["beta_fast"]),
        rope_beta_slow=float(rs["beta_slow"]),
        mscale_all_dim=float(rs["mscale_all_dim"]),
        max_seq=conf["max_position_embeddings"],
        eps=conf["rms_norm_eps"],
        dtype=dtypes[conf["numerics"]["compute_dtype"]],
        param_dtype=dtypes[conf["numerics"]["param_dtype"]],
        moe_block_rows=conf["engine"].get("moe_block_rows", 32),
        index_heads=conf["index_n_heads"],
        index_dim=conf["index_head_dim"], index_topk=conf["index_topk"])


def hyper(cfg, conf: dict = None) -> dict:
    """The reference's ``hp``: the program's config object as the
    plain dict ``deepseek_v32_reference`` reads (with ``conf``, also
    the selection's ``index_tie_eps``)."""
    hp = {"heads": cfg.n_head, "nope": cfg.nope_dim,
          "rope": cfg.rope_dim, "v": cfg.v_dim, "kv_rank": cfg.kv_rank,
          "eps": cfg.eps, "theta": cfg.rope_theta,
          "factor": cfg.rope_factor, "orig_max": cfg.rope_orig_max,
          "beta_fast": cfg.rope_beta_fast,
          "beta_slow": cfg.rope_beta_slow,
          "mscale_all_dim": cfg.mscale_all_dim, "n_group": cfg.n_group,
          "topk_group": cfg.topk_group, "top_k": cfg.top_k,
          "norm_topk": cfg.norm_topk, "route_scale": cfg.route_scale,
          "experts_held": cfg.experts_held,
          "expert_offset": cfg.expert_offset,
          "index_heads": cfg.index_heads, "index_dim": cfg.index_dim,
          "index_topk": cfg.index_topk}
    if conf is not None:
        hp["index_tie_eps"] = float(conf["correct"]["index_tie_eps"])
    return hp


def param_shapes(cfg):
    """The tree (names, shapes, types) is the program's own:
    ``eval_shape`` of its ``init_params``."""
    import jax

    from ray_tpu.models import dsa_moe

    return jax.eval_shape(lambda k: dsa_moe.init_params(k, cfg),
                          jax.random.PRNGKey(0))


def leaf_std(cfg, init: dict, name: str, shape):
    """``init["std"]``: the standard deviation by kind of leaf (the
    first key that is part of the leaf's path), else 1/sqrt(fan-in);
    norm scales are ones. A vector (the index key's LayerNorm bias, the
    router's selection bias) has no fan-in and must be listed."""
    import math

    if "scale" in name:
        return None
    for part, val in init["std"].items():
        if part in name:
            return float(val)
    return 1.0 / math.sqrt(shape[-2])


def make_engine(params, cfg, conf: dict):
    from ray_tpu.models import dsa_moe
    from ray_tpu.serve.engine import DecodeEngine

    eng = conf["engine"]
    # a prompt past index_topk is refused, never served densely
    dsa_moe.check_prompt_buckets(cfg, eng["prompt_buckets"])
    return DecodeEngine(
        params, cfg, slots=eng["slots"], chunk=eng["chunk"],
        max_len=eng["max_len"],
        prompt_buckets=tuple(eng["prompt_buckets"]),
        page_size=eng["page_size"], n_pages=eng["n_pages"],
        prefix_cache=eng["prefix_cache"],
        attn_kernel=eng["attn_kernel"], kv_dtype=eng["kv_dtype"])


def served_logits(engine, cfg, seqs, n_prompt: int, n_steps: int) -> dict:
    """``seqs`` [B, n_prompt + n_steps + 1] through the SERVED
    arithmetic: the paged prefill program (latents and index keys into
    pages, attention over every cached token), then ``n_steps + 1``
    decode steps through the pages (index scores, the selection, the
    absorbed attention over the picked tokens), on a small pool of its
    own: the logits right after prefill (key 0) and after ``n_steps``
    cached decode steps (key ``n_steps``), float32 ``[B, rows]``.

    As in ``axk1.served_logits`` the prefill is given the prompt less
    its last token, the first decode step yields the logits "after
    prefill", and the tokens fed afterwards are the sequence's own;
    ``_slot_decode_step_paged`` is the step function that the chunk
    program scans. Here the steps are ONE scan too (a thousand
    launches of one step each would cost the set-up a launch's host
    time apiece), teacher-forced where the chunk program samples."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import dsa_moe as mm

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
    params = engine.params
    for b in range(B):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n_prompt - 1] = seqs[b, :n_prompt - 1]
        _tok, cache, _key = prefill(
            params, cache, padded, np.int32(n_prompt - 1), np.int32(0),
            pt[b], np.int32(mm.PT_SENTINEL), np.int32(b),
            jax.random.PRNGKey(0))

    def steps(params, cache, tokens, pt):
        active = jnp.ones((B,), bool)

        def one(carry, fed):
            cache, first = carry
            i, tok = fed
            logits, cache, _counts = mm._slot_decode_step_paged(
                params, cache, tok, active, pt, cfg, ps, engine.kv_dtype,
                engine.attn_kernel)
            return (cache, jnp.where(i == 0, logits, first)), None

        (cache, first), _ = jax.lax.scan(
            one, (cache, jnp.zeros((B, cfg.vocab_size), jnp.float32)),
            (jnp.arange(n_steps), tokens[:-1]))
        last, _cache, _counts = mm._slot_decode_step_paged(
            params, cache, tokens[-1], active, pt, cfg, ps,
            engine.kv_dtype, engine.attn_kernel)
        return first, last

    fed = jnp.asarray(seqs[:, n_prompt - 1:total].T)     # [n_steps + 1, B]
    first, last = jax.jit(steps, donate_argnums=(1,))(
        params, cache, fed, jnp.asarray(pt))
    return {0: np.asarray(first, np.float32),
            n_steps: np.asarray(last, np.float32)}


def reference(cfg):
    import functools

    ref = plain_reference()
    hp = hyper(cfg)
    return (ref.from_program, functools.partial(ref.forward, hp=hp),
            functools.partial(ref.loss, hp=hp))


def decidable(cfg, conf: dict):
    """[rows, positions]: every discrete choice the reference makes
    for that position, in EVERY layer, clears its edge. The experts':
    the ``topk_group`` groups kept and the ``top_k`` experts chosen
    among theirs clear theirs by ``correct.tie_eps`` in the biased
    sigmoid scores (``deepseek_v32_reference.select``, the held
    experts' edge as ``axk1.decidable`` has it). The selection's: the
    cached tokens whose index scores lie within ``correct.
    index_tie_eps`` of the edge of ``S_t``, each on the side it could
    cross from, carry TOGETHER (or would carry on crossing) less than
    ``correct.index_tie_weight`` of every head's softmax: a bfloat16
    program and the float32 reference put such tokens on different
    sides, and where they carry real weight the logits part by their
    share of a head. A position's own choices only (another
    position's reach this one through attention, diluted: the
    configuration's ``correct.why``)."""
    ref = plain_reference()
    ck = conf["correct"]
    eps, weight = float(ck["tie_eps"]), float(ck["index_tie_weight"])
    hp = hyper(cfg, conf)

    def fn(weights, tokens):
        margin, edge = ref.forward(weights, tokens, hp, margins=True)
        return (margin > eps) & (edge < weight)

    return fn


# ---- operations and bytes, from shapes and the engine's counters

def _sizes(conf: dict) -> dict:
    h, H = conf["hidden_size"], conf["num_attention_heads"]
    rq, rkv = conf["q_lora_rank"], conf["kv_lora_rank"]
    dn, dr, dv = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                  conf["v_head_dim"])
    fe = conf["moe_intermediate_size"]
    Hi, Di = conf["index_n_heads"], conf["index_head_dim"]
    attention = h * rq + rq * H * (dn + dr) + h * (rkv + dr) \
        + rkv * H * (dn + dv) + H * dv * h
    return {"h": h, "H": H, "latent": rkv + dr, "kv_rank": rkv,
            "attention": attention, "norms": 2 * h + rq + rkv,
            "indexer": rq * Hi * Di + h * Di + h * Hi + 2 * Di,
            "index_heads": Hi, "index_dim": Di,
            "expert": 3 * h * fe,
            "router": h * conf["router_width"] + conf["router_width"],
            "dense_ffn": 3 * h * conf["intermediate_size"],
            "head": h * conf["vocab_size"] + h,
            "dense_layers": conf["dense_layers_held"],
            "expert_layers": conf["num_hidden_layers"]
            - conf["dense_layers_held"],
            "layers": conf["num_hidden_layers"],
            "shared": conf["n_shared_experts"]}


def experts_touched_per_layer(stats_delta: dict):
    """Held experts with at least one token, a step a layer, from the
    engine's counters; None where the program has none."""
    steps = stats_delta.get("moe_steps")
    if not steps:
        return None
    return stats_delta["moe_experts_touched_sum"] / steps


def selection_per_step(conf: dict, stats_delta: dict):
    """(index keys scored, tokens picked) a decode STEP, summed over
    the lanes, from the engine's counters (``dsa_tokens_scanned_sum``,
    ``dsa_tokens_selected_sum``; the steps are ``moe_steps`` over the
    expert layers); None where the program has none."""
    z = _sizes(conf)
    moe_steps = stats_delta.get("moe_steps")
    if not moe_steps or "dsa_tokens_scanned_sum" not in stats_delta:
        return None
    steps = moe_steps / z["expert_layers"]
    return (stats_delta["dsa_tokens_scanned_sum"] / steps,
            stats_delta["dsa_tokens_selected_sum"] / steps)


def decode_step_bytes(conf: dict, weight_bytes: int, kv_bytes: int,
                      live_tokens: float, stats_delta: dict):
    """Fewest bytes ANY program with these numerics moves in one
    decode step (``gpt2.py``'s docstring has the rule). At
    ``weight_bytes``: the dense layers and the head once; each layer's
    attention, indexer and norms once; each expert layer's router and
    shared expert once; of the routed experts those that at least one
    token was routed to, FROM THE COUNTER. At ``kv_bytes``, FROM THE
    COUNTERS too and never from ``live_tokens`` (the client's stamps
    say nothing of what was picked): in every layer the index key of
    every cached token scanned and the latent row of every token
    PICKED, the mechanism's point being that the others are not read.
    Without the counters neither a routed expert nor a cached value is
    counted (a lower bound still, and never an assumption)."""
    touched = experts_touched_per_layer(stats_delta) or 0.0
    scanned, picked = selection_per_step(conf, stats_delta) or (0.0, 0.0)
    z = _sizes(conf)
    weights = z["layers"] * (z["attention"] + z["indexer"] + z["norms"]) \
        + z["dense_layers"] * z["dense_ffn"] \
        + z["expert_layers"] * (z["router"] + z["shared"] * z["expert"]
                                + touched * z["expert"]) \
        + z["head"]
    return weights * weight_bytes + z["layers"] * kv_bytes * (
        scanned * z["index_dim"] + picked * z["latent"])


def moe_experts_cost(conf: dict, weight_bytes: int, stats_delta: dict):
    """(bytes, FLOPs) the scope ``moe.experts`` needs in ONE decode
    step, all expert layers, as ``axk1.moe_experts_cost`` counts them.
    None without the counters."""
    touched = experts_touched_per_layer(stats_delta)
    if touched is None:
        return None
    z = _sizes(conf)
    here = stats_delta["moe_tokens_here_sum"] / stats_delta["moe_steps"]
    return (z["expert_layers"] * touched * z["expert"] * weight_bytes,
            z["expert_layers"] * here * 2 * z["expert"])


def dsa_attention_cost(conf: dict, kv_bytes: int, stats_delta: dict):
    """(bytes, FLOPs) the MECHANISM needs in ONE decode step, all
    layers, whatever implements it (the scopes ``dsa.index``,
    ``dsa.select`` and ``dsa.attention`` together): every cached
    token's index key once (``index_head_dim`` values) and every PICKED
    token's latent row once (576 values), from the engine's counters;
    for each index head a ``index_head_dim``-wide score a cached token,
    and for each attention head a 576-wide score and a 512-wide
    weighted sum a picked token. A program that reads the rows it
    masks shows the difference as headroom. None without the
    counters."""
    per_step = selection_per_step(conf, stats_delta)
    if per_step is None:
        return None
    scanned, picked = per_step
    z = _sizes(conf)
    return (z["layers"] * kv_bytes * (scanned * z["index_dim"]
                                      + picked * z["latent"]),
            z["layers"] * 2 * (
                scanned * z["index_heads"] * z["index_dim"]
                + picked * z["H"] * (z["latent"] + z["kv_rank"])))
