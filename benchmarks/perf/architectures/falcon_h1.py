"""Falcon-H1-34B-Instruct (``model_type`` ``falcon_h1``): a PARALLEL
hybrid layer, a Mamba-2 mixer whose state lives per slot BESIDE rotary
grouped-query attention over key/value pages, both on the same normed
input in every layer, a dense gated MLP, an untied 261,120-row head and
a constant multiplier on every branch; served by
``ray_tpu/models/ssm_hybrid.py`` through the same ``DecodeEngine`` as
the other blocks. The contract of an architecture module is in
``gpt2.py``'s docstring; this module's plain reference is
``falcon_h1_reference.py``, beside it.

A configuration file of this architecture holds the published
``config.json`` keys at its top level under their own names
(``num_hidden_layers`` as held) and beside them the usual blocks
(``numerics`` with ``state_dtype``, ``engine``, ``deployment``,
``correct``, ``init``). There is no ``decidable``: the model makes no
discrete choice, so every vector and every served token is compared.

``init`` has ``std`` (by kind of leaf) and ``mean``:
``perf_deployment.seeded_params`` draws every leaf around zero;
``with_init_means`` adds the means (``dt_bias``, ``A_log``, the skip
``D`` and the gated norm's weight), and ``make_engine`` (and every
test that seeds weights) goes through it.

What the rooflines' numerators count is here too (``decode_step_bytes``
for the whole step; ``ssm_state_cost`` and ``hgqa_attention_cost`` for
two scopes), plain Python from shapes and from the engine's counters,
in the types the configuration STATES (``numerics``), never in how the
program holds anything.
"""
from __future__ import annotations

import os

import perf_harness as H

_HERE = os.path.dirname(os.path.abspath(__file__))
_BYTES = {"float32": 4, "bfloat16": 2}


def plain_reference():
    """This architecture's plain reference, the module beside it."""
    return H.load_file(os.path.join(_HERE, "falcon_h1_reference.py"),
                       "perf_arch_")


def vocab(conf: dict):
    return conf["vocab_size"], conf["vocab_size"]


def model_cfg(conf: dict):
    """The program's ``SSMHybridConfig`` at the sizes of a
    configuration file (the one place that maps published names to the
    program's)."""
    import jax.numpy as jnp

    from ray_tpu.models import ssm_hybrid

    dtypes = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    num = conf["numerics"]
    if conf["attn_layer_indices"] is not None or conf["rope_scaling"] \
            or conf["tie_word_embeddings"] or not conf["mamba_conv_bias"] \
            or not conf["mamba_rms_norm"] or conf["mamba_norm_before_gate"] \
            or not conf["mamba_use_mlp"] or conf["hidden_act"] != "silu" \
            or any(conf[k] for k in ("attention_bias", "mamba_proj_bias",
                                     "mlp_bias", "projectors_bias")) \
            or conf["mamba_d_ssm"] != (conf["mamba_n_heads"]
                                       * conf["mamba_d_head"]):
        raise ValueError(
            "the program has attention in every layer, plain rotary, an "
            "untied head, a gated grouped norm that takes the gate first, "
            "an MLP in every layer and no bias but the convolution's")
    return ssm_hybrid.SSMHybridConfig(
        vocab_size=conf["vocab_size"], n_layer=conf["num_hidden_layers"],
        d_model=conf["hidden_size"], n_head=conf["num_attention_heads"],
        n_kv_head=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        rope_theta=float(conf["rope_theta"]),
        ssm_heads=conf["mamba_n_heads"], ssm_head_dim=conf["mamba_d_head"],
        ssm_state=conf["mamba_d_state"], ssm_groups=conf["mamba_n_groups"],
        conv_size=conf["mamba_d_conv"], ssm_chunk=conf["mamba_chunk_size"],
        d_ff=conf["intermediate_size"],
        embed_mult=conf["embedding_multiplier"],
        ssm_in_mult=conf["ssm_in_multiplier"],
        ssm_mup=tuple(conf["ssm_multipliers"]),
        ssm_out_mult=conf["ssm_out_multiplier"],
        attn_in_mult=conf["attention_in_multiplier"],
        key_mult=conf["key_multiplier"],
        attn_out_mult=conf["attention_out_multiplier"],
        mlp_mults=tuple(conf["mlp_multipliers"]),
        head_mult=conf["lm_head_multiplier"],
        max_seq=conf["max_position_embeddings"], eps=conf["rms_norm_eps"],
        dtype=dtypes[num["compute_dtype"]],
        param_dtype=dtypes[num["param_dtype"]],
        state_dtype=dtypes[num["state_dtype"]])


def hyper(cfg) -> dict:
    """The reference's ``hp``: the program's config object as the plain
    dict ``falcon_h1_reference`` reads, the multipliers under their
    published names."""
    return {"heads": cfg.n_head, "kv_heads": cfg.n_kv_head,
            "head_dim": cfg.head_dim, "rope_theta": cfg.rope_theta,
            "ssm_heads": cfg.ssm_heads, "ssm_head_dim": cfg.ssm_head_dim,
            "ssm_state": cfg.ssm_state, "ssm_groups": cfg.ssm_groups,
            "conv": cfg.conv_size, "eps": cfg.eps,
            "embedding_multiplier": cfg.embed_mult,
            "ssm_in_multiplier": cfg.ssm_in_mult,
            "ssm_multipliers": tuple(cfg.ssm_mup),
            "ssm_out_multiplier": cfg.ssm_out_mult,
            "attention_in_multiplier": cfg.attn_in_mult,
            "key_multiplier": cfg.key_mult,
            "attention_out_multiplier": cfg.attn_out_mult,
            "mlp_multipliers": tuple(cfg.mlp_mults),
            "lm_head_multiplier": cfg.head_mult}


#: Row blocks the table and the head are held as, at most (the most
#: that divide the rows into whole 128-lane tiles of head columns):
#: ``perf_deployment.seeded_params`` draws every leaf in float32 under
#: ONE jit, so a 1.34 G-value leaf costs 5.35 GB of temporaries beside
#: 9.65 GB of results (``hbm_peak_pct.sat`` read 123: PERF.md section
#: 6, PR 52); a twelfth of it costs less than an MLP matrix does.
VOCAB_BLOCKS = 12


def vocab_blocks(cfg) -> int:
    import math

    return math.gcd(VOCAB_BLOCKS, cfg.vocab_size // 128) \
        if cfg.vocab_size % 128 == 0 else 1


def param_shapes(cfg):
    """The tree (names, shapes, types) is the program's own:
    ``eval_shape`` of its ``init_params``, the table and the head as
    row blocks (``VOCAB_BLOCKS``)."""
    import jax

    from ray_tpu.models import ssm_hybrid

    return jax.eval_shape(
        lambda k: ssm_hybrid.init_params(k, cfg,
                                         vocab_blocks=vocab_blocks(cfg)),
        jax.random.PRNGKey(0))


def leaf_std(cfg, init: dict, name: str, shape):
    """``init["std"]``: the standard deviation by kind of leaf (the
    first key that is part of the leaf's path), else 1/sqrt(fan-in);
    the three layer norms' scales are ones."""
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
    arithmetic: the paged prefill program (rotated keys and values into
    pages, the chunked state-space form into the slot's state and
    convolution tail), then single decode steps (attention over the
    pages, the recurrence on the state), on a small pool of its own:
    the logits right after prefill (key 0) and after ``n_steps`` cached
    decode steps (key ``n_steps``), float32 ``[B, rows]``.

    As in ``gpt2.served_logits``: the prefill is given the prompt less
    its last token and the first decode step yields the logits "after
    prefill"; the tokens fed afterwards are the sequence's own.
    ``_slot_decode_step_paged`` is the step function that the chunk
    program scans."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import ssm_hybrid as sh

    ps = engine.page_size
    B = len(seqs)
    total = n_prompt + n_steps
    max_pages = -(-(total + 1) // ps)
    bucket = next(b for b in engine.prompt_buckets if b >= n_prompt - 1)
    cache = sh.init_paged_cache(cfg, B, B * max_pages, ps,
                                engine.kv_dtype)
    pt = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    prefill = sh.jit_prefill_into_slot_paged(cfg, ps, 0.0,
                                             engine.kv_dtype)
    step = jax.jit(functools.partial(
        sh._slot_decode_step_paged, cfg=cfg, page_size=ps,
        kv_dtype=engine.kv_dtype, attn_kernel=engine.attn_kernel),
        donate_argnums=(1,))
    params = engine.params
    for b in range(B):
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n_prompt - 1] = seqs[b, :n_prompt - 1]
        _tok, cache, _key = prefill(
            params, cache, padded, np.int32(n_prompt - 1), np.int32(0),
            pt[b], np.int32(sh.PT_SENTINEL), np.int32(b),
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


# ---- operations and bytes, from shapes and the engine's counters

def _sizes(conf: dict) -> dict:
    h = conf["hidden_size"]
    H, P, N = conf["mamba_n_heads"], conf["mamba_d_head"], \
        conf["mamba_d_state"]
    W, bc = H * P, conf["mamba_n_groups"] * N
    hq = conf["num_attention_heads"] * conf["head_dim"]
    hkv = conf["num_key_value_heads"] * conf["head_dim"]
    return {
        "layers": conf["num_hidden_layers"],
        # every leaf of a layer: the two mixers, the MLP, the two norms
        "attn": 2 * h * hq + 2 * h * hkv,
        "ssm": h * (2 * W + 2 * bc + H) + W * h
        + (conf["mamba_d_conv"] + 1) * (W + 2 * bc) + 3 * H + W,
        "mlp": 3 * h * conf["intermediate_size"], "norms": 2 * h,
        "head": h * conf["vocab_size"] + h,
        "state": H * P * N,                 # values a lane a layer
        "kv_token": 2 * hkv,                # values a token a layer
        "heads": conf["num_attention_heads"], "head_dim": conf["head_dim"],
        "state_bytes": _BYTES[conf["numerics"]["state_dtype"]]}


def state_lanes_per_step(conf: dict, stats_delta: dict):
    """Lanes whose state a decode step read and wrote, FROM THE
    COUNTER: ``state_lanes_sum`` over the decode steps (``dispatches``
    chunk launches of ``engine.chunk`` steps each); None where the
    program has no such counter."""
    steps = (stats_delta.get("dispatches") or 0) * conf["engine"]["chunk"]
    if not steps or "state_lanes_sum" not in stats_delta:
        return None
    return stats_delta["state_lanes_sum"] / steps


def decode_step_bytes(conf: dict, weight_bytes: int, kv_bytes: int,
                      live_tokens: float, stats_delta: dict):
    """Fewest bytes ANY program with these numerics moves in one
    decode step (``gpt2.py``'s docstring has the rule). At
    ``weight_bytes``: every layer's two mixers, MLP and norms and the
    head once (the table is a gather of a row a lane: not counted). In
    ``numerics.state_dtype``: every LIVE lane's state in every layer,
    read once and written once (the recurrence changes all of it), the
    lanes FROM THE COUNTER ``state_lanes_sum``; without the counter no
    state is counted (a lower bound still, never an assumption). At
    ``kv_bytes``: the live tokens' keys and values in every layer. Not
    the convolution's tail (30 KB a lane a layer against 4.2 MB of
    state: left out, so the count stays a lower bound)."""
    z = _sizes(conf)
    lanes = state_lanes_per_step(conf, stats_delta) or 0.0
    weights = z["layers"] * (z["attn"] + z["ssm"] + z["mlp"] + z["norms"]) \
        + z["head"]
    return weights * weight_bytes \
        + lanes * z["layers"] * z["state"] * z["state_bytes"] * 2 \
        + live_tokens * z["layers"] * z["kv_token"] * kv_bytes


def ssm_state_cost(conf: dict, stats_delta: dict):
    """(bytes, FLOPs) the scope ``ssm.state`` needs in ONE decode step,
    all layers: every live lane's state read once and written once in
    ``numerics.state_dtype``; an element of it takes the decay (1), the
    rank-one term and its addition (2) and its part of ``S C`` (2). None
    without the counter."""
    lanes = state_lanes_per_step(conf, stats_delta)
    if lanes is None:
        return None
    z = _sizes(conf)
    return (lanes * z["layers"] * z["state"] * z["state_bytes"] * 2,
            lanes * z["layers"] * z["state"] * 5)


def hgqa_attention_cost(conf: dict, kv_bytes: int, live_tokens: float):
    """(bytes, FLOPs) the scope ``hgqa.attention`` needs in ONE decode
    step, all layers: every live token's keys and values once, and for
    each query head a score and a weighted sum over ``head_dim`` a live
    token, whatever implements the scope."""
    z = _sizes(conf)
    return (z["layers"] * live_tokens * z["kv_token"] * kv_bytes,
            z["layers"] * live_tokens * z["heads"] * 2 * 2 * z["head_dim"])
