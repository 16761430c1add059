"""A shortcut-connected expert decoder with two latent attentions a
layer and its paged serving programs: the fourth block
:class:`~ray_tpu.serve.engine.DecodeEngine` serves. This module IS the
model's description in the sense of :mod:`ray_tpu.models.serving`.

One layer holds TWO attention sub-blocks, TWO dense FFNs and ONE expert
layer that is a shortcut BRANCH (ScMoE): it leaves after the first
attention and rejoins after the second FFN, so the dense path runs
beside it (pre-norm, RMSNorm, no biases, untied head)::

    x += Attn_0(ln_a0(x))
    u  = ln_f0(x)
    s  = MoE(u)                  # the branch leaves here ...
    x += FFN_0(u)
    x += Attn_1(ln_a1(x))
    x += FFN_1(ln_f1(x))
    x += s                       # ... and rejoins here

**Attention** is :mod:`ray_tpu.models.mla_moe`'s latent attention,
IMPORTED under its public names: prefill materialised
(``prefill_attention``, scope ``mla.prefill``: the suffix's own rows,
and the blocks of cached prefix a hit is long under
``prefill.history``, never ``max_len`` of them), decode absorbed over
the lane's live latent pages (``decode_attention``, scope
``mla.attention``: the Pallas kernel wherever Mosaic can address a
page, else XLA over the gathered pages). What this model adds to it is
two constants that the config carries and the attention's projections
read: ``q_gain = sqrt(d_model / q_rank)`` on the
query's low-rank state and ``kv_gain = sqrt(d_model / kv_rank)`` on the
latent ``c`` (not on the rotary key), both after their norms; rotary is
plain (``rope_factor`` 1.0). A token leaves one SCALED latent row an
ATTENTION, so the pool is ``[2 * n_layer, n_pages, page_size,
latent_row]`` and attention ``i`` of layer ``l`` addresses page ``p``
at ``(2 l + i) * n_pages + p`` (:func:`cache_spec`: the entry counts
its own layers).

**The expert layer** routes over ``n_routed + n_zero`` scores
(:func:`ray_tpu.models.moe.route_softmax_bias`: softmax in float32, a
selection bias, plain top k, the unbiased score times ``route_scale``,
not renormalised). A choice below ``n_routed`` is a routed expert, of
which ``experts_held`` from ``expert_offset`` live here
(:func:`ray_tpu.models.moe.dropless_experts`, the dispatch every
routed model shares; what absent experts would add is another chip's);
a choice from ``n_routed`` on is an IDENTITY expert that adds ``w * u``
and reads no weight (:func:`ray_tpu.models.moe.zero_experts`, scope
``moe.zero``), computed where the token lives: all of them here. So
the work a token costs varies with its choices.

Layers are a list of per-layer trees, unrolled. The chunk program
returns, beside the tokens, the expert layers' counters summed over
its steps (:data:`STEP_COUNTERS`).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import mla_moe, moe, serving
# ``decode_attention_fused`` is the description's entry as it stands:
# this model's one kernel is the imported attention's
from .mla_moe import (decode_attention, decode_attention_fused,
                      prefill_attention, prefill_group_attention,
                      prefill_group_result, prefill_result)
from .serving import PT_SENTINEL, CacheEntry, CacheSpec

_THIS = sys.modules[__name__]

Params = Dict[str, Any]
Cache = Dict[str, jax.Array]

KV_DTYPES = mla_moe.KV_DTYPES
ATTN_KERNELS = mla_moe.ATTN_KERNELS
#: What the engine offers and this model does not take: the latent
#: page pool's and the expert layer's reasons, as ``mla_moe`` has them.
UNSUPPORTED = mla_moe.UNSUPPORTED
#: int32 counters the chunk program returns, summed over its steps:
#: ``mla_moe``'s four, in their places (decode steps x expert layers;
#: the held experts with a token, the token-choices that landed on
#: held experts, the fullest held expert's tokens), then the live rows
#: routed (summed over the expert layers too) and their choices that
#: fell on identity experts and cost nothing.
STEP_COUNTERS = mla_moe.STEP_COUNTERS + ("moe_tokens_sum",
                                         "moe_zero_choices_sum")


@dataclasses.dataclass(frozen=True)
class ScMoEConfig:
    vocab_size: int = 512            # rows of the table and head HELD
    n_layer: int = 2                 # each: 2 attentions, 2 FFNs, 1 MoE
    d_model: int = 64
    n_head: int = 4
    q_rank: int = 48
    kv_rank: int = 32
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    d_ff: int = 96                   # each of a layer's two dense FFNs
    d_expert: int = 32               # a routed expert's FFN
    n_routed: int = 16               # routed experts the router scores
    n_zero: int = 8                  # identity experts it scores beside
    experts_held: int = 16           # of the routed, live here ...
    expert_offset: int = 0           # ... from this one
    top_k: int = 4
    route_scale: float = 6.0
    rope_theta: float = 10000.0
    max_seq: int = 131072            # positions the rotary reaches
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    moe_block_rows: int = 32

    # plain rotary: ``mla_moe``'s frequencies without the YaRN blend
    rope_factor = 1.0

    @property
    def router_width(self) -> int:
        return self.n_routed + self.n_zero

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def latent_row(self) -> int:
        """As :attr:`ray_tpu.models.mla_moe.MLAMoEConfig.latent_row`."""
        return -(-self.latent_dim // 128) * 128

    @property
    def attn_scale(self) -> float:
        return (self.nope_dim + self.rope_dim) ** -0.5

    @property
    def q_gain(self) -> float:
        """On ``c_q`` after its norm (the attention reads it)."""
        return math.sqrt(self.d_model / self.q_rank)

    @property
    def kv_gain(self) -> float:
        """On the latent ``c`` after its norm, not on ``k_r``."""
        return math.sqrt(self.d_model / self.kv_rank)

    def decode_programs(self):
        """This model's description for the serving engine
        (:mod:`ray_tpu.models.serving`)."""
        import sys

        return sys.modules[__name__]


# sizes used by the CPU tests
CONFIGS = {
    "nano": ScMoEConfig(),
}


def init_params(rng: jax.Array, cfg: ScMoEConfig, std: Optional[dict] = None
                ) -> Params:
    """Seeded weights, one tree a layer: ``attn`` and ``ffn`` are lists
    of two. ``std`` overrides a kind's standard deviation (``"router"``,
    ``"bias"``, ``"down"``, ...; default 1/sqrt(fan-in), and for the
    selection bias 1/router_width: the order of a score). The bias is
    held in float32: it is added to float32 scores."""
    std = std or {}
    pd = cfg.param_dtype
    d, H = cfg.d_model, cfg.n_head
    n = [0]

    def w(name, *shape, fan_in=None, dtype=pd):
        n[0] += 1
        s = std.get(name, 1.0 / math.sqrt(fan_in or shape[-2]))
        return (jax.random.normal(jax.random.fold_in(rng, n[0]), shape)
                * s).astype(dtype)

    def attention():
        return {"ln1_scale": jnp.ones((d,), pd),
                "wqa": {"kernel": w("wqa", d, cfg.q_rank)},
                "q_norm_scale": jnp.ones((cfg.q_rank,), pd),
                "wqb": {"kernel": w("wqb", cfg.q_rank,
                                    H * (cfg.nope_dim + cfg.rope_dim))},
                "wkva": {"kernel": w("wkva", d, cfg.latent_dim)},
                "kv_norm_scale": jnp.ones((cfg.kv_rank,), pd),
                "wkvb": {"kernel": w("wkvb", cfg.kv_rank,
                                     H * (cfg.nope_dim + cfg.v_dim))},
                "wo": {"kernel": w("wo", H * cfg.v_dim, d)}}

    def ffn(f, lead=()):
        return {"gate": w("gate", *lead, d, f), "up": w("up", *lead, d, f),
                "down": w("down", *lead, f, d)}

    layers = []
    for _ in range(cfg.n_layer):
        layers.append({
            "attn": [attention(), attention()],
            "ffn": [dict(ffn(cfg.d_ff), ln2_scale=jnp.ones((d,), pd))
                    for _ in range(2)],
            "router": {"kernel": w("router", d, cfg.router_width),
                       "bias": w("bias", cfg.router_width,
                                 fan_in=cfg.router_width ** 2,
                                 dtype=jnp.float32)},
            "experts": ffn(cfg.d_expert, (cfg.experts_held,))})
    return {"embed": {"kernel": w("embed", cfg.vocab_size, d)},
            "head": {"kernel": w("head", d, cfg.vocab_size)},
            "ln_f_scale": jnp.ones((d,), pd), "layers": layers}


# ------------------------------------------------------------ block math
def _expert_branch(u, p, cfg: ScMoEConfig, live=None):
    """u [T, d] (normed, compute dtype) -> (s [T, d] float32: the held
    routed experts' part plus the identity experts', counts int32
    [6]: :data:`STEP_COUNTERS` for ONE expert layer)."""
    with jax.named_scope("moe.route"):
        ids, w = moe.route_softmax_bias(
            u, p["router"]["kernel"], p["router"]["bias"],
            top_k=cfg.top_k, route_scale=cfg.route_scale, dtype=cfg.dtype)
    y, held = moe.dropless_experts(
        u, ids, w, p["experts"], experts_held=cfg.experts_held,
        expert_offset=cfg.expert_offset, dtype=cfg.dtype,
        block_rows=cfg.moe_block_rows, live=live)
    z, n_zero = moe.zero_experts(u, ids, w, n_routed=cfg.n_routed,
                                 live=live)
    rows = jnp.int32(u.shape[0]) if live is None \
        else jnp.sum(live, dtype=jnp.int32)
    return y.astype(jnp.float32) + z, jnp.concatenate(
        [jnp.ones((1,), jnp.int32), held, jnp.stack([rows, n_zero])])


def _dense(x, p, cfg: ScMoEConfig, h=None):
    """x + FFN(RMSNorm(x)) for one dense FFN's tree ``p`` (``h``: the
    normed input where the caller already has it)."""
    if h is None:
        h = moe.rmsnorm(x, p["ln2_scale"], cfg.eps, cfg.dtype)
    with jax.named_scope("scmoe.dense"):
        return x + moe.gated_ffn(h, p, cfg.dtype).astype(x.dtype)


def _layer(x, p, a: int, pool, attend, cfg: ScMoEConfig, live):
    """One layer around ``attend`` (``mla_moe``'s prefill or decode
    frame): ``x`` [B, S, d] float32 with B * S rows that ``live`` [B *
    S] masks; ``a`` the index of the layer's first attention. Returns
    ``(x', pool', counts)``."""
    shape = x.shape
    x, pool = attend(x, p["attn"][0], a, pool)
    u = moe.rmsnorm(x, p["ffn"][0]["ln2_scale"], cfg.eps, cfg.dtype)
    s, counts = _expert_branch(u.reshape(-1, shape[-1]), p, cfg, live)
    x = _dense(x, p["ffn"][0], cfg, u)
    x, pool = attend(x, p["attn"][1], a + 1, pool)
    x = _dense(x, p["ffn"][1], cfg)
    return x + s.reshape(shape), pool, counts


# ----------------------------------------------------------- description
def cache_spec(cfg: ScMoEConfig, kv_dtype: str = "fp") -> CacheSpec:
    """What a token leaves in a page: ONE latent row in the compute
    dtype an ATTENTION, two a layer (the entry counts its own layers:
    ``2 * n_layer``). So the pool is ``[2 L, n_pages, page_size,
    latent_row]``."""
    serving.check_kv_dtype(_THIS, kv_dtype)
    return CacheSpec(cfg.n_layer, (CacheEntry(
        "latent", "token", (cfg.latent_row,), cfg.dtype,
        n_layer=2 * cfg.n_layer),))


max_positions = mla_moe.max_positions
# what follows from the spec and from ``UNSUPPORTED["tp"]``: the frame's
kv_bytes_per_page = serving.bind(serving.kv_bytes_per_page, _THIS)
init_paged_cache = serving.bind(serving.init_paged_cache, _THIS)
check_tp = serving.bind(serving.check_tp, _THIS)
shard_params = serving.bind(serving.shard_params, _THIS)


# -------------------------------------------------------------- programs
def prefill_into_slot_paged(params: Params, cache: Cache,
                            tokens: jax.Array, length: jax.Array,
                            hist_len: jax.Array, pt_row: jax.Array,
                            cow_src: jax.Array, slot: jax.Array,
                            rng: jax.Array, *, cfg: ScMoEConfig,
                            page_size: int, temperature: float = 0.0,
                            kv_dtype: str = "fp"
                            ) -> Tuple[jax.Array, Cache, jax.Array]:
    """Prefill one prompt SUFFIX into its pages: the contract of
    :func:`ray_tpu.models.gpt_decode.prefill_into_slot_paged`, on the
    latent pages of both attentions of every layer
    (:func:`ray_tpu.models.mla_moe.prefill_attention`)."""
    pool, live, attend = prefill_attention(
        cache, tokens.shape[1], length, hist_len, pt_row, cow_src, cfg,
        page_size)
    x = moe.embed(params, tokens)
    for l, p in enumerate(params["layers"]):
        x, pool, _ = _layer(x, p, 2 * l, pool, attend, cfg, live)
    return prefill_result(x, pool, params, cache, length, hist_len, slot,
                          rng, cfg, temperature)


def prefill_group_into_slots_paged(params: Params, cache: Cache, tokens,
                                   length: jax.Array, hist_len: jax.Array,
                                   pt_row: jax.Array, cow_src: jax.Array,
                                   slot: jax.Array, rng: jax.Array, *,
                                   cfg: ScMoEConfig, page_size: int,
                                   temperature: float = 0.0,
                                   kv_dtype: str = "fp"
                                   ) -> Tuple[jax.Array, Cache, jax.Array]:
    """:func:`prefill_into_slot_paged` for the ``G`` prompts of one
    chunk boundary in ONE launch (the frame's contract,
    ``models/serving.py``;
    :func:`ray_tpu.models.mla_moe.prefill_group_attention`): a layer's
    dense FFNs and its expert branch see all the prompts' rows at once,
    the experts in the blocks
    :func:`ray_tpu.models.moe.group_cfg` widens."""
    rows = serving.PromptRows(tokens, length, hist_len)
    pool, attend = prefill_group_attention(
        cache, rows, hist_len, pt_row, cow_src, cfg, page_size)
    x = moe.embed(params, rows.tokens)[None]
    for l, p in enumerate(params["layers"]):
        x, pool, _ = _layer(x, p, 2 * l, pool, attend,
                            moe.group_cfg(cfg, rows.G), rows.live)
    return prefill_group_result(x, pool, params, cache, rows, length,
                                hist_len, slot, rng, cfg, temperature)


def _slot_decode_step_paged(params: Params, cache: Cache,
                            token: jax.Array, active: jax.Array,
                            pt: jax.Array, cfg: ScMoEConfig,
                            page_size: int, kv_dtype: str = "fp",
                            attn_kernel: str = "gather"):
    """One masked decode step over the whole slot pool
    (:func:`ray_tpu.models.mla_moe.decode_attention` for each of a
    layer's two attentions). Inactive lanes neither write, advance nor
    route. Returns ``(logits [B, rows], cache', counts)``: the
    counters int32 [6] (:data:`STEP_COUNTERS`)."""
    pool, attend = decode_attention(cache, active, pt, cfg, page_size,
                                    attn_kernel)
    x = moe.embed(params, token)[:, None]
    counts = jnp.zeros((len(STEP_COUNTERS),), jnp.int32)
    with jax.named_scope("decode_step"):
        for l, p in enumerate(params["layers"]):
            x, pool, c = _layer(x, p, 2 * l, pool, attend, cfg, active)
            counts = counts + c
    cache_out = {"latent": pool.reshape(cache["latent"].shape),
                 "pos": cache["pos"] + active.astype(jnp.int32)}
    return moe.head(x, params, cfg)[:, 0], cache_out, counts


# the chunk program and the two factories are the frame's, around this
# model's step and its six counters (``models/serving.py``)
decode_chunk_slots_paged = functools.partial(
    serving.decode_chunk_slots_paged, step=_slot_decode_step_paged,
    counters=len(STEP_COUNTERS))
jit_prefill_into_slot_paged = serving.bind(
    serving.jit_prefill_into_slot_paged, _THIS)
jit_decode_chunk_slots_paged = serving.bind(
    serving.jit_decode_chunk_slots_paged, _THIS)
