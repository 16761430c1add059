"""A state-space / attention expert decoder, Mamba-2 layers and NoPE
grouped-query attention layers IN TURN, each followed by a
softmax-routed expert layer beside a shared MLP, and its paged serving
programs: the sixth block :class:`~ray_tpu.serve.engine.DecodeEngine`
serves. This module IS the model's description in the sense of
:mod:`ray_tpu.models.serving`.

The block (pre-norm, RMSNorm, a float32 residual stream, no bias but
the convolution's, ONE constant ``resid_mult`` on both residual
branches, a TIED head: the table is the head)::

    x_0 = E[token] * embed_mult
    x  += resid_mult * Mixer_l(RMSNorm(x))
    x  += resid_mult * (MoE(v) + SharedMLP(v)),   v = RMSNorm(x)
    logits = (RMSNorm(x) E^T) / logits_scale

``Mixer_l`` is by INDEX, from :attr:`SSMMoEConfig.layer_types`: a
layer keeps EITHER a state per slot OR pages (as ``kda_moe``'s layers
do; ``ssm_hybrid``'s keep both), and :func:`cache_spec` describes both
in one :class:`~ray_tpu.models.serving.CacheSpec`:

- ``"mamba"``: the Mamba-2 mixer of :mod:`ray_tpu.models.ssm_hybrid`,
  imported under its public names (:class:`~ray_tpu.models.ssm_hybrid.
  Mamba2Sizes`, which this config mixes in: every multiplier 1). A
  sequence keeps, whatever its length, the state (``[heads, head_dim,
  state]``, or ``N``-major with the heads side by side on lanes where a
  row of it is one lane tile: :func:`ray_tpu.models.ssm_hybrid.
  state_shape`) in :attr:`SSMMoEConfig.state_dtype` and the
  convolution's last ``conv_size - 1`` input rows: entries ``state<l>`` and
  ``conv<l>``, ``per "slot"``, ONE ARRAY A LAYER (``ssm_hybrid.
  cache_spec`` says why), for the Mamba layers ``l`` alone. Prefill
  rebuilds them from zero in the chunked form (scopes ``ssm.proj``,
  ``ssm.prefill``), a decode step reads and writes a live lane's state
  in place (scope ``ssm.state``: the Pallas kernel ``ssm_state`` of
  the entry's layout or the XLA body, by shape: :func:`ray_tpu.models.
  ssm_hybrid.state_kernel`; at granite-4.0-h-small's ``[64, 128]``
  heads the entry is ``[64, 128, 128]``, two heads side by side on
  lanes, and the kernel :func:`ray_tpu.models.ssm_hybrid.
  ssm_step_pallas_nmajor`).
- ``"attention"``: ``n_head`` query heads over ``n_kv_head`` key/value
  heads, NO positions, scores ``q . k * attn_mult`` (a published
  constant, NOT ``head_dim ** -0.5``). A token leaves ``n_kv_head x
  head_dim`` keys and values in a PAGE (entries ``k``, ``v``, per
  token, the attention layers alone). Both bodies of the shared
  attention scale by ``head_dim ** -0.5`` inside, so ``q`` is scaled
  by ``attn_mult * sqrt(head_dim)`` in float32 before its one rounding
  (:func:`q_scale`). Prefill attends causally over the prompt (scope
  ``smoe.attn_prefill``, :func:`ray_tpu.models.kda_moe.
  gqa_causal_attention`); decode attends over the lane's pages (scope
  ``smoe.attention``) through :func:`ray_tpu.models.kda_moe.
  gqa_decode_attention`: that model's Pallas kernel over each lane's
  live pages wherever Mosaic can address a page and a head, plain XLA
  over the gathered table row elsewhere, by shape under the one name
  ``"gather"``.

**MoE**, every layer: the ``top_k`` largest router LOGITS over the
router's whole published width, taken in float32 from the normed
stream before it is rounded, weights a softmax over the chosen ones
(:func:`ray_tpu.models.moe.route_topk_softmax`, scope ``moe.route``),
the ``experts_held`` experts from ``expert_offset`` that live here
computed dropless (:func:`ray_tpu.models.moe.dropless_experts`, scope
``moe.experts``), plus a shared gated MLP every token takes (scope
``moe.shared``). A state belongs to the SLOT, so no page hash shares it
and nothing rolls back: :data:`UNSUPPORTED`.

Layers are a list of per-layer trees, unrolled; the chunk program
returns the expert layers' counters, the live lanes and the positions
its attention fetched, summed over its steps (:data:`STEP_COUNTERS`).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import sys
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import kda_moe, serving, ssm_hybrid
from .moe import (dropless_experts, gated_ffn, rmsnorm,
                  route_topk_softmax)
from .serving import PT_SENTINEL, CacheEntry, CacheSpec, at_layer, flat

_THIS = sys.modules[__name__]

Params = Dict[str, Any]
Cache = Dict[str, jax.Array]

KV_DTYPES = ("fp",)
ATTN_KERNELS = ("gather",)
#: What the engine offers and this model does not take, with the reason
#: the engine raises at construction.
UNSUPPORTED = {
    "prefix_cache": "a state-space layer's recurrent state belongs to "
                    "the slot, not to a page: reusing cached pages needs "
                    "a snapshot of the state at the page boundary the hit "
                    "ends on, and none is kept",
    "spec_decode": "a recurrent state does not roll back past rejected "
                   "positions, and there is no verify program",
    "roles": "the handoff payload has no part for the per-slot state, "
             "and there are no export/import programs",
    "int8": "the key/value pages of the one attention layer in ten "
            "have no quantised layout",
    "tp": "there are no tensor-parallel programs: the deployment shares "
          "a layer by EXPERTS (experts_held / expert_offset) and by "
          "vocabulary rows, one engine a chip",
}
#: int32 counters the chunk program returns, summed over its steps, by
#: the names ``kda_moe`` gave them: the expert layers' four (``moe_steps``
#: counts expert LAYERS run: ``n_layer`` a step), the lanes whose state a
#: step read and wrote (one a lane a step, whatever the layers) and the
#: positions whose keys and values a step's attention fetched, all
#: attention layers (:func:`ray_tpu.models.kda_moe.gqa_decode_reads`).
STEP_COUNTERS = kda_moe.STEP_COUNTERS


@dataclasses.dataclass(frozen=True)
class SSMMoEConfig(ssm_hybrid.Mamba2Sizes):
    vocab_size: int = 512            # rows of the table HELD (the head's)
    #: each layer's mixer: "mamba" or "attention"
    layer_types: Tuple[str, ...] = ("mamba", "mamba", "attention", "mamba")
    d_model: int = 64
    n_head: int = 4                  # query heads
    n_kv_head: int = 2
    head_dim: int = 16
    attn_mult: float = 0.125         # the scores' scale (hd ** -0.5: 0.25)
    ssm_heads: int = 4
    ssm_head_dim: int = 16           # channels a head (P)
    ssm_state: int = 32              # the state's width (N)
    ssm_groups: int = 1              # groups of heads sharing B and C
    conv_size: int = 4
    ssm_chunk: int = 16              # prefill's chunk
    d_expert: int = 32               # ONE expert's width
    n_routed: int = 16               # the router's width
    experts_held: int = 16           # of which live here ...
    expert_offset: int = 0           # ... from this one
    top_k: int = 4
    d_shared: int = 64               # the shared MLP's width
    embed_mult: float = 4.0
    resid_mult: float = 0.5          # on BOTH residual branches
    logits_scale: float = 4.0        # the logits are DIVIDED by it
    max_seq: int = 131072            # no positions: the declared reach
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    moe_block_rows: int = 32

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def attn_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, kind in enumerate(self.layer_types)
                     if kind == "attention")

    @property
    def ssm_layers(self) -> Tuple[int, ...]:
        return tuple(l for l, kind in enumerate(self.layer_types)
                     if kind == "mamba")

    def __post_init__(self):
        assert set(self.layer_types) <= {"mamba", "attention"}, \
            self.layer_types

    def decode_programs(self):
        """This model's description for the serving engine
        (:mod:`ray_tpu.models.serving`)."""
        import sys

        return sys.modules[__name__]


# sizes used by the CPU tests: both kinds of layer, the attention layer
# NOT first
CONFIGS = {
    "nano": SSMMoEConfig(),
}

#: Means of the leaves that are not drawn around zero (as
#: ``ssm_hybrid.INIT_MEAN``: the decay spreads over (0.2, 0.999), the
#: skip and the gated norm's weight lie around 1).
INIT_MEAN = ssm_hybrid.INIT_MEAN


def q_scale(cfg: SSMMoEConfig) -> float:
    """What ``q`` is multiplied by so that the shared attention, which
    scales the scores by ``head_dim ** -0.5`` inside, scales them by
    ``attn_mult``: ``q_scale(cfg) * head_dim ** -0.5 == attn_mult``."""
    return cfg.attn_mult * math.sqrt(cfg.head_dim)


def init_std(cfg: SSMMoEConfig) -> Dict[str, float]:
    """Each kind of leaf's standard deviation: a matrix is drawn so
    that WITH the model's constant multipliers every branch moves the
    logits (a unit-variance draw under ``resid_mult`` drowns each branch
    in a table scaled by ``embed_mult``, and under ``attn_mult`` makes
    every softmax uniform). The table ``0.25 / embed_mult``: a residual
    stream that STARTS a quarter wide and ends several wide, because
    the table is also the head and a token's own row is the one
    direction the stream is sure to hold: at a unit start its own
    logit would stand a dozen deviations above the rest and every
    greedy token would repeat its input. Each output projection ``gain
    / (sqrt(fan-in) resid_mult)``, so that a branch adds about one unit
    whatever ``resid_mult`` is (the routed experts' gain the largest:
    their softmax weights take two thirds of it back); scores about
    three wide; router logits two wide (the softmax over the chosen
    ``top_k`` is peaked, as a trained router's is)."""
    d, r = cfg.d_model, cfg.resid_mult

    def fan(n, mult=1.0, gain=1.0):
        return gain / (math.sqrt(n) * mult)

    return {
        "embed": 0.25 / cfg.embed_mult,
        "wq": fan(d, q_scale(cfg), 1.7),
        "wk": fan(d, gain=1.7),
        "wo": fan(cfg.n_head * cfg.head_dim, r, 2.5),
        "out_proj": fan(cfg.ssm_width, r, 1.5),
        "router": fan(d, gain=2.0),
        "down": fan(cfg.d_expert, r, 4.0),
        "shared_down": fan(cfg.d_shared, r, 2.0),
        "conv_w": 0.5, "conv_b": 0.5, "dt_bias": 1.0, "A_log": 0.7,
        "D_skip": 0.5, "ssm_norm": 0.3,
    }


def init_params(rng: jax.Array, cfg: SSMMoEConfig,
                std: Optional[dict] = None) -> Params:
    """Seeded weights, one tree a layer, a Mamba layer's or an
    attention layer's leaves by ``layer_types``. ``std`` overrides a
    kind's standard deviation (default :func:`init_std`, else
    1/sqrt(fan-in)); :data:`INIT_MEAN` is added. There is no ``head``:
    the table is the head."""
    std = dict(init_std(cfg), **(std or {}))
    pd = cfg.param_dtype
    d, W = cfg.d_model, cfg.ssm_width
    hq, hkv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    n = [0]

    def w(name, *shape):
        n[0] += 1
        s = std.get(name, 1.0 / math.sqrt(shape[-2] if len(shape) > 1
                                          else 1.0))
        return (jax.random.normal(jax.random.fold_in(rng, n[0]), shape) * s
                + INIT_MEAN.get(name, 0.0)).astype(pd)

    def ffn(f, lead=(), down="down"):
        return {"gate": w("gate", *lead, d, f), "up": w("up", *lead, d, f),
                "down": w(down, *lead, f, d)}

    layers = []
    for kind in cfg.layer_types:
        p = {"ln1_scale": jnp.ones((d,), pd),
             "ln2_scale": jnp.ones((d,), pd)}
        if kind == "attention":
            p.update(wq={"kernel": w("wq", d, hq)},
                     wk={"kernel": w("wk", d, hkv)},
                     wv={"kernel": w("wv", d, hkv)},
                     wo={"kernel": w("wo", hq, d)})
        else:
            p.update(in_proj={"kernel": w("in_proj", d, cfg.in_width)},
                     conv_w=w("conv_w", cfg.conv_size, cfg.conv_dim),
                     conv_b=w("conv_b", cfg.conv_dim),
                     dt_bias=w("dt_bias", cfg.ssm_heads),
                     A_log=w("A_log", cfg.ssm_heads),
                     D_skip=w("D_skip", cfg.ssm_heads),
                     ssm_norm=w("ssm_norm", W),
                     out_proj={"kernel": w("out_proj", W, d)})
        p["router"] = {"kernel": w("router", d, cfg.n_routed)}
        p["experts"] = ffn(cfg.d_expert, (cfg.experts_held,))
        p["shared"] = ffn(cfg.d_shared, down="shared_down")
        layers.append(p)
    return {"embed": {"kernel": w("embed", cfg.vocab_size, d)},
            "ln_f_scale": jnp.ones((d,), pd), "layers": layers}


# ------------------------------------------------------------ block math
def _dot(x, w, dtype, contract: int = 0):
    """``x @ w`` (``x @ w.T`` where ``contract`` is 1) in ``dtype`` with
    float32 sums, the float32 result."""
    return lax.dot_general(x.astype(dtype), w.astype(dtype),
                           (((x.ndim - 1,), (contract,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _embed(params, tokens, cfg: SSMMoEConfig):
    """The residual stream starts, and stays, in float32
    (:func:`ray_tpu.models.moe.embed`), scaled by ``embed_mult``."""
    return params["embed"]["kernel"][tokens].astype(jnp.float32) \
        * cfg.embed_mult


def _head(x, params, cfg: SSMMoEConfig):
    """The final norm and the TIED head, the table's rows as the
    head's columns, divided by ``logits_scale``: float32 logits."""
    with jax.named_scope("lm.head"):
        x = rmsnorm(x, params["ln_f_scale"], cfg.eps, cfg.dtype)
        return _dot(x, params["embed"]["kernel"], cfg.dtype, 1) \
            / cfg.logits_scale


def _attn_qkv(h, p, cfg: SSMMoEConfig):
    """``h`` [..., d] -> (q [..., Hq, hd], k, v [..., Hkv, hd]) in the
    compute dtype, no positions: ``q`` scaled by :func:`q_scale` in
    float32, then rounded once."""
    dt = cfg.dtype

    def heads(name, n, mult=1.0):
        a = _dot(h, p[name]["kernel"], dt)
        if mult != 1.0:
            a = a * mult
        return a.astype(dt).reshape(a.shape[:-1] + (n, cfg.head_dim))

    return heads("wq", cfg.n_head, q_scale(cfg)), \
        heads("wk", cfg.n_kv_head), heads("wv", cfg.n_kv_head)


def _attn_out(att, p, cfg: SSMMoEConfig):
    """``att`` [..., Hq, hd] float32 -> the mixer's output [..., d]."""
    return _dot(att.reshape(att.shape[:-2] + (-1,)), p["wo"]["kernel"],
                cfg.dtype)


def _ffn(x, p, cfg: SSMMoEConfig, live=None):
    """x [T, d] -> (x + resid_mult * (MoE(v) + SharedMLP(v)), counts
    int32 [4]), ``v = RMSNorm(x)``: the held experts' part of the
    softmax-routed layer plus the shared MLP every token takes.
    ``counts``: one expert layer run, then
    :func:`ray_tpu.models.moe.dropless_experts`' three."""
    h = rmsnorm(x, p["ln2_scale"], cfg.eps, cfg.dtype)
    with jax.named_scope("moe.route"):
        # the router reads the normed stream BEFORE its rounding to the
        # compute dtype, in a float32 product
        ids, w = route_topk_softmax(
            rmsnorm(x, p["ln2_scale"], cfg.eps, jnp.float32),
            p["router"]["kernel"], top_k=cfg.top_k, dtype=jnp.float32)
    y, counts = dropless_experts(
        h, ids, w, p["experts"], experts_held=cfg.experts_held,
        expert_offset=cfg.expert_offset, dtype=cfg.dtype,
        block_rows=cfg.moe_block_rows, live=live)
    with jax.named_scope("moe.shared"):
        y = y + gated_ffn(h, p["shared"], cfg.dtype)
    return x + cfg.resid_mult * y.astype(x.dtype), \
        jnp.concatenate([jnp.ones((1,), jnp.int32), counts])


def _attn_sequence(h, p, cfg: SSMMoEConfig):
    """An attention layer over one whole sequence ``h`` [S, d]: (the
    mixer's output [S, d] float32, k, v [S, Hkv, hd] for the pages)."""
    q, k, v = _attn_qkv(h, p, cfg)
    with jax.named_scope("smoe.attn_prefill"):
        att = kda_moe.gqa_causal_attention(
            q, k, v, n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
            head_dim=cfg.head_dim, dtype=cfg.dtype)
    return _attn_out(att, p, cfg), k, v


def forward(params: Params, tokens: jax.Array, cfg: SSMMoEConfig
            ) -> jax.Array:
    """tokens [B, S] -> float32 logits [B, S, rows]: each sequence
    whole, no cache (the chunked SSD form from a zero state, causal
    attention without positions), one sequence at a time."""
    S = tokens.shape[1]
    live = jnp.ones((S,), jnp.bool_)

    def row(toks):
        x = _embed(params, toks, cfg)
        for kind, p in zip(cfg.layer_types, params["layers"]):
            h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
            y = _attn_sequence(h, p, cfg)[0] if kind == "attention" \
                else ssm_hybrid.ssm_sequence(h, p, cfg, live)[0]
            x = _ffn(x + cfg.resid_mult * y, p, cfg)[0]
        return _head(x, params, cfg)

    return lax.map(row, tokens)


# ----------------------------------------------------------- description
def cache_spec(cfg: SSMMoEConfig, kv_dtype: str = "fp") -> CacheSpec:
    """What a token leaves in a page (keys and values of the ATTENTION
    layers, ``[Hkv, hd]`` each: the pools ``[n_attn, n_pages,
    page_size, Hkv, hd]``) and what a sequence keeps in its SLOT for
    each MAMBA layer ``l`` (:func:`ray_tpu.models.ssm_hybrid.
    slot_entries`: ``state<l>`` and ``conv<l>``, one array a layer)."""
    serving.check_kv_dtype(_THIS, kv_dtype)
    row = (cfg.n_kv_head, cfg.head_dim)
    n_attn = len(cfg.attn_layers)
    return CacheSpec(cfg.n_layer, (
        CacheEntry("k", "token", row, cfg.dtype, n_attn),
        CacheEntry("v", "token", row, cfg.dtype, n_attn),
        *(e for l in cfg.ssm_layers
          for e in ssm_hybrid.slot_entries(cfg, l))))


def max_positions(cfg: SSMMoEConfig) -> int:
    """No positions are encoded: the model's declared reach."""
    return cfg.max_seq


def _gqa_kernel(cfg: SSMMoEConfig, page_size: int) -> bool:
    """:func:`ray_tpu.models.kda_moe.gqa_kernel` at this model's heads
    and compute dtype."""
    return kda_moe.gqa_kernel(cfg.n_kv_head, cfg.head_dim, cfg.dtype,
                              page_size)


def decode_attention_fused(cfg: SSMMoEConfig, page_size: int,
                           attn_kernel: str = "gather") -> bool:
    """Whether the chunk program built with these knobs holds a Pallas
    kernel (the description's entry, :mod:`ray_tpu.models.serving`).
    This model has TWO, both imported and each taken by what the
    program can see of its own shapes: the RECURRENCE on the Mamba
    layers' per-slot state (:func:`ray_tpu.models.ssm_hybrid.
    state_kernel`: the kernel of the entry's layout, at
    granite-4.0-h-small's one lane tile a row the ``N``-major one) and
    the attention layers' ATTENTION over pages
    (:func:`ray_tpu.models.kda_moe.gqa_kernel`); either one makes the
    answer true, and at granite-4.0-h-small's shapes on a TPU both
    are. ``attn_kernel`` (one value) has no say."""
    return (bool(cfg.ssm_layers) and ssm_hybrid.state_kernel(cfg)) \
        or (bool(cfg.attn_layers) and _gqa_kernel(cfg, page_size))


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
                            rng: jax.Array, *, cfg: SSMMoEConfig,
                            page_size: int, temperature: float = 0.0,
                            kv_dtype: str = "fp"
                            ) -> Tuple[jax.Array, Cache, jax.Array]:
    """Prefill one WHOLE prompt into its pages and its slot, with the
    first token's sample: the frame of
    :func:`ray_tpu.models.gpt_decode.prefill_into_slot_paged`. The
    attention layers' keys and values go to the pages ``pt_row`` names;
    every Mamba layer's state and convolution tail are rebuilt FROM
    ZERO and written over whatever slot ``slot`` held: a prefill is the
    one way a slot's state begins. Rows past ``length`` (the bucket's
    padding) write no page, advance neither state nor tail and are
    routed nowhere. ``hist_len`` and ``cow_src`` are the frame's and
    have no meaning here: without a prefix cache (:data:`UNSUPPORTED`)
    the engine's are always 0 and the sentinel."""
    del hist_len, cow_src
    S = tokens.shape[1]
    ps = page_size
    n_pages = cache["k"].shape[1]
    max_pages = pt_row.shape[0]
    x = _embed(params, tokens, cfg)[0]                      # [S, d]
    live = jnp.arange(S) < length
    wpos = jnp.arange(S)
    vp = wpos // ps
    page_w = jnp.where(live & (vp < max_pages),
                       pt_row[jnp.clip(vp, 0, max_pages - 1)],
                       jnp.int32(PT_SENTINEL))
    kpool, vpool = flat(cache["k"]), flat(cache["v"])
    slots = {}
    ia = 0
    for l, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
        if kind == "attention":
            y, k, v = _attn_sequence(h, p, cfg)
            at = (at_layer(page_w, ia, n_pages), wpos % ps)
            kpool = kpool.at[at].set(k, mode="drop")
            vpool = vpool.at[at].set(v, mode="drop")
            ia += 1
        else:
            y, S_end, padded = ssm_hybrid.ssm_sequence(h, p, cfg, live)
            state, conv = (ssm_hybrid.slot_entry(n, l)
                           for n in ("state", "conv"))
            slots[state], slots[conv] = ssm_hybrid.put_slot(
                cfg, cache[state], cache[conv], S_end, padded, length, slot)
        x = _ffn(x + cfg.resid_mult * y, p, cfg, live)[0]
    x_last = lax.dynamic_slice(x, (length - 1, 0), (1, cfg.d_model))
    token, rng = serving.sample(_head(x_last, params, cfg), temperature, rng)
    pos = lax.dynamic_update_slice(
        cache["pos"], jnp.reshape(length, (1,)).astype(jnp.int32), (slot,))
    return token[0], {"k": kpool.reshape(cache["k"].shape),
                      "v": vpool.reshape(cache["v"].shape),
                      **slots, "pos": pos}, rng


def prefill_group_into_slots_paged(params: Params, cache: Cache, tokens,
                                   length: jax.Array, hist_len: jax.Array,
                                   pt_row: jax.Array, cow_src: jax.Array,
                                   slot: jax.Array, rng: jax.Array, *,
                                   cfg: SSMMoEConfig, page_size: int,
                                   temperature: float = 0.0,
                                   kv_dtype: str = "fp"
                                   ) -> Tuple[jax.Array, Cache, jax.Array]:
    """:func:`prefill_into_slot_paged` for the ``G`` prompts of one
    chunk boundary in ONE launch (the frame's contract,
    ``models/serving.py``): the mixers' projections, the expert layer
    (in blocks ``G`` times as tall, so that a group's expert reads its
    matrices as often as one prompt's does), the shared MLP and the
    head run over all the prompts' rows at once
    (:class:`ray_tpu.models.serving.PromptRows`); each prompt's causal
    attention, its convolution and its chunked recurrence from a zero
    state are the single prefill's on its own rows, and each lands in
    its own pages and its own slot."""
    rows = serving.PromptRows(tokens, length, jnp.zeros_like(length))
    del hist_len, cow_src
    n_pages = cache["k"].shape[1]
    x = _embed(params, rows.tokens, cfg)                    # [R, d]
    live = rows.split(rows.live)
    page_w, off = rows.pages(pt_row, page_size)
    kpool, vpool = flat(cache["k"]), flat(cache["v"])
    slots = {}
    ffn_cfg = dataclasses.replace(
        cfg, moe_block_rows=rows.G * cfg.moe_block_rows)
    ia = 0
    for l, (kind, p) in enumerate(zip(cfg.layer_types, params["layers"])):
        h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
        if kind == "attention":
            q, k, v = _attn_qkv(h, p, cfg)
            with jax.named_scope("smoe.attn_prefill"):
                att = jnp.concatenate([
                    kda_moe.gqa_causal_attention(
                        qg, kg, vg, n_head=cfg.n_head,
                        n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
                        dtype=cfg.dtype)
                    for qg, kg, vg in zip(rows.split(q), rows.split(k),
                                          rows.split(v))])
            at = (at_layer(page_w, ia, n_pages), off)
            kpool = kpool.at[at].set(k, mode="drop")
            vpool = vpool.at[at].set(v, mode="drop")
            y = _attn_out(att, p, cfg)
            ia += 1
        else:
            with jax.named_scope("ssm.proj"):
                z, xBC, dt, g = ssm_hybrid.ssm_proj(h, p, cfg)
            state, conv = (ssm_hybrid.slot_entry(n, l)
                           for n in ("state", "conv"))
            slots[state], slots[conv] = cache[state], cache[conv]
            ys = []
            for i, (xBC_i, dt_i, g_i) in enumerate(zip(
                    rows.split(xBC), rows.split(dt), rows.split(g))):
                yi, S_end, padded = ssm_hybrid.ssm_mix(
                    xBC_i, dt_i, g_i, p, cfg, live[i])
                ys.append(yi)
                slots[state], slots[conv] = ssm_hybrid.put_slot(
                    cfg, slots[state], slots[conv], S_end, padded,
                    length[i], slot[i])
            with jax.named_scope("ssm.proj"):
                y = ssm_hybrid.ssm_out(jnp.concatenate(ys), z, p, cfg)
        x = _ffn(x + cfg.resid_mult * y, p, ffn_cfg, rows.live)[0]
    token, rng = serving.sample_slots(_head(x[rows.last], params, cfg),
                                      temperature, rng)
    return token, {"k": kpool.reshape(cache["k"].shape),
                   "v": vpool.reshape(cache["v"].shape),
                   **slots,
                   "pos": cache["pos"].at[slot].set(
                       length.astype(jnp.int32))}, rng


def _slot_decode_step_paged(params: Params, cache: Cache,
                            token: jax.Array, active: jax.Array,
                            pt: jax.Array, cfg: SSMMoEConfig,
                            page_size: int, kv_dtype: str = "fp",
                            attn_kernel: str = "gather"):
    """One masked decode step over the whole slot pool: in an attention
    layer each active lane writes its key and its value at its own
    position and attends over its pages
    (:func:`ray_tpu.models.kda_moe.gqa_decode_attention`: the kernel
    over its live pages or the gather over its whole table row, by
    shape); in a Mamba layer it reads and writes its state and
    convolution tail whole (:func:`ray_tpu.models.ssm_hybrid.
    ssm_decode`: the kernel or plain XLA, by shape). An inactive lane
    (idle, or parked for pages) neither writes, advances nor routes:
    its state and tail come out as they went in. Returns ``(logits [B,
    rows], cache', counts)``: int32 [6] (:data:`STEP_COUNTERS`)."""
    ps = page_size
    max_pages = pt.shape[1]
    pos = cache["pos"]
    n_pages = cache["k"].shape[1]
    x = _embed(params, token, cfg)                          # [B, d]
    vp = pos // ps
    page_w = jnp.where(
        active & (vp < max_pages),
        jnp.take_along_axis(pt, jnp.clip(vp, 0, max_pages - 1)[:, None],
                            axis=1)[:, 0], jnp.int32(PT_SENTINEL))
    ptc = jnp.clip(pt, 0, n_pages - 1)
    kpool, vpool = flat(cache["k"]), flat(cache["v"])
    slots = {}
    counts = jnp.zeros((4,), jnp.int32)
    state_kernel = ssm_hybrid.state_kernel(cfg)
    length, fetched = kda_moe.gqa_decode_reads(
        pt, pos, active, n_pages, ps, _gqa_kernel(cfg, ps))
    ia = 0
    # the step's own scope: a reader tells the decode program's state,
    # attention, expert and head time from prefill's by it
    with jax.named_scope("decode_step"):
        for l, (kind, p) in enumerate(zip(cfg.layer_types,
                                          params["layers"])):
            h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
            if kind == "attention":
                q, k, v = _attn_qkv(h, p, cfg)
                at = (at_layer(page_w, ia, n_pages), pos % ps)
                kpool = kpool.at[at].set(k, mode="drop")
                vpool = vpool.at[at].set(v, mode="drop")
                with jax.named_scope("smoe.attention"):
                    att = kda_moe.gqa_decode_attention(
                        q, kpool, vpool, ptc + ia * n_pages, pos, length,
                        n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
                        head_dim=cfg.head_dim, dtype=cfg.dtype,
                        page_size=ps)
                y = _attn_out(att, p, cfg)
                ia += 1
            else:
                state, conv = (ssm_hybrid.slot_entry(n, l)
                               for n in ("state", "conv"))
                y, slots[state], slots[conv] = ssm_hybrid.ssm_decode(
                    h, p, cfg, cache[state], cache[conv], active,
                    state_kernel)
            x, c = _ffn(x + cfg.resid_mult * y, p, cfg, active)
            counts = counts + c
        logits = _head(x, params, cfg)
    cache_out = {"k": kpool.reshape(cache["k"].shape),
                 "v": vpool.reshape(cache["v"].shape), **slots,
                 "pos": pos + active.astype(jnp.int32)}
    counts = jnp.concatenate(
        [counts, jnp.sum(active, dtype=jnp.int32)[None],
         (len(cfg.attn_layers) * fetched)[None]])
    return logits, cache_out, counts


# the chunk program and the two factories are the frame's, around this
# model's step and for this description (``models/serving.py``): the
# cache the scan carries is pages for the attention layers AND per-slot
# state for the Mamba layers
decode_chunk_slots_paged = functools.partial(
    serving.decode_chunk_slots_paged, step=_slot_decode_step_paged,
    counters=len(STEP_COUNTERS))
jit_prefill_into_slot_paged = serving.bind(
    serving.jit_prefill_into_slot_paged, _THIS)
jit_decode_chunk_slots_paged = serving.bind(
    serving.jit_decode_chunk_slots_paged, _THIS)
