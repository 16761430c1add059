"""A latent-attention, expert-routed decoder and its paged serving
programs: the second block :class:`~ray_tpu.serve.engine.DecodeEngine`
serves (the first is :mod:`ray_tpu.models.gpt_decode`'s GPT-2 block).
This module IS the model's description in the sense of
:mod:`ray_tpu.models.serving`.

The block (pre-norm, RMSNorm, no biases, untied head)::

    x += Attn(RMSNorm(x));  x += FFN(RMSNorm(x))

**Latent attention.** Queries go through a low rank (``q_rank``); keys
and values are up-projections of ONE ``kv_rank``-wide latent a token,
beside ONE ``rope_dim``-wide rotary key shared by every head::

    c_q = RMSNorm(x W_qa);   [q_n | q_r] = c_q W_qb        (per head)
    [c | k_r] = x W_kva;     c = RMSNorm(c)
    [k_n | v] = c W_kvb                                    (per head)
    scores = (q_n . k_n + rot(q_r) . rot(k_r)) * scale

so a token leaves ``kv_rank + rope_dim`` values a layer in the cache
(``c`` after its norm, ``k_r`` after rotation: 576 for 512 + 64), with
no head axis: a page is ``[page_size, 640]`` once (576 values and
zeros up to the 128-lane tile they occupy anyway:
:attr:`MLAMoEConfig.latent_row`), not ``[page_size, H, hd]`` twice
(:func:`cache_spec`). Two attention paths read it:

- **prefill** (:func:`prefill_into_slot_paged`, scope ``mla.prefill``)
  materialises ``k_n`` and ``v`` from the latents of the cached prefix
  (read through the page table) and of the prompt's own tokens, and
  attends causally per head: hundreds of queries share each
  materialised key, so the up-projection is paid once a key;
- **decode** (:func:`_slot_decode_step_paged`, scope ``mla.attention``)
  ABSORBS the up-projections: ``q~ = q_n W_uk`` (``W_uk`` the ``k_n``
  columns of ``W_kvb``), ``scores = q~ . c + q_r . k_r``, ``o = ((p c)
  W_uv) W_o``: all 64 query heads of a lane meet ONE 576-wide key a
  token, a matrix product over the lane's latent pages. ONE path, named
  ``"gather"`` (:data:`ATTN_KERNELS`), in two bodies chosen by what the
  program can see (:func:`decode_attention_fused`): a Pallas kernel
  (:func:`_latent_attention_pallas`) that copies a lane's LIVE pages
  from the pool once, by DMA, and multiplies them on the MXU, wherever
  Mosaic can address a page (a row is 640 = five whole 128-lane tiles;
  compiled for a TPU a page must also be whole sublane tiles, 16 rows
  of bfloat16; off the TPU the kernel is interpreted); else plain XLA
  over each lane's whole virtual sequence gathered through the page
  table (:func:`_latent_attention_gather`, also the tests' oracle). The
  two agree to :data:`ATTN_KERNEL_ULPS`.

Rotary positions use YaRN frequencies (:func:`yarn_inv_freq`): each of
the ``rope_dim / 2`` frequencies blends ``theta^(-2i/dim)`` with the
same over ``factor`` by a linear ramp between two correction
dimensions, at EVERY position; the attention scale carries ``mscale``
squared. Pairing: halves (dimension ``i`` rotates with ``i + dim/2``).

**FFN.** The first ``n_dense`` layers have a gated SiLU FFN of width
``d_ff``; the others a routed expert layer
(:func:`ray_tpu.models.moe.dropless_moe`: sigmoid scores over
``n_routed`` experts, group-limited top k, DROPLESS, told the
``experts_held`` experts from ``expert_offset`` that live here) plus a
shared expert of the same width (scope ``moe.shared``) that every
token takes. What absent experts would add is left out: another chip
of the deployment computes it.

Layers are NOT stacked: ``params["layers"]`` is a list of per-layer
trees (a dense layer and an expert layer differ, and per-layer leaves
keep any one leaf small) and the programs unroll it; the pool is
addressed as ``l * n_pages + page`` in its flat view, as gpt_decode's
carried pool is, so no layer's pool is ever sliced out.

The chunk program returns, beside the tokens, the expert layers'
counters summed over its steps (:data:`STEP_COUNTERS`).
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

from . import moe, serving
from .gpt import _mm
from .serving import PT_SENTINEL, CacheEntry, CacheSpec

_THIS = sys.modules[__name__]

Params = Dict[str, Any]
Cache = Dict[str, jax.Array]

KV_DTYPES = ("fp",)
ATTN_KERNELS = ("gather",)
#: What the engine offers and this model does not take, with the reason
#: the engine raises at construction.
UNSUPPORTED = {
    "int8": "the latent page pool has no quantised layout: a latent's "
            "512 values and its rotary key have no per-head scale to "
            "share",
    "tp": "latent attention and the expert layer have no tensor-"
          "parallel programs: the deployment shares a layer by EXPERTS "
          "(experts_held / expert_offset), one engine a chip",
    "spec_decode": "there is no verify program for latent pages",
    "roles": "there are no export/import programs for latent pages, so "
             "no prefill/decode roles and no KV handoff",
}
#: int32 counters the chunk program returns, summed over its steps:
#: decode steps x expert layers; over those, the held experts with at
#: least one token, the token-choices that landed on held experts, and
#: the fullest held expert's tokens.
STEP_COUNTERS = ("moe_steps", "moe_experts_touched_sum",
                 "moe_tokens_here_sum", "moe_expert_peak_sum")
#: The written bound on |kernel - gather| of decode's latent attention,
#: in ulps (2**-8, relative) of the LARGEST output of the call: both
#: bodies round every probability once to the compute dtype, the XLA
#: body after the division by the sum and the kernel before it, sum
#: p . c in float32 and round the result once (as
#: :data:`ray_tpu.models.gpt_decode.ATTN_KERNEL_ULPS`, the same
#: difference). Read 0.5-1.5 over ``tests/test_mla_attention_kernel.py``
#: on the CPU.
ATTN_KERNEL_ULPS = 4
#: Tokens the kernel multiplies at once (``512 // page_size`` pages;
#: one page where a page is larger). A block costs 0.38 us and 1.05 ns
#: a token on a v5e whether its tokens are live or not (two dependent
#: MXU products with a softmax between them), so small blocks pay the
#: first too often and large ones the second for nothing: 128 / 256 /
#: 512 / 1024 read 3.6 / 2.7 / 2.4 / 2.6 ms a step of 7 layers at 128
#: lanes of 130-1,280 tokens (PERF.md, PR 38).
_ATTN_BLOCK_TOKENS = 512
#: Blocks in flight or in use at once: the fetch of one hides behind
#: the arithmetic of the other (4 and 8 read the same).
_ATTN_RING_BLOCKS = 2


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig:
    vocab_size: int = 512            # rows of the table and head HELD
    n_layer: int = 3                 # n_dense leading + expert layers
    n_dense: int = 1
    d_model: int = 64
    n_head: int = 4
    q_rank: int = 48
    kv_rank: int = 32
    nope_dim: int = 16
    rope_dim: int = 8
    v_dim: int = 16
    d_ff: int = 160                  # the dense layers' FFN
    d_expert: int = 32               # a routed or shared expert's FFN
    n_routed: int = 16               # the router's width
    experts_held: int = 16           # of which live here ...
    expert_offset: int = 0           # ... from this one
    n_group: int = 4
    topk_group: int = 2
    top_k: int = 4
    norm_topk: bool = True
    route_scale: float = 2.5
    shared_expert: bool = True
    rope_theta: float = 10000.0
    rope_factor: float = 32.0        # YaRN; 1.0: plain rotary
    rope_orig_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    mscale_all_dim: float = 1.0
    max_seq: int = 131072            # positions the rotary reaches
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    moe_block_rows: int = 32

    # no factor on ``c_q`` or on ``c`` after their norms; a model that
    # scales them (:mod:`ray_tpu.models.scmoe`) states its own
    q_gain = 1.0
    kv_gain = 1.0

    @property
    def latent_dim(self) -> int:
        return self.kv_rank + self.rope_dim

    @property
    def latent_row(self) -> int:
        """A token's row in a page: ``latent_dim`` values and zeros up
        to the next multiple of 128 lanes (576 -> 640). The TPU tiles
        the two minor dimensions by (8/16, 128), so a 576-wide row
        occupies 640 lanes in HBM whatever the shape says; held as 576
        the compiler saw the padding, chose another (compact) layout
        for the pool where it could, and copied the whole pool between
        the two, twice a prefill and ten times a decode step (PERF.md,
        PR 37). Held as 640 the default layout has nothing to save."""
        return -(-self.latent_dim // 128) * 128

    @property
    def attn_scale(self) -> float:
        m = 1.0
        if self.rope_factor > 1.0:
            m = 0.1 * self.mscale_all_dim * math.log(self.rope_factor) + 1.0
        return (self.nope_dim + self.rope_dim) ** -0.5 * m * m

    def decode_programs(self):
        """This model's description for the serving engine
        (:mod:`ray_tpu.models.serving`)."""
        import sys

        return sys.modules[__name__]


# sizes used by the CPU tests
CONFIGS = {
    "nano": MLAMoEConfig(),
}


def init_params(rng: jax.Array, cfg: MLAMoEConfig, std: Optional[dict] = None
                ) -> Params:
    """Seeded weights, one tree a layer. ``std`` overrides a kind's
    standard deviation (``"router"``, ``"down"``, ``"wo"``, ...; default
    1/sqrt(fan-in))."""
    std = std or {}
    pd = cfg.param_dtype
    d, H = cfg.d_model, cfg.n_head
    n = [0]

    def w(name, *shape):
        n[0] += 1
        s = std.get(name, 1.0 / math.sqrt(shape[-2]))
        return (jax.random.normal(jax.random.fold_in(rng, n[0]), shape)
                * s).astype(pd)

    def ffn(f, lead=()):
        return {"gate": w("gate", *lead, d, f), "up": w("up", *lead, d, f),
                "down": w("down", *lead, f, d)}

    layers = []
    for l in range(cfg.n_layer):
        p = {"ln1_scale": jnp.ones((d,), pd),
             "ln2_scale": jnp.ones((d,), pd),
             "wqa": {"kernel": w("wqa", d, cfg.q_rank)},
             "q_norm_scale": jnp.ones((cfg.q_rank,), pd),
             "wqb": {"kernel": w("wqb", cfg.q_rank,
                                 H * (cfg.nope_dim + cfg.rope_dim))},
             "wkva": {"kernel": w("wkva", d, cfg.latent_dim)},
             "kv_norm_scale": jnp.ones((cfg.kv_rank,), pd),
             "wkvb": {"kernel": w("wkvb", cfg.kv_rank,
                                  H * (cfg.nope_dim + cfg.v_dim))},
             "wo": {"kernel": w("wo", H * cfg.v_dim, d)}}
        if l < cfg.n_dense:
            p["ffn"] = ffn(cfg.d_ff)
        else:
            p["router"] = {"kernel": w("router", d, cfg.n_routed)}
            p["experts"] = ffn(cfg.d_expert, (cfg.experts_held,))
            if cfg.shared_expert:
                p["shared"] = ffn(cfg.d_expert)
        layers.append(p)
    return {"embed": {"kernel": w("embed", cfg.vocab_size, d)},
            "head": {"kernel": w("head", d, cfg.vocab_size)},
            "ln_f_scale": jnp.ones((d,), pd), "layers": layers}


# ------------------------------------------------------------ block math
def yarn_inv_freq(cfg: MLAMoEConfig) -> jax.Array:
    """The ``rope_dim / 2`` rotary frequencies, float32. YaRN: below
    the correction dimension of ``beta_fast`` rotations over the
    original context a frequency is kept, above that of ``beta_slow``
    it is divided by ``factor``, and between the two it is blended
    linearly."""
    dim = cfg.rope_dim
    i = jnp.arange(0, dim, 2, dtype=jnp.float32)
    extra = cfg.rope_theta ** (-i / dim)
    if cfg.rope_factor <= 1.0:
        return extra

    def correction_dim(rotations):
        return dim * math.log(cfg.rope_orig_max
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)


def rope(x, positions, cfg: MLAMoEConfig):
    """Rotate ``x`` [..., S, dim] (or [..., S, H, dim] with
    ``positions`` broadcast over H) by its positions [..., S]; halves
    pairing; float32 inside, ``x``'s dtype out."""
    ang = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    if x.ndim == ang.ndim + 1:
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def latent_projections(x, p, positions, cfg: MLAMoEConfig):
    """x [B, S, d] at ``positions`` [B, S] -> (h [B, S, d]: ``x`` after
    the attention's norm, c_q [B, S, q_rank]: the query's low-rank
    state after its norm, q_n [B, S, H, nope], q_r [B, S, H, rope]
    rotated, entry [B, S, latent_row]: the token's cache row, ``c``
    after its norm, ``k_r`` rotated, zeros to the row's width). ``p``
    is ONE attention's tree (``ln1_scale`` .. ``wo``). ``cfg.q_gain``
    scales ``c_q`` after its norm (so both parts of the query: ``W_qb``
    is linear) and ``cfg.kv_gain`` the latent ``c`` after its norm, NOT
    ``k_r``; 1.0 is no factor at all. ``h`` and ``c_q`` are what a
    model reads that projects MORE from them
    (:mod:`ray_tpu.models.dsa_moe`'s indexer)."""
    B, S, _ = x.shape
    H = cfg.n_head
    h = moe.rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
    cq = moe.rmsnorm(_mm(h, p["wqa"]["kernel"], cfg.dtype),
                  p["q_norm_scale"], cfg.eps, gain=cfg.q_gain)
    q = _mm(cq, p["wqb"]["kernel"], cfg.dtype).reshape(
        B, S, H, cfg.nope_dim + cfg.rope_dim)
    qn, qr = q[..., :cfg.nope_dim], q[..., cfg.nope_dim:]
    ckv = _mm(h, p["wkva"]["kernel"], cfg.dtype)
    c = moe.rmsnorm(ckv[..., :cfg.kv_rank], p["kv_norm_scale"], cfg.eps,
                 gain=cfg.kv_gain)
    kr = rope(ckv[..., cfg.kv_rank:], positions, cfg)
    pad = jnp.zeros(c.shape[:-1] + (cfg.latent_row - cfg.latent_dim,),
                    c.dtype)
    return h, cq, qn, rope(qr, positions, cfg), \
        jnp.concatenate([c, kr, pad], axis=-1)


def wkvb(p, cfg: MLAMoEConfig):
    """``W_kvb`` as (W_uk [kv_rank, H, nope], W_uv [kv_rank, H, v])."""
    w = p["wkvb"]["kernel"].astype(cfg.dtype).reshape(
        cfg.kv_rank, cfg.n_head, cfg.nope_dim + cfg.v_dim)
    return w[..., :cfg.nope_dim], w[..., cfg.nope_dim:]


def absorbed_query(qn, qr, w_uk, cfg: MLAMoEConfig):
    """Decode's query in the latent space: ``q_n`` [B, 1, H, nope]
    through ``W_uk``, ``q_r`` [B, 1, H, rope] beside it and zeros in
    the row's pad lanes: ``[B, H, latent_row]``, which meets a cached
    row as one product."""
    return jnp.concatenate([
        jnp.einsum("bhn,rhn->bhr", qn[:, 0], w_uk,
                   preferred_element_type=jnp.float32
                   ).astype(cfg.dtype), qr[:, 0],
        jnp.zeros((qn.shape[0], cfg.n_head,
                   cfg.latent_row - cfg.latent_dim),
                  cfg.dtype)], axis=-1)


def attention_output(o, x, w_uv, p, cfg: MLAMoEConfig):
    """``x + (o W_uv) W_o``: decode's weighted latents ``o`` [B, H,
    kv_rank] through the values' up-projection and the output
    projection, added to ``x`` [B, 1, d]."""
    att = jnp.einsum("bhr,rhv->bhv", o, w_uv,
                     preferred_element_type=jnp.float32
                     ).astype(cfg.dtype).reshape(o.shape[0], 1, -1)
    return x + _mm(att, p["wo"]["kernel"], cfg.dtype).astype(x.dtype)


def forward(params: Params, tokens: jax.Array, cfg: MLAMoEConfig
            ) -> jax.Array:
    """tokens [B, S] -> float32 logits [B, S, rows]: the whole
    sequence at once, no cache (keys and values materialised, as
    prefill does)."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]
    x = moe.embed(params, tokens)
    for p in params["layers"]:
        qn, qr, ent = latent_projections(x, p, positions, cfg)[2:]
        att = _attend_materialised(qn, qr, ent, mask, p, cfg)
        x = x + _mm(att, p["wo"]["kernel"], cfg.dtype).astype(x.dtype)
        x = moe.block_ffn(x.reshape(B * S, -1), p, cfg)[0].reshape(
            B, S, -1)
    return moe.head(x, params, cfg)


def _materialised(qn, qr, latents, p, cfg: MLAMoEConfig):
    """Queries [B, S, H, .] against ``latents`` [B, K, kv_rank + rope]
    whose keys and values are materialised per head: ``(scores [B, H,
    S, K] float32, scaled; values [B, K, H, v])``."""
    w_uk, w_uv = wkvb(p, cfg)
    c = latents[..., :cfg.kv_rank]
    kr = latents[..., cfg.kv_rank:cfg.latent_dim]
    kn = jnp.einsum("bkr,rhn->bkhn", c, w_uk,
                    preferred_element_type=jnp.float32).astype(cfg.dtype)
    v = jnp.einsum("bkr,rhv->bkhv", c, w_uv,
                   preferred_element_type=jnp.float32).astype(cfg.dtype)
    lg = jnp.einsum("bqhn,bkhn->bhqk", qn, kn,
                    preferred_element_type=jnp.float32) \
        + jnp.einsum("bqhr,bkr->bhqk", qr, kr,
                     preferred_element_type=jnp.float32)
    return lg * cfg.attn_scale, v


def _attend_materialised(qn, qr, latents, mask, p, cfg: MLAMoEConfig):
    """Per-head attention of queries [B, S, H, .] over ``latents``
    [B, K, kv_rank + rope] whose keys and values are materialised:
    ``mask`` [.., S, K] says which key a query may see. Returns
    [B, S, H * v]."""
    B, S = qn.shape[:2]
    lg, v = _materialised(qn, qr, latents, p, cfg)
    probs = jax.nn.softmax(jnp.where(mask, lg, -1e30),
                           axis=-1).astype(cfg.dtype)
    return jnp.einsum("bhqk,bkhv->bqhv", probs, v,
                      preferred_element_type=jnp.float32
                      ).astype(cfg.dtype).reshape(B, S, -1)


# ----------------------------------------------------------- description
def cache_spec(cfg: MLAMoEConfig, kv_dtype: str = "fp") -> CacheSpec:
    """What a token leaves in a page, per layer: ONE latent row in the
    compute dtype, no head axis: ``kv_rank + rope_dim`` values (576)
    in a row of ``latent_row`` (640: the lanes they occupy). So the
    pool is ``[L, n_pages, page_size, latent_row]``."""
    serving.check_kv_dtype(_THIS, kv_dtype)
    return CacheSpec(cfg.n_layer, (CacheEntry(
        "latent", "token", (cfg.latent_row,), cfg.dtype),))


def max_positions(cfg: MLAMoEConfig) -> int:
    """Rotary positions need no table: the model's declared reach."""
    return cfg.max_seq


# what follows from the spec and from ``UNSUPPORTED["tp"]``: the frame's
kv_bytes_per_page = serving.bind(serving.kv_bytes_per_page, _THIS)
init_paged_cache = serving.bind(serving.init_paged_cache, _THIS)
check_tp = serving.bind(serving.check_tp, _THIS)
shard_params = serving.bind(serving.shard_params, _THIS)


# -------------------------------------------------------------- programs
def _prefill_attend(qn, qr, ent, pool, p, a: int, hist_len, T: int,
                    hist_pages, causal, cfg):
    """One prompt suffix's attention ``a`` in a paged prefill: queries
    ``[1, S, H, .]`` over the suffix's own latents ``ent`` ``[1, S,
    row]``, causally, and over the ``hist_len`` cached tokens before
    them, read from the flat ``pool`` a block of ``T`` tokens at
    ``hist_pages(j, a)`` at once, keys and values materialised per head
    (:func:`ray_tpu.models.serving.attend_history`). Returns float32
    ``[1, S, H, v]``."""
    def block(j):
        return _materialised(
            qn, qr, pool[hist_pages(j, a)].reshape(1, T, -1), p, cfg)

    lg, v = _materialised(qn, qr, ent, p, cfg)
    return serving.attend_history(
        jnp.where(causal, lg, -1e30), v, hist_len, T, block)


def prefill_attention(cache: Cache, S: int, length, hist_len, pt_row,
                       cow_src, cfg, page_size: int):
    """The paged prefill's frame around ANY model whose attentions
    leave latent rows: the copy-on-write fork, then one attention
    sub-block at a time. ``cache["latent"]`` is ``[A, n_pages, ps,
    row]`` over the model's ``A`` attentions (one a layer here; a model
    with two a layer counts both, :mod:`ray_tpu.models.scmoe`). Returns
    ``(pool, live, attend)``: the pool in its flat view after the fork,
    which of the ``S`` rows are tokens, and ``attend(x, p, a, pool) ->
    (x + Attn_a(x), pool')`` for attention ``a`` with tree ``p`` (scope
    ``mla.prefill``): suffix token ``i`` sits at position ``hist_len +
    i`` and attends over the suffix, causally, and over the ``hist_len``
    cached tokens before it, whose latents are read through ``pt_row``
    a block of :data:`ray_tpu.models.serving.HIST_BLOCK_TOKENS` at
    once (:func:`ray_tpu.models.serving.attend_history`, scope
    ``prefill.history`` inside ``mla.prefill``): keys and values are
    materialised from the latents per head for the suffix and for the
    blocks a hit is long, none without a hit, never for ``max_len``;
    the rows' own latents go into their pages, pad positions' writes
    dropped."""
    ps = page_size
    A, n_pages = cache["latent"].shape[:2]
    max_pages = pt_row.shape[0]
    positions = hist_len + jnp.arange(S)

    # COW fork first, every attention's page at once, in the pool's
    # FLAT view like every other access (a fork written as ``pool[:,
    # dst]`` made XLA hold the pool in a second layout and copy all of
    # it twice a prefill: PERF.md, PR 37); no fork copies to an
    # out-of-bounds page and is dropped.
    pool = serving.flat(cache["latent"])
    layers = jnp.arange(A, dtype=jnp.int32) * n_pages
    dst = pt_row[jnp.clip(hist_len // ps, 0, max_pages - 1)]
    dst_w = jnp.where((cow_src < n_pages) & (dst < n_pages),
                      dst + layers, jnp.int32(PT_SENTINEL))
    pool = pool.at[dst_w].set(
        pool[jnp.clip(cow_src, 0, n_pages - 1) + layers], mode="drop")

    T, hist_pages = serving.hist_blocks(pt_row, n_pages, ps)
    causal = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]
    live = jnp.arange(S) < length
    vp = positions // ps
    page_w = jnp.where(live & (vp < max_pages),
                       pt_row[jnp.clip(vp, 0, max_pages - 1)],
                       jnp.int32(PT_SENTINEL))

    def attend(x, p, a: int, pool):
        qn, qr, ent = latent_projections(x, p, positions[None], cfg)[2:]
        with jax.named_scope("mla.prefill"):
            att = _prefill_attend(
                qn, qr, ent, pool, p, a, hist_len, T, hist_pages, causal,
                cfg).astype(cfg.dtype).reshape(1, S, -1)
        x = x + _mm(att, p["wo"]["kernel"], cfg.dtype).astype(x.dtype)
        return x, pool.at[serving.at_layer(page_w, a, n_pages),
                          positions % ps].set(ent[0], mode="drop")

    return pool, live, attend


def prefill_result(x, pool, params: Params, cache: Cache, length,
                    hist_len, slot, rng, cfg, temperature: float):
    """The first token's sample from the last live row of ``x`` [1, S,
    d], and the cache with ``pool`` and the slot's ``pos``."""
    x_last = lax.dynamic_slice(x, (0, length - 1, 0), (1, 1, cfg.d_model))
    token, rng = serving.sample(moe.head(x_last, params, cfg)[:, 0],
                                temperature, rng)
    pos = lax.dynamic_update_slice(
        cache["pos"], jnp.reshape(hist_len + length, (1,)), (slot,))
    return token[0], {"latent": pool.reshape(cache["latent"].shape),
                      "pos": pos}, rng


def fork_pages(entry, hist_len, pt_row, cow_src, page_size: int):
    """The copy-on-write forks of ``G`` prompts in one per-token entry
    of the pool (``entry`` ``[A, n_pages, ps, row]``; ``hist_len``
    ``cow_src`` ``[G]``, ``pt_row`` ``[G, max_pages]``): every layer's
    page at once, in the entry's FLAT view, which is returned; no fork
    copies to an out-of-bounds page and is dropped."""
    A, n_pages = entry.shape[:2]
    max_pages = pt_row.shape[1]
    pool = serving.flat(entry)
    layers = jnp.arange(A, dtype=jnp.int32) * n_pages
    dst = jnp.take_along_axis(
        pt_row, jnp.clip(hist_len // page_size, 0, max_pages - 1)[:, None],
        axis=1)
    dst_w = jnp.where((cow_src[:, None] < n_pages) & (dst < n_pages),
                      dst + layers, jnp.int32(PT_SENTINEL))     # [G, A]
    src = jnp.clip(cow_src, 0, n_pages - 1)[:, None] + layers
    return pool.at[dst_w.reshape(-1)].set(pool[src.reshape(-1)],
                                          mode="drop")


def prefill_group_attention(cache: Cache, rows, hist_len, pt_row, cow_src,
                            cfg, page_size: int):
    """:func:`prefill_attention` for the ``G`` prompts of one launch,
    whose rows lie end to end (``rows``, a
    :class:`ray_tpu.models.serving.PromptRows`; ``hist_len`` ``cow_src``
    ``[G]``, ``pt_row`` ``[G, max_pages]``): every prompt's fork at
    once, and ``attend(x [1, R, d], p, a, pool)`` whose projections run
    over all the rows while each prompt attends over its own rows and
    its own cached prefix (:func:`_prefill_attend`, the single
    prefill's) and lands its latents in its own pages. Returns ``(pool,
    attend)``."""
    ps = page_size
    A, n_pages = cache["latent"].shape[:2]
    G, max_pages = pt_row.shape

    pool = fork_pages(cache["latent"], hist_len, pt_row, cow_src, ps)

    blocks = [serving.hist_blocks(pt_row[g], n_pages, ps) for g in range(G)]
    causal = [jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]
              for S in rows.sizes]
    page_w, off = rows.pages(pt_row, ps)

    def attend(x, p, a: int, pool):
        qn, qr, ent = latent_projections(x, p, rows.positions[None], cfg)[2:]
        with jax.named_scope("mla.prefill"):
            att = jnp.concatenate([
                _prefill_attend(n, r, e, pool, p, a, hist_len[g],
                                *blocks[g], causal[g], cfg)
                for g, (n, r, e) in enumerate(zip(
                    rows.split(qn, 1), rows.split(qr, 1),
                    rows.split(ent, 1)))],
                axis=1).astype(cfg.dtype).reshape(1, rows.R, -1)
        x = x + _mm(att, p["wo"]["kernel"], cfg.dtype).astype(x.dtype)
        return x, pool.at[serving.at_layer(page_w, a, n_pages), off].set(
            ent[0], mode="drop")

    return pool, attend


def prefill_group_result(x, pool, params: Params, cache: Cache, rows,
                         length, hist_len, slot, rng, cfg,
                         temperature: float):
    """:func:`prefill_result` for a group: every prompt's first token
    from its own last live row of ``x`` [1, R, d] with its own key, and
    the cache with ``pool`` and the slots' ``pos``."""
    token, rng = serving.sample_slots(
        moe.head(x[0, rows.last], params, cfg), temperature, rng)
    return token, {"latent": pool.reshape(cache["latent"].shape),
                   "pos": cache["pos"].at[slot].set(hist_len + length)}, rng


def prefill_into_slot_paged(params: Params, cache: Cache,
                            tokens: jax.Array, length: jax.Array,
                            hist_len: jax.Array, pt_row: jax.Array,
                            cow_src: jax.Array, slot: jax.Array,
                            rng: jax.Array, *, cfg: MLAMoEConfig,
                            page_size: int, temperature: float = 0.0,
                            kv_dtype: str = "fp"
                            ) -> Tuple[jax.Array, Cache, jax.Array]:
    """Prefill one prompt SUFFIX into its pages, with the optional
    copy-on-write fork and the first token's sample: the contract of
    :func:`ray_tpu.models.gpt_decode.prefill_into_slot_paged`, on
    latent pages (:func:`prefill_attention`)."""
    pool, live, attend = prefill_attention(
        cache, tokens.shape[1], length, hist_len, pt_row, cow_src, cfg,
        page_size)
    x = moe.embed(params, tokens)
    for l, p in enumerate(params["layers"]):
        x, pool = attend(x, p, l, pool)
        x = moe.block_ffn(x[0], p, cfg, live)[0][None]
    return prefill_result(x, pool, params, cache, length, hist_len, slot,
                          rng, cfg, temperature)


def prefill_group_into_slots_paged(params: Params, cache: Cache, tokens,
                                   length: jax.Array, hist_len: jax.Array,
                                   pt_row: jax.Array, cow_src: jax.Array,
                                   slot: jax.Array, rng: jax.Array, *,
                                   cfg: MLAMoEConfig, page_size: int,
                                   temperature: float = 0.0,
                                   kv_dtype: str = "fp"
                                   ) -> Tuple[jax.Array, Cache, jax.Array]:
    """:func:`prefill_into_slot_paged` for the ``G`` prompts of one
    chunk boundary in ONE launch (the frame's contract,
    ``models/serving.py``; :func:`prefill_group_attention`): the FFNs
    and the expert layer see all the prompts' rows as one batch, in
    blocks :func:`ray_tpu.models.moe.group_cfg` widens, so an expert's matrices are read
    once a launch."""
    rows = serving.PromptRows(tokens, length, hist_len)
    pool, attend = prefill_group_attention(
        cache, rows, hist_len, pt_row, cow_src, cfg, page_size)
    x = moe.embed(params, rows.tokens)[None]
    for l, p in enumerate(params["layers"]):
        x, pool = attend(x, p, l, pool)
        x = moe.block_ffn(x[0], p, moe.group_cfg(cfg, rows.G), rows.live)[0][None]
    return prefill_group_result(x, pool, params, cache, rows, length,
                                hist_len, slot, rng, cfg, temperature)


def decode_attention_fused(cfg: MLAMoEConfig, page_size: int,
                           attn_kernel: str = "gather") -> bool:
    """Whether the chunk program built with these knobs holds the
    Pallas kernel (the description's entry,
    :mod:`ray_tpu.models.serving`): wherever Mosaic can address a page
    of the pool. A row is whole 128-lane tiles by construction
    (:attr:`MLAMoEConfig.latent_row`); compiled for a TPU a page must
    also be whole sublane tiles of the pool's dtype (16 rows of
    bfloat16, 8 of float32). Interpreted, off the TPU, any page is
    addressable. ``attn_kernel`` has one value and no say."""
    from .._private.chip import pallas_interpret

    rows = 32 // jnp.dtype(cfg.dtype).itemsize
    return pallas_interpret() or page_size % rows == 0


def _latent_attention_gather(q, pool, pages, pos, cfg: MLAMoEConfig,
                             page_size: int, picked=None):
    """Decode's latent attention in plain XLA, the fallback and the
    tests' oracle: ``q`` [B, H, latent_row] (absorbed queries, zeros in
    the pad lanes) over each lane's WHOLE virtual sequence, gathered
    from the flat ``pool`` through ``pages`` [B, max_pages] (in bounds)
    and masked past ``pos`` (and outside ``picked`` [B, V], where
    given). Returns ``o`` [B, H, kv_rank]."""
    B = q.shape[0]
    V = pages.shape[1] * page_size
    seen = (jnp.arange(V)[None] <= pos[:, None])[:, None]    # [B, 1, V]
    if picked is not None:
        seen = seen & picked[:, None]
    lat = pool[pages].reshape(B, V, -1)
    lg = jnp.einsum("bhc,bvc->bhv", q, lat,
                    preferred_element_type=jnp.float32)
    lg = jnp.where(seen, lg * cfg.attn_scale, -1e30)
    probs = jax.nn.softmax(lg, axis=-1).astype(cfg.dtype)
    # over the whole row, the rotary key's lanes too, and cut
    # afterwards: slicing the gathered pages first is another copy
    return jnp.einsum("bhv,bvr->bhr", probs, lat,
                      preferred_element_type=jnp.float32
                      )[..., :cfg.kv_rank].astype(cfg.dtype)


def _latent_attention_pallas(q, pool, pages, length, cfg: MLAMoEConfig,
                             page_size: int, picked=None):
    """Decode's latent attention as ONE kernel that reads what is live,
    once: ``q`` [B, H, latent_row] against the first ``length[b]``
    tokens of lane ``b``, whose pages ``pages`` [B, max_pages] names in
    the flat ``pool`` [pages, page_size, latent_row]. Returns ``o``
    [B, H, kv_rank]; zeros for a lane of length 0.

    Grid ``(B,)``: one step a lane, and inside it a loop over THAT
    lane's live tokens in blocks of :data:`_ATTN_BLOCK_TOKENS`.
    ``pages``, ``length`` and ``first`` (the blocks before each lane:
    the lanes' blocks in order are one STREAM) ride as scalar-prefetch
    operands; the pool stays in HBM, never sliced, and the kernel
    copies the pages the table names into a ring of
    :data:`_ATTN_RING_BLOCKS` VMEM blocks (one DMA a page, one
    semaphore a block). Block ``i`` of the stream lives in buffer ``i
    % ring``: the first ``ring`` are started at the first lane's
    start, each later one behind the arithmetic of the block whose
    buffer it takes, so a lane's last blocks fetch the NEXT lane's
    first and no lane waits for an idle DMA engine (that wait was a
    fifth of the kernel's time on a v5e). A page is fetched ONCE; a
    page past the live length never.

    A block is rows that are key and value at once, so a lane's step
    is two MXU products a block: ``s = q . block^T`` ([H, 640] x [640,
    T], float32 sums, scaled in float32) and ``acc += p . block`` ([H,
    T] x [T, 640]), around one running-max softmax pass in float32 (the
    max ``m``, the sum ``l`` and ``acc`` are carried and rescaled as the
    max moves). The probabilities are rounded to the compute dtype
    before they meet the latents, as the XLA body rounds them, but
    BEFORE the division by ``l``: the whole numeric difference
    (:data:`ATTN_KERNEL_ULPS`). Whole blocks need no mask; the lane's
    last, partial block masks the scores AND the latents (a block's
    unfetched rows hold whatever was there, and 0 * inf is NaN). The
    first ``kv_rank`` lanes of ``acc / l`` are written once, at the
    lane's end.

    ``picked`` [B, V] bool (``V = max_pages * page_size``; absent: every
    live token) says which of a lane's live tokens the softmax runs
    over, for a model that attends over a selection
    (:mod:`ray_tpu.models.dsa_moe`): it rides as one float32 row a lane
    in VMEM, 0 or -1e30 added to the block's scores. The pages are read
    as ever, so a masked token costs what a picked one does; a block
    without a picked token folds to weights that the first picked
    token's rescale wipes (``alpha`` 0), and at least one live token
    must be picked."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .._private.chip import pallas_interpret

    B, H, R = q.shape
    ps = page_size
    bp = max(1, _ATTN_BLOCK_TOKENS // ps)          # pages a block
    T = bp * ps
    ring = _ATTN_RING_BLOCKS
    dtype = q.dtype
    scale = cfg.attn_scale         # a Python float: no captured constant
    first = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum((length + T - 1) // T, dtype=jnp.int32)])
    bias = ()
    if picked is not None:
        bias = (jnp.pad(jnp.where(picked, 0.0, -1e30).astype(jnp.float32),
                        ((0, 0), (0, -picked.shape[1] % T)),
                        constant_values=-1e30)[:, None],)

    def kernel(pt_ref, len_ref, first_ref, q_ref, pool_hbm, *rest):
        *bias_ref, o_ref, buf, sems = rest
        b = pl.program_id(0)
        n_live = len_ref[b]
        base, total = first_ref[b], first_ref[B]

        def each_page(lane, j, i, what):
            """``what`` (start or wait) on the copy of every live page
            of ``lane``'s block ``j``, block ``i`` of the stream."""
            n = (len_ref[lane] + ps - 1) // ps         # its live pages

            def page(g, _):
                what(pltpu.make_async_copy(
                    pool_hbm.at[pt_ref[lane, g]],
                    buf.at[i % ring, g - j * bp], sems.at[i % ring]))

            lax.fori_loop(j * bp, jnp.minimum((j + 1) * bp, n), page, None)

        def start(i, lane):
            """Fetch block ``i`` of the stream, which is ``lane``'s or a
            later lane's."""
            lane = lax.while_loop(lambda c: first_ref[c + 1] <= i,
                                  lambda c: c + 1, lane)
            each_page(lane, i - first_ref[lane], i,
                      lambda copy: copy.start())

        @pl.when(b == 0)
        def _():
            lax.fori_loop(0, jnp.minimum(total, ring),
                          lambda i, _: start(i, 0), None)

        qv = q_ref[0]                                        # [H, R]

        def fold(j, carry, whole=True):
            """Block ``j`` of the lane into ``(m, l, acc)``: the waits
            first, the refill last (behind the second product the
            block's buffer is free), the arithmetic between them."""
            m, l, acc = carry
            i = base + j
            each_page(b, j, i, lambda copy: copy.wait())
            blk = buf[i % ring].reshape(T, R)
            s = lax.dot_general(qv, blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if bias_ref:
                s = s + bias_ref[0][0, :, pl.ds(pl.multiple_of(j * T, T), T)]
            if not whole:
                s = jnp.where(j * T + lax.broadcasted_iota(
                    jnp.int32, (1, T), 1) < n_live, s, -1e30)
                blk = jnp.where(j * T + lax.broadcasted_iota(
                    jnp.int32, (T, 1), 0) < n_live, blk,
                    jnp.zeros_like(blk))
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)                       # 0 where masked
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(
                p.astype(dtype), blk, preferred_element_type=jnp.float32)
            pl.when(i + ring < total)(lambda: start(i + ring, b))
            return m_new, l, acc

        n_whole = n_live // T                  # blocks that need no mask
        carry = lax.fori_loop(
            0, n_whole, fold,
            (jnp.full((H, 1), -1e30, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, R), jnp.float32)))
        m, l, acc = lax.cond(
            n_live > n_whole * T,
            lambda carry: fold(n_whole, carry, whole=False),
            lambda carry: carry, carry)
        o_ref[0] = (acc / jnp.where(l > 0.0, l, 1.0)
                    )[:, :cfg.kv_rank].astype(dtype)

    def lane_map(b, *prefetched):
        return (b, 0, 0)

    # `name` names the device operation ("latent_attention.N") and the
    # last component of its path before "pallas_call"; the rest of the
    # path is the caller's scope.
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, R), lane_map),
                      pl.BlockSpec(memory_space=pl.ANY)]
            + [pl.BlockSpec((1, 1, b.shape[2]), lane_map) for b in bias],
            out_specs=pl.BlockSpec((1, H, cfg.kv_rank), lane_map),
            scratch_shapes=[pltpu.VMEM((ring, bp, ps, R), pool.dtype),
                            pltpu.SemaphoreType.DMA((ring,))]),
        out_shape=jax.ShapeDtypeStruct((B, H, cfg.kv_rank), dtype),
        # Every index a copy takes is in bounds (``pages``) or a
        # remainder (the ring): the checks Mosaic adds cannot fire.
        compiler_params=pltpu.CompilerParams(disable_bounds_checks=True),
        interpret=pallas_interpret(),
        name="latent_attention",
    )(pages, length, first, q, pool, *bias)


def latent_attention(q, pool, pages, pos, length, cfg: MLAMoEConfig,
                     page_size: int, picked=None):
    """Decode's latent attention of ``q`` [B, H, latent_row] over each
    lane's pages ``pages`` [B, max_pages] of the flat ``pool``, under a
    public name: the kernel where ``length`` [B] is given (the caller
    asked :func:`decode_attention_fused`), else plain XLA over the
    gathered pages up to ``pos``; over the ``picked`` [B, V] tokens
    alone where given. Returns ``o`` [B, H, kv_rank]."""
    if length is not None:
        return _latent_attention_pallas(q, pool, pages, length, cfg,
                                        page_size, picked)
    return _latent_attention_gather(q, pool, pages, pos, cfg, page_size,
                                    picked)


def decode_lanes(cache: Cache, active, pt, cfg, page_size: int,
                 attn_kernel: str = "gather"):
    """Where a decode step's lanes write and read: ``(page_w [B]: the
    page each active lane's own position lands in, the sentinel for an
    inactive lane or a position past its table; ptc [B, max_pages]: the
    table clipped into the pool; length [B]: the tokens the kernel
    reads a lane, or None where the step holds no kernel`` (:func:`
    decode_attention_fused`)."""
    ps = page_size
    max_pages = pt.shape[1]
    pos = cache["pos"]
    n_pages = cache["latent"].shape[1]
    vp = pos // ps
    page_w = jnp.where(
        active & (vp < max_pages),
        jnp.take_along_axis(pt, jnp.clip(vp, 0, max_pages - 1)[:, None],
                            axis=1)[:, 0], jnp.int32(PT_SENTINEL))
    ptc = jnp.clip(pt, 0, n_pages - 1)
    length = serving.live_length(pt, pos, active, n_pages, ps) \
        if decode_attention_fused(cfg, ps, attn_kernel) else None
    return page_w, ptc, length


def decode_attention(cache: Cache, active, pt, cfg, page_size: int,
                      attn_kernel: str = "gather"):
    """One decode step's frame around ANY model whose attentions leave
    latent rows (``cache["latent"]`` ``[A, n_pages, ps, row]`` over its
    ``A`` attentions). Returns ``(pool, attend)``: the pool in its flat
    view and ``attend(x, p, a, pool) -> (x + Attn_a(x), pool')`` for
    ``x`` [B, 1, d] (scope ``mla.attention``): each active lane writes
    its latent row at its own position and attends, in the latent
    space with the up-projections absorbed, over its own pages up to
    it (the kernel wherever :func:`decode_attention_fused`, else the
    XLA body). Inactive lanes do not write."""
    ps = page_size
    pos = cache["pos"]
    n_pages = cache["latent"].shape[1]
    page_w, ptc, length = decode_lanes(cache, active, pt, cfg, ps,
                                       attn_kernel)

    def attend(x, p, a: int, pool):
        qn, qr, ent = latent_projections(x, p, pos[:, None], cfg)[2:]
        pool = pool.at[serving.at_layer(page_w, a, n_pages),
                       pos % ps].set(ent[:, 0], mode="drop")
        w_uk, w_uv = wkvb(p, cfg)
        q = absorbed_query(qn, qr, w_uk, cfg)
        with jax.named_scope("mla.attention"):
            pages = ptc + a * n_pages
            o = latent_attention(q, pool, pages, pos, length, cfg, ps)
        return attention_output(o, x, w_uv, p, cfg), pool

    return serving.flat(cache["latent"]), attend


def _slot_decode_step_paged(params: Params, cache: Cache,
                            token: jax.Array, active: jax.Array,
                            pt: jax.Array, cfg: MLAMoEConfig,
                            page_size: int, kv_dtype: str = "fp",
                            attn_kernel: str = "gather"):
    """One masked decode step over the whole slot pool
    (:func:`decode_attention`, then the layer's FFN). Inactive lanes
    neither write, advance nor route. Returns ``(logits [B, rows],
    cache', counts)``: the expert layers' counters int32 [4]
    (:data:`STEP_COUNTERS`)."""
    pool, attend = decode_attention(cache, active, pt, cfg, page_size,
                                    attn_kernel)
    x = moe.embed(params, token)[:, None]
    counts = jnp.zeros((4,), jnp.int32)
    # the step's own scope: a reader tells the decode program's
    # expert and attention time from prefill's by it
    with jax.named_scope("decode_step"):
        for l, p in enumerate(params["layers"]):
            x, pool = attend(x, p, l, pool)
            y, c = moe.block_ffn(x[:, 0], p, cfg, active)
            x, counts = y[:, None], counts + c
    cache_out = {"latent": pool.reshape(cache["latent"].shape),
                 "pos": cache["pos"] + active.astype(jnp.int32)}
    return moe.head(x, params, cfg)[:, 0], cache_out, counts


# the chunk program and the two factories are the frame's, around this
# model's step and for this description (``models/serving.py``)
decode_chunk_slots_paged = functools.partial(
    serving.decode_chunk_slots_paged, step=_slot_decode_step_paged,
    counters=len(STEP_COUNTERS))
jit_prefill_into_slot_paged = serving.bind(
    serving.jit_prefill_into_slot_paged, _THIS)
jit_decode_chunk_slots_paged = serving.bind(
    serving.jit_decode_chunk_slots_paged, _THIS)
