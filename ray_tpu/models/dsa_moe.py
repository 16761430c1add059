"""A latent-attention expert decoder whose attention reads only the
tokens a learned INDEXER picks, and its paged serving programs: the
seventh block :class:`~ray_tpu.serve.engine.DecodeEngine` serves. This
module IS the model's description in the sense of
:mod:`ray_tpu.models.serving`.

The block is :mod:`ray_tpu.models.mla_moe`'s (pre-norm, RMSNorm, no
biases, untied head; latent attention with YaRN rotary; ``n_dense``
dense FFNs, then sigmoid-routed expert layers with a shared expert),
IMPORTED under its public names, with two additions:

**The indexer**, in every layer. Beside its latent row a token leaves
ONE index key; a query scores every cached key with ``index_heads``
small heads and attends over the ``index_topk`` best alone (``h`` the
normed residual, ``c_q`` the query's low-rank state after its norm)::

    k^I_s   = rot(LayerNorm(h_s W_ik))                  one head, index_dim
    q^I_t,j = rot(c_q,t W_iq)                           index_heads heads
    w_t     = h_t W_iw * index_heads^-1/2 * index_dim^-1/2
    I_t,s   = sum_j w_t,j ReLU(q^I_t,j . k^I_s)         s <= t
    S_t     = the min(index_topk, t + 1) positions of largest I_t,s
    Attn_t  = latent attention over s in S_t ONLY

(rotary on the first ``rope_dim`` values of a key or query head, the
latent attention's own frequencies and pairing). A tie at the edge of
``S_t`` goes to the earlier position, as :func:`jax.lax.top_k` breaks
it.

- **the page** holds a second ``per="token"`` entry, ``ikey``
  (:func:`cache_spec`): ``index_dim`` values in a row of whole 128-lane
  tiles, in the compute dtype, under the SAME page ids as the latent
  row. The engine's prefix cache, eviction and copy-on-write move page
  ids and never look inside, so they carry it as they stand; the fork
  inside a prefill copies both entries.
- **decode** (:func:`decode_attention`) is where the selection lives:
  scope ``dsa.index`` scores the lane's cached keys (plain XLA over the
  keys gathered through the page table), ``dsa.select`` finds the
  ``k``-th largest score a lane by bisection on the scores' bits and
  the set above it (:func:`pick_top`: ONE kernel, 64 lanes' scores a
  grid step in VMEM, 32 counting passes and no sort), and
  ``dsa.attention`` is
  ``mla_moe``'s absorbed attention over the lane's live pages with
  every token outside ``S_t`` masked out of the softmax
  (:func:`ray_tpu.models.mla_moe.latent_attention`, ``picked``): the
  attention is over EXACTLY ``S_t``, whatever is read.
- **prefill** is ``mla_moe``'s to the letter plus the index keys' write
  (scope ``dsa.prefill``): a prompt is at most ``index_topk`` tokens
  (:func:`check_prompt_buckets`; the prefill programs refuse a wider
  bucket when they are built), so ``S_t`` is every cached token there.
  Selection inside a prefill belongs with chunked prefill and is
  listed under :data:`UNSUPPORTED`.

**The router** carries a selection bias
(:func:`ray_tpu.models.moe.route_sigmoid`, ``bias``): groups and
experts are chosen on ``score + bias``, weighted by the unbiased
scores.

The chunk program returns, beside the tokens, ``mla_moe``'s expert
counters and four of the selection's, summed over its steps
(:data:`STEP_COUNTERS`).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import sys
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import mla_moe, moe, serving
from .gpt import _mm
from .mla_moe import decode_attention_fused, max_positions
from .serving import PT_SENTINEL, CacheEntry, CacheSpec

_THIS = sys.modules[__name__]

Params = Dict[str, Any]
Cache = Dict[str, jax.Array]

KV_DTYPES = mla_moe.KV_DTYPES
ATTN_KERNELS = mla_moe.ATTN_KERNELS
#: What the engine offers and this model does not take: the latent page
#: pool's and the expert layer's reasons, as ``mla_moe`` has them, with
#: the index keys named where they add one, and the one limit of its
#: own.
UNSUPPORTED = dict(
    mla_moe.UNSUPPORTED,
    roles="there are no export/import programs for latent pages, so no "
          "prefill/decode roles and no KV handoff (a handoff's payload "
          "would carry the index keys beside the latents)",
    long_prompt="a prompt bucket past index_topk: the paged prefill "
                "attends over every cached token, which is the model's "
                "attention only while a sequence is at most index_topk "
                "tokens long; selection inside a prefill belongs with "
                "chunked prefill",
)
#: int32 counters the chunk program returns, summed over its steps:
#: ``mla_moe``'s four in their places, then, over the active lanes of
#: each STEP (every layer of a step scans and picks the same counts):
#: cached keys scored, tokens picked, lane-steps, and lane-steps with
#: more than ``index_topk`` tokens cached (the ones that select).
STEP_COUNTERS = mla_moe.STEP_COUNTERS + (
    "dsa_tokens_scanned_sum", "dsa_tokens_selected_sum",
    "dsa_lane_steps_sum", "dsa_lane_steps_selecting_sum")


@dataclasses.dataclass(frozen=True)
class DSAMoEConfig(mla_moe.MLAMoEConfig):
    """``mla_moe``'s sizes and the indexer's."""
    index_heads: int = 4
    index_dim: int = 16              # rotary on the first ``rope_dim``
    index_topk: int = 16
    rope_factor: float = 40.0

    @property
    def index_row(self) -> int:
        """An index key's row in a page: ``index_dim`` values and zeros
        up to whole 128-lane tiles (128 -> 128), as
        :attr:`ray_tpu.models.mla_moe.MLAMoEConfig.latent_row`."""
        return -(-self.index_dim // 128) * 128

    def decode_programs(self):
        """This model's description for the serving engine
        (:mod:`ray_tpu.models.serving`)."""
        return _THIS


# sizes used by the CPU tests
CONFIGS = {
    "nano": DSAMoEConfig(),
}


def init_params(rng: jax.Array, cfg: DSAMoEConfig, std: Optional[dict] = None
                ) -> Params:
    """Seeded weights: ``mla_moe``'s tree, and in every layer the
    indexer's leaves (``wiq``, ``wik``, ``wiw``, the key's LayerNorm)
    and, in an expert layer, the router's selection bias (float32: it
    is added to float32 scores). ``std`` overrides a kind's standard
    deviation (default 1/sqrt(fan-in); ``"ik_norm_bias"`` 0.1 and the
    selection ``"bias"`` 0.1, the order of a difference between
    scores)."""
    std = std or {}
    pd = cfg.param_dtype
    params = mla_moe.init_params(rng, cfg, std)
    rng = jax.random.fold_in(rng, 0x15A)
    n = [0]

    def w(name, *shape, default=None, dtype=pd):
        n[0] += 1
        s = std.get(name, default or 1.0 / math.sqrt(shape[0]))
        return (jax.random.normal(jax.random.fold_in(rng, n[0]), shape)
                * s).astype(dtype)

    for l, p in enumerate(params["layers"]):
        p["wiq"] = {"kernel": w("wiq", cfg.q_rank,
                                cfg.index_heads * cfg.index_dim)}
        p["wik"] = {"kernel": w("wik", cfg.d_model, cfg.index_dim)}
        p["wiw"] = {"kernel": w("wiw", cfg.d_model, cfg.index_heads)}
        p["ik_norm_scale"] = jnp.ones((cfg.index_dim,), pd)
        p["ik_norm_bias"] = w("ik_norm_bias", cfg.index_dim, default=0.1)
        if l >= cfg.n_dense:
            p["router"]["bias"] = w("bias", cfg.n_routed, default=0.1,
                                    dtype=jnp.float32)
    return params


# ------------------------------------------------------------ block math
def check_prompt_buckets(cfg: DSAMoEConfig, buckets: Sequence[int]):
    """Raise :data:`UNSUPPORTED`'s reason for a prompt bucket past
    ``index_topk``. The engine admits no prompt longer than its widest
    bucket, a prefix hit included, so the buckets bound every prefill.
    Whoever constructs an engine for this model calls it with the
    engine's buckets; a prefill program refuses a wider bucket itself
    when it is built (``warm_up()``, before a replica reports ready)."""
    wide = [int(b) for b in buckets if int(b) > cfg.index_topk]
    if wide:
        raise ValueError(
            f"{type(cfg).__name__} cannot be served with prompt buckets "
            f"{wide} (index_topk {cfg.index_topk}): "
            + UNSUPPORTED["long_prompt"])


def _tile(x, row: int):
    """``x`` [..., n] with zeros up to ``row`` lanes."""
    if x.shape[-1] == row:
        return x
    return jnp.concatenate(
        [x, jnp.zeros(x.shape[:-1] + (row - x.shape[-1],), x.dtype)], axis=-1)


def _rot_first(x, positions, cfg: DSAMoEConfig):
    """Rotary on the first ``rope_dim`` values of ``x`` [..., S, (H,)
    index_dim] at ``positions`` [..., S]."""
    r = cfg.rope_dim
    return jnp.concatenate(
        [mla_moe.rope(x[..., :r], positions, cfg), x[..., r:]], axis=-1)


def index_key(h, p, positions, cfg: DSAMoEConfig):
    """The index key a token leaves: ``h`` [B, S, d] (the normed
    residual) -> ``[B, S, index_row]``: ``h W_ik`` through a LayerNorm
    (float32 inside, scale and bias), rotary on its first ``rope_dim``
    values, zeros to the row's width, in the compute dtype."""
    k = _mm(h, p["wik"]["kernel"], cfg.dtype).astype(jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(jnp.square(k), axis=-1, keepdims=True)
                      + cfg.eps)
    k = (k * p["ik_norm_scale"].astype(jnp.float32)
         + p["ik_norm_bias"].astype(jnp.float32)).astype(cfg.dtype)
    return _tile(_rot_first(k, positions, cfg), cfg.index_row)


def index_query(h, cq, p, positions, cfg: DSAMoEConfig):
    """The indexer's side of a query: ``(q^I [B, S, index_heads,
    index_row], w [B, S, index_heads] float32)``: the heads' queries
    from ``c_q``, rotated as the keys are, and the heads' weights from
    ``h`` with both constant factors on them."""
    B, S, _ = h.shape
    q = _mm(cq, p["wiq"]["kernel"], cfg.dtype).reshape(
        B, S, cfg.index_heads, cfg.index_dim)
    w = _mm(h, p["wiw"]["kernel"], cfg.dtype).astype(jnp.float32) \
        * (cfg.index_heads ** -0.5 * cfg.index_dim ** -0.5)
    return _tile(_rot_first(q, positions, cfg), cfg.index_row), w


def index_scores(qi, w, keys):
    """``I`` [B, V] float32 of one query a lane: ``qi`` [B, H, row]
    against ``keys`` [B, V, row] (products in their dtype, float32
    sums), ``sum_j w[b, j] ReLU(q_j . k_v)``."""
    s = jnp.einsum("bhd,bvd->bhv", qi, keys,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bhv,bh->bv", jax.nn.relu(s), w)


#: rows of scores a grid step of :func:`pick_top` holds in VMEM: eight
#: sublane tiles, whose counting passes overlap (a pass ends in a row
#: sum that the next one waits for; at ONE tile a step the kernel took
#: 0.15 ms a call at [128, 5120] on a v5e, PERF.md section 6, PR 63)
_PICK_ROWS = 64


def pick_top(scores, n):
    """``scores`` [B, V] float32 (no NaN; ``-inf`` where there is no
    token) and ``n`` [B] int32 (at most the row's tokens) -> bool [B,
    V]: the ``n[b]`` largest of row ``b``, a tie at the edge to the
    lower index. ONE kernel, :data:`_PICK_ROWS` rows a grid step in
    VMEM, and no sort: a float's bits, the magnitude flipped under a
    set sign, order as the floats do, and the ``n``-th largest is
    built bit by bit from the top, each bit one counting pass (``count
    (bits >= candidate) >= n``): 32 compares and row sums over what is
    read from HBM once, where ``lax.top_k`` at ``k`` 2,048 of 5,120
    sorts every row. How many of the values EQUAL to it still belong
    (``need``) is bisected the same way on the column index (the
    largest ``c`` with fewer than ``need`` ties before it). Rows and
    columns are padded to whole tiles with nothing to pick."""
    from jax.experimental import pallas as pl

    from .._private.chip import pallas_interpret

    B, V = scores.shape
    rows, cols = -B % _PICK_ROWS, -V % 128
    scores = jnp.pad(scores, ((0, rows), (0, cols)),
                     constant_values=-jnp.inf)
    n = jnp.pad(n.astype(jnp.int32), (0, rows))[:, None]
    Bp, Vp = scores.shape
    index_bits = max(Vp - 1, 1).bit_length()

    def kernel(n_ref, s_ref, o_ref):
        bits = lax.bitcast_convert_type(s_ref[...], jnp.int32)
        key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
        want = n_ref[...].astype(jnp.float32)                  # [R, 1]
        col = lax.broadcasted_iota(jnp.int32, key.shape, 1)

        def count(mask):        # exact in float32: at most V of them
            return jnp.sum(jnp.where(mask, 1.0, 0.0), axis=1,
                           keepdims=True)

        def value_bit(i, t):    # from the sign's bit down; the int32
            cand = t + (jnp.int32(1) << (31 - i))           # sum wraps
            return jnp.where(count(key >= cand) >= want, cand, t)

        kth = lax.fori_loop(
            0, 32, value_bit,
            jnp.full(want.shape, jnp.iinfo(jnp.int32).min, jnp.int32))
        above, tie = key > kth, key == kth
        need = want - count(above)

        def index_bit(i, c):
            cand = c | (jnp.int32(1) << (index_bits - 1 - i))
            return jnp.where(count(tie & (col < cand)) < need, cand, c)

        last = lax.fori_loop(0, index_bits, index_bit,
                             jnp.zeros(want.shape, jnp.int32))
        o_ref[...] = (above | (tie & (col <= last) & (need >= 1.0))
                      ).astype(jnp.int32)

    # `name` names the device operation ("pick_top.N") and the last
    # component of its path before "pallas_call"
    picked = pl.pallas_call(
        kernel,
        grid=(Bp // _PICK_ROWS,),
        in_specs=[pl.BlockSpec((_PICK_ROWS, 1), lambda b: (b, 0)),
                  pl.BlockSpec((_PICK_ROWS, Vp), lambda b: (b, 0))],
        out_specs=pl.BlockSpec((_PICK_ROWS, Vp), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((Bp, Vp), jnp.int32),
        interpret=pallas_interpret(),
        name="pick_top",
    )(n, scores)
    return picked[:B, :V] != 0


def _block_ffn(x, p, cfg: DSAMoEConfig, live=None):
    """:func:`ray_tpu.models.moe.block_ffn` with the router's selection
    bias: x [T, d] -> (x + FFN(RMSNorm(x)), counts int32 [4])."""
    if "ffn" in p:
        return moe.block_ffn(x, p, cfg, live)
    h = moe.rmsnorm(x, p["ln2_scale"], cfg.eps, cfg.dtype)
    with jax.named_scope("moe.route"):
        ids, w = moe.route_sigmoid(
            h, p["router"]["kernel"], n_group=cfg.n_group,
            topk_group=cfg.topk_group, top_k=cfg.top_k,
            norm_topk=cfg.norm_topk, route_scale=cfg.route_scale,
            dtype=cfg.dtype, bias=p["router"]["bias"])
    y, counts = moe.dropless_experts(
        h, ids, w, p["experts"], experts_held=cfg.experts_held,
        expert_offset=cfg.expert_offset, dtype=cfg.dtype,
        block_rows=cfg.moe_block_rows, live=live)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + moe.gated_ffn(h, p["shared"], cfg.dtype)
    return x + y.astype(x.dtype), \
        jnp.concatenate([jnp.ones((1,), jnp.int32), counts])


# ----------------------------------------------------------- description
def cache_spec(cfg: DSAMoEConfig, kv_dtype: str = "fp") -> CacheSpec:
    """What a token leaves in a page, per layer: ``mla_moe``'s latent
    row and ONE index key in a row of whole lane tiles, both in the
    compute dtype, under the same page ids: the pool is ``latent``
    ``[L, n_pages, page_size, latent_row]`` and ``ikey`` ``[L, n_pages,
    page_size, index_row]``."""
    serving.check_kv_dtype(_THIS, kv_dtype)
    return CacheSpec(cfg.n_layer, (
        CacheEntry("latent", "token", (cfg.latent_row,), cfg.dtype),
        CacheEntry("ikey", "token", (cfg.index_row,), cfg.dtype)))


# what follows from the spec and from ``UNSUPPORTED["tp"]``: the frame's
kv_bytes_per_page = serving.bind(serving.kv_bytes_per_page, _THIS)
init_paged_cache = serving.bind(serving.init_paged_cache, _THIS)
check_tp = serving.bind(serving.check_tp, _THIS)
shard_params = serving.bind(serving.shard_params, _THIS)


# -------------------------------------------------------------- programs
def _prefill_layers(params, x, pool, ipool, attend, positions, page_w, off,
                    live, cfg, n_pages: int):
    """The layers of a paged prefill around ``mla_moe``'s ``attend``:
    ``x`` [1, R, d] whose rows sit at ``positions`` [R] and land at
    ``(page_w, off)`` [R] (sentinel: dropped); each layer writes its
    rows' index keys (scope ``dsa.prefill``), attends as ``mla_moe``
    does and runs its FFN. Returns ``(x, pool, ipool)``."""
    for l, p in enumerate(params["layers"]):
        with jax.named_scope("dsa.prefill"):
            h = moe.rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
            ipool = ipool.at[serving.at_layer(page_w, l, n_pages), off].set(
                index_key(h, p, positions[None], cfg)[0], mode="drop")
        x, pool = attend(x, p, l, pool)
        x = _block_ffn(x[0], p, cfg, live)[0][None]
    return x, pool, ipool


def prefill_into_slot_paged(params: Params, cache: Cache,
                            tokens: jax.Array, length: jax.Array,
                            hist_len: jax.Array, pt_row: jax.Array,
                            cow_src: jax.Array, slot: jax.Array,
                            rng: jax.Array, *, cfg: DSAMoEConfig,
                            page_size: int, temperature: float = 0.0,
                            kv_dtype: str = "fp"
                            ) -> Tuple[jax.Array, Cache, jax.Array]:
    """Prefill one prompt SUFFIX into its pages: the contract of
    :func:`ray_tpu.models.gpt_decode.prefill_into_slot_paged`, on
    latent pages (:func:`ray_tpu.models.mla_moe.prefill_attention`: the
    attention over every cached token, which is ``S_t`` while the
    sequence is at most ``index_topk`` long) and the index keys beside
    them."""
    S = tokens.shape[1]
    check_prompt_buckets(cfg, (S,))
    ps = page_size
    n_pages, max_pages = cache["latent"].shape[1], pt_row.shape[0]
    pool, live, attend = mla_moe.prefill_attention(
        cache, S, length, hist_len, pt_row, cow_src, cfg, ps)
    ipool = mla_moe.fork_pages(cache["ikey"], hist_len[None], pt_row[None],
                               cow_src[None], ps)
    positions = hist_len + jnp.arange(S)
    vp = positions // ps
    page_w = jnp.where(live & (vp < max_pages),
                       pt_row[jnp.clip(vp, 0, max_pages - 1)],
                       jnp.int32(PT_SENTINEL))
    x, pool, ipool = _prefill_layers(
        params, moe.embed(params, tokens), pool, ipool, attend, positions,
        page_w, positions % ps, live, cfg, n_pages)
    token, out, rng = mla_moe.prefill_result(
        x, pool, params, cache, length, hist_len, slot, rng, cfg,
        temperature)
    return token, dict(out, ikey=ipool.reshape(cache["ikey"].shape)), rng


def prefill_group_into_slots_paged(params: Params, cache: Cache, tokens,
                                   length: jax.Array, hist_len: jax.Array,
                                   pt_row: jax.Array, cow_src: jax.Array,
                                   slot: jax.Array, rng: jax.Array, *,
                                   cfg: DSAMoEConfig, page_size: int,
                                   temperature: float = 0.0,
                                   kv_dtype: str = "fp"
                                   ) -> Tuple[jax.Array, Cache, jax.Array]:
    """:func:`prefill_into_slot_paged` for the ``G`` prompts of one
    chunk boundary in ONE launch (the frame's contract,
    ``models/serving.py``;
    :func:`ray_tpu.models.mla_moe.prefill_group_attention`)."""
    rows = serving.PromptRows(tokens, length, hist_len)
    check_prompt_buckets(cfg, rows.sizes)
    n_pages = cache["latent"].shape[1]
    pool, attend = mla_moe.prefill_group_attention(
        cache, rows, hist_len, pt_row, cow_src, cfg, page_size)
    ipool = mla_moe.fork_pages(cache["ikey"], hist_len, pt_row, cow_src,
                               page_size)
    page_w, off = rows.pages(pt_row, page_size)
    x, pool, ipool = _prefill_layers(
        params, moe.embed(params, rows.tokens)[None], pool, ipool, attend,
        rows.positions, page_w, off, rows.live,
        moe.group_cfg(cfg, rows.G), n_pages)
    token, out, rng = mla_moe.prefill_group_result(
        x, pool, params, cache, rows, length, hist_len, slot, rng, cfg,
        temperature)
    return token, dict(out, ikey=ipool.reshape(cache["ikey"].shape)), rng


def decode_attention(cache: Cache, active, pt, cfg: DSAMoEConfig,
                     page_size: int, attn_kernel: str = "gather"):
    """One decode step's frame: ``(pool, ipool, counts, attend)``, the
    two pools in their flat views, the step's four selection counters
    (:data:`STEP_COUNTERS`' last) and ``attend(x, p, a, pool, ipool) ->
    (x + Attn_a(x), pool', ipool')`` for ``x`` [B, 1, d]: each active
    lane writes its latent row and its index key at its own position,
    scores its cached keys (``dsa.index``), picks (``dsa.select``) and
    attends, in the latent space with the up-projections absorbed, over
    the picked tokens of its own pages (``dsa.attention``). Inactive
    lanes do not write."""
    ps = page_size
    B, max_pages = pt.shape
    pos = cache["pos"]
    n_pages = cache["latent"].shape[1]
    page_w, ptc, length = mla_moe.decode_lanes(cache, active, pt, cfg, ps,
                                               attn_kernel)
    cached = jnp.where(active, pos.astype(jnp.int32) + 1, 0)
    seen = jnp.arange(max_pages * ps)[None] < cached[:, None]    # [B, V]
    n_pick = jnp.minimum(cached, cfg.index_topk)
    counts = jnp.stack([
        jnp.sum(cached), jnp.sum(n_pick), jnp.sum(active, dtype=jnp.int32),
        jnp.sum(cached > cfg.index_topk, dtype=jnp.int32)])

    def attend(x, p, a: int, pool, ipool):
        h, cq, qn, qr, ent = mla_moe.latent_projections(
            x, p, pos[:, None], cfg)
        at = serving.at_layer(page_w, a, n_pages), pos % ps
        pool = pool.at[at].set(ent[:, 0], mode="drop")
        ipool = ipool.at[at].set(
            index_key(h, p, pos[:, None], cfg)[:, 0], mode="drop")
        qi, w = index_query(h, cq, p, pos[:, None], cfg)
        w_uk, w_uv = mla_moe.wkvb(p, cfg)
        q = mla_moe.absorbed_query(qn, qr, w_uk, cfg)
        pages = ptc + a * n_pages
        with jax.named_scope("dsa.index"):
            scores = jnp.where(seen, index_scores(
                qi[:, 0], w[:, 0], ipool[pages].reshape(B, seen.shape[1], -1)),
                -jnp.inf)
        with jax.named_scope("dsa.select"):
            picked = pick_top(scores, n_pick)
        with jax.named_scope("dsa.attention"):
            o = mla_moe.latent_attention(q, pool, pages, pos, length, cfg,
                                         ps, picked)
        return mla_moe.attention_output(o, x, w_uv, p, cfg), pool, ipool

    return serving.flat(cache["latent"]), serving.flat(cache["ikey"]), \
        counts, attend


def _slot_decode_step_paged(params: Params, cache: Cache,
                            token: jax.Array, active: jax.Array,
                            pt: jax.Array, cfg: DSAMoEConfig,
                            page_size: int, kv_dtype: str = "fp",
                            attn_kernel: str = "gather"):
    """One masked decode step over the whole slot pool
    (:func:`decode_attention`, then the layer's FFN). Inactive lanes
    neither write, advance, route nor count. Returns ``(logits [B,
    rows], cache', counts)``: int32 [8] (:data:`STEP_COUNTERS`)."""
    pool, ipool, selection, attend = decode_attention(
        cache, active, pt, cfg, page_size, attn_kernel)
    x = moe.embed(params, token)[:, None]
    counts = jnp.zeros((4,), jnp.int32)
    with jax.named_scope("decode_step"):
        for l, p in enumerate(params["layers"]):
            x, pool, ipool = attend(x, p, l, pool, ipool)
            y, c = _block_ffn(x[:, 0], p, cfg, active)
            x, counts = y[:, None], counts + c
    cache_out = {"latent": pool.reshape(cache["latent"].shape),
                 "ikey": ipool.reshape(cache["ikey"].shape),
                 "pos": cache["pos"] + active.astype(jnp.int32)}
    return moe.head(x, params, cfg)[:, 0], cache_out, \
        jnp.concatenate([counts, selection])


# the chunk program and the two factories are the frame's, around this
# model's step and its eight counters (``models/serving.py``)
decode_chunk_slots_paged = functools.partial(
    serving.decode_chunk_slots_paged, step=_slot_decode_step_paged,
    counters=len(STEP_COUNTERS))
jit_prefill_into_slot_paged = serving.bind(
    serving.jit_prefill_into_slot_paged, _THIS)
jit_decode_chunk_slots_paged = serving.bind(
    serving.jit_decode_chunk_slots_paged, _THIS)
