"""KV-cache autoregressive decoding for the GPT model.

The serving-side twin of :mod:`ray_tpu.models.gpt` (reference
capability: vLLM-style decode loops the reference serves behind Ray
Serve; here designed TPU-first): static-shape caches so XLA compiles
a fixed set of programs (one prefill per prompt bucket, one decode
step, one fused k-step chunk per (bucket, k)), scan over the stacked
layer parameters, and masked full-length attention reads so the decode
step costs O(max_len) with no dynamic shapes.

Layout notes for the MXU/HBM:
- cache is [L, B, max_len, H, hd] in the model compute dtype (bf16 on
  TPU) — the decode step's attention reads it once per token; keeping
  it bf16 halves the HBM traffic that dominates decode latency.
- the single-token block math reuses the training block's weights via
  the same ``_mm`` helper, so MXU-friendly dtypes match training.

Chunked-decode contract (the serve hot path):

- :func:`decode_chunk` fuses k autoregressive steps (sample → embed →
  attend → append KV) into ONE jitted ``lax.scan``, so the host pays a
  single dispatch + one device→host transfer per k tokens instead of
  per token. Greedy when ``temperature == 0``; otherwise temperature
  sampling with the PRNG key threaded through the scan carry (the key
  chain matches :func:`generate`'s per-step ``jax.random.split``).
- Compile matrix: one XLA program per (batch, max_len bucket, k,
  temperature-is-zero, eos_token). Serving stacks should pick k from a
  small fixed set (e.g. {8, 16}) exactly like prompt buckets.
- EOS semantics (mask-and-carry): once a stream samples ``eos_token``
  its lane keeps emitting ``eos_token`` for the rest of the chunk and
  every later chunk — finished lanes are masked, not compacted, so
  shapes stay static. :func:`decode_until` trims the emitted slice at
  the first position where EVERY stream is done, so an early-stopping
  batch never streams (or re-pays for) tokens past its last EOS.
- Streaming granularity: drivers yield one ``[B, j]`` slice per chunk
  (j ≤ k after EOS/max_new trimming); the serve replica forwards each
  slice as one stream item, so HTTP chunked streaming stays
  incremental at chunk granularity.
- Cache writes past ``max_len`` clamp to the last slot (XLA
  ``dynamic_update_slice`` semantics). Tokens emitted past ``max_new``
  are discarded by the driver before any such position is read, so the
  clamp is unobservable as long as prompt + max_new ≤ max_len.

At ``temperature == 0`` the chunked path is asserted token-for-token
identical to the per-token :func:`decode_step` loop (see
``tests/test_models_gpt_decode_chunk.py``).

Slot-pool primitives (the continuous-batching engine's device half,
ISSUE 5; paged since ISSUE 6, and since ISSUE 34 the only pool):
:func:`init_paged_cache` allocates ONE long-lived pool of fixed-size
pages ``[L, n_pages, page_size, H, hd]`` whose ``pos`` is per-slot
``[B_slots]`` instead of a batch-wide scalar, so every slot decodes at
its own depth. A slot's sequence lives wherever its **page table**
points — a ``[max_pages]`` int32 row of physical page indices, padded
with :data:`PT_SENTINEL`. The page table is *traced data*, never a
shape: :func:`prefill_into_slot_paged` and
:func:`_slot_decode_step_paged` gather K/V through it
(``pool[clip(pt)]`` → a virtual ``[max_pages * page_size]`` sequence;
sentinel entries clamp to an arbitrary real page whose garbage the
``<= pos`` mask hides) and write new tokens by scatter at
``(pt[pos // page_size], pos % page_size)`` with out-of-bounds **drop**
semantics — a sentinel write target (a position the host never mapped a
page for) is silently discarded, never clamped into another slot's
page. A dense per-slot cache is the special case of the fixed table
``pt[s, j] = s * max_pages + j`` (the model drafter's,
:mod:`ray_tpu.serve.draft`).

:func:`prefill_into_slot_paged` writes a (right-padded) prompt's K/V
into one slot's pages — one compiled program per prompt bucket, with
the TRUE prompt length traced dynamically, so any length within a
bucket reuses the bucket's program. :func:`decode_chunk_slots_paged`
is the masked twin of :func:`decode_chunk`: k fused steps over the
whole pool in one dispatch, with inactive slots' cache writes and
position advances masked out (their rows compute garbage that the host
ignores, which is cheaper than a dynamic-shape gather/compact on TPU).
Per-slot PRNG lanes keep each stream's sampling chain independent of
admission order. Right-padding is exact, not approximate: pad
positions' writes are dropped, and every decode step writes position
``pos`` BEFORE attention reads ``<= pos``, so pad keys are never
attended — the engine's greedy output is asserted token-identical to
:func:`generate_chunked` (see ``tests/test_serve_engine.py``). The
compiled-program set is one prefill program per (suffix) prompt bucket
+ one chunk program, for ANY page-table contents.

The pool is CARRIED through the decode and verify steps' layer scans,
not scanned: as ``xs``/``ys`` of the scan XLA slices each layer's pool
out of the stacked pool and writes it back, K and V, in every layer of
every token step (a quarter to two fifths of a step on the chip at
2048 pages, whatever the live tokens: PERF.md, PR 25). The carry is
the stacked pool viewed as ``[L * n_pages, page_size, H, hd]``
(:func:`_flat_pool`; merging the leading axes moves no bytes), and
layer ``l`` addresses page ``p`` at ``l * n_pages + p``
(:func:`_layer_pages`, which leaves :data:`PT_SENTINEL` a sentinel):
the scatter of the new rows, the int8 merge, the gather and the
kernel's scalar-prefetched page map then run on the carried pool as
they would on one layer's, and a step writes its rows in place. The
cache dict keeps the stacked ``[L, n_pages, ...]`` shapes at every
program boundary (prefill, export/import, the tp specs).

Shared-prefix reuse rides the same machinery: a prompt whose prefix is
already resident (the engine's prefix cache) maps the cached pages into
its page table and prefills only the **suffix** — ``hist_len`` is a
traced scalar, the suffix attends over history K/V read through the
page table a block at a time (as many blocks as the hit is long, never
``max_len``: :func:`ray_tpu.models.serving.attend_history`), and the
one copy-on-write fork a lane may need (when the
cached prefix ends mid-page) is fused into the same prefill program as
a masked page copy, so prefix hits add ZERO compiled programs.

Token identity with a dense cache (:func:`generate_chunked`) holds
bitwise on CPU: the gathered virtual sequence contains the same K/V
values at the same virtual positions, extra masked positions contribute
exact zeros to the softmax (``exp(-1e30 - max)`` underflows to 0.0),
and the per-slot PRNG lanes split as the batch-wide key does — asserted
at temperature 0 AND seeded temperature > 0 in
``tests/test_serve_engine_paged.py``.

Speculative verify (ISSUE 9): chunked decode pays one TARGET forward
per token (k sequential steps fused per dispatch).
:func:`verify_chunk_slots_paged` replaces
those k sequential forwards with ONE batched forward over the k tokens
a cheap drafter proposed per slot: the kernel feeds ``[last, d_1..d_k]``
(k+1 positions), writes their K/V at each slot's own ``pos..pos+k``,
scores all k+1 logit rows, computes the per-slot accepted length with
rejection sampling (:func:`_spec_accept` — greedy exact-match at
temperature 0, point-mass residual resampling above it, so the output
distribution is the target's for ANY drafter), samples the
bonus/correction token from the target's own row, and advances ``pos``
by ``1 + n_acc`` per slot — the write cursor rolls back past rejected
positions, whose garbage K/V is overwritten before it is ever attended
(the same write-at-pos-before-reading-<=pos exactness argument as
prompt right-padding). Everything is traced with chunk-static shapes:
one verify program per (pool shape, k) on top of the usual
``len(prompt_buckets) + 1``, for any acceptance pattern.

KV handoff (ISSUE 14): disaggregated prefill/decode ships a prefilled
slot between engines. :func:`export_slot_kv_paged`
extracts one slot's K/V into contiguous ship order (the host trims to the
true ``pos`` — pad/stale garbage never crosses the wire, so the shipped
fp bytes are identical whichever page size produced them), and
:func:`import_slot_kv_paged` scatters a
host-padded ship buffer into a target pool's mapped pages
and sets the slot's ``pos``. Slot index, page table, and length are all
traced: the whole handoff plane adds exactly TWO compiled programs per
engine (one export, one import) on top of the usual set, for any
prompt length and any pairing of fp page sizes.

Tensor-parallel decode (ISSUE 20): every slot-pool primitive above has
a mesh-aware twin path selected by the factories' trailing ``tp``
static. ``tp > 1`` shards the program over the 1-D ``("tp",)`` mesh
built by :func:`ray_tpu._private.jax_compat.decode_mesh`: qkv and the
ffn up-projection are column-parallel (each device owns ``H/tp`` whole
heads and ``d_ff/tp`` ffn lanes — contractions run over the full
``d_model``, so per-shard math is bitwise the tp=1 math), the output
projections ``wo``/``w2`` are row-parallel with the f32 partial sums
``lax.psum``-reduced BEFORE the compute-dtype cast (:func:`_mm_row` —
the only tp-introduced arithmetic difference is f32 summation order,
far below the compute dtype's resolution, the same argument as the
pallas kernel above), and the pooled KV cache (fp AND
int8) is sharded over the HEAD axis so attention stays embarrassingly
head-parallel. Sampling runs replicated on the psum'd logits with the
same PRNG lanes on every device, so every device commits the same
token. The factories wrap the SAME inner functions in ``shard_map``
(through the jax_compat shim) inside ``jax.jit`` with the same
donation — tp=1 callers get byte-identical wrappers to before, and the
compiled-program budget is counted per (bucket, tp) key by the same
lru_cache discipline. The handoff plane is the resharding boundary:
exports emit head-sharded device arrays whose host gather
(``np.asarray``) is the canonical layout regardless of tp, and imports
scatter host-canonical buffers into the target's own mesh — so N-way
prefill hands off to M-way decode with the digest computed over
layout-independent bytes. MoE (``n_experts > 0``) is rejected under
tp>1: :func:`ray_tpu.models.moe.moe_ffn` is not tp-aware.

Paged-attention kernel + int8 KV (ISSUE 16): two orthogonal,
engine-static knobs on the paged hot path. ``attn_kernel="pallas"``
swaps the decode step's gather-then-mask attention for
:func:`paged_attention`'s fused Pallas kernel, which since ISSUE 61 is
the grouped-query kernel of :mod:`ray_tpu.models.kda_moe`
(:func:`~ray_tpu.models.kda_moe.gqa_decode_attention`) at a group of
ONE: multi-head attention is grouped-query attention whose every query
head has keys and values of its own. One grid step a slot, whose work
is that slot's OWN live pages — the page table, the live lengths and
the count of blocks before each lane ride as scalar-prefetch operands,
the pool stays in HBM viewed ``[pages, page_size * H, hd]`` (a page's
rows as they lie, ``(token, head)``: no copy), and the kernel copies
each live page ONCE into a ring of VMEM blocks of pages (of a page
larger than a block, a lane's whole ``max_len`` say, in equal parts, a
part a block), the lanes' blocks one stream, so a lane's last blocks
fetch the next lane's first. A block is ONE operand of two MXU products over all heads at
once (``q . K^T`` with a foreign head's score masked to -1e30, then ``p
. V``) around one running-max softmax pass in float32. Pages that are
:data:`PT_SENTINEL`-unmapped or past ``pos`` cost nothing: no fetch,
and a lane without a live token returns zeros. Off-TPU the same kernel
runs in interpret mode, so CPU tier-1 exercises the shipping kernel
body; on a TPU a page's rows (or such a part's) must be whole sublane
tiles (``page_size * H`` a multiple of 16 in bfloat16, 32 in int8) and
``hd`` whole lanes,
and a pool Mosaic cannot address is refused by name. The kernel rounds
its probabilities to the compute dtype before they meet V, as the
gather path does, but before the division by the sum instead of after
it: the two paths agree to :data:`ATTN_KERNEL_ULPS` bf16 ulp of the
largest output, not to the bit, and streams are held to the reference
by margin, not by token identity (ROADMAP D10).
``kv_dtype="int8"`` stores pages as symmetric int8 codes with one f32
scale per (layer, page, head) per side (~2x the pages in the same
HBM at bf16): scatters become page-granular requantize-and-merge
(:func:`_merge_span_int8` — monotone scales make rewrites drift-free,
fresh pages reset, positions past ``pos`` stay zero so page bytes are
canonical for digests), and every read dequantizes through
:func:`_deq_page` at the point of use. Neither knob changes the
compiled-program COUNT: both are baked statics selecting WHICH
program each existing factory builds.
"""
from __future__ import annotations

import functools
import sys
from typing import Dict, Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .._private.jax_compat import decode_mesh, shard_map
from .gpt import (GPTConfig, Params, _mm, _project_vocab, _rmsnorm)
from . import serving
from .serving import PT_SENTINEL, CacheEntry, CacheSpec

Cache = Dict[str, jax.Array]
_THIS = sys.modules[__name__]

# ------------------------------------------------------- tensor parallel
#: Block kernels sharded on their OUTPUT dim (column-parallel): each
#: device owns whole heads (wq/wk/wv) or an ffn slice (w1), so the
#: contraction runs over the full d_model and per-shard results are
#: bitwise the tp=1 results.
_TP_COL = frozenset({"wq", "wk", "wv", "w1"})
#: Block kernels sharded on their INPUT dim (row-parallel): wo/w2
#: consume the head-/ffn-sharded activations and psum f32 partials.
_TP_ROW = frozenset({"wo", "w2"})


def _mm_row(x, w, dtype, tp_axis=None):
    """Row-parallel :func:`ray_tpu.models.gpt._mm`: under shard_map the
    local contraction covers only this device's slice of the input dim,
    so the f32 partial sums are ``lax.psum``-reduced across ``tp_axis``
    BEFORE the compute-dtype cast — the cast point matches tp=1's
    ``_mm`` exactly, so the only difference is f32 summation order.
    With ``tp_axis=None`` this IS ``_mm``, bit for bit."""
    if tp_axis is None:
        return _mm(x, w, dtype)
    out = lax.dot_general(x.astype(dtype), w.astype(dtype),
                          (((x.ndim - 1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    return lax.psum(out, tp_axis).astype(dtype)


def _tp_mesh(cfg: GPTConfig, tp: int):
    """Validate a (cfg, tp) pairing and return its decode mesh — or
    None for tp=1, the signal to every factory that the stock
    single-device path (byte-identical to pre-tp builds) applies."""
    tp = int(tp)
    if tp <= 1:
        return None
    if cfg.n_experts > 0:
        raise ValueError(
            f"tensor-parallel decode (tp={tp}) does not support MoE "
            f"configs (n_experts={cfg.n_experts}): moe_ffn is not "
            f"tp-aware")
    if cfg.n_head % tp or cfg.d_ff % tp or cfg.d_model % tp:
        raise ValueError(
            f"tp={tp} must divide n_head={cfg.n_head}, "
            f"d_ff={cfg.d_ff} and d_model={cfg.d_model}")
    return decode_mesh(tp)


def _tp_param_specs(params):
    """PartitionSpec pytree for the decode params under a ``("tp",)``
    mesh: column-parallel kernels shard their last axis, row-parallel
    kernels their axis 1 (axis 0 is the stacked layer axis), everything
    else (embed, pos_embed, norm scales) replicates."""
    P = jax.sharding.PartitionSpec

    def spec(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", None))
                 for p in path]
        nd = jnp.ndim(leaf)
        if any(n in _TP_COL for n in names):
            return P(*([None] * (nd - 1) + ["tp"]))
        if any(n in _TP_ROW for n in names):
            return P(*(["tp"] if nd < 2 else [None, "tp"]
                       + [None] * (nd - 2)))
        return P()

    return jax.tree_util.tree_map_with_path(spec, params)


def _tp_cache_specs(cache):
    """PartitionSpec dict for a pool cache (fp or int8)
    under a ``("tp",)`` mesh: K/V pages shard their HEAD axis (axis
    3), int8 per-page scales their head axis (last), and
    ``pos`` replicates."""
    P = jax.sharding.PartitionSpec
    out = {}
    for name in cache:
        if name in ("k", "v"):
            out[name] = P(None, None, None, "tp", None)
        elif name in ("ks", "vs"):
            out[name] = P(None, None, "tp")
        else:
            out[name] = P()
    return out


#: The engine's name for the (cfg, tp) validation.
check_tp = _tp_mesh


def shard_params(params: Params, cfg: GPTConfig, tp: int) -> Params:
    """Device-put the decode params into their tp layout
    (:func:`_tp_param_specs` under :func:`decode_mesh`) so every
    sharded program consumes pre-placed weights instead of re-slicing
    host copies per dispatch. tp=1 returns ``params`` untouched."""
    mesh = _tp_mesh(cfg, tp)
    if mesh is None:
        return params
    specs = _tp_param_specs(params)
    return jax.tree_util.tree_map(
        lambda x, s: jax.device_put(
            x, jax.sharding.NamedSharding(mesh, s)), params, specs)


def shard_program(inner, mesh, n_out: int, cache_out: int = 1,
                  check_vma: bool = True):
    """``inner(params, cache, *rest)`` under ``shard_map`` on ``mesh``:
    the weights by :func:`_tp_param_specs`, the pool by
    :func:`_tp_cache_specs` (in, and out at ``cache_out`` of its
    ``n_out`` values), everything else replicated. What a mesh adds to
    a program of the frame (``models/serving.py``) and of this
    module."""
    P = jax.sharding.PartitionSpec

    def fn(params, cache, *rest):
        cspec = _tp_cache_specs(cache)
        outs = [P()] * n_out
        outs[cache_out] = cspec
        return shard_map(
            inner, mesh=mesh,
            in_specs=(_tp_param_specs(params), cspec)
            + (P(),) * len(rest),
            out_specs=tuple(outs), check_vma=check_vma)(
                params, cache, *rest)

    return fn


def init_cache(cfg: GPTConfig, batch: int, max_len: int) -> Cache:
    shape = (cfg.n_layer, batch, max_len, cfg.n_head, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, cfg.dtype),
        "v": jnp.zeros(shape, cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def _block_kv(x, p, cfg: GPTConfig):
    """Training block minus attention: returns (q, k, v, pre-attn x).
    The head-count reshape is ``-1`` so that under shard_map (where the
    local qkv kernels project to ``H/tp`` heads) the same code yields
    the local head slice."""
    B, S, _ = x.shape
    h = _rmsnorm(x, p["ln1_scale"])
    q = _mm(h, p["wq"]["kernel"], cfg.dtype).reshape(B, S, -1,
                                                     cfg.head_dim)
    k = _mm(h, p["wk"]["kernel"], cfg.dtype).reshape(B, S, -1,
                                                     cfg.head_dim)
    v = _mm(h, p["wv"]["kernel"], cfg.dtype).reshape(B, S, -1,
                                                     cfg.head_dim)
    return q, k, v


def _ffn(x, p, cfg: GPTConfig, tp_axis=None):
    h = _rmsnorm(x, p["ln2_scale"])
    if cfg.n_experts > 0:
        from ray_tpu.models.moe import moe_ffn

        y, _ = moe_ffn(h, p["router"]["kernel"], p["w_up"]["kernel"],
                       p["w_down"]["kernel"], top_k=cfg.expert_top_k,
                       capacity_factor=cfg.capacity_factor,
                       dtype=cfg.dtype)
        return x + y
    h = _mm(h, p["w1"]["kernel"], cfg.dtype)
    h = jax.nn.gelu(h)
    return x + _mm_row(h, p["w2"]["kernel"], cfg.dtype, tp_axis)


def prefill(params: Params, tokens: jax.Array, cfg: GPTConfig,
            cache: Cache) -> Tuple[jax.Array, Cache]:
    """Run the prompt once, filling the cache.

    tokens [B, S] → (last-position logits [B, vocab], cache with
    pos=S). S must be <= the cache's max_len; compile once per padded
    prompt bucket.
    """
    B, S = tokens.shape
    max_len = cache["k"].shape[2]
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    x = params["embed"]["kernel"].astype(cfg.dtype)[tokens]
    x = x + params["pos_embed"][:S].astype(cfg.dtype)[None]

    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))

    def body(carry, layer):
        x = carry
        p, kc, vc = layer
        q, k, v = _block_kv(x, p, cfg)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(mask, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                         preferred_element_type=jnp.float32
                         ).astype(q.dtype).reshape(B, S, cfg.d_model)
        x = x + _mm(att, p["wo"]["kernel"], cfg.dtype)
        x = _ffn(x, p, cfg)
        kc = lax.dynamic_update_slice(kc, k, (0, 0, 0, 0))
        vc = lax.dynamic_update_slice(vc, v, (0, 0, 0, 0))
        return x, (kc, vc)

    x, (k_new, v_new) = lax.scan(
        body, x, (params["block"], cache["k"], cache["v"]))
    x = _rmsnorm(x, params["ln_f_scale"])
    logits = _project_vocab(x[:, -1:], params["embed"]["kernel"], cfg)
    new_cache = {"k": k_new, "v": v_new,
                 "pos": jnp.asarray(S, jnp.int32)}
    return logits[:, 0], new_cache


def decode_step(params: Params, cache: Cache, token: jax.Array,
                cfg: GPTConfig) -> Tuple[jax.Array, Cache]:
    """One autoregressive step: token [B] int32 → (logits [B, vocab],
    cache advanced by one). Static shapes: attention reads the full
    cache length with future positions masked."""
    B = token.shape[0]
    max_len = cache["k"].shape[2]
    pos = cache["pos"]
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    x = params["embed"]["kernel"].astype(cfg.dtype)[token][:, None]
    x = x + jnp.take(params["pos_embed"], pos, axis=0
                     ).astype(cfg.dtype)[None, None]
    # Positions <= pos are valid history (incl. the token being written).
    valid = (jnp.arange(max_len) <= pos)[None, None, None, :]

    def body(carry, layer):
        x = carry
        p, kc, vc = layer
        q, k, v = _block_kv(x, p, cfg)   # [B, 1, H, hd]
        kc = lax.dynamic_update_slice(kc, k, (0, pos, 0, 0))
        vc = lax.dynamic_update_slice(vc, v, (0, pos, 0, 0))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(valid, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, vc,
                         preferred_element_type=jnp.float32
                         ).astype(q.dtype).reshape(B, 1, cfg.d_model)
        x = x + _mm(att, p["wo"]["kernel"], cfg.dtype)
        x = _ffn(x, p, cfg)
        return x, (kc, vc)

    x, (k_new, v_new) = lax.scan(
        body, x, (params["block"], cache["k"], cache["v"]))
    x = _rmsnorm(x, params["ln_f_scale"])
    logits = _project_vocab(x, params["embed"]["kernel"], cfg)
    return logits[:, 0], {"k": k_new, "v": v_new, "pos": pos + 1}


def generate(params: Params, prompt: jax.Array, cfg: GPTConfig,
             max_new_tokens: int, max_len: int = 0,
             temperature: float = 0.0, rng: jax.Array = None):
    """Greedy/sampled generation; yields one [B] token array per step
    (the serving replica streams these). Jits prefill and decode_step
    once each per (batch, max_len) shape."""
    B, S = prompt.shape
    max_len = max_len or cfg.max_seq
    if S + max_new_tokens > max_len:
        raise ValueError(
            f"prompt ({S}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"cache length {max_len}")
    if temperature > 0.0 and rng is None:
        rng = jax.random.PRNGKey(0)
    cache = init_cache(cfg, B, max_len)
    pf = _jitted_prefill()
    step = _jitted_decode_step()
    logits, cache = pf(params, prompt, cfg, cache)
    for i in range(max_new_tokens):
        if temperature > 0.0:
            rng, sub = jax.random.split(rng)
            token = jax.random.categorical(
                sub, logits / temperature, axis=-1).astype(jnp.int32)
        else:
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        yield token
        if i + 1 < max_new_tokens:
            logits, cache = step(params, cache, token, cfg)


def decode_chunk(params: Params, cache: Cache, token: jax.Array,
                 rng: jax.Array = None, *, cfg: GPTConfig, k: int,
                 temperature: float = 0.0, eos_token: int = -1):
    """k fused autoregressive steps in ONE program: a ``lax.scan`` over
    the single-step body, so the whole chunk is one host→device
    dispatch instead of k.

    ``token`` [B] int32 is the last emitted token (fed as the first
    step's input); returns ``(tokens [B, k], cache advanced k, done [B],
    rng')``. Finished streams (``eos_token`` sampled, or fed in as
    ``token``) are masked-and-carried: they keep emitting ``eos_token``
    and their ``done`` flag survives across chunks via the returned
    tokens' final column. ``cfg``/``k``/``temperature``/``eos_token``
    are compile-time constants — jit through :func:`jit_decode_chunk`.
    """
    B = token.shape[0]
    if rng is None:
        rng = jax.random.PRNGKey(0)
    eos = jnp.asarray(eos_token, jnp.int32)
    done0 = (token == eos) if eos_token >= 0 \
        else jnp.zeros((B,), jnp.bool_)

    def body(carry, _):
        cache, tok, done, key = carry
        logits, cache = decode_step(params, cache, tok, cfg)
        nxt, key = serving.sample(logits, temperature, key)
        if eos_token >= 0:
            nxt = jnp.where(done, eos, nxt)
            done = done | (nxt == eos)
        return (cache, nxt, done, key), nxt

    (cache, _, done, rng), toks = lax.scan(
        body, (cache, token, done0, rng), None, length=k)
    return jnp.moveaxis(toks, 0, 1), cache, done, rng


# rtlint: program-budget: 1
@functools.lru_cache(maxsize=64)
def jit_decode_chunk(cfg: GPTConfig, k: int, temperature: float = 0.0,
                     eos_token: int = -1):
    """Jitted :func:`decode_chunk` with the static knobs baked in: one
    compiled program per (cache bucket, k). Returns
    ``step(params, cache, token, rng) -> (tokens, cache, done, rng)``.
    Cached on the (hashable) static knobs — repeated calls return the
    SAME jit wrapper, so per-request drivers reuse the compiled program
    instead of retracing (jax keys its cache on wrapper identity)."""
    return jax.jit(serving.program(
        decode_chunk, cfg=cfg, k=k, temperature=temperature,
        eos_token=eos_token))


# rtlint: program-budget: 1
@functools.lru_cache(maxsize=None)
def _jitted_prefill():
    return jax.jit(prefill, static_argnums=(2,))


# rtlint: program-budget: 1
@functools.lru_cache(maxsize=None)
def _jitted_decode_step():
    return jax.jit(decode_step, static_argnums=(3,))


def decode_until(step, params: Params, cache: Cache, token: jax.Array,
                 max_new: int, *, eos_token: int = -1,
                 rng: jax.Array = None) -> Iterator[np.ndarray]:
    """Drive a jitted chunk step until ``max_new`` tokens are emitted or
    every stream has sampled ``eos_token``. Yields one trimmed np.int32
    ``[B, j]`` slice per chunk (j ≤ k) — the streaming granularity.

    EOS handling happens in two layers: inside the scan, finished lanes
    are masked to keep emitting eos (static shapes); here, the emitted
    slice is cut at the first position where ALL lanes are done, so an
    early-stopping batch never streams tokens past its final EOS.
    """
    if rng is None:
        rng = jax.random.PRNGKey(0)
    done = np.zeros((token.shape[0],), bool)
    if eos_token >= 0:
        done |= np.asarray(token) == eos_token
    remaining = max_new
    while remaining > 0 and not done.all():
        toks_dev, cache, _, rng = step(params, cache, token, rng)
        toks = np.asarray(toks_dev)        # ONE transfer per chunk
        j = min(toks.shape[1], remaining)
        if eos_token >= 0:
            cum = np.logical_or.accumulate(toks == eos_token, axis=1) \
                | done[:, None]
            all_done = np.all(cum, axis=0)
            if all_done.any():
                j = min(j, int(all_done.argmax()) + 1)
            done = cum[:, j - 1].copy()
        yield toks[:, :j]
        remaining -= j
        token = toks_dev[:, -1]            # stays on device


def generate_chunked(params: Params, prompt: jax.Array, cfg: GPTConfig,
                     max_new_tokens: int, *, chunk: int = 8,
                     max_len: int = 0, temperature: float = 0.0,
                     rng: jax.Array = None,
                     eos_token: int = -1) -> Iterator[np.ndarray]:
    """Chunked twin of :func:`generate`: yields np.int32 ``[B, j]``
    slices — first the prefill-derived token alone (minimal TTFT), then
    one slice per fused k-step chunk. At temperature 0 the concatenated
    tokens are identical to :func:`generate`'s; at temperature > 0 the
    PRNG split chain matches generate's per-step splits."""
    B, S = prompt.shape
    max_len = max_len or cfg.max_seq
    if max_new_tokens <= 0:
        return
    if S + max_new_tokens > max_len:
        raise ValueError(
            f"prompt ({S}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"cache length {max_len}")
    if temperature > 0.0 and rng is None:
        rng = jax.random.PRNGKey(0)
    cache = init_cache(cfg, B, max_len)
    logits, cache = _jitted_prefill()(params, prompt, cfg, cache)
    token, rng = serving.sample(logits, temperature,
                         rng if rng is not None else jax.random.PRNGKey(0))
    first = np.asarray(token)[:, None]
    yield first
    if max_new_tokens <= 1 or (eos_token >= 0
                               and (first == eos_token).all()):
        return
    step = jit_decode_chunk(cfg, chunk, temperature, eos_token)
    yield from decode_until(step, params, cache, token,
                            max_new_tokens - 1, eos_token=eos_token,
                            rng=rng)


# --------------------------------------------------------------- slot pool
def shard_cache(cache: Cache, mesh) -> Cache:
    """Device-put a freshly-zeroed pool into its tp layout so the first
    donated dispatch doesn't pay a resharding copy (and donation sees
    matching input/output shardings)."""
    specs = _tp_cache_specs(cache)
    return {name: jax.device_put(
        v, jax.sharding.NamedSharding(mesh, specs[name]))
        for name, v in cache.items()}


# -------------------------------------------------------------- paged pool
#: Page-table padding (:data:`ray_tpu.models.serving.PT_SENTINEL`,
#: imported above): out of bounds for a scatter, clipped for a read.

#: KV-pool storage dtypes. ``"fp"`` stores pages in the model compute
#: dtype; ``"int8"`` stores symmetric per-page-per-head int8 codes plus
#: one float32 scale per (layer, page, head) per side, so the same HBM
#: budget holds ~2x the pages at bf16 compute.
KV_DTYPES = ("fp", "int8")

#: Decode attention implementations for the paged pool. ``"gather"`` is
#: the stock-XLA page-table gather + masked full-length attention;
#: ``"pallas"`` is the fused kernel over each slot's live pages
#: (interpret mode off TPU). They agree to :data:`ATTN_KERNEL_ULPS`.
ATTN_KERNELS = ("gather", "pallas")
#: The written bound on |kernel - gather| of :func:`paged_attention`, in
#: ulps (2**-8, relative) of the LARGEST output of the call. Both paths
#: round every probability once to the compute dtype (half an ulp of
#: each term of a sum of like-weighted terms) — the gather path after
#: dividing by the sum, the kernel before — and round the float32
#: result once: read 0.5-1.6 over the shapes of
#: ``tests/test_gpt_decode_kernel.py`` on the CPU and 0.5-1.1 (fp) /
#: 1.1-1.5 (int8) at the serving shape on a TPU v5e (PR 35). 4 leaves
#: room for a call whose largest output is an average far below its
#: largest value.
ATTN_KERNEL_ULPS = 4

#: Quantization scale floor: an all-zero page quantizes (and
#: dequantizes) to exact zeros instead of dividing by zero.
_KV_EPS = 1e-8


def cache_spec(cfg: GPTConfig, kv_dtype: str = "fp") -> CacheSpec:
    """What a token leaves in a page, per layer: keys and values per
    head, ``[H, hd]`` each, in the compute dtype (``"fp"``) or as int8
    codes with one float32 scale per (page, head) per side
    (``"int8"``). The pool's shapes, its page cost, the engine's
    handoff checks and ``kv_bytes_per_token`` all come from here."""
    serving.check_kv_dtype(_THIS, kv_dtype)
    row = (cfg.n_head, cfg.head_dim)
    if kv_dtype == "int8":
        entries = (CacheEntry("k", "token", row, jnp.int8),
                   CacheEntry("v", "token", row, jnp.int8),
                   CacheEntry("ks", "page", row[:1], jnp.float32),
                   CacheEntry("vs", "page", row[:1], jnp.float32))
    else:
        entries = (CacheEntry("k", "token", row, cfg.dtype),
                   CacheEntry("v", "token", row, cfg.dtype))
    return CacheSpec(cfg.n_layer, entries)


def max_positions(cfg: GPTConfig) -> int:
    """The longest sequence the model can place: the rows of its
    learned position table."""
    return cfg.max_seq


#: What the engine offers and this model does not take: nothing.
UNSUPPORTED: Dict[str, str] = {}
#: The chunk program returns tokens, cache, done and keys, no counters.
STEP_COUNTERS: Tuple[str, ...] = ()


def decode_attention_fused(cfg: GPTConfig, page_size: int,
                           attn_kernel: str = "gather") -> bool:
    """Whether the chunk program built with these knobs holds the
    Pallas kernel (the description's entry,
    :mod:`ray_tpu.models.serving`): this model chooses by the knob's
    NAME, not by shape (:data:`ATTN_KERNELS`)."""
    return attn_kernel == "pallas"


def _deq_page(codes: jax.Array, scales: jax.Array, dtype) -> jax.Array:
    """Dequantize int8 page codes ``[..., page_size, H, hd]`` under
    their per-(page, head) scales ``[..., H]`` into the compute dtype.
    The gather path and the pallas kernel both read K/V through this
    exact expression, so the two attention implementations see
    bit-identical inputs."""
    return (codes.astype(jnp.float32)
            * scales[..., None, :, None]).astype(dtype)


def _flat_pool(cache: Cache) -> Cache:
    """The stacked pool of ``cache`` (``k``, ``v``, and ``ks``, ``vs``
    for int8; not ``pos``) viewed as ONE pool of ``L * n_pages`` pages.
    Merging the two leading axes moves no bytes; it is what lets a
    layer scan CARRY the pool and hand each layer its pages by index
    (:func:`_layer_pages`) instead of slicing the layer's pool out of
    the stacked one and writing it back. :func:`_stacked_pool` undoes
    the view."""
    return {n: a.reshape((-1,) + a.shape[2:])
            for n, a in cache.items() if n != "pos"}


def _stacked_pool(pool: Cache, like: Cache) -> Cache:
    """A flattened ``pool`` (:func:`_flat_pool`) back in the stacked
    ``[L, n_pages, ...]`` shapes of the cache ``like``."""
    return {n: a.reshape(like[n].shape) for n, a in pool.items()}


def _layer_pages(pages: jax.Array, layer: jax.Array,
                 n_pages: int) -> jax.Array:
    """Page indices of one layer's pool (a page table, or the pages a
    step writes) as indices into the flattened stacked pool: page ``p``
    of layer ``l`` is page ``l * n_pages + p``. Everything that is not
    a page of the layer's pool (:data:`PT_SENTINEL`, any index at or
    past ``n_pages``) stays :data:`PT_SENTINEL`, so it is still dropped
    on a write, skipped by the kernel and clipped on a read, and can
    never land in a neighbour layer's page."""
    return jnp.where(pages < n_pages, pages + layer * n_pages,
                     jnp.int32(PT_SENTINEL))


def _merge_span_int8(codes: jax.Array, scales: jax.Array,
                     vals: jax.Array, pt: jax.Array, start: jax.Array,
                     count, active: jax.Array, page_size: int):
    """Scatter a contiguous span of fp K (or V) rows into int8 pages.

    ``vals`` ``[B, S, H, hd]`` lands at each slot's virtual positions
    ``start[b] + i`` for ``i < count`` (decode: S = count = 1; verify:
    S = k+1; prefill: count = traced true length ≤ S bucket). Because
    scales are page-granular, a span write is a read-modify-write on
    every touched page: gather the page, requantize the surviving old
    codes, insert the new rows, scatter back. Three invariants make
    this exact and deterministic:

    - **Monotone scales.** A touched page's new scale is
      ``max(s_old, absmax(new) / 127)`` (floored at :data:`_KV_EPS`),
      so when the scale does not change, requantizing old codes is the
      identity (``round(q * s / s) == q``) — repeated writes to a page
      never drift its existing codes.
    - **Fresh pages reset.** A page with no valid old content for this
      slot (its page-start is at/past ``start``) takes ``s_old = 0``
      and drops its stale codes entirely: scales and garbage left by a
      previous tenant of the physical page never leak in.
    - **Canonical zeros.** Positions at/past ``start + count`` in a
      touched page are zeroed, so a page's bytes are a pure function of
      the tokens it holds — which is what lets the handoff digest and
      the prefix cache byte-verify quantized pages.

    Only touched pages scatter back (untouched shared-prefix pages are
    never rewritten); inactive slots and unmapped targets drop, exactly
    like every other paged scatter in this module. Returns the updated
    ``(codes, scales)``."""
    B, S, H, hd = vals.shape
    n_pages = codes.shape[0]
    ps = page_size
    max_pages = pt.shape[1]
    # Pages a span of S positions can straddle (static): full pages
    # plus a partial one at each end.
    T = (S - 1) // ps + 2
    vp = start[:, None] // ps + jnp.arange(T)[None, :]        # [B, T]
    page_idx = jnp.take_along_axis(
        pt, jnp.clip(vp, 0, max_pages - 1), axis=1)           # [B, T]
    pstart = vp * ps
    o = jnp.arange(ps)[None, None, :]
    src = pstart[:, :, None] + o - start[:, None, None]       # [B, T, ps]
    wmask = (src >= 0) & (src < count)
    bidx = jnp.arange(B)[:, None, None]
    new = vals.astype(jnp.float32)[bidx, jnp.clip(src, 0, S - 1)]
    new = jnp.where(wmask[..., None, None], new, 0.0)
    pc = jnp.clip(page_idx, 0, n_pages - 1)
    old_c = codes[pc]                                 # [B, T, ps, H, hd]
    old_s = scales[pc]                                # [B, T, H]
    has_old = pstart < start[:, None]                 # [B, T]
    old_keep = (pstart[:, :, None] + o) < start[:, None, None]
    s_base = jnp.where(has_old[..., None], old_s, 0.0)
    s_new = jnp.maximum(
        jnp.maximum(s_base, jnp.abs(new).max(axis=(2, 4)) / 127.0),
        _KV_EPS)
    ratio = (s_base / s_new)[:, :, None, :, None]
    old_rq = jnp.where(old_keep[..., None, None],
                       jnp.round(old_c.astype(jnp.float32) * ratio), 0.0)
    merged = jnp.clip(
        jnp.where(wmask[..., None, None],
                  jnp.round(new / s_new[:, :, None, :, None]), old_rq),
        -127, 127).astype(jnp.int8)
    touched = wmask.any(axis=2) & (vp < max_pages) \
        & (page_idx < n_pages) & active[:, None]
    page_w = jnp.where(touched, page_idx, jnp.int32(PT_SENTINEL))
    codes = codes.at[page_w].set(merged, mode="drop")
    scales = scales.at[page_w].set(s_new, mode="drop")
    return codes, scales


#: Paged KV pool for the continuous-batching engine, the frame's from
#: :func:`cache_spec` and placed by :func:`shard_cache` under a mesh:
#: physical storage is page-granular (``[L, n_pages, page_size, H,
#: hd]``), a slot's sequence lives wherever its page table points, and
#: ``pos`` stays per-slot ``[slots]`` (virtual position). The layer axis
#: leads and the page axis follows it, so the decode and verify steps
#: can carry the whole pool through their layer scans as ``L * n_pages``
#: pages (:func:`_flat_pool`) and never copy a layer's pool out of it.
init_paged_cache = serving.bind(serving.init_paged_cache, _THIS)
#: HBM bytes ONE physical page costs across all layers, K and V sides
#: together: the unit the engine's page budget is denominated in.
kv_bytes_per_page = serving.bind(serving.kv_bytes_per_page, _THIS)


def paged_attention(q: jax.Array, kc: jax.Array, vc: jax.Array,
                    pt: jax.Array, pos: jax.Array, *, page_size: int,
                    kernel: str = "gather", ks=None, vs=None
                    ) -> jax.Array:
    """One decode-step of paged attention: each slot's single query
    ``q [B, 1, H, hd]`` attends over its virtual sequence (the pages
    mapped by its page-table row ``pt [B, max_pages]``), valid at
    positions ``<= pos[b]``. Returns the attention context
    ``[B, 1, H, hd]`` in ``q.dtype``.

    ``kc``/``vc`` ``[n_pages, page_size, H, hd]`` are ONE pool of
    pages, indexed by ``pt``: a single layer's pool with the slots'
    page table, or — as the decode step calls it — the whole stacked
    pool flattened to ``L * n_pages`` pages with the layer's offset
    page table (:func:`_layer_pages`). It is the same call; nothing
    here knows of layers.

    ``kernel="gather"`` is the reference path: gather every mapped page
    into virtual order and run masked full-length attention (sentinel
    entries clip to an arbitrary real page whose garbage the mask
    hides). ``kernel="pallas"`` fuses the gather, the length masking,
    and the softmax into one kernel whose grid is ``(B,)``
    (:func:`_paged_attention_pallas`: the grouped-query kernel of
    :mod:`ray_tpu.models.kda_moe` at a group of one): a slot's step
    loops over THAT slot's live pages (``pos[b] // page_size + 1``
    inside the mapped prefix of its row, none for a row of
    :data:`PT_SENTINEL`) in blocks of pages, copied from the pool in
    HBM into a ring of VMEM blocks ahead of the arithmetic, steered by
    the scalar-prefetched page table, each read once; the lanes'
    blocks are one stream, so the ring is filled once a call and a
    lane's last blocks fetch the next lane's first. ``max_pages``
    multiplies nothing: the kernel does O(pages actually held) work.

    A block's rows, ``(token, head)`` as the pool holds them, are ONE
    operand: ``q . K^T`` over all heads at once on the MXU
    (compute-dtype operands, float32 sums, scaled in float32) with a
    foreign head's score set to -1e30, one softmax pass (flash
    decoding: a running max, a running sum and a float32 accumulator,
    rescaled as the max moves, divided once at the slot's end), then
    ``p . V`` with the probabilities rounded to the compute dtype
    before they meet V — as the gather path's
    ``softmax(...).astype(dtype)`` rounds them, but before the division
    by the sum, not after — summed in float32 and rounded
    once. So the two paths are NOT bit-identical: they agree to
    :data:`ATTN_KERNEL_ULPS` ulps of the largest output. Every live
    position enters the softmax; a position past ``pos[b]`` or in an
    unmapped page contributes exactly 0 whatever bytes lie there (inf
    and NaN included); a slot with no live page returns zeros.

    With int8 pools pass ``ks``/``vs`` (per-(page, head) scales); both
    paths dequantize through :func:`_deq_page` semantics at the point
    of use (the kernel a block's rows where it reads them: the same
    body), so the same bound holds quantized."""
    if kernel == "pallas":
        return _paged_attention_pallas(q, kc, vc, pt, pos, page_size,
                                       ks, vs)
    return _paged_attention_gather(q, kc, vc, pt, pos, page_size,
                                   ks, vs)


def _paged_attention_gather(q, kc, vc, pt, pos, page_size, ks, vs):
    """Reference paged attention: page-table gather + masked
    full-length softmax, verbatim the ISSUE 6 decode math (with an
    int8 dequant at the gather when scales are supplied)."""
    B = q.shape[0]
    H, hd = q.shape[2], q.shape[3]
    n_pages = kc.shape[0]
    max_pages = pt.shape[1]
    V = max_pages * page_size
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    ptc = jnp.clip(pt, 0, n_pages - 1)
    hk = kc[ptc]
    hv = vc[ptc]
    if ks is not None:
        hk = _deq_page(hk, ks[ptc], q.dtype)
        hv = _deq_page(hv, vs[ptc], q.dtype)
    hk = hk.reshape(B, V, H, hd)
    hv = hv.reshape(B, V, H, hd)
    valid = (jnp.arange(V)[None, :] <= pos[:, None])[:, None, None, :]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, hk,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, hv,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _paged_attention_pallas(q, kc, vc, pt, pos, page_size, ks, vs):
    """:func:`paged_attention` through the grouped-query kernel
    (:func:`ray_tpu.models.kda_moe.gqa_decode_attention`) at a group of
    ONE: multi-head attention is grouped-query attention whose every
    query head has keys and values of its own, and a page's rows,
    ``(token, head)`` as they lie, are that kernel's operand whatever
    the heads are called. What this adds is the block's own: each
    lane's live length (:func:`ray_tpu.models.serving.live_length`:
    ``pos + 1`` for a lane the engine steps, 0 for a row of
    :data:`PT_SENTINEL`); the table clipped, because the kernel
    names a page by what the table holds and an int8 pool's scales are
    gathered through every column; the refusal by name of a pool
    Mosaic cannot address; and the scope a trace's readers know the
    kernel by."""
    from . import kda_moe

    H, hd = q.shape[2], q.shape[3]
    n_pages = kc.shape[0]
    if not kda_moe.gqa_kernel(H, hd, kc.dtype, page_size):
        # Mosaic addresses a page of the pool in whole tiles of rows.
        raise ValueError(
            f"attn_kernel='pallas' fetches pages of [page_size * H, hd] "
            f"rows by DMA (a page larger than a block in equal parts), "
            f"which on a TPU needs hd a multiple of 128 and the rows of "
            f"a page or part whole sublane tiles of the pool's dtype (16 "
            f"rows of bfloat16, 32 of int8); got H={H}, hd={hd}, "
            f"page_size={page_size}, {jnp.dtype(kc.dtype).name}: use "
            f"attn_kernel='gather' for this model")
    length = serving.live_length(pt, pos, True, n_pages, page_size)
    # The scope names the kernel's path for a trace's readers
    # (".../paged_attention/gqa_attention/pallas_call").
    with jax.named_scope("paged_attention"):
        out = kda_moe.gqa_decode_attention(
            q[:, 0], kc, vc, jnp.clip(pt, 0, n_pages - 1), pos, length,
            n_head=H, n_kv_head=H, head_dim=hd, dtype=q.dtype,
            page_size=page_size, kscale=ks, vscale=vs)
    return out.astype(q.dtype)[:, None]


def _prefill_attend(q, k, v, hist: Cache, hist_len, T: int, hist_pages,
                    l, scale, self_mask):
    """One prompt suffix's attention in a paged prefill: ``q`` ``k``
    ``v`` ``[1, S, H, hd]`` over themselves, causally, and over the
    ``hist_len`` cached tokens before them, read from the flat pool
    ``hist`` (after the fork; with ``ks`` / ``vs`` an int8 pool's, read
    through :func:`_deq_page`) a block of ``T`` tokens at
    ``hist_pages(j, l)`` at once
    (:func:`ray_tpu.models.serving.attend_history`); ``self_mask`` the
    rows' causal mask ``[1, 1, S, S]``. Returns float32 ``[1, S, H,
    hd]``."""
    hd = q.shape[-1]

    def block(j):
        pages = hist_pages(j, l)
        if "ks" in hist:
            hk = _deq_page(hist["k"][pages], hist["ks"][pages], q.dtype)
            hv = _deq_page(hist["v"][pages], hist["vs"][pages], q.dtype)
        else:
            hk, hv = hist["k"][pages], hist["v"][pages]
        lg_h = jnp.einsum("bqhd,khd->bhqk", q, hk.reshape(T, -1, hd),
                          preferred_element_type=jnp.float32) * scale
        return lg_h, hv.reshape(1, T, -1, hd)

    lg_s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                      preferred_element_type=jnp.float32) * scale
    return serving.attend_history(
        jnp.where(self_mask, lg_s, -1e30), v, hist_len, T, block)


def prefill_into_slot_paged(params: Params, cache: Cache,
                            tokens: jax.Array, length: jax.Array,
                            hist_len: jax.Array, pt_row: jax.Array,
                            cow_src: jax.Array, slot: jax.Array,
                            rng: jax.Array, *, cfg: GPTConfig,
                            page_size: int, temperature: float = 0.0,
                            kv_dtype: str = "fp", tp_axis=None
                            ) -> Tuple[jax.Array, Cache, jax.Array]:
    """Prefill one prompt **suffix** into its page-table pages, fused
    with an optional copy-on-write fork and the first-token sample.

    ``tokens`` is ``[1, S_bucket]`` — the prompt MINUS the cached
    prefix, right-padded to its bucket (the bucket is the only shape XLA
    sees; ``hist_len`` and ``length`` are traced, so a prefix hit of any
    depth reuses the suffix-bucket's program). ``pt_row`` ``[max_pages]``
    maps the slot's virtual pages (shared-prefix pages first, then fresh
    ones; :data:`PT_SENTINEL` beyond). ``cow_src`` is the physical page
    to fork into ``pt_row[hist_len // page_size]`` before writing (a
    cached prefix that ends mid-page; pass :data:`PT_SENTINEL` for
    none): the copy is a masked in-program page copy, so COW costs zero
    extra compiled programs.

    With ``kv_dtype="int8"`` the COW fork copies codes AND scales, the
    history view dequantizes through :func:`_deq_page`, and the suffix
    K/V land through :func:`_merge_span_int8` (page-granular
    requantize-and-merge) instead of a per-position scatter; the block
    math itself — including the suffix tokens' self-attention — runs on
    the exact fp K/V, so the first sampled token is independent of the
    quantizer.

    Suffix tokens sit at absolute positions ``hist_len + i`` and attend
    over (a) themselves, causally, and (b) the ``hist_len`` cached
    tokens before them, read through the page table a block of
    :data:`ray_tpu.models.serving.HIST_BLOCK_TOKENS` at once inside the
    layer's body (:func:`ray_tpu.models.serving.attend_history`, scope
    ``prefill.history``): what a prefill
    reads, dequantizes and multiplies of its history follows the hit,
    not ``max_len``. With ``hist_len == 0`` that loop makes no trip and
    the math is bitwise :func:`prefill`'s. Returns ``(first_token,
    cache', rng')``; pad-position writes are dropped, not written."""
    B, S = tokens.shape
    L = cfg.n_layer
    n_pages = cache["k"].shape[1]
    ps = page_size
    max_pages = pt_row.shape[0]
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    positions = hist_len + jnp.arange(S)
    x = params["embed"]["kernel"].astype(cfg.dtype)[tokens]
    x = x + jnp.take(params["pos_embed"],
                     jnp.clip(positions, 0,
                              params["pos_embed"].shape[0] - 1),
                     axis=0).astype(cfg.dtype)[None]

    # COW fork first: dst (the page holding position hist_len) takes
    # src's contents across every layer; no-fork runs the same copy at
    # an out-of-bounds dst and drops it.
    dst = pt_row[jnp.clip(hist_len // ps, 0, max_pages - 1)]
    dst_w = jnp.where(cow_src < n_pages, dst, jnp.int32(PT_SENTINEL))
    src_c = jnp.clip(cow_src, 0, n_pages - 1)
    kpool = cache["k"].at[:, dst_w].set(cache["k"][:, src_c],
                                        mode="drop")
    vpool = cache["v"].at[:, dst_w].set(cache["v"][:, src_c],
                                        mode="drop")
    quant = kv_dtype == "int8"
    if quant:
        kscale = cache["ks"].at[:, dst_w].set(cache["ks"][:, src_c],
                                              mode="drop")
        vscale = cache["vs"].at[:, dst_w].set(cache["vs"][:, src_c],
                                              mode="drop")

    # The cached prefix as the layers read it: a block of pages at
    # (layer, pages) of the stacked pool's flat view, after the fork.
    T, hist_pages = serving.hist_blocks(pt_row, n_pages, ps)
    hist = _flat_pool({"k": kpool, "v": vpool,
                       **({"ks": kscale, "vs": vscale} if quant else {})})
    self_mask = jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]

    def body(carry, layer):
        x = carry
        p, l = layer
        q, k, v = _block_kv(x, p, cfg)          # [1, S, H, hd]
        att = _prefill_attend(q, k, v, hist, hist_len, T, hist_pages, l,
                              scale, self_mask
                              ).astype(q.dtype).reshape(B, S, -1)
        x = x + _mm_row(att, p["wo"]["kernel"], cfg.dtype, tp_axis)
        x = _ffn(x, p, cfg, tp_axis)
        return x, (k[0], v[0])

    x, (k_new, v_new) = lax.scan(body, x,
                                 (params["block"], jnp.arange(L)))
    x = _rmsnorm(x, params["ln_f_scale"])
    x_last = lax.dynamic_slice(x, (0, length - 1, 0), (1, 1, cfg.d_model))
    logits = _project_vocab(x_last, params["embed"]["kernel"], cfg)
    token, rng = serving.sample(logits[:, 0], temperature, rng)

    # Suffix K/V writes, scattered page-wise: token i lands at virtual
    # position hist_len + i → (pt_row[vpos // ps], vpos % ps). Pad
    # positions (i >= length) target the sentinel and are dropped.
    pos = lax.dynamic_update_slice(
        cache["pos"], jnp.reshape(hist_len + length, (1,)), (slot,))
    if quant:
        one = jnp.ones((1,), jnp.bool_)
        merge = jax.vmap(lambda c, s, vl: _merge_span_int8(
            c, s, vl[None], pt_row[None],
            jnp.reshape(hist_len, (1,)), length, one, ps))
        kpool, kscale = merge(kpool, kscale, k_new)
        vpool, vscale = merge(vpool, vscale, v_new)
        return token[0], {"k": kpool, "v": vpool, "ks": kscale,
                          "vs": vscale, "pos": pos}, rng
    wpos = hist_len + jnp.arange(S)
    vp = wpos // ps
    page_idx = pt_row[jnp.clip(vp, 0, max_pages - 1)]
    ok = (jnp.arange(S) < length) & (vp < max_pages)
    page_w = jnp.where(ok, page_idx, jnp.int32(PT_SENTINEL))
    off = wpos % ps
    kpool = kpool.at[:, page_w, off].set(k_new, mode="drop")
    vpool = vpool.at[:, page_w, off].set(v_new, mode="drop")
    return token[0], {"k": kpool, "v": vpool, "pos": pos}, rng


def prefill_group_into_slots_paged(params: Params, cache: Cache, tokens,
                                   length: jax.Array, hist_len: jax.Array,
                                   pt_row: jax.Array, cow_src: jax.Array,
                                   slot: jax.Array, rng: jax.Array, *,
                                   cfg: GPTConfig, page_size: int,
                                   temperature: float = 0.0,
                                   kv_dtype: str = "fp", tp_axis=None
                                   ) -> Tuple[jax.Array, Cache, jax.Array]:
    """:func:`prefill_into_slot_paged` for the ``G`` prompts of one
    chunk boundary in ONE launch (the frame's contract,
    ``models/serving.py``): ``tokens`` a tuple of ``[1, S_g]`` suffixes,
    each in its own bucket, ``length`` ``hist_len`` ``cow_src`` ``slot``
    ``[G]``, ``pt_row`` ``[G, max_pages]``, ``rng`` ``[G, 2]``. The
    blocks' projections, the FFN and the head run over all the prompts'
    rows at once (:class:`ray_tpu.models.serving.PromptRows`; the
    once-a-launch casts of the weights with them); every prompt forks
    its own page, attends over its own rows and its own cached prefix
    (:func:`_prefill_attend`, the single prefill's), lands in its own
    pages and samples with its own key. Returns ``(first tokens [G],
    cache', rngs [G, 2])``."""
    rows = serving.PromptRows(tokens, length, hist_len)
    G, R = rows.G, rows.R
    L = cfg.n_layer
    n_pages = cache["k"].shape[1]
    ps = page_size
    max_pages = pt_row.shape[1]
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    x = params["embed"]["kernel"].astype(cfg.dtype)[rows.tokens]
    x = (x + jnp.take(params["pos_embed"],
                      jnp.clip(rows.positions, 0,
                               params["pos_embed"].shape[0] - 1),
                      axis=0).astype(cfg.dtype))[None]        # [1, R, d]

    # every prompt's COW fork first (its dst is a fresh page of its own)
    dst = jnp.take_along_axis(
        pt_row, jnp.clip(hist_len // ps, 0, max_pages - 1)[:, None],
        axis=1)[:, 0]
    dst_w = jnp.where(cow_src < n_pages, dst, jnp.int32(PT_SENTINEL))
    src_c = jnp.clip(cow_src, 0, n_pages - 1)
    quant = kv_dtype == "int8"
    pool = {n: cache[n].at[:, dst_w].set(cache[n][:, src_c], mode="drop")
            for n in (("k", "v", "ks", "vs") if quant else ("k", "v"))}
    hist = _flat_pool(pool)
    blocks = [serving.hist_blocks(pt_row[g], n_pages, ps) for g in range(G)]
    masks = [jnp.tril(jnp.ones((S, S), jnp.bool_))[None, None]
             for S in rows.sizes]

    def body(carry, layer):
        x = carry
        p, l = layer
        q, k, v = _block_kv(x, p, cfg)          # [1, R, H, hd]
        att = jnp.concatenate([
            _prefill_attend(qg, kg, vg, hist, hist_len[g], *blocks[g], l,
                            scale, masks[g])
            for g, (qg, kg, vg) in enumerate(zip(
                rows.split(q, 1), rows.split(k, 1), rows.split(v, 1)))],
            axis=1).astype(q.dtype).reshape(1, R, -1)
        x = x + _mm_row(att, p["wo"]["kernel"], cfg.dtype, tp_axis)
        if cfg.n_experts > 0:
            # a capacity router drops by who shares its batch: a
            # prompt keeps the single prefill's batch, itself
            x = jnp.concatenate([_ffn(xg, p, cfg, tp_axis)
                                 for xg in rows.split(x, 1)], axis=1)
        else:
            x = _ffn(x, p, cfg, tp_axis)
        return x, (k[0], v[0])

    x, (k_new, v_new) = lax.scan(body, x,
                                 (params["block"], jnp.arange(L)))
    x = _rmsnorm(x, params["ln_f_scale"])
    logits = _project_vocab(x[0, rows.last][:, None],
                            params["embed"]["kernel"], cfg)
    token, rng = serving.sample_slots(logits[:, 0], temperature, rng)

    pos = cache["pos"].at[slot].set(hist_len + length)
    if quant:
        one = jnp.ones((1,), jnp.bool_)
        for g, (kg, vg) in enumerate(zip(rows.split(k_new, 1),
                                         rows.split(v_new, 1))):
            merge = jax.vmap(lambda c, s, vl, g=g: _merge_span_int8(
                c, s, vl[None], pt_row[g][None], hist_len[g][None],
                length[g], one, ps))
            pool["k"], pool["ks"] = merge(pool["k"], pool["ks"], kg)
            pool["v"], pool["vs"] = merge(pool["v"], pool["vs"], vg)
        return token, {**pool, "pos": pos}, rng
    page_w, off = rows.pages(pt_row, ps)
    return token, {
        "k": pool["k"].at[:, page_w, off].set(k_new, mode="drop"),
        "v": pool["v"].at[:, page_w, off].set(v_new, mode="drop"),
        "pos": pos}, rng


def _slot_decode_step_paged(params: Params, cache: Cache,
                            token: jax.Array, active: jax.Array,
                            pt: jax.Array, cfg: GPTConfig,
                            page_size: int, kv_dtype: str = "fp",
                            attn_kernel: str = "gather", tp_axis=None
                            ) -> Tuple[jax.Array, Cache]:
    """One masked decode step over the whole slot pool: each active
    slot writes its new K/V at ITS OWN position,
    ``(pt[b, pos[b] // ps], pos[b] % ps)`` (scatter with
    drop semantics — an unmapped write target is discarded, never
    clamped into another slot's page; int8 pools merge through
    :func:`_merge_span_int8` instead) and attends over its virtual
    sequence via :func:`paged_attention`, valid ``<= pos[b]``.
    Inactive slots neither write nor advance.

    The layer scan carries the pool (``xs`` are the block weights and
    the layer index, there are no ``ys``): layer ``l`` writes and reads
    its pages inside the flattened stacked pool at ``l * n_pages +
    page`` (:func:`_flat_pool`, :func:`_layer_pages`), so no layer's
    pool is ever sliced out of the stacked pool or written back, and
    the step's rows land in place. Takes and returns the cache in its
    stacked ``[L, n_pages, ...]`` shapes."""
    B = token.shape[0]
    ps = page_size
    max_pages = pt.shape[1]
    pos = cache["pos"]
    quant = kv_dtype == "int8"
    x = params["embed"]["kernel"].astype(cfg.dtype)[token][:, None]
    x = x + jnp.take(params["pos_embed"],
                     jnp.clip(pos, 0, params["pos_embed"].shape[0] - 1),
                     axis=0).astype(cfg.dtype)[:, None]
    vp = pos // ps
    page_idx = jnp.take_along_axis(
        pt, jnp.clip(vp, 0, max_pages - 1)[:, None], axis=1)[:, 0]
    page_w = jnp.where(active & (vp < max_pages), page_idx,
                       jnp.int32(PT_SENTINEL))
    off = pos % ps
    L, n_pages = cache["k"].shape[:2]

    def body(carry, layer):
        x, pool = carry                      # [L * n_pages, ps, H, hd]
        p, l = layer
        pt_l = _layer_pages(pt, l, n_pages)
        q, k, v = _block_kv(x, p, cfg)       # [B, 1, H, hd]
        if quant:
            kc, ksc = _merge_span_int8(pool["k"], pool["ks"], k, pt_l,
                                       pos, 1, active, ps)
            vc, vsc = _merge_span_int8(pool["v"], pool["vs"], v, pt_l,
                                       pos, 1, active, ps)
            pool = {"k": kc, "v": vc, "ks": ksc, "vs": vsc}
        else:
            page_w_l = _layer_pages(page_w, l, n_pages)
            pool = {n: pool[n].at[page_w_l, off].set(new[:, 0],
                                                     mode="drop")
                    for n, new in (("k", k), ("v", v))}
        att = paged_attention(q, pool["k"], pool["v"], pt_l, pos,
                              page_size=ps, kernel=attn_kernel,
                              ks=pool.get("ks"), vs=pool.get("vs"))
        x = x + _mm_row(att.reshape(B, 1, -1), p["wo"]["kernel"],
                        cfg.dtype, tp_axis)
        x = _ffn(x, p, cfg, tp_axis)
        return (x, pool), None

    (x, pool), _ = lax.scan(body, (x, _flat_pool(cache)),
                            (params["block"], jnp.arange(L)))
    cache_out = {**_stacked_pool(pool, cache),
                 "pos": pos + active.astype(jnp.int32)}
    x = _rmsnorm(x, params["ln_f_scale"])
    logits = _project_vocab(x, params["embed"]["kernel"], cfg)
    return logits[:, 0], cache_out


#: The masked twin of :func:`decode_chunk` over the slot pool: the
#: frame's chunk program (``models/serving.py``) around this model's
#: two-valued step, so four outputs. ``kv_dtype``/``attn_kernel`` select
#: the pool layout and attention implementation per
#: :func:`paged_attention`; ``tp_axis`` reaches the step under a mesh.
decode_chunk_slots_paged = functools.partial(
    serving.decode_chunk_slots_paged, step=_slot_decode_step_paged,
    counters=len(STEP_COUNTERS))
#: The frame's two factories for this description. ``tp > 1`` runs the
#: same inner functions under :func:`shard_program` on
#: :func:`decode_mesh` with weights column/row-parallel and the pool
#: head-sharded: that wrapper is all the mesh adds.
jit_prefill_into_slot_paged = serving.bind(
    serving.jit_prefill_into_slot_paged, _THIS)
jit_decode_chunk_slots_paged = serving.bind(
    serving.jit_decode_chunk_slots_paged, _THIS)


# rtlint: program-budget: 1
@functools.lru_cache(maxsize=64)
def jit_paged_attention(cfg: GPTConfig, page_size: int,
                        attn_kernel: str = "gather",
                        kv_dtype: str = "fp"):
    """Jitted standalone :func:`paged_attention` (test/benchmark
    surface; the engine hot path reaches the kernel through
    :func:`jit_decode_chunk_slots_paged`): ONE program per (pool shape,
    page_size, kernel, kv_dtype) — page tables and positions are
    traced data. int8 wrappers take ``(q, kc, vc, pt, pos, ks, vs)``,
    fp wrappers ``(q, kc, vc, pt, pos)``."""
    if kv_dtype == "int8":
        def fn(q, kc, vc, pt, pos, ks, vs):
            return paged_attention(q, kc, vc, pt, pos,
                                   page_size=page_size,
                                   kernel=attn_kernel, ks=ks, vs=vs)
    else:
        def fn(q, kc, vc, pt, pos):
            return paged_attention(q, kc, vc, pt, pos,
                                   page_size=page_size,
                                   kernel=attn_kernel)
    return jax.jit(serving.program(fn, "paged_attention"))


# ------------------------------------------------------ speculative verify
def _spec_accept(logits, draft, keys, temperature: float, k: int):
    """Shared acceptance/correction math for the verify kernels.

    ``logits`` ``[B, k+1, vocab]`` are the target's rows over the fed
    sequence ``[last, d_1..d_k]`` (row i predicts the token AFTER input
    i, so row i scores ``d_{i+1}`` and row k samples the bonus token);
    ``draft`` ``[B, k]`` holds the proposals. Drafters propose POINT
    tokens (deterministic), so lossless acceptance reduces to:

    - temperature 0: accept ``d_{i+1}`` iff ``argmax(row_i) == d_{i+1}``;
      the correction/bonus token is ``argmax(row_{n_acc})`` — committed
      tokens are bitwise the greedy target stream for ANY drafter.
    - temperature > 0: accept ``d`` with probability ``p_t(d)`` (the
      point-mass proposal makes ``min(1, p/q) = p``); on rejection
      sample the residual ``norm(max(p_t - q, 0))`` — ``p_t`` with
      ``d``'s mass removed; on full acceptance sample the bonus from
      row k unmasked. The committed distribution is exactly the
      target's (the standard rejection-sampling identity), and PRNG
      consumption is STATIC — ``k + 2`` splits per slot per verify —
      so seeded streams replay deterministically through any
      acceptance pattern.

    Returns ``(committed [B, k+1], n_acc [B], keys')``:
    ``committed[b, :n_acc[b]]`` are the accepted drafts,
    ``committed[b, n_acc[b]]`` the correction/bonus token, and later
    entries repeat it — hosts deliver ``committed[b, :n_acc[b]+1]``.
    """
    am = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # [B, k+1]
    if temperature <= 0.0:
        acc = am[:, :k] == draft                             # [B, k]
        samples = am
    else:
        def per_slot(key, lg, d):
            ks = jax.random.split(key, k + 2)
            carry, dec = ks[0], ks[1:]
            sub = jax.vmap(jax.random.split)(dec)            # [k+1, 2, 2]
            ukeys, skeys = sub[:, 0], sub[:, 1]
            scaled = lg / temperature
            p = jax.nn.softmax(scaled[:k], axis=-1)
            pd = jnp.take_along_axis(p, d[:, None], axis=1)[:, 0]
            u = jax.vmap(jax.random.uniform)(ukeys[:k])
            a = u < pd
            residual = scaled[:k].at[jnp.arange(k), d].set(-1e30)
            corr = jax.vmap(jax.random.categorical)(skeys[:k], residual)
            bonus = jax.random.categorical(skeys[k], scaled[k])
            smp = jnp.concatenate([corr, bonus[None]]).astype(jnp.int32)
            return carry, a, smp

        keys, acc, samples = jax.vmap(per_slot)(keys, logits, draft)
    n_acc = jnp.cumprod(acc.astype(jnp.int32), axis=1).sum(axis=1)
    c = jnp.take_along_axis(samples, n_acc[:, None], axis=1)   # [B, 1]
    committed = jnp.where(
        jnp.arange(k + 1)[None, :] < n_acc[:, None],
        jnp.concatenate([draft, c], axis=1), c)
    return committed, n_acc.astype(jnp.int32), keys


def verify_chunk_slots_paged(params: Params, cache: Cache,
                             token: jax.Array, draft: jax.Array,
                             rngs: jax.Array, active: jax.Array,
                             pt: jax.Array, *, cfg: GPTConfig, k: int,
                             page_size: int, temperature: float = 0.0,
                             kv_dtype: str = "fp", tp_axis=None):
    """ONE batched target forward verifying k drafted tokens per active
    slot (ISSUE 9 tentpole; the draft-k-verify-once step).

    ``token`` ``[B]`` is each slot's last committed token, ``draft``
    ``[B, k]`` its drafter proposals, ``rngs``/``active`` as in
    :func:`decode_chunk_slots_paged`. The kernel feeds
    ``[last, d_1..d_k]`` (k+1 positions per slot), scores all k+1 logit
    rows against the proposals (:func:`_spec_accept`), and advances
    ``pos`` by ``1 + n_acc`` per active slot — the write cursor rolls
    back past rejected positions in-program. Garbage K/V beyond the new
    ``pos`` is overwritten before any later query attends it (every
    decode and verify step writes position ``pos`` before reading
    ``<= pos``), the same exactness argument as prompt right-padding.

    Returns ``(committed [B, k+1], n_acc [B], cache', rngs')``; rows of
    inactive slots are garbage. The host delivers
    ``committed[b, :n_acc[b]+1]`` trimmed by remaining/EOS and feeds
    the LAST DELIVERED token next. EOS needs no in-kernel
    mask-and-carry here: there is no sequential feedback inside the
    verify (all inputs were proposed up front), and the engine frees
    the lane at the chunk boundary where it trims.

    K/V writes scatter at
    ``(pt[b, (pos+i) // ps], (pos+i) % ps)`` with drop semantics (an
    unmapped or inactive target is discarded, never clamped into
    another slot's page — the engine never un-maps a page that still
    holds committed tokens, so rollback is just the smaller ``pos``),
    and each query attends its virtual sequence gathered through its
    page-table row, valid ``<= pos + i``. int8 pools
    merge the k+1 drafted rows through :func:`_merge_span_int8` and
    read them back dequantized — so accept/reject decisions are made
    on exactly the K/V any later decode step will see; a rejected
    span's codes past the rolled-back ``pos`` are re-zeroed by the
    next write to that page (the merge's canonical-zeros invariant).
    The pool is carried through the layer scan and addressed per layer
    exactly as in :func:`_slot_decode_step_paged`, with which the
    speculative engine alternates this program on the same pool."""
    B = token.shape[0]
    S = k + 1
    hd = cfg.head_dim
    L, n_pages = cache["k"].shape[:2]
    ps = page_size
    max_pages = pt.shape[1]
    V = max_pages * ps
    pos = cache["pos"]
    scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
    seq = jnp.concatenate([token[:, None], draft], axis=1)     # [B, S]
    positions = pos[:, None] + jnp.arange(S)[None, :]          # [B, S]
    x = params["embed"]["kernel"].astype(cfg.dtype)[seq]
    x = x + jnp.take(params["pos_embed"],
                     jnp.clip(positions, 0,
                              params["pos_embed"].shape[0] - 1),
                     axis=0).astype(cfg.dtype)
    vp = positions // ps
    page_idx = jnp.take_along_axis(
        pt, jnp.clip(vp, 0, max_pages - 1), axis=1)            # [B, S]
    page_w = jnp.where(active[:, None] & (vp < max_pages), page_idx,
                       jnp.int32(PT_SENTINEL))
    off = positions % ps
    arv = jnp.arange(V)
    valid = arv[None, None, None, :] <= positions[:, None, :, None]
    quant = kv_dtype == "int8"

    def body(carry, layer):
        x, pool = carry                      # [L * n_pages, ps, H, hd]
        p, l = layer
        pt_l = _layer_pages(pt, l, n_pages)
        ptc = jnp.clip(pt_l, 0, L * n_pages - 1)       # [B, max_pages]
        q, kk, vv = _block_kv(x, p, cfg)     # [B, S, H, hd]
        if quant:
            kc, ksc = _merge_span_int8(pool["k"], pool["ks"], kk, pt_l,
                                       pos, S, active, ps)
            vc, vsc = _merge_span_int8(pool["v"], pool["vs"], vv, pt_l,
                                       pos, S, active, ps)
            pool = {"k": kc, "v": vc, "ks": ksc, "vs": vsc}
            hk = _deq_page(kc[ptc], ksc[ptc],
                           q.dtype).reshape(B, V, -1, hd)
            hv = _deq_page(vc[ptc], vsc[ptc],
                           q.dtype).reshape(B, V, -1, hd)
        else:
            page_w_l = _layer_pages(page_w, l, n_pages)
            pool = {n: pool[n].at[page_w_l, off].set(new, mode="drop")
                    for n, new in (("k", kk), ("v", vv))}
            hk = pool["k"][ptc].reshape(B, V, -1, hd)
            hv = pool["v"][ptc].reshape(B, V, -1, hd)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, hk,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(valid, logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", probs, hv,
                         preferred_element_type=jnp.float32
                         ).astype(q.dtype).reshape(B, S, -1)
        x = x + _mm_row(att, p["wo"]["kernel"], cfg.dtype, tp_axis)
        x = _ffn(x, p, cfg, tp_axis)
        return (x, pool), None

    (x, pool), _ = lax.scan(body, (x, _flat_pool(cache)),
                            (params["block"], jnp.arange(L)))
    x = _rmsnorm(x, params["ln_f_scale"])
    logits = _project_vocab(x, params["embed"]["kernel"], cfg)
    committed, n_acc, rngs = _spec_accept(logits, draft, rngs,
                                          temperature, k)
    pos2 = pos + (1 + n_acc) * active.astype(jnp.int32)
    cache_out = {**_stacked_pool(pool, cache), "pos": pos2}
    return committed, n_acc, cache_out, rngs


# ------------------------------------------------------- KV handoff (ship)
def export_slot_kv_paged(cache: Cache, pt_row: jax.Array, *,
                         cfg: GPTConfig, page_size: int,
                         kv_dtype: str = "fp"):
    """Extract one slot's K/V for a prefill → decode handoff
    (ISSUE 14): gather the slot's pages through its page-table row
    into virtual order — ``(k, v)`` each
    ``[L, max_pages * page_size, H, hd]``. Sentinel entries clip to a
    real page whose garbage sits past ``pos``; the host trims the
    returned rows to the slot's true ``pos`` before shipping (positions
    past ``pos`` hold pad/stale garbage that the attention mask never
    read — shipping them would make the digest depend on pool
    history). The page-table CONTENTS are traced data: one program per
    pool shape. The cache is NOT donated: the exporting engine keeps
    serving out of it.
    int8 pools additionally return the gathered per-page scales
    ``(k, v, ks, vs)`` — the handoff ships codes + scales and the
    digest covers both."""
    L = cache["k"].shape[0]
    n_pages = cache["k"].shape[1]
    H, hd = cfg.n_head, cfg.head_dim
    max_pages = pt_row.shape[0]
    V = max_pages * page_size
    ptc = jnp.clip(pt_row, 0, n_pages - 1)
    k = cache["k"][:, ptc].reshape(L, V, -1, hd)
    v = cache["v"][:, ptc].reshape(L, V, -1, hd)
    if kv_dtype == "int8":
        return k, v, cache["ks"][:, ptc], cache["vs"][:, ptc]
    return k, v


def import_slot_kv_paged(cache: Cache, k_pages: jax.Array,
                         v_pages: jax.Array, pt_row: jax.Array,
                         slot: jax.Array, length: jax.Array, *,
                         cfg: GPTConfig, page_size: int,
                         ks_pages=None, vs_pages=None) -> Cache:
    """Scatter a shipped slot's K/V (a handoff import, ISSUE 14) into
    the pool pages mapped by ``pt_row`` and set the slot's ``pos`` to
    ``length``. ``k_pages``/``v_pages`` are
    ``[L, max_pages, page_size, H, hd]`` (host-padded to the full table
    width — one program per pool shape); pages the host never mapped
    (``pt_row`` sentinel, or wholly past ``length``) are DROPPED, never
    clamped into another slot's page — the same write discipline as
    every other paged scatter in this module. For int8 pools the
    shipped per-page scales ride in ``ks_pages``/``vs_pages``
    ``[L, max_pages, H]`` and scatter under the same mask."""
    n_pages = cache["k"].shape[1]
    max_pages = pt_row.shape[0]
    ar = jnp.arange(max_pages)
    ok = (ar * page_size < length) & (pt_row < n_pages)
    page_w = jnp.where(ok, pt_row, jnp.int32(PT_SENTINEL))
    kp = cache["k"].at[:, page_w].set(k_pages, mode="drop")
    vp = cache["v"].at[:, page_w].set(v_pages, mode="drop")
    pos = lax.dynamic_update_slice(cache["pos"],
                                   jnp.reshape(length, (1,)), (slot,))
    out = {"k": kp, "v": vp, "pos": pos}
    if ks_pages is not None:
        out["ks"] = cache["ks"].at[:, page_w].set(ks_pages, mode="drop")
        out["vs"] = cache["vs"].at[:, page_w].set(vs_pages, mode="drop")
    return out


# rtlint: program-budget: 1
@serving.knob_cache
def jit_export_slot_kv_paged(cfg: GPTConfig, page_size: int,
                             kv_dtype: str = "fp", tp: int = 1):
    """Jitted :func:`export_slot_kv_paged`: ONE program per (pool
    shape, page_size, kv_dtype, tp) — the page table is data. NOT
    donated — the exporter keeps its pool. Under tp the returned rows
    are head-sharded device arrays whose host gather (``np.asarray``)
    is the CANONICAL ``[L, max_pages * page_size, H, hd]`` layout —
    identical bytes for any exporter tp, which is what makes the
    handoff digest layout-independent."""
    mesh = _tp_mesh(cfg, tp)
    if mesh is None:
        return jax.jit(serving.program(export_slot_kv_paged, cfg=cfg,
                                       page_size=page_size,
                                       kv_dtype=kv_dtype))
    P = jax.sharding.PartitionSpec
    inner = functools.partial(export_slot_kv_paged, cfg=cfg,
                              page_size=page_size, kv_dtype=kv_dtype)
    hspec = P(None, None, "tp", None)
    sspec = P(None, None, "tp")
    outs = (hspec, hspec, sspec, sspec) if kv_dtype == "int8" \
        else (hspec, hspec)

    def fn(cache, pt_row):
        return shard_map(
            inner, mesh=mesh,
            in_specs=(_tp_cache_specs(cache), P()),
            out_specs=outs)(cache, pt_row)

    return jax.jit(serving.program(fn, "export_slot_kv_paged"))


# rtlint: program-budget: 1
@serving.knob_cache
def jit_import_slot_kv_paged(cfg: GPTConfig, page_size: int,
                             kv_dtype: str = "fp", tp: int = 1):
    """Jitted :func:`import_slot_kv_paged`: ONE program per (pool
    shape, page_size, kv_dtype, tp) — int8 wrappers take the shipped
    scales as trailing positional args. Pool donated — the importer
    immediately rebinds. Under tp the host-canonical ship buffer is
    scattered into THIS engine's mesh — the resharding half of the
    handoff boundary, so an N-way exporter feeds an M-way importer with
    no layout coupling."""
    mesh = _tp_mesh(cfg, tp)
    if kv_dtype == "int8":
        def raw(cache, k_pages, v_pages, ks_pages, vs_pages, pt_row,
                slot, length):
            return import_slot_kv_paged(
                cache, k_pages, v_pages, pt_row, slot, length, cfg=cfg,
                page_size=page_size, ks_pages=ks_pages,
                vs_pages=vs_pages)
        if mesh is None:
            return jax.jit(serving.program(raw, "import_slot_kv_paged"),
                           donate_argnums=(0,))
        P = jax.sharding.PartitionSpec
        hspec = P(None, None, None, "tp", None)
        sspec = P(None, None, "tp")

        def fn(cache, k_pages, v_pages, ks_pages, vs_pages, pt_row,
               slot, length):
            cspec = _tp_cache_specs(cache)
            return shard_map(
                raw, mesh=mesh,
                in_specs=(cspec, hspec, hspec, sspec, sspec,
                          P(), P(), P()),
                out_specs=cspec)(cache, k_pages, v_pages, ks_pages,
                                 vs_pages, pt_row, slot, length)

        return jax.jit(serving.program(fn, "import_slot_kv_paged"),
                       donate_argnums=(0,))
    if mesh is None:
        return jax.jit(serving.program(import_slot_kv_paged, cfg=cfg,
                                       page_size=page_size),
                       donate_argnums=(0,))
    P = jax.sharding.PartitionSpec
    inner = functools.partial(import_slot_kv_paged, cfg=cfg,
                              page_size=page_size)
    hspec = P(None, None, None, "tp", None)

    def fn(cache, k_pages, v_pages, pt_row, slot, length):
        cspec = _tp_cache_specs(cache)
        return shard_map(
            inner, mesh=mesh,
            in_specs=(cspec, hspec, hspec, P(), P(), P()),
            out_specs=cspec)(cache, k_pages, v_pages, pt_row, slot,
                             length)

    return jax.jit(serving.program(fn, "import_slot_kv_paged"),
                   donate_argnums=(0,))


# rtlint: program-budget: 1
@serving.knob_cache
def jit_verify_chunk_slots_paged(cfg: GPTConfig, k: int, page_size: int,
                                 temperature: float = 0.0,
                                 kv_dtype: str = "fp", tp: int = 1):
    """Jitted :func:`verify_chunk_slots_paged`: ONE program per (pool
    shape, k, page_size, kv_dtype, tp) — the page table is data. Pool
    donated."""
    return serving.jit_program(
        _THIS, verify_chunk_slots_paged, "verify_chunk_slots_paged",
        _tp_mesh(cfg, tp), 4, cache_out=2, cfg=cfg, k=k,
        page_size=page_size, temperature=temperature, kv_dtype=kv_dtype)
