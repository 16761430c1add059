"""A hybrid linear-attention, expert-routed decoder and its paged
serving programs: the third block
:class:`~ray_tpu.serve.engine.DecodeEngine` serves (beside
:mod:`ray_tpu.models.gpt_decode`'s GPT-2 block and
:mod:`ray_tpu.models.mla_moe`'s latent-attention decoder). This module
IS the model's description in the sense of
:mod:`ray_tpu.models.serving`.

The block (pre-norm, RMSNorm, no biases, no positions anywhere, untied
head)::

    x += Mixer_l(RMSNorm(x));  x += MoE(RMSNorm(x))

``Mixer_l`` is a softmax GQA layer where ``l`` is in
:attr:`KDAMoEConfig.gqa_layers` and a gated delta-rule linear-attention
layer (KDA) everywhere else. The two kinds keep DIFFERENT things of a
sequence, and :func:`cache_spec` describes both in one
:class:`~ray_tpu.models.serving.CacheSpec`:

- **GQA** (``n_head`` query heads over ``n_kv_head`` key/value heads,
  query head ``j`` with KV head ``j // (n_head / n_kv_head)``; no
  rotary; an elementwise output gate ``sigmoid(x W_z)``): a token leaves
  ``n_kv_head x head_dim`` keys and values in a PAGE (entries ``k``,
  ``v``, per token, ``n_gqa`` layers). Prefill attends causally over
  the prompt (scope ``gqa.prefill``); decode attends over the lane's
  pages (scope ``gqa.attention``): ONE path, named ``"gather"``
  (:data:`ATTN_KERNELS`), in two bodies chosen by what the program can
  see (:func:`_gqa_kernel`). A Pallas kernel
  (:func:`_gqa_attention_pallas`) copies a lane's LIVE pages from the
  pools once, by DMA, and never a page past the live length, and
  multiplies a block's rows, ``(token, KV head)`` as a page holds
  them, by ALL the query heads on the MXU with the foreign heads'
  columns masked (eight queries a KV head are no work for the VPU, and
  the head axis in the middle of a page is no batch axis for Mosaic),
  wherever Mosaic can address a page and a head (``head_dim`` whole
  128-lane tiles and ``page_size x n_kv_head`` rows whole sublane tiles
  compiled for a TPU; interpreted off it). Else plain XLA over each
  lane's whole virtual sequence gathered through the page table, in
  blocks of :data:`_GQA_LANE_BLOCK` lanes
  (:func:`_gqa_attention_gather`, also the tests' oracle). The two
  agree to :data:`ATTN_KERNEL_ULPS`. Both bodies take head counts, not
  this model's config: :func:`gqa_decode_attention` (with
  :func:`gqa_kernel` and :func:`gqa_decode_reads`) is the PUBLIC entry
  through which :mod:`ray_tpu.models.ssm_hybrid` runs the same path at
  20 queries over 4 KV heads, its rotary applied by the caller, and
  :mod:`ray_tpu.models.ssm_moe` at 32 over 8 with its own score scale
  folded into ``q``; :func:`gqa_causal_attention` is prefill's, public
  the same way. Since ISSUE 61 the GPT-2 block's decode attention is
  the kernel too, at a group of ONE (16 heads, each with keys and
  values of its own: a page's rows are ``(token, head)`` whatever the
  heads are called), and its int8 pools with it, dequantized a block
  where it is read (``kscale`` / ``vscale``). The latent decoder's (64
  absorbed queries over one row a token) is another inner loop around
  the same ring of copies, which is copied here, not shared (ROADMAP
  D13).
- **KDA** (``kda_heads`` heads of ``kda_head_dim`` keys and values): a
  width-``conv_size`` causal depthwise convolution and SiLU on the
  ``q``/``k``/``v`` projections, L2-normalised ``q`` (scaled) and ``k``
  a head, a decay PER CHANNEL ``alpha = exp(-exp(A_log) softplus(low
  rank(x) + dt_bias))``, a step size ``beta = 2 sigmoid(x W_b)`` (the 2
  where ``neg_eigval``) and the recurrence, a head::

      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t

  then a per-head RMSNorm and a low-rank sigmoid gate on ``o``. A
  sequence keeps, whatever its length, ``S`` (``[heads, dk, dv]`` in
  :attr:`KDAMoEConfig.state_dtype`) and the last ``conv_size - 1`` rows
  of the three projections: entries ``state`` and ``conv``, ``per
  "slot"``, ``n_kda`` layers. They belong to the SLOT: every prefill
  into a slot rebuilds them from zero (:func:`prefill_into_slot_paged`,
  the chunked form, scope ``kda.prefill``), every decode step reads an
  active lane's state ONCE and writes it once, in place (scope
  ``kda.state``), and moves no byte of an idle or parked lane's. So no
  page hash shares them and nothing rolls back: :data:`UNSUPPORTED`.
  The step's recurrence is a Pallas kernel (:func:`_kda_step_pallas`:
  a block of a live lane's heads in VMEM, both sums, the decay, the
  rank-one update and ``o`` from that one copy in float32, the whole
  per-slot entry aliased in and out) wherever Mosaic can address a
  head's state (:func:`_state_kernel`: ``kda_head_dim`` a multiple of
  128 on a TPU, any width interpreted off it), chosen by shape under
  the model's one ``attn_kernel`` name; elsewhere :func:`_kda_step` in
  plain XLA, which is also the tests' oracle.

**MoE**, every layer: :func:`ray_tpu.models.moe.dropless_moe` with one
group (sigmoid scores over ``n_routed`` experts, plain top k,
normalised), told the ``experts_held`` experts from ``expert_offset``
that live here, plus a shared expert:
:func:`ray_tpu.models.moe.block_ffn` as it stands.

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
from jax.scipy.linalg import solve_triangular

from . import serving
from .moe import block_ffn, embed, group_cfg, head, rmsnorm
from .serving import (PT_SENTINEL, CacheEntry, CacheSpec, at_layer, flat,
                      live_length)

_THIS = sys.modules[__name__]

Params = Dict[str, Any]
Cache = Dict[str, jax.Array]

KV_DTYPES = ("fp",)
ATTN_KERNELS = ("gather",)
#: What the engine offers and this model does not take, with the reason
#: the engine raises at construction.
UNSUPPORTED = {
    "prefix_cache": "a linear-attention layer's recurrent state belongs "
                    "to the slot, not to a page: reusing cached pages "
                    "needs a snapshot of the state at the page boundary "
                    "the hit ends on, and none is kept",
    "spec_decode": "a recurrent state does not roll back past rejected "
                   "positions, and there is no verify program",
    "roles": "the handoff payload has no part for the per-slot state, "
             "and there are no export/import programs",
    "int8": "the key/value pages of the one attention layer in four "
            "have no quantised layout",
    "tp": "there are no tensor-parallel programs: the deployment shares "
          "a layer by EXPERTS (experts_held / expert_offset), one engine "
          "a chip",
}
#: int32 counters the chunk program returns, summed over its steps: the
#: expert layers' four (:data:`ray_tpu.models.mla_moe.STEP_COUNTERS`),
#: the lanes whose state a step read and wrote, and the positions whose
#: keys and values a step's attention fetched from the pools, all GQA
#: layers (the kernel: each lane's live tokens rounded up to whole
#: pages; the gather: ``slots x max_pages x page_size`` whatever is
#: live). Readers go by name or by the first five's places: append.
STEP_COUNTERS = ("moe_steps", "moe_experts_touched_sum",
                 "moe_tokens_here_sum", "moe_expert_peak_sum",
                 "state_lanes_sum", "gqa_tokens_read_sum")
#: Lanes whose pages decode's attention gathers at once: the gathered
#: keys and values of ALL lanes (``slots x max_len`` rows) would be a
#: temporary as large as the pool.
_GQA_LANE_BLOCK = 32
#: The written bound on |kernel - gather| of decode's GQA attention, in
#: ulps (2**-8, relative) of the LARGEST output of the call: both bodies
#: round every probability once to the compute dtype, the XLA body
#: after the division by the sum and the kernel before it, and sum p . v
#: in float32 (as :data:`ray_tpu.models.mla_moe.ATTN_KERNEL_ULPS`, the
#: same difference).
ATTN_KERNEL_ULPS = 4
#: Tokens the GQA kernel multiplies at once (``// page_size`` pages; a
#: part of a page where a page is larger: :func:`_gqa_block`), and the
#: blocks of keys (and as many of values) in flight or in use at once. Measured on a v5e at the cell's
#: shapes (254 lanes of 128-1,792 live tokens, 253 k in all, 1.05 GB of
#: pages; the gather 9.54 ms): blocks of 128 / 192 / 256 / 384 / 512 /
#: 1,024 tokens read 1.62 / 1.46 / 1.44 / 1.48 / 1.47 / 1.67 ms at the
#: best ring depth of each; 256 with a ring of 2 / 3 / 4 / 6 / 8 reads
#: 1.56 / 1.48 / 1.44 / 1.48 / 1.45 (PERF.md section 6, PR 46): the
#: copies bound it (725 GB/s), so what counts is how many are in
#: flight, and a ring that is a power of two indexes with a mask.
_GQA_BLOCK_TOKENS = 256
_GQA_RING_BLOCKS = 4
#: Most ``(token, KV head)`` rows of a block, whatever the heads are
#: called: 2,048 rows of 128 bfloat16 are 512 KiB a block and side, 4
#: MiB of VMEM at the ring of 4, and the scores and probabilities of a
#: block are ``[Hq, rows]`` float32 each. Eight KV heads fill it with
#: the 256 tokens above; sixteen (multi-head attention, a group of
#: one: the GPT-2 block's) with 128. Measured on a v5e at THAT shape
#: (32 lanes of 250-450 live tokens, 24 layers a step; the VPU kernel
#: this replaced 4.31 ms): 1,024 / 2,048 / 4,096 rows read 3.27 / 3.27
#: / 3.30 ms at the best ring of each, and 2,048 rows with a ring of 2
#: / 4 / 8 read 3.51 / 3.27 / 3.26 (PERF.md section 6, PR 61): the
#: copies bound it here too (87% of 819 GB/s), so the rows follow the
#: 512 KiB of the measurement above and not the heads. (The tokens
#: above are kept beside the rows only for four KV heads, whose block
#: they hold to the 1,024 rows it had: the rule is the rows alone once
#: ``tests/test_models_frame.py: PARENT_TEXT`` may move.)
_GQA_BLOCK_ROWS = 2048
#: Heads of a lane's state the recurrence's kernel holds in VMEM at
#: once (the largest common divisor with ``kda_heads``): 32 heads of
#: [128, 128] float32 are 2 MiB, 8 MiB with the block before and the
#: block after in flight, in and out (of the 16 MiB a kernel may take
#: on a v5e), and their four columns a head fill one 128-lane tile.
#: Measured there: 8 / 16 / 32 heads 4.16 / 3.63 / 3.46 ms a layer of
#: 254 lanes, the copies alone 3.30 (PERF.md section 6, PR 40).
_KDA_BLOCK_HEADS = 32
_HI = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class KDAMoEConfig:
    vocab_size: int = 512            # rows of the table and head HELD
    n_layer: int = 4
    gqa_layers: Tuple[int, ...] = (0,)   # the others are KDA layers
    d_model: int = 64
    n_head: int = 4                  # GQA query heads
    n_kv_head: int = 2
    head_dim: int = 16
    gqa_gate: bool = True
    kda_heads: int = 4
    kda_head_dim: int = 16           # keys and values alike
    conv_size: int = 4
    kda_rank: int = 16               # of the decay and gate projections
    neg_eigval: bool = True          # beta in (0, 2)
    kda_chunk: int = 64              # prefill's chunk
    d_expert: int = 32
    n_routed: int = 16               # the router's width
    experts_held: int = 16           # of which live here ...
    expert_offset: int = 0           # ... from this one
    top_k: int = 4
    norm_topk: bool = True
    route_scale: float = 1.0
    shared_expert: bool = True
    max_seq: int = 1048576           # no positions: the declared reach
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32
    moe_block_rows: int = 32

    # one group: ``dropless_moe``'s group limit is a plain top k
    n_group = 1
    topk_group = 1

    @property
    def n_gqa(self) -> int:
        return sum(l in self.gqa_layers for l in range(self.n_layer))

    @property
    def n_kda(self) -> int:
        return self.n_layer - self.n_gqa

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    def decode_programs(self):
        """This model's description for the serving engine
        (:mod:`ray_tpu.models.serving`)."""
        import sys

        return sys.modules[__name__]


# sizes used by the CPU tests
CONFIGS = {
    "nano": KDAMoEConfig(),
}

#: Means of the leaves that are not drawn around zero: ``dt_bias`` sits
#: where ``softplus`` is small, so that the decay ``alpha`` spreads over
#: (0.5, 0.999) instead of around ``exp(-ln 2)``.
INIT_MEAN = {"dt_bias": -3.5}
INIT_STD = {"embed": 1.0, "A_log": 0.5, "dt_bias": 1.2, "conv": 0.5}


def init_params(rng: jax.Array, cfg: KDAMoEConfig,
                std: Optional[dict] = None) -> Params:
    """Seeded weights, one tree a layer. ``std`` overrides a kind's
    standard deviation (default :data:`INIT_STD`, else 1/sqrt(fan-in));
    :data:`INIT_MEAN` is added."""
    std = dict(INIT_STD, **(std or {}))
    pd = cfg.param_dtype
    d, W = cfg.d_model, cfg.kda_width
    n = [0]

    def w(name, *shape):
        n[0] += 1
        s = next((v for k, v in std.items() if k in name),
                 1.0 / math.sqrt(shape[-2] if len(shape) > 1 else 1.0))
        return (jax.random.normal(jax.random.fold_in(rng, n[0]), shape) * s
                + INIT_MEAN.get(name, 0.0)).astype(pd)

    def ffn(f, lead=()):
        return {"gate": w("gate", *lead, d, f), "up": w("up", *lead, d, f),
                "down": w("down", *lead, f, d)}

    layers = []
    for l in range(cfg.n_layer):
        p = {"ln1_scale": jnp.ones((d,), pd),
             "ln2_scale": jnp.ones((d,), pd)}
        if l in cfg.gqa_layers:
            hq, hkv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
            p.update(wq={"kernel": w("wq", d, hq)},
                     wk={"kernel": w("wk", d, hkv)},
                     wv={"kernel": w("wv", d, hkv)},
                     wo={"kernel": w("wo", hq, d)})
            if cfg.gqa_gate:
                p["wz"] = {"kernel": w("wz", d, hq)}
        else:
            p.update(wq={"kernel": w("wq", d, W)},
                     wk={"kernel": w("wk", d, W)},
                     wv={"kernel": w("wv", d, W)},
                     conv_q=w("conv_q", cfg.conv_size, W),
                     conv_k=w("conv_k", cfg.conv_size, W),
                     conv_v=w("conv_v", cfg.conv_size, W),
                     A_log=w("A_log", cfg.kda_heads),
                     dt_bias=w("dt_bias", W),
                     wf_down={"kernel": w("wf_down", d, cfg.kda_rank)},
                     wf_up={"kernel": w("wf_up", cfg.kda_rank, W)},
                     wb={"kernel": w("wb", d, cfg.kda_heads)},
                     wg_down={"kernel": w("wg_down", d, cfg.kda_rank)},
                     wg_up={"kernel": w("wg_up", cfg.kda_rank, W)},
                     o_norm_scale=jnp.ones((cfg.kda_head_dim,), pd),
                     wo={"kernel": w("wo", W, d)})
        p["router"] = {"kernel": w("router", d, cfg.n_routed)}
        p["experts"] = ffn(cfg.d_expert, (cfg.experts_held,))
        if cfg.shared_expert:
            p["shared"] = ffn(cfg.d_expert)
        layers.append(p)
    return {"embed": {"kernel": w("embed", cfg.vocab_size, d)},
            "head": {"kernel": w("head", d, cfg.vocab_size)},
            "ln_f_scale": jnp.ones((d,), pd), "layers": layers}


# ------------------------------------------------------------ block math
def _dot(x, w, dtype):
    """``x @ w`` in ``dtype`` with float32 sums, the float32 result."""
    return lax.dot_general(x.astype(dtype), w.astype(dtype),
                           (((x.ndim - 1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _kda_proj(h, p, cfg: KDAMoEConfig):
    """``h`` [..., d] (normed) -> (pre [..., 3 W]: the ``q | k | v``
    projections BEFORE the convolution, in the compute dtype (what the
    convolution's tail keeps); g [..., H, dk]: the log of the decay, <=
    0; beta [..., H]; gate [..., H, dv]: the output gate; float32)."""
    H, D = cfg.kda_heads, cfg.kda_head_dim
    dt = cfg.dtype
    pre = jnp.concatenate([_dot(h, p[n]["kernel"], dt).astype(dt)
                           for n in ("wq", "wk", "wv")], axis=-1)

    def low_rank(name):
        return _dot(_dot(h, p[name + "_down"]["kernel"], dt),
                    p[name + "_up"]["kernel"], dt)

    f = low_rank("wf") + p["dt_bias"].astype(jnp.float32)
    g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] \
        * jax.nn.softplus(f).reshape(f.shape[:-1] + (H, D))
    beta = jax.nn.sigmoid(_dot(h, p["wb"]["kernel"], dt))
    if cfg.neg_eigval:
        beta = 2.0 * beta
    gate = jax.nn.sigmoid(low_rank("wg")).reshape(f.shape[:-1] + (H, D))
    return pre, g, beta, gate


def _kda_qkv(window, p, cfg: KDAMoEConfig):
    """The short convolution's output for positions whose ``conv_size``
    input rows are ``window[i]`` (a list of ``[..., 3 W]`` arrays,
    oldest first): SiLU of the depthwise sum, then per head ``q``
    L2-normalised and scaled by ``dk^-1/2``, ``k`` L2-normalised, ``v``
    as it is. Returns float32 ``[..., H, dk]`` each."""
    H, D = cfg.kda_heads, cfg.kda_head_dim
    taps = jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]],
                           axis=-1).astype(jnp.float32)      # [conv, 3 W]
    y = sum(taps[i] * window[i].astype(jnp.float32)
            for i in range(cfg.conv_size))
    y = jax.nn.silu(y)
    q, k, v = (a.reshape(a.shape[:-1] + (H, D))
               for a in jnp.split(y, 3, axis=-1))

    def unit(a):
        return a * lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    return unit(q) * D ** -0.5, unit(k), v


def _kda_out(o, gate, p, cfg: KDAMoEConfig):
    """``o`` [..., H, dv] float32 -> the mixer's output [..., d]: a
    per-head RMSNorm (one learned scale, ``dv`` wide), the gate, W_o."""
    o = rmsnorm(o, p["o_norm_scale"], cfg.eps, jnp.float32) * gate
    return _dot(o.reshape(o.shape[:-2] + (-1,)), p["wo"]["kernel"],
                cfg.dtype)


def _kda_step(S, q, k, v, g, beta):
    """The recurrence, one token a lane, in plain XLA: the fallback
    where :func:`decode_attention_fused` is false, and the oracle the
    kernel (:func:`_kda_step_pallas`) is tested against. ``S`` [B, H,
    dk, dv], ``q`` ``k`` ``g`` [B, H, dk], ``v`` [B, H, dv], ``beta``
    [B, H], float32. Returns ``(S', o [B, H, dv])``. Written as
    elementwise products and sums over ``S`` (a matrix-vector product
    a head is no work for the MXU): ``S^T (alpha k)`` and ``S^T (alpha
    q)`` each read ``S`` (XLA makes them two reductions, not one), a
    third pass reads it again and writes ``S'``; ``o = S'^T q`` follows
    from the two sums without a fourth. Three reads where the kernel
    has one: 42% of the state's bandwidth bound on a v5e."""
    a = jnp.exp(g)
    u = jnp.sum(S * (k * a)[..., None], axis=-2)            # S^T (a k)
    w = jnp.sum(S * (q * a)[..., None], axis=-2)            # S^T (a q)
    du = beta[..., None] * (v - u)
    S = S * a[..., None] + k[..., None] * du[..., None, :]
    return S, w + jnp.sum(k * q, axis=-1, keepdims=True) * du


def _state_kernel(cfg: KDAMoEConfig) -> bool:
    """Whether the step's recurrence is :func:`_kda_step_pallas`:
    wherever Mosaic can address a head's state as whole float32 tiles
    with ``dk`` on sublanes and ``dv`` on lanes, ``kda_head_dim`` a
    multiple of 128 compiled for a TPU, any width interpreted off it;
    elsewhere :func:`_kda_step`."""
    from .._private.chip import pallas_interpret

    return pallas_interpret() or cfg.kda_head_dim % 128 == 0


def _gqa_block(n_kv_head: int, page_size: int) -> Tuple[int, int]:
    """A block of the GQA kernel as ``(pages a block, blocks a page)``:
    :data:`_GQA_BLOCK_TOKENS` tokens or :data:`_GQA_BLOCK_ROWS` rows of
    ``n_kv_head`` a token, whichever is fewer, in whole pages; a page
    that holds more is read in the fewest equal parts that hold no
    more, a part a block, so what a block takes of VMEM is bounded
    whatever the page is (a page of a lane's whole ``max_len``
    included)."""
    cap = max(1, min(_GQA_BLOCK_TOKENS, _GQA_BLOCK_ROWS // n_kv_head))
    if page_size <= cap:
        return cap // page_size, 1
    return 1, next(s for s in range(2, page_size + 1)
                   if page_size % s == 0 and page_size // s <= cap)


def gqa_kernel(n_kv_head: int, head_dim: int, dtype, page_size: int
               ) -> bool:
    """Whether decode's GQA attention (:func:`gqa_decode_attention`) is
    the Pallas kernel: wherever Mosaic can address what the kernel
    copies of the pool, a page viewed ``[page_size * n_kv_head,
    head_dim]`` rows or the part of it that is a block
    (:func:`_gqa_block`): compiled for a TPU ``head_dim`` must be whole
    128-lane tiles and a copy's rows whole sublane tiles of the pool's
    dtype (16 of bfloat16, 8 of float32, 32 of an int8 pool's codes);
    interpreted, off the TPU, any page is addressable. Elsewhere the
    gather."""
    from .._private.chip import pallas_interpret

    rows = page_size // _gqa_block(n_kv_head, page_size)[1] * n_kv_head
    return pallas_interpret() or (
        head_dim % 128 == 0
        and rows % (32 // jnp.dtype(dtype).itemsize) == 0)


def _gqa_kernel(cfg: KDAMoEConfig, page_size: int) -> bool:
    """:func:`gqa_kernel` at this model's heads and compute dtype."""
    return gqa_kernel(cfg.n_kv_head, cfg.head_dim, cfg.dtype, page_size)


def decode_attention_fused(cfg: KDAMoEConfig, page_size: int,
                           attn_kernel: str = "gather") -> bool:
    """Whether the chunk program built with these knobs holds a Pallas
    kernel (the description's optional entry,
    :mod:`ray_tpu.models.serving`). This model has TWO, each taken by
    what the program can see of its own shapes: the RECURRENCE
    (:func:`_kda_step_pallas`, :func:`_state_kernel`: from the state's
    head) and the GQA layers' ATTENTION (:func:`_gqa_attention_pallas`,
    :func:`_gqa_kernel`: from the page and the head); the answer is
    for the program, so either one makes it true. ``attn_kernel`` (one
    value) has no say."""
    return _state_kernel(cfg) or (cfg.n_gqa > 0
                                  and _gqa_kernel(cfg, page_size))


def _kda_step_pallas(state, layer: int, q, k, v, g, beta, active):
    """:func:`_kda_step` on layer ``layer`` of the whole per-slot entry
    ``state`` [n_kda, B, H, dk, dv], IN PLACE, as one kernel that reads
    a live lane's state once and writes it once. ``q`` ``k`` ``g`` [B,
    H, dk], ``v`` [B, H, dv], ``beta`` [B, H] float32, ``active`` [B]
    bool. Returns ``(state', o [B, H, dv])`` with zeros in ``o`` for an
    inactive lane, whose state no byte of is moved.

    Grid ``(B, H / hb)``: step ``(i, j)`` is block ``j``
    (:data:`_KDA_BLOCK_HEADS` heads, ``[hb, dk, dv]``) of the ``i``-th
    LIVE lane, named by the scalar-prefetched
    :func:`ray_tpu.models.serving.live_lanes`; the steps past the last
    live lane name the block before them again, which the
    pipeline neither fetches nor writes twice, and do nothing. The
    state is the kernel's input AND output (``input_output_aliases`` on
    the whole entry, the layer in the index map): a block comes into
    VMEM, both sums ``S^T (alpha k)`` and ``S^T (alpha q)``, the decay,
    the rank-one update and ``o`` are taken from that one copy in
    float32 on the VPU (``dk`` lies on sublanes: a sum over it adds
    whole vregs), and the block goes back where it came from. The
    vector operands are laid out by the caller's XLA, under the same
    scope: ``alpha k``, ``alpha q``, ``alpha`` and ``k`` TRANSPOSED
    ``[B, H / hb, dk, 4 hb]`` so that a head's is a column over ``dk``
    (one lane, broadcast over ``dv``), and ``v``, ``beta`` and ``k . q``
    as rows over ``dv``. Where no lane is live the one block named is
    copied through, so that the aliased entry comes out as it went
    in."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .._private.chip import pallas_interpret

    B, H, dk = q.shape
    dv = v.shape[-1]
    hb = math.gcd(H, _KDA_BLOCK_HEADS)
    nb = H // hb
    a = jnp.exp(g)
    cols = jnp.stack([k * a, q * a, a, k], axis=2)           # [B, H, 4, dk]
    cols = cols.reshape(B, nb, hb, 4, dk).transpose(0, 1, 4, 3, 2) \
        .reshape(B, nb, dk, 4 * hb)
    rows = jnp.stack(
        [jnp.broadcast_to(r, v.shape).reshape(B, nb, hb, dv) for r in
         (v, beta[..., None], jnp.sum(k * q, axis=-1, keepdims=True))],
        axis=2)                                      # [B, nb, 3, hb, dv]
    lanes, n = serving.live_lanes(active)

    def kernel(lanes_ref, n_ref, s_ref, cols_ref, rows_ref, s_out, o_ref):
        i, j = pl.program_id(0), pl.program_id(1)

        @pl.when(i < n_ref[0])
        def _():
            c = cols_ref[...]                                # [dk, 4 hb]
            for h in range(hb):
                S = s_ref[h].astype(jnp.float32)             # [dk, dv]
                ak, aq, al, kk = (c[:, m * hb + h:m * hb + h + 1]
                                  for m in range(4))         # [dk, 1]
                u = jnp.sum(S * ak, axis=0, keepdims=True)   # [1, dv]
                w = jnp.sum(S * aq, axis=0, keepdims=True)
                vv, bb, kq = (rows_ref[m, h:h + 1] for m in range(3))
                du = bb * (vv - u)
                s_out[h] = (S * al + kk * du).astype(s_out.dtype)
                o_ref[h:h + 1] = w + kq * du

        @pl.when((n_ref[0] == 0) & (i == 0) & (j == 0))
        def _():
            s_out[...] = s_ref[...]

    def at(*lead, rest=2):
        """The index map of an operand whose leading indices are
        ``lead`` (statics), then the lane and the block of heads, then
        ``rest`` whole dimensions."""
        def index(i, j, lanes_ref, n_ref):
            return lead + (lanes_ref[i],
                           jnp.where(i < n_ref[0], j, nb - 1)) + (0,) * rest
        return index

    state, o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nb),
            in_specs=[
                pl.BlockSpec((None, None, hb, dk, dv), at(layer)),
                pl.BlockSpec((None, None, dk, 4 * hb), at()),
                pl.BlockSpec((None, None, 3, hb, dv), at(rest=3)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, hb, dk, dv), at(layer)),
                pl.BlockSpec((None, None, hb, dv), at()),
            ]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, nb, hb, dv), jnp.float32)],
        # operand 2 (after the two scalar operands) is the state
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name="kda_state",
    )(lanes, n, state, cols, rows)
    return state, jnp.where(active[:, None, None], o.reshape(B, H, dv), 0.0)


def _kda_chunked(q, k, v, g, beta, S0, chunk: int):
    """The same recurrence over a whole sequence in chunks: ``q`` ``k``
    ``g`` [T, H, dk], ``v`` [T, H, dv], ``beta`` [T, H], ``S0`` [H, dk,
    dv], float32; ``T`` a multiple of ``chunk``. Returns ``(o [T, H,
    dv], S_T)``. Rows with ``beta = 0`` and ``g = 0`` leave the state
    as it is (a prompt's padding).

    Within a chunk, with ``G_t`` the running sum of ``g`` from the
    chunk's start and ``S`` the state before it, the updates ``u_t =
    beta_t (v_t - S_{t-1}^T (alpha_t k_t))`` solve the unit lower
    triangular system ``(I + Diag(beta) A) U = Diag(beta) (V - (K e^G)
    S)``, ``A[t, i] = sum_c k_t[c] k_i[c] e^{G_t[c] - G_i[c]}`` for ``i
    < t``; then ``o_t = S^T (q_t e^{G_t}) + sum_{i <= t} (sum_c q_t[c]
    k_i[c] e^{G_t[c] - G_i[c]}) u_i`` and the state after the chunk is
    ``Diag(e^{G_C}) S + (K e^{G_C - G})^T U``. Every exponent taken is
    <= 0: ``e^{-G_i}`` alone overflows where a channel decays fast for
    a whole chunk, so the two decay-weighted Gram matrices are summed
    over channels with the difference in the exponent (the exponentials
    are fused into the reduction; nothing ``[C, C, dk]`` is kept)."""
    T, H, dk = q.shape
    C = chunk
    N = T // C

    def chunks(a):                                  # [T, H, .] -> [N, H, C, .]
        return jnp.moveaxis(a.reshape((N, C) + a.shape[1:]), 1, 2)

    lower = jnp.tril(jnp.ones((C, C), jnp.bool_))
    eye = jnp.eye(C, dtype=jnp.float32)

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HI,
                          preferred_element_type=jnp.float32)

    def one(S, xs):
        q, k, v, g, beta = xs                       # [H, C, .], beta [H, C]
        G = jnp.cumsum(g, axis=1)
        E = jnp.exp(jnp.where(lower[None, :, :, None],
                              G[:, :, None] - G[:, None], -jnp.inf))
        kk = jnp.sum(k[:, :, None] * k[:, None] * E, axis=-1)   # [H, C, C]
        qk = jnp.sum(q[:, :, None] * k[:, None] * E, axis=-1)
        eG = jnp.exp(G)
        rhs = beta[..., None] * (v - mm("hck,hkv->hcv", k * eG, S))
        U = solve_triangular(
            eye + beta[..., None] * jnp.where(lower & ~eye.astype(bool),
                                              kk, 0.0),
            rhs, lower=True, unit_diagonal=True)
        o = mm("hck,hkv->hcv", q * eG, S) + mm("hct,htv->hcv", qk, U)
        last = G[:, -1]                                         # [H, dk]
        S = jnp.exp(last)[..., None] * S + mm(
            "hck,hcv->hkv", k * jnp.exp(last[:, None] - G), U)
        return S, o

    S, o = lax.scan(one, S0, (chunks(q), chunks(k), chunks(v), chunks(g),
                              chunks(beta[..., None])[..., 0]))
    return jnp.moveaxis(o, 1, 2).reshape(T, H, -1), S


def _kda_mix(pre, g, beta, p, cfg: KDAMoEConfig, live):
    """What of a KDA mixer is ONE sequence's, from a zero state: the
    convolution over its projections ``pre`` [S, 3 W] and the chunked
    recurrence (``g`` [S, H, dk], ``beta`` [S, H]; ``live`` [S] bool:
    rows past the prompt advance nothing). Returns ``(o [S, H, dv]
    float32, S_end [H, dk, dv], padded [conv_size - 1 + S, 3 W]: the
    projections behind the zero rows that stand before the sequence's
    start)``."""
    S = pre.shape[0]
    with jax.named_scope("kda.proj"):
        back = cfg.conv_size - 1
        padded = jnp.concatenate(
            [jnp.zeros((back, pre.shape[-1]), pre.dtype), pre])
        q, k, v = _kda_qkv([padded[i:i + S] for i in range(cfg.conv_size)],
                           p, cfg)
        g = jnp.where(live[:, None, None], g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    with jax.named_scope("kda.prefill"):
        C = min(cfg.kda_chunk, S)
        pad = -S % C
        if pad:
            q, k, v, g, beta = (jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)])
                for a in (q, k, v, g, beta))
        o, S_end = _kda_chunked(
            q, k, v, g, beta, jnp.zeros(
                (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim),
                jnp.float32), C)
    return o[:S], S_end, padded


def _kda_sequence(h, p, cfg: KDAMoEConfig, live):
    """A KDA mixer over one whole sequence from a zero state: ``h`` [S,
    d] (normed), ``live`` [S] bool (rows past the prompt advance
    nothing). Returns ``(y [S, d] float32, S_end [H, dk, dv], padded
    [conv_size - 1 + S, 3 W]: the projections before the convolution
    behind the zero rows that stand before the sequence's start)``."""
    with jax.named_scope("kda.proj"):
        pre, g, beta, gate = _kda_proj(h, p, cfg)
    o, S_end, padded = _kda_mix(pre, g, beta, p, cfg, live)
    with jax.named_scope("kda.proj"):
        y = _kda_out(o, gate, p, cfg)
    return y, S_end, padded


def _gqa_qkvz(h, p, cfg: KDAMoEConfig):
    """``h`` [..., d] -> (q [..., Hq, hd], k, v [..., Hkv, hd] in the
    compute dtype, gate [..., Hq * hd] float32 or None)."""
    dt = cfg.dtype

    def heads(name, n):
        a = _dot(h, p[name]["kernel"], dt).astype(dt)
        return a.reshape(a.shape[:-1] + (n, cfg.head_dim))

    z = jax.nn.sigmoid(_dot(h, p["wz"]["kernel"], dt)) if "wz" in p \
        else None
    return heads("wq", cfg.n_head), heads("wk", cfg.n_kv_head), \
        heads("wv", cfg.n_kv_head), z


def _gqa_out(att, z, p, cfg: KDAMoEConfig):
    """``att`` [..., Hq, hd] float32 -> the mixer's output [..., d]."""
    att = att.reshape(att.shape[:-2] + (-1,))
    if z is not None:
        att = att * z
    return _dot(att, p["wo"]["kernel"], cfg.dtype)


def gqa_causal_attention(q, k, v, *, n_head: int, n_kv_head: int,
                         head_dim: int, dtype):
    """A prefill's grouped-query attention over ONE whole sequence,
    for any model of such heads (PUBLIC, as
    :func:`gqa_decode_attention` is decode's): causal softmax of ``q``
    [S, n_head, head_dim] over ``k``, ``v`` [S, n_kv_head, head_dim]
    (positions, where the model has any, already applied), scores
    scaled by ``head_dim ** -0.5`` as the decode kernel scales them,
    probabilities rounded to ``dtype``. Returns float32 [S, n_head,
    head_dim]."""
    S = q.shape[0]
    G = n_head // n_kv_head
    qg = q.reshape(S, n_kv_head, G, head_dim)
    lg = jnp.einsum("qkgd,tkd->kgqt", qg, k,
                    preferred_element_type=jnp.float32) \
        * head_dim ** -0.5
    lg = jnp.where(jnp.tril(jnp.ones((S, S), jnp.bool_)), lg, -1e30)
    probs = jax.nn.softmax(lg, axis=-1).astype(dtype)
    return jnp.einsum("kgqt,tkd->qkgd", probs, v,
                      preferred_element_type=jnp.float32
                      ).reshape(S, n_head, head_dim)


def _gqa_causal(q, k, v, cfg: KDAMoEConfig):
    """:func:`gqa_causal_attention` at this model's heads (no
    positions)."""
    return gqa_causal_attention(q, k, v, n_head=cfg.n_head,
                                n_kv_head=cfg.n_kv_head,
                                head_dim=cfg.head_dim, dtype=cfg.dtype)


def _gqa_attention_gather(q, kpool, vpool, pages, pos, n_kv_head: int,
                          dtype, page_size: int):
    """Decode's attention in plain XLA: ``q`` [B, Hq, hd] over each
    lane's whole virtual sequence, gathered from the flat pools [pages,
    page_size, Hkv, hd] through ``pages`` [B, max_pages] (in bounds) and
    masked past ``pos``; :data:`_GQA_LANE_BLOCK` lanes at a time.
    Returns float32 [B, Hq, hd]."""
    B, n_head, head_dim = q.shape
    V = pages.shape[1] * page_size
    G = n_head // n_kv_head

    def lane(args):
        q, pages, pos = args
        k = kpool[pages].reshape(V, n_kv_head, head_dim)
        v = vpool[pages].reshape(V, n_kv_head, head_dim)
        lg = jnp.einsum("kgd,tkd->kgt",
                        q.reshape(n_kv_head, G, head_dim), k,
                        preferred_element_type=jnp.float32) \
            * head_dim ** -0.5
        lg = jnp.where(jnp.arange(V) <= pos, lg, -1e30)
        probs = jax.nn.softmax(lg, axis=-1).astype(dtype)
        return jnp.einsum("kgt,tkd->kgd", probs, v,
                          preferred_element_type=jnp.float32
                          ).reshape(n_head, head_dim)

    return lax.map(lane, (q, pages, pos),
                   batch_size=min(B, _GQA_LANE_BLOCK))


def _gqa_attention_pallas(q, kpool, vpool, pages, length, n_kv_head: int,
                          page_size: int, kscale=None, vscale=None):
    """Decode's attention as ONE kernel that reads what is live, once:
    ``q`` [B, Hq, hd] against the first ``length[b]`` tokens of lane
    ``b``, whose pages ``pages`` [B, max_pages] names in the flat pools
    [pages, page_size, Hkv, hd]. Returns float32 [B, Hq, hd]; zeros for
    a lane of length 0, which costs no byte.

    The frame is :func:`ray_tpu.models.mla_moe._latent_attention_pallas`'s
    (its schedule copied, not shared: ROADMAP D13): grid ``(B,)``, one
    step a lane and inside it a loop over THAT lane's live tokens in
    blocks of :data:`_GQA_BLOCK_TOKENS`; ``pages``, ``length`` and
    ``first`` (the blocks before each lane: the lanes' blocks in order
    are one STREAM) ride as scalar-prefetch operands; the pools stay in
    HBM, viewed ``[pages, page_size * Hkv, hd]`` (a page's rows as they
    lie, token-major: no copy) and never sliced; a block's live pages
    come by DMA, one copy a page and pool, into buffer ``i % ring`` of
    :data:`_GQA_RING_BLOCKS` VMEM blocks of keys and as many of values,
    each later block started behind the arithmetic of the block whose
    buffer it takes, so a lane's last blocks fetch the NEXT lane's
    first. A page is fetched ONCE; a page past the live length never.
    A page that holds more than a block (:func:`_gqa_block`) is a row
    of equal parts, a part a block and a copy, so the ring is as large
    whatever the page, and of the lane's last page only the parts that
    hold a live token are fetched.

    A page's rows are ``(token, KV head)`` with the head in the MIDDLE
    of ``[page_size, Hkv, hd]``, which Mosaic takes neither as a
    contraction's batch axis nor as a strided slice of packed rows. So
    a block is ONE operand ``[T * Hkv, hd]`` and a lane's step two MXU
    products a block over ALL heads at once: ``s = q . K^T`` ([Hq, hd] x
    [hd, T * Hkv], float32 sums, scaled in float32) plus ``own``, which
    is 0 where the row's KV head is the column's and -1e30 elsewhere
    (an operand laid out by the caller's XLA, fetched once), then ``acc
    += p . V`` ([Hq, T * Hkv] x [T * Hkv, hd]) around one running-max
    softmax pass in float32: a foreign head's probability is exactly 0,
    so the ``Hkv`` times more columns add MXU passes and exponentials
    (both far under the bytes' time at eight KV heads) and nothing to
    the sums. The probabilities are rounded to the compute dtype before
    they meet V, as the XLA body rounds them, but BEFORE the division
    by the sum: the whole numeric difference
    (:data:`ATTN_KERNEL_ULPS`). Whole blocks take no other mask; the
    lane's last, partial block masks the scores AND the values past
    the live length (a block's unfetched rows hold whatever was there,
    and 0 * inf is NaN).

    Pools of int8 codes come with ``kscale`` and ``vscale`` [pages,
    Hkv] float32, a scale a page and KV head (``pages`` then in
    bounds in EVERY column): the same body, whose block is dequantized
    where it is read, ``(code * scale)`` rounded to the compute dtype
    before the product as :func:`ray_tpu.models.gpt_decode._deq_page`
    rounds it. A row's scale is its page's and its head's, so a lane's
    scales ride beside its query as ``[R, max_pages]``, ``R`` the
    float32 sublane tiles that hold whole tokens' heads, and a block's
    pages pick their columns by a mask and a lane reduction: a column
    ``[R, 1]`` a page, repeated down the page's rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .._private.chip import pallas_interpret

    B, Hq, hd = q.shape
    Hkv = n_kv_head
    G = Hq // Hkv
    ps = page_size
    bp, split = _gqa_block(Hkv, ps)       # pages a block, blocks a page
    cp = ps // split                      # tokens a copy: a page, or a part
    rows = cp * Hkv                                # rows a copy
    T = bp * cp
    ring = _GQA_RING_BLOCKS
    dtype = q.dtype
    quant = kscale is not None
    scale = hd ** -0.5             # a Python float: no captured constant
    kpool = kpool.reshape(-1, ps * Hkv, hd)
    vpool = vpool.reshape(-1, ps * Hkv, hd)
    first = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum((length + T - 1) // T, dtype=jnp.int32)])
    own = jnp.where(
        (jnp.arange(T * Hkv) % Hkv)[None] == (jnp.arange(Hq) // G)[:, None],
        0.0, -1e30).astype(jnp.float32)            # [Hq, T * Hkv]
    # rows of a float32 tile pattern that holds whole tokens' heads
    R = math.lcm(Hkv, 8)
    R = R if rows % R == 0 else rows

    def kernel(pt_ref, len_ref, first_ref, q_ref, own_ref, k_hbm, v_hbm,
               *rest):
        ks_ref, vs_ref = rest[:-4] if quant else (None, None)
        o_ref, kbuf, vbuf, sems = rest[-4:]
        b = pl.program_id(0)
        n_live = len_ref[b]
        base, total = first_ref[b], first_ref[B]

        def each_page(lane, j, i, what):
            """``what`` (start or wait) on the two copies of every live
            page of ``lane``'s block ``j``, block ``i`` of the stream
            (of a page larger than a block, its part ``g % split``)."""
            n = (len_ref[lane] + cp - 1) // cp         # its live copies

            def page(g, _):
                for side, (hbm, buf) in enumerate(((k_hbm, kbuf),
                                                   (v_hbm, vbuf))):
                    what(pltpu.make_async_copy(
                        hbm.at[pt_ref[lane, g]] if split == 1 else
                        hbm.at[pt_ref[lane, g // split],
                               pl.ds(pl.multiple_of(g % split * rows, rows),
                                     rows)],
                        buf.at[i % ring, g - j * bp],
                        sems.at[side, i % ring]))

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

        qv = q_ref[0]                                        # [Hq, hd]

        def block(buf, s_ref, i, j):
            """The lane's block ``j``, block ``i`` of the stream, as
            ONE operand [T * Hkv, hd]. int8: its codes under their
            scales, rounded to the compute dtype; a table column past
            the lane's last page scales by what the caller gathered
            there or by 0, and the partial block's masks cover both."""
            codes = buf[i % ring]
            if not quant:
                return codes.reshape(T * Hkv, hd)
            width = s_ref.shape[2]
            col = lax.broadcasted_iota(jnp.int32, (bp, R, width), 2) \
                - lax.broadcasted_iota(jnp.int32, (bp, R, width), 0)
            sc = jnp.sum(jnp.where(col == (j * bp if split == 1 else
                                           j // split),
                                   s_ref[0][None], 0.0),
                         axis=2, keepdims=True)              # [bp, R, 1]
            rows_f = codes.astype(jnp.float32).reshape(
                bp, rows // R, R, hd) * sc[:, None]
            return rows_f.reshape(bp, rows, hd).astype(dtype).reshape(
                T * Hkv, hd)

        def fold(j, carry, whole=True):
            """Block ``j`` of the lane into ``(m, l, acc)``: the waits
            first, the refill last (behind the second product the
            block's buffers are free), the arithmetic between them."""
            m, l, acc = carry
            i = base + j
            each_page(b, j, i, lambda copy: copy.wait())
            kb = block(kbuf, ks_ref, i, j)
            vb = block(vbuf, vs_ref, i, j)
            s = lax.dot_general(qv, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
                * scale + own_ref[...]
            if not whole:
                live = (n_live - j * T) * Hkv          # rows of the block
                s = jnp.where(lax.broadcasted_iota(
                    jnp.int32, (1, T * Hkv), 1) < live, s, -1e30)
                vb = jnp.where(lax.broadcasted_iota(
                    jnp.int32, (T * Hkv, 1), 0) < live, vb,
                    jnp.zeros_like(vb))
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)                       # 0 where masked
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc = alpha * acc + jnp.dot(
                p.astype(dtype), vb, preferred_element_type=jnp.float32)
            pl.when(i + ring < total)(lambda: start(i + ring, b))
            return m_new, l, acc

        n_whole = n_live // T                  # blocks that need no mask
        carry = lax.fori_loop(
            0, n_whole, fold,
            (jnp.full((Hq, 1), -1e30, jnp.float32),
             jnp.zeros((Hq, 1), jnp.float32),
             jnp.zeros((Hq, hd), jnp.float32)))
        m, l, acc = lax.cond(
            n_live > n_whole * T,
            lambda carry: fold(n_whole, carry, whole=False),
            lambda carry: carry, carry)
        o_ref[0] = acc / jnp.where(l > 0.0, l, 1.0)

    def lane_map(b, *prefetched):
        return (b, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    scales = [jnp.tile(s[pages].transpose(0, 2, 1), (1, R // Hkv, 1))
              for s in ((kscale, vscale) if quant else ())]
    # `name` names the device operation ("gqa_attention.N") and the last
    # component of its path before "pallas_call"; the rest of the path
    # is the caller's scope.
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, Hq, hd), lane_map),
                      pl.BlockSpec((Hq, T * Hkv), lambda b, *_: (0, 0)),
                      hbm, hbm]
            + [pl.BlockSpec((1, R, pages.shape[1]), lane_map)] * len(scales),
            out_specs=pl.BlockSpec((1, Hq, hd), lane_map),
            scratch_shapes=[pltpu.VMEM((ring, bp, rows, hd), kpool.dtype),
                            pltpu.VMEM((ring, bp, rows, hd), vpool.dtype),
                            pltpu.SemaphoreType.DMA((2, ring))]),
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), jnp.float32),
        # Every index a copy takes is in bounds (``pages``) or a
        # remainder (the ring): the checks Mosaic adds cannot fire.
        compiler_params=pltpu.CompilerParams(disable_bounds_checks=True),
        interpret=pallas_interpret(),
        name="gqa_attention",
    )(pages, length, first, q, own, kpool, vpool, *scales)


def gqa_decode_reads(pt, pos, active, n_pages: int, page_size: int,
                     kernel: bool):
    """What one decode step's GQA attention reads, the same for every
    layer of the step: ``(length, fetched)``. With the kernel
    (:func:`gqa_kernel`) ``length`` int32 [B] is each lane's live
    tokens (:func:`~ray_tpu.models.serving.live_length`; the step
    writes a token's keys and values before it attends) and
    ``fetched`` those rounded up to whole pages, summed (a bound from
    above where a page is read in parts, :func:`_gqa_block`); with the
    gather ``length`` is None and ``fetched`` the whole table,
    ``slots x max_pages x page_size``, whatever is live."""
    if not kernel:
        return None, jnp.int32(pt.shape[0] * pt.shape[1] * page_size)
    length = live_length(pt, pos, active, n_pages, page_size)
    return length, jnp.sum((length + page_size - 1) // page_size * page_size,
                           dtype=jnp.int32)


def gqa_decode_attention(q, kpool, vpool, pages, pos, length, *,
                         n_head: int, n_kv_head: int, head_dim: int,
                         dtype, page_size: int, kscale=None, vscale=None):
    """Decode's grouped-query attention over pages, for any model whose
    pages hold ``[n_kv_head, head_dim]`` keys and values a token: ``q``
    [B, n_head, head_dim] in ``dtype`` (positions, where the model has
    any, already applied to it and to the keys in the pool) over the
    flat pools [pages, page_size, n_kv_head, head_dim] through
    ``pages`` [B, max_pages] (in bounds), query head ``j`` with KV head
    ``j // (n_head / n_kv_head)``. ONE path in two bodies
    (:func:`gqa_decode_reads` says which, by :func:`gqa_kernel`):
    ``length`` int32 [B], each lane's live tokens, takes the Pallas
    kernel over the live pages (:func:`_gqa_attention_pallas`); None
    takes plain XLA over the whole table row masked past ``pos``
    (:func:`_gqa_attention_gather`). Returns float32 [B, n_head,
    head_dim]; the two agree to :data:`ATTN_KERNEL_ULPS`. The kernel
    alone also reads pools of int8 codes, given their scales a page
    and KV head, ``kscale`` and ``vscale`` [pages, n_kv_head] float32
    (:mod:`ray_tpu.models.gpt_decode`'s, whose gather is its own)."""
    assert q.shape[1:] == (n_head, head_dim), (q.shape, n_head, head_dim)
    if length is None:
        assert kscale is None, "the gather reads no int8 pool"
        return _gqa_attention_gather(q, kpool, vpool, pages, pos,
                                     n_kv_head, dtype, page_size)
    return _gqa_attention_pallas(q, kpool, vpool, pages, length, n_kv_head,
                                 page_size, kscale, vscale)


def forward(params: Params, tokens: jax.Array, cfg: KDAMoEConfig
            ) -> jax.Array:
    """tokens [B, S] -> float32 logits [B, S, rows]: each sequence
    whole, no cache (the chunked KDA form from a zero state, causal
    attention), one sequence at a time."""
    S = tokens.shape[1]
    live = jnp.ones((S,), jnp.bool_)

    def row(toks):
        x = embed(params, toks)
        for l, p in enumerate(params["layers"]):
            h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
            if l in cfg.gqa_layers:
                q, k, v, z = _gqa_qkvz(h, p, cfg)
                y = _gqa_out(_gqa_causal(q, k, v, cfg), z, p, cfg)
            else:
                y = _kda_sequence(h, p, cfg, live)[0]
            x = block_ffn(x + y.astype(x.dtype), p, cfg)[0]
        return head(x, params, cfg)

    return lax.map(row, tokens)


# ----------------------------------------------------------- description
def cache_spec(cfg: KDAMoEConfig, kv_dtype: str = "fp") -> CacheSpec:
    """What a token leaves in a page (keys and values of the GQA layers,
    ``[Hkv, hd]`` each) and what a sequence keeps in its SLOT (a KDA
    layer's state ``[H, dk, dv]`` in the state dtype and the
    convolution's last ``conv_size - 1`` input rows ``[conv - 1, 3 W]``
    in the compute dtype), each with the count of layers that keep it:
    the pools ``[n_gqa, n_pages, page_size, Hkv, hd]``, ``state``
    ``[n_kda, slots, H, dk, dv]`` and ``conv`` ``[n_kda, slots, conv -
    1, 3 W]``."""
    serving.check_kv_dtype(_THIS, kv_dtype)
    row = (cfg.n_kv_head, cfg.head_dim)
    D = cfg.kda_head_dim
    return CacheSpec(cfg.n_layer, (
        CacheEntry("k", "token", row, cfg.dtype, cfg.n_gqa),
        CacheEntry("v", "token", row, cfg.dtype, cfg.n_gqa),
        CacheEntry("state", "slot", (cfg.kda_heads, D, D), cfg.state_dtype,
                   cfg.n_kda),
        CacheEntry("conv", "slot", (cfg.conv_size - 1, 3 * cfg.kda_width),
                   cfg.dtype, cfg.n_kda)))


def max_positions(cfg: KDAMoEConfig) -> int:
    """No positions are encoded: the model's declared reach."""
    return cfg.max_seq


# what follows from the spec and from ``UNSUPPORTED["tp"]``: the frame's
kv_bytes_per_page = serving.bind(serving.kv_bytes_per_page, _THIS)
init_paged_cache = serving.bind(serving.init_paged_cache, _THIS)
check_tp = serving.bind(serving.check_tp, _THIS)
shard_params = serving.bind(serving.shard_params, _THIS)


# -------------------------------------------------------------- programs
def _put(pool, rows, *start):
    """``rows`` written at ``pool[start]`` in place (the leading
    indices; a traced slot among them)."""
    lead = len(start)
    return lax.dynamic_update_slice(
        pool, rows.astype(pool.dtype)[(None,) * lead],
        tuple(start) + (0,) * (pool.ndim - lead))


def prefill_into_slot_paged(params: Params, cache: Cache,
                            tokens: jax.Array, length: jax.Array,
                            hist_len: jax.Array, pt_row: jax.Array,
                            cow_src: jax.Array, slot: jax.Array,
                            rng: jax.Array, *, cfg: KDAMoEConfig,
                            page_size: int, temperature: float = 0.0,
                            kv_dtype: str = "fp"
                            ) -> Tuple[jax.Array, Cache, jax.Array]:
    """Prefill one WHOLE prompt into its pages and its slot, with the
    first token's sample: the frame of
    :func:`ray_tpu.models.gpt_decode.prefill_into_slot_paged`. The GQA
    layers' keys and values go to the pages ``pt_row`` names; every KDA
    layer's state and convolution tail are rebuilt FROM ZERO and written
    over whatever slot ``slot`` held (the last request's, a preempted
    lane's, the warm-up's): a prefill is the one way a slot's state
    begins. Rows past ``length`` (the bucket's padding) write no page
    and advance neither state nor tail. ``hist_len`` and ``cow_src`` are
    the frame's and have no meaning here: without a prefix cache
    (:data:`UNSUPPORTED`) the engine's are always 0 and the sentinel."""
    del hist_len, cow_src
    S = tokens.shape[1]
    ps = page_size
    n_pages = cache["k"].shape[1]
    max_pages = pt_row.shape[0]
    x = embed(params, tokens)[0]                            # [S, d]
    live = jnp.arange(S) < length
    wpos = jnp.arange(S)
    vp = wpos // ps
    page_w = jnp.where(live & (vp < max_pages),
                       pt_row[jnp.clip(vp, 0, max_pages - 1)],
                       jnp.int32(PT_SENTINEL))
    kpool, vpool = flat(cache["k"]), flat(cache["v"])
    state, conv = cache["state"], cache["conv"]
    back = cfg.conv_size - 1
    ig = ik = 0
    for l, p in enumerate(params["layers"]):
        h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
        if l in cfg.gqa_layers:
            q, k, v, z = _gqa_qkvz(h, p, cfg)
            with jax.named_scope("gqa.prefill"):
                att = _gqa_causal(q, k, v, cfg)
            at = (at_layer(page_w, ig, n_pages), wpos % ps)
            kpool = kpool.at[at].set(k, mode="drop")
            vpool = vpool.at[at].set(v, mode="drop")
            y = _gqa_out(att, z, p, cfg)
            ig += 1
        else:
            y, S_end, padded = _kda_sequence(h, p, cfg, live)
            state = _put(state, S_end, ik, slot)
            conv = _put(conv, lax.dynamic_slice(
                padded, (length, 0), (back, padded.shape[1])), ik, slot)
            ik += 1
        x = block_ffn(x + y.astype(x.dtype), p, cfg, live)[0]
    x_last = lax.dynamic_slice(x, (length - 1, 0), (1, cfg.d_model))
    token, rng = serving.sample(head(x_last, params, cfg), temperature,
                                rng)
    pos = lax.dynamic_update_slice(
        cache["pos"], jnp.reshape(length, (1,)).astype(jnp.int32), (slot,))
    return token[0], {"k": kpool.reshape(cache["k"].shape),
                      "v": vpool.reshape(cache["v"].shape),
                      "state": state, "conv": conv, "pos": pos}, rng


def prefill_group_into_slots_paged(params: Params, cache: Cache, tokens,
                                   length: jax.Array, hist_len: jax.Array,
                                   pt_row: jax.Array, cow_src: jax.Array,
                                   slot: jax.Array, rng: jax.Array, *,
                                   cfg: KDAMoEConfig, page_size: int,
                                   temperature: float = 0.0,
                                   kv_dtype: str = "fp"
                                   ) -> Tuple[jax.Array, Cache, jax.Array]:
    """:func:`prefill_into_slot_paged` for the ``G`` prompts of one
    chunk boundary in ONE launch (the frame's contract,
    ``models/serving.py``): the mixers' projections, the expert layer
    (in the blocks :func:`ray_tpu.models.moe.group_cfg` widens) and the
    head run over all the prompts' rows at once
    (:class:`ray_tpu.models.serving.PromptRows`); each prompt's causal
    attention, its convolution and its chunked recurrence from a zero
    state are the single prefill's on its own rows (:func:`_gqa_causal`,
    :func:`_kda_mix`), and each lands in its own pages and its own
    slot."""
    rows = serving.PromptRows(tokens, length, jnp.zeros_like(length))
    del hist_len, cow_src
    G = rows.G
    n_pages = cache["k"].shape[1]
    x = embed(params, rows.tokens)                          # [R, d]
    live = rows.split(rows.live)
    page_w, off = rows.pages(pt_row, page_size)
    kpool, vpool = flat(cache["k"]), flat(cache["v"])
    state, conv = cache["state"], cache["conv"]
    back = cfg.conv_size - 1
    ffn_cfg = group_cfg(cfg, G)
    ig = ik = 0
    for l, p in enumerate(params["layers"]):
        h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
        if l in cfg.gqa_layers:
            q, k, v, z = _gqa_qkvz(h, p, cfg)
            with jax.named_scope("gqa.prefill"):
                att = jnp.concatenate([
                    _gqa_causal(qg, kg, vg, cfg) for qg, kg, vg in zip(
                        rows.split(q), rows.split(k), rows.split(v))])
            at = (at_layer(page_w, ig, n_pages), off)
            kpool = kpool.at[at].set(k, mode="drop")
            vpool = vpool.at[at].set(v, mode="drop")
            y = _gqa_out(att, z, p, cfg)
            ig += 1
        else:
            with jax.named_scope("kda.proj"):
                pre, g, beta, gate = _kda_proj(h, p, cfg)
            outs = []
            for i, (pre_i, g_i, beta_i) in enumerate(zip(
                    rows.split(pre), rows.split(g), rows.split(beta))):
                o, S_end, padded = _kda_mix(pre_i, g_i, beta_i, p, cfg,
                                            live[i])
                outs.append(o)
                state = _put(state, S_end, ik, slot[i])
                conv = _put(conv, lax.dynamic_slice(
                    padded, (length[i], 0), (back, padded.shape[1])), ik,
                    slot[i])
            with jax.named_scope("kda.proj"):
                y = _kda_out(jnp.concatenate(outs), gate, p, cfg)
            ik += 1
        x = block_ffn(x + y.astype(x.dtype), p, ffn_cfg, rows.live)[0]
    token, rng = serving.sample_slots(head(x[rows.last], params, cfg),
                                      temperature, rng)
    return token, {"k": kpool.reshape(cache["k"].shape),
                   "v": vpool.reshape(cache["v"].shape),
                   "state": state, "conv": conv,
                   "pos": cache["pos"].at[slot].set(
                       length.astype(jnp.int32))}, rng


def _slot_decode_step_paged(params: Params, cache: Cache,
                            token: jax.Array, active: jax.Array,
                            pt: jax.Array, cfg: KDAMoEConfig,
                            page_size: int, kv_dtype: str = "fp",
                            attn_kernel: str = "gather"):
    """One masked decode step over the whole slot pool: each active lane
    writes its keys and values at its own position and attends over its
    pages (GQA layers: the kernel over its live pages wherever
    :func:`_gqa_kernel`, else :func:`_gqa_attention_gather` over its
    whole table row), and reads and writes its state and convolution
    tail whole (KDA layers: the recurrence as the kernel wherever
    :func:`_state_kernel`, else :func:`_kda_step`). An inactive lane
    (idle, or parked for pages) neither writes, advances nor routes:
    its state and tail come out as they went in. Returns ``(logits [B,
    rows], cache', counts)``: int32 [6] (:data:`STEP_COUNTERS`)."""
    ps = page_size
    max_pages = pt.shape[1]
    pos = cache["pos"]
    n_pages = cache["k"].shape[1]
    x = embed(params, token)                                # [B, d]
    vp = pos // ps
    page_w = jnp.where(
        active & (vp < max_pages),
        jnp.take_along_axis(pt, jnp.clip(vp, 0, max_pages - 1)[:, None],
                            axis=1)[:, 0], jnp.int32(PT_SENTINEL))
    ptc = jnp.clip(pt, 0, n_pages - 1)
    kpool, vpool = flat(cache["k"]), flat(cache["v"])
    state, conv = cache["state"], cache["conv"]
    counts = jnp.zeros((4,), jnp.int32)
    ig = ik = 0
    state_kernel = _state_kernel(cfg)
    length, fetched = gqa_decode_reads(pt, pos, active, n_pages, ps,
                                       _gqa_kernel(cfg, ps))
    # the step's own scope: a reader tells the decode program's state,
    # attention and expert time from prefill's by it
    with jax.named_scope("decode_step"):
        for l, p in enumerate(params["layers"]):
            h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
            if l in cfg.gqa_layers:
                q, k, v, z = _gqa_qkvz(h, p, cfg)
                at = (at_layer(page_w, ig, n_pages), pos % ps)
                kpool = kpool.at[at].set(k, mode="drop")
                vpool = vpool.at[at].set(v, mode="drop")
                with jax.named_scope("gqa.attention"):
                    att = gqa_decode_attention(
                        q, kpool, vpool, ptc + ig * n_pages, pos, length,
                        n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
                        head_dim=cfg.head_dim, dtype=cfg.dtype,
                        page_size=ps)
                y = _gqa_out(att, z, p, cfg)
                ig += 1
            else:
                with jax.named_scope("kda.proj"):
                    pre, g, beta, gate = _kda_proj(h, p, cfg)
                    tail = conv[ik]                       # [B, back, 3 W]
                    window = [tail[:, i] for i in range(tail.shape[1])] \
                        + [pre]
                    q, k, v = _kda_qkv(window, p, cfg)
                    conv = conv.at[ik].set(jnp.where(
                        active[:, None, None],
                        jnp.stack(window[1:], axis=1), tail))
                with jax.named_scope("kda.state"):
                    if state_kernel:
                        state, o = _kda_step_pallas(
                            state, ik, q, k, v, g, beta, active)
                    else:
                        S = state[ik].astype(jnp.float32)
                        S_new, o = _kda_step(S, q, k, v, g, beta)
                        state = state.at[ik].set(jnp.where(
                            active[:, None, None, None], S_new, S
                        ).astype(state.dtype))
                with jax.named_scope("kda.proj"):
                    y = _kda_out(o, gate, p, cfg)
                ik += 1
            x, c = block_ffn(x + y.astype(x.dtype), p, cfg, active)
            counts = counts + c
    cache_out = {"k": kpool.reshape(cache["k"].shape),
                 "v": vpool.reshape(cache["v"].shape),
                 "state": state, "conv": conv,
                 "pos": pos + active.astype(jnp.int32)}
    counts = jnp.concatenate(
        [counts, jnp.sum(active, dtype=jnp.int32)[None],
         (cfg.n_gqa * fetched)[None]])
    return head(x, params, cfg), cache_out, counts


# the chunk program and the two factories are the frame's, around this
# model's step and for this description (``models/serving.py``): the
# cache the scan carries is pages AND per-slot state
decode_chunk_slots_paged = functools.partial(
    serving.decode_chunk_slots_paged, step=_slot_decode_step_paged,
    counters=len(STEP_COUNTERS))
jit_prefill_into_slot_paged = serving.bind(
    serving.jit_prefill_into_slot_paged, _THIS)
jit_decode_chunk_slots_paged = serving.bind(
    serving.jit_decode_chunk_slots_paged, _THIS)
