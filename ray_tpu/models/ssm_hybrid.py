"""A PARALLEL hybrid decoder, a selective state-space mixer BESIDE
rotary grouped-query attention in every layer, and its paged serving
programs: the fifth block :class:`~ray_tpu.serve.engine.DecodeEngine`
serves. This module IS the model's description in the sense of
:mod:`ray_tpu.models.serving`.

The block (pre-norm, RMSNorm, a float32 residual stream, an untied
head, a constant multiplier on every branch; no bias but the
convolution's)::

    u  = RMSNorm(x)
    x += SSM(u) * ssm_out_mult + Attention(u) * attn_out_mult
    x += MLP(RMSNorm(x))

Both mixers read the SAME normed input and add into the residual
together, so every layer keeps BOTH kinds of thing of a sequence, and
:func:`cache_spec` describes them in one
:class:`~ray_tpu.models.serving.CacheSpec` (``kda_moe`` keeps one or
the other, by layer):

- **Attention** (``n_head`` query heads over ``n_kv_head`` key/value
  heads, query head ``j`` with KV head ``j // (n_head / n_kv_head)``;
  rotary over the whole head, halves pairing; ``q`` scaled by
  ``attn_in_mult`` and ``k`` by ``key_mult``): a token leaves
  ``n_kv_head x head_dim`` ROTATED keys and its values in a PAGE
  (entries ``k``, ``v``, per token, every layer). Prefill attends
  causally over the prompt (scope ``hgqa.prefill``); decode attends
  over the lane's pages (scope ``hgqa.attention``) through
  :func:`ray_tpu.models.kda_moe.gqa_decode_attention`: that model's
  Pallas kernel over each lane's live pages wherever Mosaic can address
  a page and a head (:func:`ray_tpu.models.kda_moe.gqa_kernel`), plain
  XLA over the gathered table row elsewhere, chosen by shape under the
  one name ``"gather"``.
- **SSM** (Mamba-2: ``ssm_heads`` heads of ``ssm_head_dim`` channels
  over a state ``ssm_state`` wide, ``B`` and ``C`` shared by
  ``ssm_groups`` groups of heads). With ``[z | xBC | dt] = (u W_in) *
  ssm_in_mult * mup`` (``mup``: one of :attr:`SSMHybridConfig.ssm_mup`
  a segment ``z | x | B | C | dt``)::

      xBC_t = silu(sum_j w[:, j] xBC_{t-3+j} + b)     width-4, depthwise
      dt_t  = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) dt_t)
      S_t   = a_t S_{t-1} + dt_t x_t (x) B_t          a head, S [P, N]
      y_t   = S_t C_t + D x_t
      out   = (RMSNorm_groups(y_t * silu(z_t)) * w_norm) W_out

  A sequence keeps, whatever its length, ``S`` (``[heads, head_dim,
  state]`` in :attr:`SSMHybridConfig.state_dtype`; where a row of it
  is ONE lane tile, ``ssm_state`` 128, held ``N``-major with the heads
  side by side on lanes: :func:`lane_heads`, one layout a shape) and
  the last ``conv_size - 1`` rows of ``xBC`` before the convolution:
  entries ``state<l>`` and ``conv<l>``, ``per "slot"``, ONE ARRAY A
  LAYER (:func:`cache_spec` says why). They belong to
  the SLOT: every prefill rebuilds them from zero (the chunked form,
  scope ``ssm.prefill``: inside a chunk the masked quadratic form with
  ``exp`` of the decays' running sums, between chunks the state passed
  on), every decode step reads a live lane's state ONCE and writes it
  once, in place (scope ``ssm.state``: one Pallas kernel a layer, in
  float32, wherever Mosaic can address a head's state: at two lane
  tiles a row or more :func:`ssm_step_pallas`, at one
  :func:`ssm_step_pallas_nmajor` on the ``N``-major entry,
  :func:`state_kernel`; plain XLA, :func:`ssm_step`, elsewhere), and
  an idle or parked lane's comes out as it went in, not
  a byte of it moved. So no page hash shares them and nothing rolls
  back: :data:`UNSUPPORTED`.

**MLP**, every layer, dense: ``W_down[silu((v W_gate) * mlp_mults[0])
* (v W_up)] * mlp_mults[1]`` (scope ``hybrid.mlp``). The table's rows
are scaled by ``embed_mult`` and the logits by ``head_mult`` (scope
``lm.head`` around the final norm and the head product).

Layers are a list of per-layer trees, unrolled; the chunk program
returns the live lanes and the positions its attention fetched, summed
over its steps (:data:`STEP_COUNTERS`).

**The SSM mixer is SHARED**, under public names, by sizes
(:class:`Mamba2Sizes`, which a model's config mixes in) and not by this
model's config: :func:`ssm_proj`, :func:`ssm_conv`, :func:`ssm_out`
(projection, convolution, gated norm and output projection),
:func:`ssm_step` / :func:`ssm_step_pallas` /
:func:`ssm_step_pallas_nmajor` / :func:`state_kernel` (the recurrence
of one token a lane), :func:`lane_heads` / :func:`state_shape` /
:func:`state_entry` / :func:`state_heads` (how a layer's state lies in
its entry), :func:`ssm_decode` (a decode step's
whole mixer on a layer's per-slot entries), :func:`ssd_chunked`,
:func:`ssm_mix`, :func:`ssm_sequence` (a whole sequence from a zero
state, with its convolution tail), and :func:`slot_entries` /
:func:`put_slot` (a layer's per-slot entries and a prefill's write into
them). :mod:`ray_tpu.models.ssm_moe` imports them (a model whose Mamba-2
layers STAND IN for attention, with every multiplier 1 and one group);
this module's own programs call the same functions, and its lowered
programs are what they were before the names went public
(``tests/test_models_frame.py``).
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

from . import kda_moe, serving
from .moe import rmsnorm
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
    "int8": "the key/value pages beside the state have no quantised "
            "layout",
    "tp": "there are no tensor-parallel programs: the state belongs to "
          "the slot and the deployment cuts the model by depth, one "
          "engine a chip",
}
#: int32 counters the chunk program returns, summed over its steps: the
#: lanes whose state a step read and wrote (one a lane a step, whatever
#: the layers), and the positions whose keys and values a step's
#: attention fetched from the pools, all layers
#: (:func:`ray_tpu.models.kda_moe.gqa_decode_reads`).
STEP_COUNTERS = ("state_lanes_sum", "gqa_tokens_read_sum")
#: Bytes of a lane's state the recurrence's kernel holds in VMEM at
#: once (:func:`block_heads` turns them into heads and fits those to the
#: groups): 2 MiB, 8 MiB with the block before and the block after in
#: flight, in and out (of the 16 MiB a kernel may take on a v5e; 4 MiB
#: need the limit raised). That is 16 heads of [128, 256] float32
#: (Falcon-H1: measured there, alone, 8 / 16 / 32 heads 1.73 / 1.68 /
#: 1.71 ms a layer of 127 live lanes, the copies alone 1.70 / 1.67 /
#: 1.69, the XLA body 2.36: PERF.md section 6, PR 53) and 64 heads of
#: [64, 128] (granite-4.0-h-small, 128 heads in one group: held
#: ``N``-major, 32 rows of two heads, :func:`ssm_step_pallas_nmajor`;
#: the copies alone 16 / 32 / 64 heads 1.68 / 1.66 / 1.65 ms a layer of
#: 127 live lanes: a cap in HEADS, 16, would cost 2%; PERF.md section
#: 6, PRs 55 and 56).
_SSM_BLOCK_BYTES = 2 << 20
_HI = lax.Precision.HIGHEST


class Mamba2Sizes:
    """What the SHARED mixer's frames read of a config: a model's
    (frozen dataclass) config mixes this in and provides the fields
    ``ssm_heads``, ``ssm_head_dim`` (channels a head, ``P``),
    ``ssm_state`` (the state's width, ``N``), ``ssm_groups`` (groups of
    heads sharing ``B`` and ``C``), ``conv_size``, ``ssm_chunk``
    (prefill's chunk), ``eps``, ``dtype`` (compute) and
    ``state_dtype``. The widths below follow from them; the two
    multipliers are 1 unless the model states its own."""
    #: on the output projection's result (1.0: not applied)
    ssm_out_mult = 1.0

    @property
    def ssm_col_mults(self) -> Optional[Tuple[float, ...]]:
        """The input projection's multiplier a segment ``z | x | B | C
        | dt``, or None: none applied."""
        return None

    @property
    def ssm_width(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def bc_width(self) -> int:
        """``B`` (and ``C``) over all groups."""
        return self.ssm_groups * self.ssm_state

    @property
    def conv_dim(self) -> int:
        """``x | B | C``: what the convolution runs over."""
        return self.ssm_width + 2 * self.bc_width

    @property
    def in_width(self) -> int:
        """``z | x | B | C | dt``: the input projection's columns."""
        return self.ssm_width + self.conv_dim + self.ssm_heads


@dataclasses.dataclass(frozen=True)
class SSMHybridConfig(Mamba2Sizes):
    vocab_size: int = 512
    n_layer: int = 2
    d_model: int = 64
    n_head: int = 4                  # query heads
    n_kv_head: int = 2
    head_dim: int = 16
    rope_theta: float = 10000.0
    ssm_heads: int = 4
    ssm_head_dim: int = 16           # channels a head (P)
    ssm_state: int = 32              # the state's width (N)
    ssm_groups: int = 2              # groups of heads sharing B and C
    conv_size: int = 4
    ssm_chunk: int = 16              # prefill's chunk
    d_ff: int = 128
    # the branches' constant multipliers
    embed_mult: float = 2.0
    ssm_in_mult: float = 0.5
    ssm_mup: Tuple[float, ...] = (0.7, 0.5, 0.35, 0.5, 0.7)  # z|x|B|C|dt
    ssm_out_mult: float = 0.5
    attn_in_mult: float = 1.0
    key_mult: float = 0.25
    attn_out_mult: float = 0.5
    mlp_mults: Tuple[float, float] = (0.5, 0.25)     # gate, down
    head_mult: float = 0.125
    max_seq: int = 262144            # positions the rotary reaches
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32

    @property
    def ssm_col_mults(self) -> Tuple[float, ...]:
        """``ssm_in_mult`` times its segment's of ``ssm_mup``."""
        return tuple(self.ssm_in_mult * m for m in self.ssm_mup)

    def decode_programs(self):
        """This model's description for the serving engine
        (:mod:`ray_tpu.models.serving`)."""
        import sys

        return sys.modules[__name__]


# sizes used by the CPU tests
CONFIGS = {
    "nano": SSMHybridConfig(),
}

#: Means of the leaves that are not drawn around zero: ``dt_bias`` sits
#: where ``softplus`` is about 0.02 and ``A_log`` where ``exp`` is 4, so
#: that the decay ``a`` spreads over (0.2, 0.999) instead of around
#: ``exp(-ln 2)``; the skip ``D`` and the gated norm's weight around 1.
INIT_MEAN = {"dt_bias": -4.0, "A_log": 1.4, "D_skip": 1.0, "ssm_norm": 1.0}


def init_std(cfg: SSMHybridConfig) -> Dict[str, float]:
    """Each kind of leaf's standard deviation: a matrix is drawn so
    that WITH its branch's multiplier it acts as a ``1 / sqrt(fan-in)``
    matrix (a unit-variance draw under ``key_mult`` makes every softmax
    uniform and under the three output multipliers drowns the branch in
    the table): scores about three wide, a unit residual stream at the
    table, each branch adding to it at a like scale, logits about one
    wide."""
    d = cfg.d_model

    def fan(n, mult=1.0, gain=1.0):
        return gain / (math.sqrt(n) * mult)

    return {
        "embed": 1.0 / cfg.embed_mult,
        "head": fan(d, cfg.head_mult),
        "wq": fan(d, cfg.attn_in_mult, 1.7),
        "wk": fan(d, cfg.key_mult, 1.7),
        "wo": fan(cfg.n_head * cfg.head_dim, cfg.attn_out_mult),
        # one draw for all five segments: ``x`` comes out one wide
        "in_proj": fan(d, cfg.ssm_in_mult * cfg.ssm_mup[1]),
        "out_proj": fan(cfg.ssm_width, cfg.ssm_out_mult),
        "gate": fan(d, cfg.mlp_mults[0]),
        "down": fan(cfg.d_ff, cfg.mlp_mults[1], 2.0),
        "conv_w": 0.5, "conv_b": 0.5, "dt_bias": 1.0, "A_log": 0.7,
        "D_skip": 0.5, "ssm_norm": 0.3,
    }


def init_params(rng: jax.Array, cfg: SSMHybridConfig,
                std: Optional[dict] = None, vocab_blocks: int = 1) -> Params:
    """Seeded weights, one tree a layer. ``std`` overrides a kind's
    standard deviation (default :func:`init_std`, else 1/sqrt(fan-in));
    :data:`INIT_MEAN` is added. ``vocab_blocks`` > 1 holds the table
    and the head as that many blocks of vocabulary rows (a list under
    ``"kernel"``: the programs read either form off the tree), so that
    no single leaf is 1.3 G values where the vocabulary is 261,120
    rows: whoever fills the tree under one jit then needs a block's
    worth of temporaries, not a table's."""
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

    layers = []
    for _ in range(cfg.n_layer):
        layers.append({
            "ln1_scale": jnp.ones((d,), pd), "ln2_scale": jnp.ones((d,), pd),
            "wq": {"kernel": w("wq", d, hq)},
            "wk": {"kernel": w("wk", d, hkv)},
            "wv": {"kernel": w("wv", d, hkv)},
            "wo": {"kernel": w("wo", hq, d)},
            "in_proj": {"kernel": w("in_proj", d, cfg.in_width)},
            "conv_w": w("conv_w", cfg.conv_size, cfg.conv_dim),
            "conv_b": w("conv_b", cfg.conv_dim),
            "dt_bias": w("dt_bias", cfg.ssm_heads),
            "A_log": w("A_log", cfg.ssm_heads),
            "D_skip": w("D_skip", cfg.ssm_heads),
            "ssm_norm": w("ssm_norm", W),
            "out_proj": {"kernel": w("out_proj", W, d)},
            "ffn": {"gate": w("gate", d, cfg.d_ff),
                    "up": w("up", d, cfg.d_ff),
                    "down": w("down", cfg.d_ff, d)}})
    rows, rest = divmod(cfg.vocab_size, vocab_blocks)
    assert not rest, (cfg.vocab_size, vocab_blocks)
    table = [w("embed", rows, d) for _ in range(vocab_blocks)]
    head = [w("head", d, rows) for _ in range(vocab_blocks)]
    return {"embed": {"kernel": table if vocab_blocks > 1 else table[0]},
            "head": {"kernel": head if vocab_blocks > 1 else head[0]},
            "ln_f_scale": jnp.ones((d,), pd), "layers": layers}


# ------------------------------------------------------------ block math
def _dot(x, w, dtype):
    """``x @ w`` in ``dtype`` with float32 sums, the float32 result."""
    return lax.dot_general(x.astype(dtype), w.astype(dtype),
                           (((x.ndim - 1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _vocab_blocks(kernel):
    """The table or the head as its blocks of vocabulary rows: a tree
    holds either one array or a list of them (:func:`init_params`)."""
    return kernel if isinstance(kernel, (list, tuple)) else [kernel]


def _embed(params, tokens, cfg: SSMHybridConfig):
    """The residual stream starts, and stays, in float32
    (:func:`ray_tpu.models.moe.embed`), scaled by ``embed_mult``. A
    token's row comes from the block that holds it."""
    blocks = _vocab_blocks(params["embed"]["kernel"])
    rows = blocks[0].shape[0]
    x = blocks[0][tokens] if len(blocks) == 1 else sum(
        jnp.where((tokens // rows == b)[..., None], blk[tokens % rows], 0)
        for b, blk in enumerate(blocks))
    return x.astype(jnp.float32) * cfg.embed_mult


def _head(x, params, cfg: SSMHybridConfig):
    """The final norm and the untied head, scaled: float32 logits (a
    block of vocabulary rows at a time where the tree holds blocks)."""
    with jax.named_scope("lm.head"):
        x = rmsnorm(x, params["ln_f_scale"], cfg.eps, cfg.dtype)
        return jnp.concatenate(
            [_dot(x, blk, cfg.dtype)
             for blk in _vocab_blocks(params["head"]["kernel"])],
            axis=-1) * cfg.head_mult


def _mlp(x, p, cfg: SSMHybridConfig):
    """x [T, d] -> x + MLP(RMSNorm(x))."""
    with jax.named_scope("hybrid.mlp"):
        dt = cfg.dtype
        h = rmsnorm(x, p["ln2_scale"], cfg.eps, dt)
        f = p["ffn"]
        a = (jax.nn.silu(_dot(h, f["gate"], dt) * cfg.mlp_mults[0])
             * _dot(h, f["up"], dt))
        return x + _dot(a, f["down"], dt) * cfg.mlp_mults[1]


def _rope(x, positions, theta: float):
    """Rotary over the whole head, halves pairing: ``x`` [..., heads,
    hd] float32 at ``positions`` [...] (the leading axes of ``x``)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attn_qkv(h, p, positions, cfg: SSMHybridConfig):
    """``h`` [..., d] at ``positions`` [...] -> (q [..., Hq, hd], k, v
    [..., Hkv, hd]) in the compute dtype: ``q`` and ``k`` scaled by
    their multipliers and rotated in float32, then rounded once."""
    dt = cfg.dtype

    def heads(name, n, mult=1.0):
        a = _dot(h, p[name]["kernel"], dt)
        if mult != 1.0:
            a = a * mult
        return a.reshape(a.shape[:-1] + (n, cfg.head_dim))

    q = _rope(heads("wq", cfg.n_head, cfg.attn_in_mult), positions,
              cfg.rope_theta)
    k = _rope(heads("wk", cfg.n_kv_head, cfg.key_mult), positions,
              cfg.rope_theta)
    return q.astype(dt), k.astype(dt), heads("wv", cfg.n_kv_head).astype(dt)


def _attn_out(att, p, cfg: SSMHybridConfig):
    """``att`` [..., Hq, hd] float32 -> the branch's part of the
    residual [..., d]."""
    return _dot(att.reshape(att.shape[:-2] + (-1,)), p["wo"]["kernel"],
                cfg.dtype) * cfg.attn_out_mult


def _attn_causal(q, k, v, cfg: SSMHybridConfig):
    """:func:`ray_tpu.models.kda_moe.gqa_causal_attention` at this
    model's heads: ``q`` [S, Hq, hd] over ``k``, ``v`` [S, Hkv, hd],
    rotated already. Returns float32 [S, Hq, hd]."""
    return kda_moe.gqa_causal_attention(
        q, k, v, n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
        head_dim=cfg.head_dim, dtype=cfg.dtype)


# ---------------------------------------------- the shared Mamba-2 mixer
# Public, by ``Mamba2Sizes`` (``m``): what :mod:`ray_tpu.models.ssm_moe`
# imports and this module's own programs call. A layer's tree ``p``
# holds ``in_proj``, ``conv_w``, ``conv_b``, ``dt_bias``, ``A_log``,
# ``D_skip``, ``ssm_norm`` and ``out_proj`` (:func:`init_params`).

def _mup(m: Mamba2Sizes):
    """The input projection's multiplier a column, from
    ``ssm_col_mults`` (``z | x | B | C | dt``)."""
    widths = (m.ssm_width, m.ssm_width, m.bc_width, m.bc_width,
              m.ssm_heads)
    return jnp.concatenate([
        jnp.full((n,), mult, jnp.float32)
        for n, mult in zip(widths, m.ssm_col_mults)])


def ssm_proj(h, p, m: Mamba2Sizes):
    """``h`` [..., d] (normed) -> (z [..., W] float32: the gate; xBC
    [..., conv_dim]: ``x | B | C`` BEFORE the convolution, in the
    compute dtype (what the convolution's tail keeps); dt [..., H]
    float32: the step size, ``softplus`` taken; g [..., H]: the log of
    the decay, ``-exp(A_log) dt`` <= 0)."""
    W = m.ssm_width
    zxbcdt = _dot(h, p["in_proj"]["kernel"], m.dtype)
    if m.ssm_col_mults is not None:
        zxbcdt = zxbcdt * _mup(m)
    z, xBC, dt = (zxbcdt[..., :W], zxbcdt[..., W:W + m.conv_dim],
                  zxbcdt[..., W + m.conv_dim:])
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * dt
    return z, xBC.astype(m.dtype), dt, g


def ssm_conv(window, p, m: Mamba2Sizes):
    """The short convolution's output for positions whose ``conv_size``
    input rows are ``window[i]`` (a list of ``[..., conv_dim]`` arrays,
    oldest first): SiLU of the depthwise sum plus the bias, in float32,
    split into ``x`` [..., H, P], ``B`` and ``C`` [..., G, N]."""
    taps = p["conv_w"].astype(jnp.float32)               # [conv, conv_dim]
    y = sum(taps[i] * window[i].astype(jnp.float32)
            for i in range(m.conv_size))
    y = jax.nn.silu(y + p["conv_b"].astype(jnp.float32))
    W, bc = m.ssm_width, m.bc_width
    lead = y.shape[:-1]
    return (y[..., :W].reshape(lead + (m.ssm_heads, m.ssm_head_dim)),
            y[..., W:W + bc].reshape(lead + (m.ssm_groups, m.ssm_state)),
            y[..., W + bc:].reshape(lead + (m.ssm_groups, m.ssm_state)))


def per_head(a, m: Mamba2Sizes):
    """``B`` or ``C`` [..., G, N] -> [..., H, N]: head ``h`` reads
    group ``h // (H / G)``."""
    return jnp.repeat(a, m.ssm_heads // m.ssm_groups, axis=-2)


def ssm_out(y, z, p, m: Mamba2Sizes):
    """``y`` [..., H, P] float32 (the skip added) and the gate ``z``
    [..., W] -> the branch's part of the residual [..., d]: the gate,
    then an RMSNorm a GROUP of ``W / ssm_groups`` channels with one
    learned weight a channel, the output projection, its multiplier
    (``ssm_out_mult``, where the model has one)."""
    lead = y.shape[:-2]
    y = y.reshape(lead + (-1,)) * jax.nn.silu(z)
    y = y.reshape(lead + (m.ssm_groups, -1))
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + m.eps)
    y = y.reshape(lead + (-1,)) * p["ssm_norm"].astype(jnp.float32)
    out = _dot(y, p["out_proj"]["kernel"], m.dtype)
    return out if m.ssm_out_mult == 1.0 else out * m.ssm_out_mult


def ssm_step(S, x, B, C, dt, g, D):
    """The recurrence, one token a lane, in plain XLA float32: the
    fallback where :func:`state_kernel` is false, and the oracle the
    kernel (:func:`ssm_step_pallas`) is tested against. ``S`` [B, H,
    P, N], ``x`` [B, H, P], ``B`` ``C`` [B, H, N] (per head), ``dt``
    ``g`` [B, H], ``D`` [H]. Returns ``(S', y [B, H, P])``. ``y = S' C
    + D x`` is taken from the state BEFORE the step, ``a (S C) + dt x
    (B . C)``: the sum and the update both read the old state, and the
    caller's select against the old state for an inactive lane reads it
    a third time: three passes where the kernel has two, 55.6% of the
    state's bandwidth bound on a v5e."""
    a = jnp.exp(g)
    dx = dt[..., None] * x
    y = a[..., None] * jnp.sum(S * C[..., None, :], axis=-1) \
        + dx * jnp.sum(B * C, axis=-1)[..., None] + D[:, None] * x
    return S * a[..., None, None] + dx[..., None] * B[..., None, :], y


def lane_heads(m: Mamba2Sizes) -> int:
    """How a layer's state LIES, by the sizes alone (the same on every
    platform): 0 where the entry is ``[H, P, N]``, ``N`` on lanes (a
    row of two lane tiles or more, and every shape no lane tile fits);
    ``k`` >= 1 where a row of state is ONE lane tile (``ssm_state``
    128) and the entry is held ``N``-MAJOR with ``k`` heads side by
    side on lanes, ``[H / k, N, k P]``: ``k = 128 / P`` heads fill the
    128 lanes (granite-4.0-h-small: two heads of 64), one head where
    ``P`` is whole lane tiles itself. The ``k`` heads of a row share a
    group. :func:`state_entry` / :func:`state_heads` turn one layout
    into the other; :func:`state_kernel` names each layout's kernel."""
    P = m.ssm_head_dim
    k = max(1, 128 // P)
    if m.ssm_state != 128 or (k * P) % 128 \
            or (m.ssm_heads // m.ssm_groups) % k:
        return 0
    return k


def state_shape(m: Mamba2Sizes) -> Tuple[int, int, int]:
    """A slot's state as the entry holds it (:func:`lane_heads`)."""
    H, P, N = m.ssm_heads, m.ssm_head_dim, m.ssm_state
    k = lane_heads(m)
    return (H // k, N, k * P) if k else (H, P, N)


def state_entry(S, m: Mamba2Sizes):
    """``S`` [..., H, P, N] -> as the entry holds it: itself, or
    ``[..., H / k, N, k P]`` (:func:`lane_heads`)."""
    k = lane_heads(m)
    if not k:
        return S
    lead, (H, P, N) = S.shape[:-3], S.shape[-3:]
    return jnp.moveaxis(S.reshape(lead + (H // k, k, P, N)), -1, -3) \
        .reshape(lead + (H // k, N, k * P))


def state_heads(S, m: Mamba2Sizes):
    """The entry's layout -> ``[..., H, P, N]``, what :func:`ssm_step`
    and the chunked form speak: :func:`state_entry`'s inverse."""
    k = lane_heads(m)
    if not k:
        return S
    lead, (R, N, W) = S.shape[:-3], S.shape[-3:]
    return jnp.moveaxis(S.reshape(lead + (R, N, k, W // k)), -3, -1) \
        .reshape(lead + (R * k, W // k, N))


def state_kernel(m: Mamba2Sizes) -> bool:
    """Whether the step's recurrence is a Pallas kernel: compiled for a
    TPU wherever Mosaic can address a block of the entry as whole tiles
    of the state dtype, any shape interpreted off it; elsewhere
    :func:`ssm_step`. WHICH kernel follows the entry's layout
    (:func:`lane_heads`), by the row's width:

    - two lane tiles a row or more (``ssm_state`` a multiple of 256),
      the entry ``[H, P, N]``: :func:`ssm_step_pallas`, with ``P`` on
      sublanes (``ssm_head_dim`` a multiple of 8 in float32, 16 in
      bfloat16). It pays one cross-lane sum and two lane broadcasts a
      ROW TILE: at ``[128, 256]`` heads a pair of vregs shares them and
      they hide under the block's copies (78% of the state's bandwidth
      bound where the XLA body reads 56: PERF.md section 6, PR 53).
    - ONE lane tile a row (``ssm_state`` 128), the entry ``N``-major
      with the heads side by side on lanes:
      :func:`ssm_step_pallas_nmajor`, whose blocks are whole tiles in
      either state dtype. In the other layout every vreg of such a
      state is a row tile of its own and THAT kernel is bound by its
      own arithmetic (ALONE at ``[64, 128]`` heads, nine layers of 127
      live lanes, 2.58 ms a layer where the same blocks copied take
      1.65 and the XLA body 2.41: PERF.md section 6, PR 55); held
      ``N``-major the sum over ``N`` adds whole vregs and the per-head
      factors are rows (PERF.md section 6, PR 56).

    By what the program can see of its own shapes; no knob."""
    from .._private.chip import pallas_interpret

    rows = 32 // jnp.dtype(m.state_dtype).itemsize
    return pallas_interpret() or bool(lane_heads(m)) or (
        m.ssm_state % 256 == 0 and m.ssm_head_dim % rows == 0)


def block_heads(heads: int, groups: int, head_bytes: int) -> int:
    """Heads a block of the kernel: the most, up to
    :data:`_SSM_BLOCK_BYTES` of state at ``head_bytes`` a head, that
    divide the heads into blocks of whole groups or blocks inside one
    group (a block then names its ``B`` and ``C`` by a block of
    groups)."""
    per_group = heads // groups
    cap = max(1, _SSM_BLOCK_BYTES // head_bytes)
    return max(d for d in range(1, min(heads, cap) + 1)
               if heads % d == 0
               and (d % per_group == 0 or per_group % d == 0))


def ssm_step_pallas(state, x, B, C, dt, g, D, active):
    """:func:`ssm_step` on a layer's whole per-slot entry ``state``
    [1, slots, H, P, N], IN PLACE, as one kernel that reads a live
    lane's state once and writes it once. ``x`` [B, H, P], ``B`` ``C``
    [B, G, N] (per GROUP: the kernel reads a group's once for all its
    heads), ``dt`` ``g`` [B, H], ``D`` [H] float32, ``active`` [B]
    bool. Returns ``(state', y [B, H, P])`` with zeros in ``y`` for an
    inactive lane, whose state no byte of is moved.

    Grid ``(B, H / hb)``: step ``(i, j)`` is block ``j``
    (:func:`block_heads` heads, ``[hb, P, N]``) of the ``i``-th LIVE
    lane, named by the scalar-prefetched
    :func:`ray_tpu.models.serving.live_lanes`; the steps past the last
    live lane name the block before them again, which the pipeline
    neither fetches nor writes twice, and do nothing. The state is the
    kernel's input AND output (``input_output_aliases`` on the whole
    entry): a block comes into VMEM, and the sum ``S C``, the decay
    ``a S``, the rank-one term ``dt x (x) B`` and ``y`` are taken from
    that one copy in float32 on the VPU, operation for operation
    :func:`ssm_step`'s, and the block goes back where it came from.
    The state lies as the entry holds it, ``N`` on lanes: the sum over
    it is a cross-lane reduction of a head's 16 row tiles, which hides
    under the block's copies (PERF.md section 6, PR 53: within 1.5% of
    an entry held ``[N, P]``, whose sum adds whole vregs). The small
    operands are laid out by the caller's XLA, under the same scope:
    ``dt x``, ``a``, ``B . C`` and ``D x`` TRANSPOSED ``[B, H / hb, P,
    4 hb]`` so that a head's is a column over ``P`` (one lane,
    broadcast over ``N``) and so is its ``y``; ``B`` and ``C`` as rows
    over ``N``, a group's for all its heads. Where no lane is live the
    one block named is copied through, so that the aliased entry comes
    out as it went in."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .._private.chip import pallas_interpret

    Bn, H, P = x.shape
    G, N = B.shape[1:]
    hb = block_heads(H, G, P * N * state.dtype.itemsize)
    nb, per_group = H // hb, H // G
    gb = max(1, hb // per_group)             # groups a block reads
    a = jnp.exp(g)
    dx = dt[..., None] * x
    cols = jnp.stack([
        dx, jnp.broadcast_to(a[..., None], x.shape),
        jnp.broadcast_to(jnp.repeat(jnp.sum(B * C, axis=-1), per_group,
                                    axis=-1)[..., None], x.shape),
        D[:, None] * x], axis=2)                             # [B, H, 4, P]
    cols = cols.reshape(Bn, nb, hb, 4, P).transpose(0, 1, 4, 3, 2) \
        .reshape(Bn, nb, P, 4 * hb)
    rows = jnp.stack([B, C], axis=2)                         # [B, G, 2, N]
    lanes, n = serving.live_lanes(active)

    def kernel(lanes_ref, n_ref, s_ref, cols_ref, rows_ref, s_out, y_ref):
        i, j = pl.program_id(0), pl.program_id(1)

        @pl.when(i < n_ref[0])
        def _():
            c = cols_ref[...]                                # [P, 4 hb]
            for h in range(hb):
                S = s_ref[h].astype(jnp.float32)             # [P, N]
                Bh, Ch = (rows_ref[h // per_group, m:m + 1]
                          for m in range(2))                 # [1, N]
                dxh, ah, bc, Dx = (c[:, m * hb + h:m * hb + h + 1]
                                   for m in range(4))        # [P, 1]
                y_ref[:, h:h + 1] = ah * jnp.sum(
                    S * Ch, axis=-1, keepdims=True) + dxh * bc + Dx
                s_out[h] = (S * ah + dxh * Bh).astype(s_out.dtype)

        @pl.when((n_ref[0] == 0) & (i == 0) & (j == 0))
        def _():
            s_out[...] = s_ref[...]

    def at(*lead, groups=False):
        """The index map of an operand whose leading indices are
        ``lead`` (statics), then the lane and the block of heads (or
        the block of groups that holds those heads), then two whole
        dimensions."""
        def index(i, j, lanes_ref, n_ref):
            j = jnp.where(i < n_ref[0], j, nb - 1)
            if groups and hb < per_group:
                j = j * hb // per_group
            return lead + (lanes_ref[i], j, 0, 0)
        return index

    state, y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Bn, nb),
            in_specs=[
                pl.BlockSpec((None, None, hb, P, N), at(0)),
                pl.BlockSpec((None, None, P, 4 * hb), at()),
                pl.BlockSpec((None, gb, 2, N), at(groups=True)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, hb, P, N), at(0)),
                pl.BlockSpec((None, None, P, hb), at()),
            ]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((Bn, nb, P, hb), jnp.float32)],
        # operand 2 (after the two scalar operands) is the state
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name="ssm_state",
    )(lanes, n, state, cols, rows)
    y = y.transpose(0, 1, 3, 2).reshape(Bn, H, P)
    return state, jnp.where(active[:, None, None], y, 0.0)


def ssm_step_pallas_nmajor(state, x, B, C, dt, g, D, active):
    """:func:`ssm_step_pallas` for an entry held ``N``-MAJOR
    (:func:`lane_heads`): ``state`` [1, slots, H / k, N, k P], ``k``
    heads side by side on lanes, a row of state ONE lane tile. The
    other operands, the result, the grid over the live lanes' blocks,
    the aliased entry and what happens where no lane is live are that
    kernel's; the two share :func:`block_heads` (here in ROWS of ``k``
    heads, ``[N, k P]`` each) and :func:`ray_tpu.models.serving.
    live_lanes`, and no line of their bodies, because every operand
    lies the other way round:

    - the sum ``S C`` runs over SUBLANES and row tiles: ``S`` times
      ``C`` laid along ``N`` in every lane, then whole vregs added over
      the row tiles and ONE sublane reduction a row of heads (``[P,
      N]``: a cross-lane reduction a vreg);
    - a head's ``a``, ``dt x`` and ``dt x (B . C) + D x`` are ROWS
      over its ``P`` lanes (``[3, hp, k P]`` a block), broadcast over
      sublanes once a row of heads (``[P, N]``: a lane broadcast a
      vreg), and ``y`` leaves as a lane-dense row;
    - ``B`` and ``C`` come laid along ``N`` in every lane, ``[G, 2, N,
      k P]`` by the caller's XLA under the same scope (128 KiB a lane a
      group beside 8 MiB of state in and out), fetched once a lane.

    Some five VPU operations a vreg of state and nothing on the XLU but
    that one reduction in sixteen: the kernel runs at its copies' pace
    (PERF.md section 6, PR 56)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .._private.chip import pallas_interpret

    Bn, H, P = x.shape
    G, N = B.shape[1:]
    R, W = state.shape[2], state.shape[4]    # rows of k heads, [N, W] each
    hp = block_heads(R, G, N * W * state.dtype.itemsize)
    nb, per_group = R // hp, R // G
    gb = max(1, hp // per_group)             # groups a block reads
    a = jnp.exp(g)
    dx = dt[..., None] * x
    rest = dx * jnp.repeat(jnp.sum(B * C, axis=-1), H // G,
                           axis=-1)[..., None] + D[:, None] * x
    rows = jnp.stack([r.reshape(Bn, nb, hp, W) for r in (
        jnp.broadcast_to(a[..., None], x.shape), dx, rest)],
        axis=2)                                      # [B, nb, 3, hp, W]
    along = jnp.stack([B, C], axis=2)                        # [B, G, 2, N]
    along = jnp.broadcast_to(along[..., None], along.shape + (W,))
    lanes, n = serving.live_lanes(active)

    def kernel(lanes_ref, n_ref, s_ref, rows_ref, along_ref, s_out, y_ref):
        i, j = pl.program_id(0), pl.program_id(1)

        @pl.when(i < n_ref[0])
        def _():
            for h in range(hp):
                S = s_ref[h].astype(jnp.float32)             # [N, W]
                Bh, Ch = (along_ref[h // per_group, m]
                          for m in range(2))                 # [N, W]
                ah, dxh, rh = (rows_ref[m, h:h + 1]
                               for m in range(3))            # [1, W]
                y_ref[h:h + 1] = ah * jnp.sum(
                    S * Ch, axis=0, keepdims=True) + rh
                s_out[h] = (S * ah + Bh * dxh).astype(s_out.dtype)

        @pl.when((n_ref[0] == 0) & (i == 0) & (j == 0))
        def _():
            s_out[...] = s_ref[...]

    def at(*lead, tail, groups=False):
        """The index map of an operand whose leading indices are
        ``lead`` (statics), then the lane and the block of rows (or the
        block of groups that holds those rows), then ``tail`` whole
        dimensions."""
        def index(i, j, lanes_ref, n_ref):
            j = jnp.where(i < n_ref[0], j, nb - 1)
            if groups and hp < per_group:
                j = j * hp // per_group
            return lead + (lanes_ref[i], j) + (0,) * tail
        return index

    state, y = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(Bn, nb),
            in_specs=[
                pl.BlockSpec((None, None, hp, N, W), at(0, tail=2)),
                pl.BlockSpec((None, None, 3, hp, W), at(tail=3)),
                pl.BlockSpec((None, gb, 2, N, W), at(tail=3, groups=True)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, hp, N, W), at(0, tail=2)),
                pl.BlockSpec((None, None, hp, W), at(tail=2)),
            ]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((Bn, nb, hp, W), jnp.float32)],
        # operand 2 (after the two scalar operands) is the state
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=pallas_interpret(),
        name="ssm_state",
    )(lanes, n, state, rows, along)
    return state, jnp.where(active[:, None, None], y.reshape(Bn, H, P), 0.0)


def ssd_chunked(x, B, C, dt, g, S0, chunk: int):
    """The same recurrence over a whole sequence in chunks (the SSD
    form): ``x`` [T, H, P], ``B`` ``C`` [T, H, N] (per head), ``dt``
    ``g`` [T, H], ``S0`` [H, P, N], float32; ``T`` a multiple of
    ``chunk``. Returns ``(y [T, H, P]`` without the skip, ``S_T)``.
    Rows with ``dt = 0`` and ``g = 0`` leave the state as it is (a
    prompt's padding).

    Within a chunk, with ``G_t`` the running sum of ``g`` from the
    chunk's start and ``S`` the state before it: ``y_t = e^{G_t} S C_t
    + sum_{s <= t} e^{G_t - G_s} (C_t . B_s) dt_s x_s`` (the masked
    quadratic form) and the state after the chunk is ``e^{G_C} S +
    sum_s e^{G_C - G_s} dt_s x_s (x) B_s``. Every exponent taken is
    <= 0."""
    T = x.shape[0]
    Cn = chunk
    N = T // Cn

    def chunks(a):                           # [T, H, .] -> [N, C, H, .]
        return a.reshape((N, Cn) + a.shape[1:])

    lower = jnp.tril(jnp.ones((Cn, Cn), jnp.bool_))

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HI,
                          preferred_element_type=jnp.float32)

    def one(S, xs):
        x, B, C, dt, g = xs                  # [C, H, .]; dt, g [C, H]
        G = jnp.cumsum(g, axis=0)
        L = jnp.exp(jnp.where(lower[:, :, None],
                              G[:, None] - G[None], -jnp.inf))  # [t, s, H]
        xdt = x * dt[..., None]
        y = mm("tsh,shp->thp", mm("thn,shn->tsh", C, B) * L, xdt) \
            + mm("thn,hpn->thp", C, S) * jnp.exp(G)[..., None]
        last = G[-1]                                              # [H]
        S = jnp.exp(last)[:, None, None] * S + mm(
            "shp,shn->hpn", xdt * jnp.exp(last[None] - G)[..., None], B)
        return S, y

    S, y = lax.scan(one, S0, (chunks(x), chunks(B), chunks(C),
                              chunks(dt), chunks(g)))
    return y.reshape((T,) + y.shape[2:]), S


def ssm_mix(xBC, dt, g, p, m: Mamba2Sizes, live):
    """What of the SSM mixer is ONE sequence's, from a zero state: the
    convolution over its ``xBC`` [S, conv_dim] and the chunked SSD form
    (``dt`` ``g`` [S, H]; ``live`` [S] bool: rows past the prompt
    advance nothing). Returns ``(y [S, H, P] float32 with the skip,
    S_end [H, P, N], padded [conv_size - 1 + S, conv_dim]: ``xBC``
    behind the zero rows that stand before the sequence's start)``."""
    S = xBC.shape[0]
    with jax.named_scope("ssm.proj"):
        back = m.conv_size - 1
        padded = jnp.concatenate(
            [jnp.zeros((back, xBC.shape[-1]), xBC.dtype), xBC])
        x, B, C = ssm_conv([padded[i:i + S] for i in range(m.conv_size)],
                           p, m)
        dt = jnp.where(live[:, None], dt, 0.0)
        g = jnp.where(live[:, None], g, 0.0)
    with jax.named_scope("ssm.prefill"):
        Cn = min(m.ssm_chunk, S)
        pad = -S % Cn
        ops = (x, per_head(B, m), per_head(C, m), dt, g)
        if pad:
            ops = tuple(jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) for a in ops)
        y, S_end = ssd_chunked(*ops, jnp.zeros(
            (m.ssm_heads, m.ssm_head_dim, m.ssm_state), jnp.float32),
            Cn)
        y = y[:S] + p["D_skip"].astype(jnp.float32)[:, None] * x
    return y, S_end, padded


def ssm_sequence(h, p, m: Mamba2Sizes, live):
    """The SSM mixer over one whole sequence from a zero state: ``h``
    [S, d] (normed), ``live`` [S] bool (rows past the prompt advance
    nothing). Returns ``(out [S, d] float32, S_end [H, P, N], padded
    [conv_size - 1 + S, conv_dim]: ``xBC`` before the convolution
    behind the zero rows that stand before the sequence's start)``."""
    with jax.named_scope("ssm.proj"):
        z, xBC, dt, g = ssm_proj(h, p, m)
    y, S_end, padded = ssm_mix(xBC, dt, g, p, m, live)
    with jax.named_scope("ssm.proj"):
        out = ssm_out(y, z, p, m)
    return out, S_end, padded


def ssm_decode(h, p, m: Mamba2Sizes, state, conv, active, kernel: bool):
    """One decode step of the SSM mixer on a layer's per-slot entries:
    ``h`` [B, d] (normed), ``state`` [1, slots, *:func:`state_shape`]
    and ``conv`` [1, slots, conv_size - 1, conv_dim]
    (:func:`slot_entries`), ``active`` [B] bool. Every active lane
    reads and writes its state and its convolution tail whole, the
    state through its layout's kernel (:func:`ssm_step_pallas`, or
    :func:`ssm_step_pallas_nmajor` where :func:`lane_heads`) where
    ``kernel`` (the caller's :func:`state_kernel`) and through
    :func:`ssm_step` elsewhere, which reads either layout as ``[H, P,
    N]`` (:func:`state_heads`); an inactive lane's come out as they
    went in. Returns ``(out [B, d] float32,
    state', conv')``; scopes ``ssm.proj`` and ``ssm.state``."""
    with jax.named_scope("ssm.proj"):
        z, xBC, dt, g = ssm_proj(h, p, m)
        tail = conv[0]                             # [B, back, conv_dim]
        window = [tail[:, i] for i in range(tail.shape[1])] + [xBC]
        xs, Bs, Cs = ssm_conv(window, p, m)
        conv = jnp.where(
            active[:, None, None], jnp.stack(window[1:], axis=1),
            tail)[None]
    with jax.named_scope("ssm.state"):
        D = p["D_skip"].astype(jnp.float32)
        if kernel:
            step = ssm_step_pallas_nmajor if lane_heads(m) \
                else ssm_step_pallas
            state, y = step(state, xs, Bs, Cs, dt, g, D, active)
        else:
            S = state_heads(state[0], m).astype(jnp.float32)
            S_new, y = ssm_step(S, xs, per_head(Bs, m), per_head(Cs, m),
                                dt, g, D)
            state = state_entry(
                jnp.where(active[:, None, None, None], S_new, S), m
            ).astype(state.dtype)[None]
    with jax.named_scope("ssm.proj"):
        return ssm_out(y, z, p, m), state, conv


# the names this module's tests, and ``tests/perf``'s, go by
_ssm_proj, _ssm_step, _ssm_step_pallas = ssm_proj, ssm_step, ssm_step_pallas
_ssd_chunked, _state_kernel, _block_heads = ssd_chunked, state_kernel, \
    block_heads


def forward(params: Params, tokens: jax.Array, cfg: SSMHybridConfig
            ) -> jax.Array:
    """tokens [B, S] -> float32 logits [B, S, rows]: each sequence
    whole, no cache (the chunked SSD form from a zero state, causal
    rotary attention), one sequence at a time."""
    S = tokens.shape[1]
    live = jnp.ones((S,), jnp.bool_)
    positions = jnp.arange(S)

    def row(toks):
        x = _embed(params, toks, cfg)
        for p in params["layers"]:
            h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
            q, k, v = _attn_qkv(h, p, positions, cfg)
            x = _mlp(x + ssm_sequence(h, p, cfg, live)[0]
                     + _attn_out(_attn_causal(q, k, v, cfg), p, cfg), p, cfg)
        return _head(x, params, cfg)

    return lax.map(row, tokens)


# ----------------------------------------------------------- description
def slot_entry(name: str, layer: int) -> str:
    """The pool's key of a layer's per-slot entry (``state3``)."""
    return f"{name}{layer}"


def _put(pool, rows, *start):
    """``rows`` written at ``pool[start]`` in place (the leading
    indices; a traced slot among them)."""
    lead = len(start)
    return lax.dynamic_update_slice(
        pool, rows.astype(pool.dtype)[(None,) * lead],
        tuple(start) + (0,) * (pool.ndim - lead))


def slot_entries(m: Mamba2Sizes, layer: int
                 ) -> Tuple[CacheEntry, CacheEntry]:
    """The two per-slot entries of ONE layer's mixer (shared): the
    state in the state dtype (``state<layer>``), ``[H, P, N]`` or,
    where a row of it is one lane tile, ``N``-major with ``k`` heads
    side by side on lanes, ``[H / k, N, k P]`` (:func:`state_shape`:
    ONE layout a shape, the one its kernel reads), and the
    convolution's last ``conv_size - 1`` input rows ``[conv - 1,
    conv_dim]`` in the compute dtype (``conv<layer>``), each an array
    of its own (:func:`cache_spec` says why)."""
    return (CacheEntry(slot_entry("state", layer), "slot",
                       state_shape(m), m.state_dtype, 1),
            CacheEntry(slot_entry("conv", layer), "slot",
                       (m.conv_size - 1, m.conv_dim), m.dtype, 1))


def put_slot(m: Mamba2Sizes, state, conv, S_end, padded, length, slot):
    """A prefill's write into a layer's per-slot entries (shared):
    ``S_end`` [H, P, N], laid as the entry holds it
    (:func:`state_entry`), over slot ``slot`` of ``state`` and, over
    the slot's ``conv``, the ``conv_size - 1`` rows of ``padded``
    (:func:`ssm_mix`) that end at the prompt's LAST token (row
    ``length`` on of the padded rows). Returns ``(state', conv')``."""
    back = conv.shape[2]
    return _put(state, state_entry(S_end, m), 0, slot), _put(
        conv, lax.dynamic_slice(
            padded, (length, 0), (back, padded.shape[1])), 0, slot)


def cache_spec(cfg: SSMHybridConfig, kv_dtype: str = "fp") -> CacheSpec:
    """What a token leaves in a page (rotated keys, and values, ``[Hkv,
    hd]`` each, every layer: the pools ``[L, n_pages, page_size, Hkv,
    hd]``) and what a sequence keeps in its SLOT, every layer: the
    state ``[H, P, N]`` in the state dtype and the convolution's last
    ``conv_size - 1`` input rows ``[conv - 1, conv_dim]`` in the compute
    dtype, as entries of ONE layer each (:func:`slot_entry`:
    ``state<l>`` ``[1, slots, H, P, N]``, ``conv<l>`` ``[1, slots, conv
    - 1, conv_dim]``).

    One array a layer, not one stacked over the layers: a decode step
    then updates a layer's state as a whole array in place, and no
    layer's update is a slice written into an array that a LATER layer
    still reads. Stacked (``[L, slots, ...]``, each layer a
    ``dynamic_update_slice`` of the last layer's result), every
    update's result had two readers, the next layer's read of its own
    slice and the next layer's update, and at the cell's size (14 GB of
    arguments, the pool donated) XLA for the TPU rematerialised the
    first layer's in-place update behind the second reader ON THE
    BUFFER IT HAD ALREADY OVERWRITTEN: that layer's state advanced
    twice a step, at 128 slots and not at 8 (PERF.md section 6, PR
    52)."""
    serving.check_kv_dtype(_THIS, kv_dtype)
    row = (cfg.n_kv_head, cfg.head_dim)
    return CacheSpec(cfg.n_layer, (
        CacheEntry("k", "token", row, cfg.dtype),
        CacheEntry("v", "token", row, cfg.dtype),
        *(e for l in range(cfg.n_layer) for e in slot_entries(cfg, l))))


def max_positions(cfg: SSMHybridConfig) -> int:
    """The positions the rotary is declared to reach."""
    return cfg.max_seq


def _gqa_kernel(cfg: SSMHybridConfig, page_size: int) -> bool:
    """:func:`ray_tpu.models.kda_moe.gqa_kernel` at this model's heads
    and compute dtype: whether decode's attention is the kernel over
    each lane's live pages (the other reading is the gather over its
    whole table row)."""
    return kda_moe.gqa_kernel(cfg.n_kv_head, cfg.head_dim, cfg.dtype,
                              page_size)


def decode_attention_fused(cfg: SSMHybridConfig, page_size: int,
                           attn_kernel: str = "gather") -> bool:
    """Whether the chunk program built with these knobs holds a Pallas
    kernel (the description's entry, :mod:`ray_tpu.models.serving`).
    This model has TWO, each taken by what the program can see of its
    own shapes: its ATTENTION over pages
    (:func:`ray_tpu.models.kda_moe.gqa_kernel`: from the page and the
    head) and the RECURRENCE on the per-slot state
    (:func:`ssm_step_pallas` or :func:`ssm_step_pallas_nmajor`,
    :func:`state_kernel`: from the state's head); the answer is for
    the program, so either one makes it true.
    ``attn_kernel`` (one value) has no say."""
    return _state_kernel(cfg) or _gqa_kernel(cfg, page_size)


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
                            rng: jax.Array, *, cfg: SSMHybridConfig,
                            page_size: int, temperature: float = 0.0,
                            kv_dtype: str = "fp"
                            ) -> Tuple[jax.Array, Cache, jax.Array]:
    """Prefill one WHOLE prompt into its pages and its slot, with the
    first token's sample: the frame of
    :func:`ray_tpu.models.gpt_decode.prefill_into_slot_paged`. Every
    layer's rotated keys and values go to the pages ``pt_row`` names,
    and every layer's state and convolution tail are rebuilt FROM ZERO
    and written over whatever slot ``slot`` held: a prefill is the one
    way a slot's state begins. Rows past ``length`` (the bucket's
    padding) write no page and advance neither state nor tail: the
    slot holds what the prompt's LAST token left. ``hist_len`` and
    ``cow_src`` are the frame's and have no meaning here: without a
    prefix cache (:data:`UNSUPPORTED`) the engine's are always 0 and
    the sentinel."""
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
    for l, p in enumerate(params["layers"]):
        h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
        q, k, v = _attn_qkv(h, p, wpos, cfg)
        with jax.named_scope("hgqa.prefill"):
            att = _attn_causal(q, k, v, cfg)
        at = (at_layer(page_w, l, n_pages), wpos % ps)
        kpool = kpool.at[at].set(k, mode="drop")
        vpool = vpool.at[at].set(v, mode="drop")
        y, S_end, padded = ssm_sequence(h, p, cfg, live)
        state, conv = slot_entry("state", l), slot_entry("conv", l)
        slots[state], slots[conv] = put_slot(
            cfg, cache[state], cache[conv], S_end, padded, length, slot)
        x = _mlp(x + y + _attn_out(att, p, cfg), p, cfg)
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
                                   cfg: SSMHybridConfig, page_size: int,
                                   temperature: float = 0.0,
                                   kv_dtype: str = "fp"
                                   ) -> Tuple[jax.Array, Cache, jax.Array]:
    """:func:`prefill_into_slot_paged` for the ``G`` prompts of one
    chunk boundary in ONE launch (the frame's contract,
    ``models/serving.py``): both branches' projections, the MLP and the
    head (its 261k rows read once) run over all the prompts' rows at
    once (:class:`ray_tpu.models.serving.PromptRows`); each prompt's
    causal attention, its convolution and its chunked recurrence from a
    zero state are the single prefill's on its own rows
    (:func:`_attn_causal`, :func:`ssm_mix`), and each lands in its own
    pages and its own slot."""
    rows = serving.PromptRows(tokens, length, jnp.zeros_like(length))
    del hist_len, cow_src
    n_pages = cache["k"].shape[1]
    x = _embed(params, rows.tokens, cfg)                    # [R, d]
    live = rows.split(rows.live)
    page_w, off = rows.pages(pt_row, page_size)
    kpool, vpool = flat(cache["k"]), flat(cache["v"])
    slots = {}
    back = cfg.conv_size - 1
    for l, p in enumerate(params["layers"]):
        h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
        q, k, v = _attn_qkv(h, p, rows.positions, cfg)
        with jax.named_scope("hgqa.prefill"):
            att = jnp.concatenate([
                _attn_causal(qg, kg, vg, cfg) for qg, kg, vg in zip(
                    rows.split(q), rows.split(k), rows.split(v))])
        at = (at_layer(page_w, l, n_pages), off)
        kpool = kpool.at[at].set(k, mode="drop")
        vpool = vpool.at[at].set(v, mode="drop")
        with jax.named_scope("ssm.proj"):
            z, xBC, dt, g = ssm_proj(h, p, cfg)
        state, conv = slot_entry("state", l), slot_entry("conv", l)
        slots[state], slots[conv] = cache[state], cache[conv]
        ys = []
        for i, (xBC_i, dt_i, g_i) in enumerate(zip(
                rows.split(xBC), rows.split(dt), rows.split(g))):
            y, S_end, padded = ssm_mix(xBC_i, dt_i, g_i, p, cfg, live[i])
            ys.append(y)
            # put_slot's two writes, spelled out: the order in which
            # ``slot[i]`` and ``length[i]`` are sliced is the lowered
            # text's, held to the parent's (tests/test_models_frame.py)
            slots[state] = _put(slots[state], state_entry(S_end, cfg), 0,
                                slot[i])
            slots[conv] = _put(slots[conv], lax.dynamic_slice(
                padded, (length[i], 0), (back, padded.shape[1])), 0,
                slot[i])
        with jax.named_scope("ssm.proj"):
            y = ssm_out(jnp.concatenate(ys), z, p, cfg)
        x = _mlp(x + y + _attn_out(att, p, cfg), p, cfg)
    token, rng = serving.sample_slots(_head(x[rows.last], params, cfg),
                                      temperature, rng)
    return token, {"k": kpool.reshape(cache["k"].shape),
                   "v": vpool.reshape(cache["v"].shape),
                   **slots,
                   "pos": cache["pos"].at[slot].set(
                       length.astype(jnp.int32))}, rng


def _slot_decode_step_paged(params: Params, cache: Cache,
                            token: jax.Array, active: jax.Array,
                            pt: jax.Array, cfg: SSMHybridConfig,
                            page_size: int, kv_dtype: str = "fp",
                            attn_kernel: str = "gather"):
    """One masked decode step over the whole slot pool: in every layer
    each active lane writes its rotated key and its value at its own
    position and attends over its pages
    (:func:`ray_tpu.models.kda_moe.gqa_decode_attention`: the kernel
    over its live pages or the gather over its whole table row, by
    shape), AND reads and writes its state and convolution tail whole
    (:func:`ssm_decode`: the state through :func:`ssm_step_pallas` or
    :func:`ssm_step`, by shape: :func:`state_kernel`). An inactive lane
    (idle, or parked for pages) neither writes nor advances: its state
    and tail come out as they went in. Returns
    ``(logits [B, rows], cache', counts)``: int32 [2]
    (:data:`STEP_COUNTERS`)."""
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
    length, fetched = kda_moe.gqa_decode_reads(
        pt, pos, active, n_pages, ps, _gqa_kernel(cfg, ps))
    # the step's own scope: a reader tells the decode program's state,
    # attention, MLP and head time from prefill's by it
    with jax.named_scope("decode_step"):
        for l, p in enumerate(params["layers"]):
            h = rmsnorm(x, p["ln1_scale"], cfg.eps, cfg.dtype)
            q, k, v = _attn_qkv(h, p, pos, cfg)
            at = (at_layer(page_w, l, n_pages), pos % ps)
            kpool = kpool.at[at].set(k, mode="drop")
            vpool = vpool.at[at].set(v, mode="drop")
            with jax.named_scope("hgqa.attention"):
                att = kda_moe.gqa_decode_attention(
                    q, kpool, vpool, ptc + l * n_pages, pos, length,
                    n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
                    head_dim=cfg.head_dim, dtype=cfg.dtype, page_size=ps)
            state, conv = slot_entry("state", l), slot_entry("conv", l)
            y, slots[state], slots[conv] = ssm_decode(
                h, p, cfg, cache[state], cache[conv], active,
                _state_kernel(cfg))
            x = _mlp(x + y + _attn_out(att, p, cfg), p, cfg)
        logits = _head(x, params, cfg)
    cache_out = {"k": kpool.reshape(cache["k"].shape),
                 "v": vpool.reshape(cache["v"].shape), **slots,
                 "pos": pos + active.astype(jnp.int32)}
    counts = jnp.stack([jnp.sum(active, dtype=jnp.int32),
                        cfg.n_layer * fetched])
    return logits, cache_out, counts


# the chunk program and the two factories are the frame's, around this
# model's step and for this description (``models/serving.py``): the
# cache the scan carries is pages AND per-slot state, in every layer
decode_chunk_slots_paged = functools.partial(
    serving.decode_chunk_slots_paged, step=_slot_decode_step_paged,
    counters=len(STEP_COUNTERS))
jit_prefill_into_slot_paged = serving.bind(
    serving.jit_prefill_into_slot_paged, _THIS)
jit_decode_chunk_slots_paged = serving.bind(
    serving.jit_decode_chunk_slots_paged, _THIS)
