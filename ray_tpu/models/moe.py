"""Mixture-of-Experts FFN with expert parallelism over the ``ep`` mesh axis.

Capability parity with the reference's expert-parallel training path (the
reference reaches MoE through wrapped torch models + custom process groups;
e.g. its collective library powers DeepSpeed-MoE style all-to-alls). On TPU
the native formulation is the GShard/Switch dispatch-einsum pattern:

- a router scores tokens per expert; top-k selection with a static
  capacity C keeps shapes XLA-friendly (dropped tokens fall through the
  residual connection),
- dispatch/combine are one-hot einsums, so the token→expert shuffle is a
  pair of matmuls whose sharding (tokens over dp, experts over ``ep``)
  makes XLA insert the all-to-all on ICI automatically,
- expert FFNs are a single batched matmul over the expert dim — MXU-dense.

The [T, E, C] one-hot dispatch tensor is the classic memory cost of this
formulation; a sort-based scatter variant can replace it later without
changing the interface.

Below it: the serving-side DROPLESS expert layer (routing and dispatch
apart: three routers, one dispatch), and the block math the served
expert decoders share around it (``rmsnorm``, ``embed``, ``head``,
``block_ffn``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def capacity(tokens: int, n_experts: int, top_k: int,
             capacity_factor: float) -> int:
    """Static per-expert slot count, padded to a multiple of 8 lanes."""
    c = int(math.ceil(capacity_factor * top_k * tokens / n_experts))
    return max(8, ((c + 7) // 8) * 8)


def top_k_gating(probs: jax.Array, top_k: int, cap: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """probs [T, E] → (dispatch [T,E,C], combine [T,E,C], aux_loss scalar).

    Position assignment is first-come-first-served per expert across the
    flattened token dim; tokens past capacity are dropped (zero dispatch).
    """
    T, E = probs.shape
    gates, idx = lax.top_k(probs, top_k)                    # [T, k]
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)       # renormalize

    counts = jnp.zeros((E,), jnp.int32)
    dispatch = jnp.zeros((T, E, cap), probs.dtype)
    combine = jnp.zeros((T, E, cap), probs.dtype)
    for j in range(top_k):                                  # static k
        m = jax.nn.one_hot(idx[:, j], E, dtype=jnp.int32)   # [T, E]
        pos_in_e = jnp.cumsum(m, axis=0) - 1 + counts[None, :]
        pos = jnp.sum(pos_in_e * m, axis=-1)                # [T]
        keep = (pos < cap).astype(probs.dtype)
        slot = jax.nn.one_hot(pos, cap, dtype=probs.dtype)  # [T, C]
        d_j = (m.astype(probs.dtype) * keep[:, None])[:, :, None] \
            * slot[:, None, :]
        dispatch = dispatch + d_j
        combine = combine + gates[:, j][:, None, None] * d_j
        counts = counts + jnp.sum(m, axis=0)

    # Load-balance loss (Switch: E * sum_e f_e * p_e) on top-1 assignment.
    top1 = jax.nn.one_hot(idx[:, 0], E, dtype=probs.dtype)
    frac = jnp.mean(top1, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_prob)
    return dispatch, combine, aux


def moe_ffn(x: jax.Array, router_kernel: jax.Array, w_up: jax.Array,
            w_down: jax.Array, *, top_k: int, capacity_factor: float,
            dtype, ep_axis: Optional[str] = None, mesh=None
            ) -> Tuple[jax.Array, jax.Array]:
    """x [B,S,d] → (y [B,S,d], aux_loss).

    router_kernel [d,E]; w_up [E,d,f]; w_down [E,f,d]. Under jit with the
    expert dim sharded over ``ep`` the two dispatch einsums become
    all-to-alls over the ICI ring.
    """
    B, S, d = x.shape
    E = router_kernel.shape[-1]
    xt = x.reshape(B * S, d)
    logits = jnp.dot(xt.astype(jnp.float32),
                     router_kernel.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    cap = capacity(B * S, E, top_k, capacity_factor)
    dispatch, combine, aux = top_k_gating(probs, top_k, cap)

    def constrain(v, spec):
        if mesh is not None and ep_axis and ep_axis in mesh.axis_names:
            from jax.sharding import NamedSharding

            return lax.with_sharding_constraint(
                v, NamedSharding(mesh, spec))
        return v

    from jax.sharding import PartitionSpec as P

    xe = jnp.einsum("tec,td->ecd", dispatch.astype(dtype), xt.astype(dtype),
                    preferred_element_type=jnp.float32).astype(dtype)
    xe = constrain(xe, P(ep_axis, None, None))
    h = jnp.einsum("ecd,edf->ecf", xe, w_up.astype(dtype),
                   preferred_element_type=jnp.float32).astype(dtype)
    h = jax.nn.gelu(h)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dtype),
                    preferred_element_type=jnp.float32).astype(dtype)
    ye = constrain(ye, P(ep_axis, None, None))
    y = jnp.einsum("tec,ecd->td", combine.astype(dtype), ye,
                   preferred_element_type=jnp.float32).astype(dtype)
    return y.reshape(B, S, d), aux.astype(jnp.float32)


# ---------------------------------------------------------------- dropless
# The serving-side expert layer (ROADMAP M1, D9): nothing is dropped,
# so a token's result does not depend on who shares its batch, and the
# layer is TOLD which of the routed experts it holds (expert
# parallelism's share of one chip): it routes over all ``n_routed``
# experts and computes its own experts' part for the tokens routed to
# them. What the absent experts would add is another chip's to compute
# and is not stood in for. ROUTING and DISPATCH are apart: a router
# (``route_sigmoid``: sigmoid scores, group-limited, normalised;
# ``route_softmax_bias``: softmax scores, a selection bias, ids that may
# lie past the routed experts; ``route_topk_softmax``: the top k LOGITS,
# a softmax over the chosen ones) gives ids and weights, and ONE
# dispatch, block loop and combine (``dropless_experts``) serves them all;
# ``zero_experts`` is the part of the ids that cost nothing.

def group_limited_top_k(scores: jax.Array, n_group: int, topk_group: int,
                        top_k: int, floor: float = -1.0
                        ) -> Tuple[jax.Array, jax.Array]:
    """scores [T, E] (each in (0, 1]) -> (ids [T, k] int32, their
    scores [T, k]), best first. The E experts lie in ``n_group``
    contiguous groups; a group's score is the sum of its two highest
    scores, the ``topk_group`` best groups stay, and the ``top_k``
    highest scores among their experts are chosen. ``topk_group ==
    n_group`` is a plain top k. ``floor``: what a closed group's
    experts score, below every open one's (scores that carry a
    selection bias may lie below -1)."""
    T, E = scores.shape
    if topk_group < n_group:
        per = E // n_group
        best2 = lax.top_k(scores.reshape(T, n_group, per), 2)[0]
        _, keep = lax.top_k(best2.sum(-1), topk_group)      # [T, kg]
        kept = jnp.any(keep[:, :, None] == jnp.arange(n_group), axis=1)
        scores = jnp.where(jnp.repeat(kept, per, axis=1), scores, floor)
    chosen, ids = lax.top_k(scores, top_k)
    return ids.astype(jnp.int32), chosen


def _router_logits(x: jax.Array, router_kernel: jax.Array, dtype,
                   precision=None) -> jax.Array:
    """x [T, d] times the router [d, width] in ``dtype``, float32 sums
    (``precision``: the product's, where ``dtype`` is float32 and the
    chip would else multiply in bfloat16 passes)."""
    return lax.dot_general(x.astype(dtype), router_kernel.astype(dtype),
                           (((1,), (0,)), ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def route_sigmoid(x: jax.Array, router_kernel: jax.Array, *, n_group: int,
                  topk_group: int, top_k: int, norm_topk: bool,
                  route_scale: float, dtype,
                  bias: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """x [T, d] -> (ids [T, k], weights [T, k] float32): sigmoid scores
    over ALL routed experts (the router keeps its published width),
    group-limited selection, the chosen scores normalised to sum to one
    where ``norm_topk``, times ``route_scale``. ``bias`` [width]
    float32 (absent: none, and the program is what it was) steers the
    SELECTION only, the groups' and the experts' alike: both are chosen
    on ``score + bias``, and the weights are the chosen experts'
    UNBIASED scores."""
    s = jax.nn.sigmoid(_router_logits(x, router_kernel, dtype))
    if bias is None:
        ids, s = group_limited_top_k(s, n_group, topk_group, top_k)
    else:
        ids, _ = group_limited_top_k(s + bias.astype(jnp.float32), n_group,
                                     topk_group, top_k, floor=-jnp.inf)
        s = jnp.take_along_axis(s, ids, axis=1)
    if norm_topk:
        s = s / (jnp.sum(s, axis=-1, keepdims=True) + 1e-20)
    return ids, s * route_scale


def gated_ffn(x: jax.Array, p, dtype) -> jax.Array:
    """``down(silu(x gate) * (x up))``, matmuls in ``dtype`` with
    float32 accumulation."""
    def mm(a, w):
        return lax.dot_general(a.astype(dtype), w.astype(dtype),
                               (((a.ndim - 1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    h = (jax.nn.silu(mm(x, p["gate"])) * mm(x, p["up"])).astype(dtype)
    return mm(h, p["down"]).astype(dtype)


def route_softmax_bias(x: jax.Array, router_kernel: jax.Array,
                       bias: jax.Array, *, top_k: int, route_scale: float,
                       dtype) -> Tuple[jax.Array, jax.Array]:
    """x [T, d] -> (ids [T, k], weights [T, k] float32): softmax scores
    ``p`` in float32 over the router's WHOLE width (which may reach
    past the routed experts: ids from ``n_routed`` on are zero-compute
    experts, :func:`zero_experts`), the ``top_k`` highest ``p + bias``
    chosen (``bias`` [width] float32 steers the SELECTION only: a
    buffer that training moves and serving reads), no groups, and the
    weights the UNBIASED scores times ``route_scale``, not
    renormalised."""
    p = jax.nn.softmax(_router_logits(x, router_kernel, dtype), axis=-1)
    _, ids = lax.top_k(p + bias.astype(jnp.float32), top_k)
    ids = ids.astype(jnp.int32)
    return ids, jnp.take_along_axis(p, ids, axis=1) * route_scale


def route_topk_softmax(x: jax.Array, router_kernel: jax.Array, *,
                       top_k: int, dtype) -> Tuple[jax.Array, jax.Array]:
    """x [T, d] -> (ids [T, k], weights [T, k] float32): the ``top_k``
    largest LOGITS over the router's whole width (best first; a tie
    goes to the lower id, as :func:`jax.lax.top_k` breaks it), and the
    weights a softmax over THOSE k logits in float32: they sum to one,
    and an expert that was not chosen has no say in them. The
    softmax over the whole width renormalised over the chosen ones is
    the same number in another order of operations (the two differ by
    float32 rounding alone); sigmoid scores normalised are not.
    ``dtype`` float32 means a float32 PRODUCT (precision ``highest``:
    a width-``k`` choice among logits two wide flips on a bfloat16
    product's rounding; the router is a thousandth of a layer's
    operations)."""
    precision = lax.Precision.HIGHEST if dtype == jnp.float32 else None
    top, ids = lax.top_k(
        _router_logits(x, router_kernel, dtype, precision), top_k)
    return ids.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def zero_experts(x: jax.Array, ids: jax.Array, w: jax.Array, *,
                 n_routed: int, live: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """The identity experts' part of a routed layer: ``y[t] = (sum of
    w[t, j] over the choices with ids[t, j] >= n_routed) * x[t]``,
    float32. They hold no weight and are computed where the token
    lives, whoever holds the routed experts: every such choice of every
    row here, once. Also the count of those choices, int32 (``live``
    [T] bool masks rows that are no tokens)."""
    with jax.named_scope("moe.zero"):
        zero = ids >= n_routed
        if live is not None:
            zero = zero & live[:, None]
        y = jnp.sum(jnp.where(zero, w, 0.0), axis=1, keepdims=True) \
            * x.astype(jnp.float32)
    return y, jnp.sum(zero, dtype=jnp.int32)


def dropless_moe(x: jax.Array, router_kernel: jax.Array, experts, *,
                 experts_held: int, expert_offset: int, n_group: int,
                 topk_group: int, top_k: int, norm_topk: bool,
                 route_scale: float, dtype, block_rows: int = 32,
                 live: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of a sigmoid-routed layer:
    :func:`route_sigmoid` over ``router_kernel`` [d, n_routed] (scope
    ``moe.route``), then :func:`dropless_experts` on its choices."""
    with jax.named_scope("moe.route"):
        ids, w = route_sigmoid(x, router_kernel, n_group=n_group,
                               topk_group=topk_group, top_k=top_k,
                               norm_topk=norm_topk,
                               route_scale=route_scale, dtype=dtype)
    return dropless_experts(x, ids, w, experts, experts_held=experts_held,
                            expert_offset=expert_offset, dtype=dtype,
                            block_rows=block_rows, live=live)


def dropless_experts(x: jax.Array, ids: jax.Array, w: jax.Array, experts,
                     *, experts_held: int, expert_offset: int, dtype,
                     block_rows: int = 32,
                     live: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of a routed layer, dropless, for a
    ROUTING its caller made: ``ids`` [T, k] int32 and ``w`` [T, k]
    float32 from whichever router the model has
    (:func:`route_sigmoid`, :func:`route_softmax_bias`,
    :func:`route_topk_softmax`); an id outside
    the held range, another chip's expert or no routed expert at all,
    lands nowhere here.

    ``x`` [T, d]; ``experts`` holds ``gate``/``up`` [held, d, f] and
    ``down`` [held, f, d] for experts ``expert_offset .. expert_offset
    + experts_held``. Returns ``(y [T, d] in dtype, counts int32 [3])``:
    ``y[t] = sum over the experts e that t chose AND that are held of
    w[t, e] * FFN_e(x[t])``; ``counts`` = (held experts with at least
    one token, token-choices that landed on held experts, the fullest
    held expert's tokens). ``live`` [T] bool masks rows that are no
    tokens (padding, idle lanes): they are routed nowhere and counted
    nowhere.

    One formulation for a prefill's hundreds of rows and a decode
    step's one row a lane. The token-choices that land here are sorted
    by expert and cut into blocks of ``block_rows`` rows of ONE expert
    each (a group's last block is padded); a loop over the blocks THAT
    EXIST multiplies each by its expert's three matrices. So an expert
    nobody chose is never read, every choice is computed whatever the
    load (no capacity, nothing dropped), and a row's result is the
    same rows-of-a-matmul arithmetic whoever shares the batch: each
    token then sums its own choices in its own order of choice."""
    T, d = x.shape
    k, held, bm = ids.shape[1], experts_held, block_rows
    n_max = -(-T * k // bm) + held          # blocks there can be at most
    with jax.named_scope("moe.route"):
        local = ids - expert_offset
        here = (local >= 0) & (local < held)
        if live is not None:
            here = here & live[:, None]
        key = jnp.where(here, local, held).reshape(-1)       # [T * k]
        sizes = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                        dtype=jnp.int32)                     # [held]
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        rank = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))              # place sorted
        start = jnp.cumsum(sizes) - sizes                    # of a group
        blocks = -(-sizes // bm)
        first = jnp.cumsum(blocks) - blocks                  # block of a group
        n_blocks = jnp.sum(blocks)
        # which expert a block is of, and the sorted places of its rows
        blk = jnp.arange(n_max, dtype=jnp.int32)
        owner = jnp.clip(jnp.searchsorted(jnp.cumsum(blocks), blk,
                                          side="right"), 0, held - 1
                         ).astype(jnp.int32)
        place = start[owner][:, None] + (blk - first[owner])[:, None] * bm \
            + jnp.arange(bm, dtype=jnp.int32)[None]          # [n_max, bm]
        filled = (place < (start + sizes)[owner][:, None]) \
            & (blk < n_blocks)[:, None]
        tok = order[jnp.clip(place, 0, T * k - 1)] // k
        xs = jnp.where(filled[..., None], x.astype(dtype)[tok], 0)
    with jax.named_scope("moe.experts"):
        def mm(a, m, e):
            return lax.dot_general(a, m[e].astype(dtype),
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

        def one_block(j, out):
            e, xb = owner[j], xs[j]
            h = (jax.nn.silu(mm(xb, experts["gate"], e))
                 * mm(xb, experts["up"], e)).astype(dtype)
            return out.at[j].set(mm(h, experts["down"], e).astype(dtype))

        out = lax.fori_loop(0, n_blocks, one_block,
                            jnp.zeros((n_max, bm, d), dtype))
    with jax.named_scope("moe.route"):
        # combine: where a token's choice lies among the blocks
        loc = jnp.clip(local, 0, held - 1)
        r = rank.reshape(T, k) - start[loc]
        at = jnp.where(here, (first[loc] + r // bm) * bm + r % bm, 0)
        part = out.reshape(n_max * bm, d)[at].astype(jnp.float32)
        y = jnp.sum(jnp.where(here, w, 0.0)[..., None] * part, axis=1)
        counts = jnp.stack([jnp.sum(sizes > 0, dtype=jnp.int32),
                            jnp.sum(sizes), jnp.max(sizes)])
    return y.astype(dtype), counts


# ------------------------------------------- the expert decoders' block
# What the served expert decoders share around the layer above
# (``mla_moe``, ``kda_moe``, ``scmoe``: pre-norm, RMSNorm, no biases,
# a float32 residual stream, an untied head; ``ssm_moe`` takes the norm
# and the dispatch and has a tied head and multipliers of its own):
# here, beside the expert layer, because ``block_ffn`` IS that layer
# behind its norm and the models already import this module; no model
# imports another for them.

def rmsnorm(x, scale, eps, dtype=None, gain: float = 1.0):
    """RMSNorm in float32; the result in ``dtype`` (``x``'s own if
    absent), ready to be multiplied. ``gain``: a constant factor on
    the normed values, applied in float32 before the one rounding."""
    dtype = dtype or x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * lax.rsqrt(var + eps)
    if gain != 1.0:
        y = y * gain
    return y.astype(dtype) * scale.astype(dtype)


def embed(params, tokens):
    """The residual stream starts, and stays, in float32: every block
    adds into it unrounded, and only what a matrix multiplies is cast
    to the compute dtype (a stream held in bfloat16 rounds at every
    add). What it buys is small: the logits' median distance from the
    float32 reference 0.020 -> 0.018 of the largest logit (PERF.md,
    PR 37); what it costs is one float32 row a token."""
    return params["embed"]["kernel"][tokens].astype(jnp.float32)


def head(x, params, cfg):
    """The final norm and the untied head: float32 logits."""
    x = rmsnorm(x, params["ln_f_scale"], cfg.eps, cfg.dtype)
    return lax.dot_general(
        x.astype(cfg.dtype), params["head"]["kernel"].astype(cfg.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def group_cfg(cfg, G: int):
    """``cfg`` for a prefill launch of ``G`` prompts: an expert's rows
    grow ``G``-fold, and its blocks with them (``moe_block_rows``), so
    that a group's expert reads its matrices as often as ONE prompt's
    does; a row's arithmetic does not depend on its block's size."""
    return dataclasses.replace(cfg, moe_block_rows=G * cfg.moe_block_rows)


def block_ffn(x, p, cfg, live=None):
    """x [T, d] -> (x + FFN(RMSNorm(x)), counts int32 [4]): a dense
    gated FFN (``p["ffn"]``), or the held experts' part of a
    sigmoid-routed layer (:func:`dropless_moe`, by ``cfg``'s
    ``experts_held`` .. ``moe_block_rows``) plus the shared expert
    every token takes (scope ``moe.shared``). ``counts``: whether this
    was an expert layer, then :func:`dropless_experts`' three. The
    first router's block (``mla_moe``, ``kda_moe``); the second's
    (:func:`route_softmax_bias`, its shortcut branch) is ``scmoe``'s
    own and the third's (:func:`route_topk_softmax`, with one
    multiplier on the residual branch) ``ssm_moe``'s, each around
    :func:`dropless_experts` and :func:`gated_ffn` as this one is."""
    h = rmsnorm(x, p["ln2_scale"], cfg.eps, cfg.dtype)
    if "ffn" in p:
        return x + gated_ffn(h, p["ffn"], cfg.dtype).astype(x.dtype), \
            jnp.zeros((4,), jnp.int32)
    y, counts = dropless_moe(
        h, p["router"]["kernel"], p["experts"],
        experts_held=cfg.experts_held, expert_offset=cfg.expert_offset,
        n_group=cfg.n_group, topk_group=cfg.topk_group, top_k=cfg.top_k,
        norm_topk=cfg.norm_topk, route_scale=cfg.route_scale,
        dtype=cfg.dtype, block_rows=cfg.moe_block_rows, live=live)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + gated_ffn(h, p["shared"], cfg.dtype)
    return x + y.astype(x.dtype), \
        jnp.concatenate([jnp.ones((1,), jnp.int32), counts])
