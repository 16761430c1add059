"""Blockwise (flash) causal attention as Pallas TPU kernels.

The XLA einsum path materializes the full ``[B, H, S, S]`` float32 logit
tensor in HBM — at GPT-2 bench shapes that is the dominant memory traffic
of the whole step. This kernel keeps the softmax online in VMEM: each
``(batch, head, q-block)`` program streams K/V blocks through the MXU,
tracking the running row max/sum, and writes only the ``[bq, hd]`` output
block plus a logsumexp residual for the backward pass.

At GPT-2 head sizes (hd=64) the kernel is VPU-bound, not MXU-bound: the
softmax (exp, masking, online max/sum) does as many vector ops as the two
small-K matmuls do MACs. Three measured-on-v5e design points follow:

- all dots keep bf16 inputs (MXU-native) with f32 accumulation via
  ``preferred_element_type`` — casting inputs to f32 forces a multi-pass
  matmul ~4x slower;
- the softmax scale is folded into ``q`` *outside* the kernel (one XLA
  elementwise op that fuses into the producing matmul) instead of a
  per-block ``[bq, bk]`` multiply inside it;
- the causal mask is applied only to blocks that straddle the diagonal
  (with ``block_q == block_k`` that is exactly the ``j == i`` block);
  fully-visible blocks skip the compare/select pass entirely, and the
  mask itself is a broadcast of a per-program ``[bq, 1]`` row-id column
  against a ``[1, bk]`` col-id row — one vector pass, no 2D iota pair.

This beats ``jax.experimental.pallas.ops.tpu.flash_attention`` by ~5x at
GPT-2 bench shapes on v5e (36ms vs 200ms for 12 fwd layers, B=32,
S=1024). The reference framework has no native attention at all — its
long-context story is delegated to integrations (SURVEY.md §5
"long-context: nothing native") — so this file is new TPU-first
capability, not a port.

Backward follows the flash decomposition — an XLA precompute of
``delta = rowsum(dO * O)``, then block softmax recomputed from the saved
logsumexp instead of stored probabilities — but in ONE fused kernel
(grid over k-blocks) producing dK, dV *and* dQ. The textbook two-kernel
split recomputes the softmax twice (once for dQ over q-blocks, once for
dK/dV over k-blocks); at GPT-2 head sizes the kernel is VPU-bound on
exactly that exp/mask work, so halving it is ~1.3x on the backward
(measured 101ms → 77ms for 12 layers fwd+bwd, B=32, S=1024, v5e). The
fusion exploits the TPU's sequential grid: every j-program accumulates
its ``ds @ k_j`` contribution into a full-sequence dQ accumulator that
lives in VMEM across the j-sweep (zeroed at j==0), which only works
because grid steps with the same (b, h) run back-to-back on one core —
this is a Mosaic-specific accumulation pattern, not portable flash.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .._private.chip import pallas_interpret

NEG_INF = -1e30


def _mask_diag_block(s, i, j, bq, bk):
    """Causal-mask logits of the diagonal block (rows i*bq+r, cols j*bk+c)."""
    rows = lax.broadcasted_iota(jnp.int32, (bq, 1), 0) + i * bq
    cols = lax.broadcasted_iota(jnp.int32, (1, bk), 1) + j * bk
    return jnp.where(cols > rows, NEG_INF, s)


# ------------------------------------------------------------------ forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, block_k):
    bq, hd = q_ref.shape[2], q_ref.shape[3]
    kv_len = k_ref.shape[2]
    i = pl.program_id(2)
    num_kb = kv_len // block_k
    # Causal: q rows in block i never see k blocks past (i+1)*bq.
    upper = pl.cdiv((i + 1) * bq, block_k) if causal else num_kb

    q = q_ref[0, 0]                                  # [bq, hd] bf16, scaled

    def make_body(masked):
        def body(j, carry):
            acc, m, l = carry
            kj = k_ref[0, 0, pl.ds(j * block_k, block_k), :]  # [bk, hd]
            vj = v_ref[0, 0, pl.ds(j * block_k, block_k), :]
            s = lax.dot_general(q, kj, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            if masked:
                s = _mask_diag_block(s, i, j, bq, block_k)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)                   # [bq, bk] f32
            alpha = jnp.exp(m - m_new)               # [bq, 1]
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            pv = lax.dot_general(p.astype(vj.dtype), vj,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            acc = acc * alpha + pv
            return acc, m_new, l
        return body

    carry = (jnp.zeros((bq, hd), jnp.float32),
             jnp.full((bq, 1), NEG_INF, jnp.float32),
             jnp.zeros((bq, 1), jnp.float32))
    if causal:
        # Off-diagonal blocks (fully visible) skip the mask pass; only the
        # final (diagonal-straddling) block pays for it.
        carry = lax.fori_loop(0, upper - 1, make_body(False), carry)
        carry = make_body(True)(upper - 1, carry)
    else:
        carry = lax.fori_loop(0, upper, make_body(False), carry)
    acc, m, l = carry
    o_ref[0, 0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l)                   # [bq, 1]


def _flash_fwd(q, k, v, *, causal, block_q, block_k, interpret):
    """q is pre-scaled. Shapes [B, H, S, hd]."""
    B, H, S, hd = q.shape
    Sk = k.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    assert S % bq == 0 and Sk % bk == 0, (S, Sk, bq, bk)
    if causal:
        assert bq == bk, "causal path requires block_q == block_k"
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, block_k=bk),
        grid=(B, H, S // bq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((1, 1, Sk, hd), lambda b, h, i: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, Sk, hd), lambda b, h, i: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i: (b, h, i, 0)),
            # lse kept 4D [B,H,S,1]: trailing dims (bq, 1) satisfy the
            # (8,128)-or-full tiling rule; a 3D [.., bq] block does not.
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ----------------------------------------------------------------- backward
def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, causal, block_q):
    """One k-block program computes dK/dV for its block AND accumulates
    every q-block's dQ contribution into a full-sequence VMEM
    accumulator. Correct only because TPU grid steps with the same
    (b, h) run sequentially on one core: dq_ref's block index ignores j,
    so Mosaic keeps the buffer resident across the j-sweep."""
    bk, hd = k_ref.shape[2], k_ref.shape[3]
    q_len = q_ref.shape[2]
    j = pl.program_id(2)
    num_qb = q_len // block_q
    # Causal: q blocks strictly before the diagonal contribute nothing.
    start = j * bk // block_q if causal else 0

    @pl.when(j == 0)
    def _zero_dq():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    kj = k_ref[0, 0]                                 # [bk, hd] bf16
    vj = v_ref[0, 0]

    def make_body(masked):
        def body(i, carry):
            dk, dv = carry
            qi = q_ref[0, 0, pl.ds(i * block_q, block_q), :]  # scaled
            doi = do_ref[0, 0, pl.ds(i * block_q, block_q), :]
            lse = lse_ref[0, 0, pl.ds(i * block_q, block_q), :]
            delta = delta_ref[0, 0, pl.ds(i * block_q, block_q), :]
            s = lax.dot_general(qi, kj, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            if masked:
                s = _mask_diag_block(s, i, j, block_q, bk)
            p = jnp.exp(s - lse)                     # [bq, bk] f32
            pb = p.astype(doi.dtype)
            dv = dv + lax.dot_general(pb, doi, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            dp = lax.dot_general(doi, vj, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta)).astype(qi.dtype)
            dk = dk + lax.dot_general(ds, qi, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            # dQ_i += ds @ K_j — the whole point of the fusion: the same
            # (s, p) recompute serves dK/dV and dQ.
            dq_i = lax.dot_general(ds, kj, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
            sl = (0, 0, pl.ds(i * block_q, block_q), slice(None))
            dq_ref[sl] += dq_i
            return dk, dv
        return body

    carry = (jnp.zeros((bk, hd), jnp.float32),
             jnp.zeros((bk, hd), jnp.float32))
    if causal:
        # The first visible q block (the diagonal) is masked; the rest see
        # this k block in full.
        carry = make_body(True)(start, carry)
        carry = lax.fori_loop(start + 1, num_qb, make_body(False), carry)
    else:
        carry = lax.fori_loop(0, num_qb, make_body(False), carry)
    dk, dv = carry
    # qi carried the softmax scale, so dk = ds^T (q*scale) is complete.
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _flash_bwd(qs, k, v, o, lse, do, *, sm_scale, causal, block_q, block_k,
               interpret):
    """qs is the pre-scaled q. Returns grads wrt the ORIGINAL q, k, v."""
    B, H, S, hd = qs.shape
    Sk = k.shape[2]
    bq = min(block_q, S)
    bk = min(block_k, Sk)
    if causal:
        # The fused kernel masks exactly one diagonal-straddling q-block
        # per k-block, which is only the full causal boundary when the
        # blocks match (same invariant _flash_fwd enforces).
        assert bq == bk, "causal backward requires block_q == block_k"
    # delta = rowsum(dO * O): tiny, let XLA fuse it. Kept [B,H,S,1] like lse.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)

    dqs, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, causal=causal, block_q=bq),
        grid=(B, H, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, S, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, S, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, 1), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, S, 1), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_specs=[
            # dq: full-S accumulator, same block for every j (resident
            # in VMEM across the j-sweep; f32 so += stays exact).
            pl.BlockSpec((1, 1, S, hd), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j: (b, h, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, Sk, hd), k.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, hd), v.dtype),
        ],
        interpret=interpret,
    )(qs, k, v, do, lse, delta)
    # dL/dq = dL/dqs * sm_scale (qs = q * sm_scale).
    dq = (dqs * sm_scale).astype(qs.dtype)
    return dq, dk, dv


# -------------------------------------------------------------- public API
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    qs = (q * jnp.asarray(sm_scale, q.dtype)) if sm_scale != 1.0 else q
    o, _ = _flash_fwd(qs, k, v, causal=causal, block_q=block_q,
                      block_k=block_k, interpret=interpret)
    return o


def _flash_fwd_rule(q, k, v, sm_scale, causal, block_q, block_k, interpret):
    qs = (q * jnp.asarray(sm_scale, q.dtype)) if sm_scale != 1.0 else q
    o, lse = _flash_fwd(qs, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    return o, (qs, k, v, o, lse)


def _flash_bwd_rule(sm_scale, causal, block_q, block_k, interpret, res, g):
    qs, k, v, o, lse = res
    return _flash_bwd(qs, k, v, o, lse, g, sm_scale=sm_scale, causal=causal,
                      block_q=block_q, block_k=block_k, interpret=interpret)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _pick_block(S: int) -> int:
    """Largest power-of-two block (<=512, measured best on v5e) dividing S."""
    for b in (512, 256, 128, 64, 32, 16, 8):
        if S % b == 0:
            return b
    return S


def flash_attention(q, k, v, *, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jax.Array:
    """Blockwise attention. q, k, v: ``[B, S, H, hd]`` → ``[B, S, H, hd]``.

    Differentiable (custom VJP, flash backward). Compiled through
    Mosaic on a TPU, interpreted on the CPU so tests run the same kernel
    body on the virtual mesh — :func:`pallas_interpret` decides, nobody
    else does.
    """
    interpret = pallas_interpret()
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if block_q is None:
        block_q = _pick_block(q.shape[1])
    if block_k is None:
        block_k = block_q if causal else _pick_block(k.shape[1])
    qt = jnp.transpose(q, (0, 2, 1, 3))              # [B, H, S, hd]
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    o = _flash(qt, kt, vt, sm_scale, causal, block_q, block_k, interpret)
    return jnp.transpose(o, (0, 2, 1, 3))
