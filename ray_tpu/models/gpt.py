"""GPT-style decoder LM, pure JAX, built for the MXU.

Flagship model for the framework (the reference has no model zoo of its
own — its Train library wraps user torch models, e.g.
``python/ray/train/examples/``; here the framework ships a TPU-first LM so
Train/Tune/Serve/bench have a real workload).

Design notes (TPU-first):
- params are a flat dict-of-dicts pytree; per-layer weights are STACKED
  along a leading ``layer`` dim and the forward pass is a ``lax.scan`` over
  layers — one compiled block regardless of depth (fast compiles, XLA sees
  a loop it can pipeline).
- all matmuls run in bfloat16 with float32 accumulation
  (``preferred_element_type``) — the MXU-native regime.
- ``remat='block'`` wraps each layer in ``jax.checkpoint`` so activations
  are rematerialized in backward — HBM for FLOPs.
- attention backend is pluggable: "xla" (einsum softmax), "flash"
  (pallas), "ring" (sequence-parallel over a mesh axis; ops/ring_attention).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private.jax_compat import shard_map

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # 50257 padded to a multiple of 128
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 3072
    max_seq: int = 1024
    dtype: Any = jnp.bfloat16        # activation/matmul dtype
    param_dtype: Any = jnp.float32   # master params
    remat: Any = "dots"              # none|dots|full (bool accepted)
    attn_backend: str = "auto"       # auto | xla | flash | ring
    sp_axis: Optional[str] = None    # mesh axis for ring attention
    pp_axis: Optional[str] = None    # mesh axis for pipeline parallelism
    num_microbatches: int = 0        # pp microbatches (0 → 2 * pp size)
    n_experts: int = 0               # >0 → MoE FFN in every block
    expert_top_k: int = 2            # tokens routed to k experts
    capacity_factor: float = 1.25    # per-expert slots = cf*k*T/E
    moe_aux_coef: float = 0.01       # load-balance loss weight
    ep_axis: Optional[str] = "ep"    # mesh axis sharding the expert dim
    loss_chunk: int = 0              # seq chunk for cross-entropy (0=off):
    # the f32 [B, S, vocab] logits are the single biggest buffer of a
    # training step (GPT-2-small @ B=32, S=1024: 6.6 GB); chunking the
    # final projection+CE over S keeps one chunk's logits live at a time
    # and rematerializes them in backward (one extra projection matmul).
    # Measured on v5e: ~5% slower at GPT-2-small shapes (recompute beats
    # bandwidth saved), so OFF by default; REQUIRED at 1b+/long-seq
    # shapes where the unchunked logits alone exceed HBM.

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    def num_params(self) -> int:
        d, f, v, L = self.d_model, self.d_ff, self.vocab_size, self.n_layer
        per_layer = 4 * d * d + 2 * d * f + 2 * d  # qkv,o + mlp + 2 ln scales
        return v * d + self.max_seq * d + L * per_layer + d

    def flops_per_token(self) -> int:
        # 6ND approximation per forward+backward token.
        return 6 * self.num_params()

    def decode_programs(self):
        """This model's description for the serving engine
        (:mod:`ray_tpu.models.serving`)."""
        from . import gpt_decode

        return gpt_decode


# sizes used by benchmarks / examples
CONFIGS = {
    "nano": GPTConfig(vocab_size=512, n_layer=2, n_head=2, d_model=64,
                      d_ff=256, max_seq=128),
    "small": GPTConfig(),                                   # GPT-2 124M
    "medium": GPTConfig(n_layer=24, n_head=16, d_model=1024, d_ff=4096),
    "1b": GPTConfig(n_layer=24, n_head=16, d_model=2048, d_ff=8192,
                    max_seq=2048, loss_chunk=256),
}


def _dense_init(key, shape, dtype, scale=None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


def init_params(rng: jax.Array, cfg: GPTConfig) -> Params:
    """Stacked-layer parameter pytree (leading dim = layer)."""
    pd = cfg.param_dtype
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layer
    keys = jax.random.split(rng, 8)

    def stack(key, shape, scale=None):
        ks = jax.random.split(key, L)
        return jnp.stack([_dense_init(k, shape, pd, scale) for k in ks])

    resid_scale = 1.0 / math.sqrt(2 * L * d)
    block = {
        "ln1_scale": jnp.ones((L, d), pd),
        "ln2_scale": jnp.ones((L, d), pd),
        "wq": {"kernel": stack(keys[2], (d, d))},
        "wk": {"kernel": stack(keys[3], (d, d))},
        "wv": {"kernel": stack(keys[4], (d, d))},
        "wo": {"kernel": stack(keys[5], (d, d), resid_scale)},
    }
    if cfg.n_experts > 0:
        E = cfg.n_experts
        ks = jax.random.split(keys[6], 3)

        def stack_e(key, shape, scale=None):
            kk = jax.random.split(key, L)
            return jnp.stack([
                jnp.stack([_dense_init(k2, shape, pd, scale)
                           for k2 in jax.random.split(k, E)])
                for k in kk])

        block["router"] = {"kernel": stack(ks[0], (d, E), 0.02)}
        block["w_up"] = {"kernel": stack_e(ks[1], (d, f))}
        block["w_down"] = {"kernel": stack_e(ks[2], (f, d), resid_scale)}
    else:
        block["w1"] = {"kernel": stack(keys[6], (d, f))}
        block["w2"] = {"kernel": stack(keys[7], (f, d), resid_scale)}
    return {
        "embed": {"kernel": _dense_init(keys[0], (cfg.vocab_size, d), pd,
                                        scale=0.02)},
        "pos_embed": _dense_init(keys[1], (cfg.max_seq, d), pd, scale=0.01),
        "block": block,
        "ln_f_scale": jnp.ones((d,), pd),
    }


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + 1e-6)).astype(x.dtype) * scale.astype(x.dtype)


def _mm(x, w, dtype):
    return lax.dot_general(x.astype(dtype), w.astype(dtype),
                           (((x.ndim - 1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32).astype(dtype)


def _attention_xla(q, k, v, cfg: GPTConfig):
    """[B, S, H, hd] causal attention via einsum softmax (XLA fuses)."""
    S = q.shape[1]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = jnp.tril(jnp.ones((S, S), jnp.bool_))
    logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _resolve_attn_backend(cfg: GPTConfig, seq: int) -> str:
    """auto → flash where the Pallas kernel's SHAPE constraints hold.
    The platform is not consulted: how the kernel then runs is
    :func:`ray_tpu._private.chip.pallas_interpret`'s decision alone."""
    if cfg.attn_backend != "auto":
        return cfg.attn_backend
    if seq >= 512 and seq % 256 == 0 and cfg.head_dim % 8 == 0:
        return "flash"
    return "xla"


def attention_plan(cfg: GPTConfig, seq: int) -> Dict[str, str]:
    """Which attention a step at this (cfg, seq) runs and how — the
    fact a caller prints or asserts instead of guessing: ``backend`` is
    what ``attn_backend`` resolves to, ``mode`` is ``"compiled"`` /
    ``"interpret"`` for the Pallas kernel and ``"xla"`` otherwise."""
    from ray_tpu._private.chip import pallas_interpret

    backend = _resolve_attn_backend(cfg, seq)
    if backend != "flash":
        return {"backend": backend, "mode": "xla"}
    return {"backend": backend,
            "mode": "interpret" if pallas_interpret() else "compiled"}


def _sp_shard_map(fn, cfg: GPTConfig, mesh):
    """Wrap a per-device SP attention fn in shard_map over the mesh.

    Activations are [B, S, H, hd]: batch over (dp, fsdp), seq over the sp
    axis, heads over tp — matching LM_RULES' qkv column sharding.
    """
    import functools

    from jax.sharding import PartitionSpec as P

    names = set(mesh.axis_names)
    bt = tuple(a for a in ("dp", "fsdp") if a in names) or None
    tp = "tp" if "tp" in names else None
    spec = P(bt, cfg.sp_axis, tp, None)
    inner = functools.partial(fn, axis_name=cfg.sp_axis, causal=True,
                              axis_size=mesh.shape[cfg.sp_axis])
    return shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec)


def _attention(q, k, v, cfg: GPTConfig, mesh=None):
    backend = _resolve_attn_backend(cfg, q.shape[1])
    if backend == "flash":
        import functools

        from ray_tpu.ops.flash_attention import flash_attention

        fn = functools.partial(flash_attention, causal=True)
        if mesh is not None and mesh.size > 1:
            # GSPMD cannot auto-partition Mosaic kernels; on a multi-device
            # mesh the kernel must run per-device under shard_map (batch
            # over dp/fsdp, heads over tp, sequence unsharded).
            from jax.sharding import PartitionSpec as P

            names = set(mesh.axis_names)
            bt = tuple(a for a in ("dp", "fsdp") if a in names) or None
            tp = "tp" if "tp" in names else None
            spec = P(bt, None, tp, None)
            # check_vma=False: pallas_call's out_shape carries no vma
            # annotation, which strict shard_map rejects.
            return shard_map(lambda q, k, v: fn(q, k, v), mesh=mesh,
                             in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)
        return fn(q, k, v)
    if backend in ("ring", "ulysses"):
        from ray_tpu.ops import ring_attention as ra

        if mesh is None or not cfg.sp_axis or cfg.sp_axis not in set(
                mesh.axis_names):
            raise ValueError(
                f"attn_backend={backend!r} needs a mesh with the sp axis "
                f"{cfg.sp_axis!r}; pass mesh via make_train_step")
        fn = (ra.ring_attention if backend == "ring"
              else ra.ulysses_attention)
        return _sp_shard_map(fn, cfg, mesh)(q, k, v)
    if backend != "xla":
        raise ValueError(f"unknown attn_backend {backend!r}")
    return _attention_xla(q, k, v, cfg)


# How a block COMPUTES with its matrices: column matrices split their
# output over tp, row matrices their input (``_attention``: heads over
# tp), and nothing over fsdp, which shards the batch. Where a matrix is
# STORED is ``parallel/sharding.py LM_RULES``' to say.
_COLUMN, _ROW = ("wq", "wk", "wv", "w1"), ("wo", "w2")


def _gather_layer(layer_params, cfg: GPTConfig, mesh):
    """ZeRO-3's all-gather, asked for by name: one layer's matrices cast
    to ``cfg.dtype`` and then constrained to their compute layout, which
    is replicated over ``fsdp``. Left to itself the partitioner keeps a
    weight that is sharded over the batch's own axis where it lies and
    all-gathers / all-reduces the whole batch's ACTIVATIONS in every
    layer instead. The cast comes first, so the gather moves
    ``cfg.dtype`` bytes and not the float32 masters'. Called INSIDE the
    function ``jax.checkpoint`` wraps: the gathered copy is no residual
    of the scan, backward gathers the layer again, and the constraint's
    transpose sums the layer's gradient over the chips there (on a TPU
    a reduce-scatter). A no-op on a mesh without an ``fsdp`` axis."""
    if mesh is None or "fsdp" not in mesh.axis_names:
        return layer_params
    from jax.sharding import NamedSharding, PartitionSpec as P

    tp = "tp" if "tp" in mesh.axis_names else None
    out = dict(layer_params)
    for names, spec in ((_COLUMN, P(None, tp)), (_ROW, P(tp, None))):
        for name in names:
            if name in out:
                w = out[name]["kernel"].astype(cfg.dtype)
                out[name] = {"kernel": lax.with_sharding_constraint(
                    w, NamedSharding(mesh, spec))}
    return out


def _block(x, layer_params, cfg: GPTConfig, mesh=None):
    """One transformer block → (x, aux_loss).

    ``layer_params`` leaves have no layer dim. ``aux_loss`` is the MoE
    load-balance term (0 for dense FFN).
    """
    B, S, d = x.shape
    H, hd = cfg.n_head, cfg.head_dim
    p = _gather_layer(layer_params, cfg, mesh)
    h = _rmsnorm(x, p["ln1_scale"])
    q = _mm(h, p["wq"]["kernel"], cfg.dtype).reshape(B, S, H, hd)
    k = _mm(h, p["wk"]["kernel"], cfg.dtype).reshape(B, S, H, hd)
    v = _mm(h, p["wv"]["kernel"], cfg.dtype).reshape(B, S, H, hd)
    att = _attention(q, k, v, cfg, mesh).reshape(B, S, d)
    x = x + _mm(att, p["wo"]["kernel"], cfg.dtype)
    h = _rmsnorm(x, p["ln2_scale"])
    if cfg.n_experts > 0:
        from ray_tpu.models.moe import moe_ffn

        y, aux = moe_ffn(
            h, p["router"]["kernel"], p["w_up"]["kernel"],
            p["w_down"]["kernel"], top_k=cfg.expert_top_k,
            capacity_factor=cfg.capacity_factor, dtype=cfg.dtype,
            ep_axis=cfg.ep_axis, mesh=mesh)
        return x + y, aux
    h = _mm(h, p["w1"]["kernel"], cfg.dtype)
    h = jax.nn.gelu(h)
    x = x + _mm(h, p["w2"]["kernel"], cfg.dtype)
    return x, jnp.zeros((), jnp.float32)


def _block_pp_tp(x, p, cfg: GPTConfig, tp_axis: str, tp_size: int):
    """Transformer block for a pipeline stage with Megatron-style tensor
    parallelism done by hand: qkv/up are column-parallel (each tp rank
    computes n_head/tp heads and d_ff/tp hidden units), out/down are
    row-parallel with a psum over tp. Runs per-device inside
    pipeline_apply's shard_map, so these collectives cannot come from
    GSPMD."""
    B, S, d = x.shape
    hd = cfg.head_dim
    h_local = cfg.n_head // tp_size
    p_ = p
    h = _rmsnorm(x, p_["ln1_scale"])
    q = _mm(h, p_["wq"]["kernel"], cfg.dtype).reshape(B, S, h_local, hd)
    k = _mm(h, p_["wk"]["kernel"], cfg.dtype).reshape(B, S, h_local, hd)
    v = _mm(h, p_["wv"]["kernel"], cfg.dtype).reshape(B, S, h_local, hd)
    if _resolve_attn_backend(cfg, S) == "flash":
        from ray_tpu.ops.flash_attention import flash_attention

        att = flash_attention(q, k, v, causal=True)
    else:
        att = _attention_xla(q, k, v, cfg)
    att = att.reshape(B, S, h_local * hd)
    o = _mm(att, p_["wo"]["kernel"], cfg.dtype)
    if tp_size > 1:
        o = lax.psum(o, tp_axis)
    x = x + o
    h = _rmsnorm(x, p_["ln2_scale"])
    h = jax.nn.gelu(_mm(h, p_["w1"]["kernel"], cfg.dtype))
    y = _mm(h, p_["w2"]["kernel"], cfg.dtype)
    if tp_size > 1:
        y = lax.psum(y, tp_axis)
    return x + y


def _block_pp_sp(x, p, cfg: GPTConfig, sp_axis: str, sp_size: int):
    """Transformer block for a pipeline stage with sequence parallelism:
    activations are [B, S/sp, d] per device and attention is a ring
    collective over ``sp_axis``. Runs per-device inside pipeline_apply's
    shard_map (GSPMD does not reach under it), so the ring ppermutes are
    written by hand exactly like the sp-only path's
    ``ops/ring_attention``."""
    from ray_tpu.ops import ring_attention as ra

    B, S_loc, d = x.shape
    H, hd = cfg.n_head, cfg.head_dim
    h = _rmsnorm(x, p["ln1_scale"])
    q = _mm(h, p["wq"]["kernel"], cfg.dtype).reshape(B, S_loc, H, hd)
    k = _mm(h, p["wk"]["kernel"], cfg.dtype).reshape(B, S_loc, H, hd)
    v = _mm(h, p["wv"]["kernel"], cfg.dtype).reshape(B, S_loc, H, hd)
    att = ra.ring_attention(q, k, v, axis_name=sp_axis, causal=True,
                            axis_size=sp_size).reshape(B, S_loc, d)
    x = x + _mm(att, p["wo"]["kernel"], cfg.dtype)
    h = _rmsnorm(x, p["ln2_scale"])
    h = jax.nn.gelu(_mm(h, p["w1"]["kernel"], cfg.dtype))
    return x + _mm(h, p["w2"]["kernel"], cfg.dtype)


def _pp_tp_param_specs(block_params, pp_axis: str, tp_axis: str):
    """PartitionSpecs for a pipeline stage's stacked params under pp x
    tp: layer dim over pp; column weights (wq/wk/wv/w1) shard their
    output dim over tp, row weights (wo/w2) their input dim."""
    from jax.sharding import PartitionSpec as P

    col = {"wq", "wk", "wv", "w1"}
    row = {"wo", "w2"}

    def spec(path, leaf):
        keys = {getattr(k, "key", getattr(k, "name", None)) for k in path}
        if keys & col:
            return P(pp_axis, *([None] * (leaf.ndim - 2)), tp_axis)
        if keys & row:
            return P(pp_axis, tp_axis, *([None] * (leaf.ndim - 2)))
        return P(pp_axis, *([None] * (leaf.ndim - 1)))

    import jax.tree_util as jtu

    return jtu.tree_map_with_path(spec, block_params)


def forward(params: Params, tokens: jax.Array, cfg: GPTConfig,
            mesh=None, *, return_aux: bool = False,
            final_hidden: bool = False):
    """tokens [B, S] int32 → logits [B, S, vocab] float32.

    ``mesh`` is only needed for shard_map attention backends (ring,
    ulysses) and MoE/PP sharding constraints; plain GSPMD backends (xla,
    flash) ignore it. With ``return_aux`` also returns a dict of auxiliary
    losses (MoE load balance). ``final_hidden`` skips the vocab
    projection and returns the post-norm hidden states (the chunked loss
    projects per chunk itself).
    """
    B, S = tokens.shape
    x = params["embed"]["kernel"].astype(cfg.dtype)[tokens]
    x = x + params["pos_embed"][:S].astype(cfg.dtype)[None]

    # Remat policy: "full" recomputes everything (max HBM savings, +1 fwd
    # of FLOPs); "dots" keeps matmul outputs and recomputes only cheap
    # elementwise ops; "none" saves all activations (fastest when the
    # model fits — GPT-2-small at bench shapes trivially does).
    remat = {True: "full", False: "none"}.get(cfg.remat, cfg.remat)
    block_fn = _block
    if remat == "full":
        block_fn = jax.checkpoint(_block, static_argnums=(2, 3))
    elif remat == "dots":
        block_fn = jax.checkpoint(
            _block, static_argnums=(2, 3),
            policy=jax.checkpoint_policies.checkpoint_dots)
    elif remat != "none":
        raise ValueError(f"unknown remat policy {cfg.remat!r}")

    aux = jnp.zeros((), jnp.float32)
    if cfg.pp_axis and mesh is not None and cfg.pp_axis in mesh.axis_names:
        if cfg.n_experts > 0:
            raise NotImplementedError(
                "MoE inside a pipeline stage is not supported yet; use an "
                "{ep, dp} mesh for expert parallelism")
        from ray_tpu.parallel.pipeline import pipeline_apply

        tp_ax = "tp" if "tp" in mesh.axis_names else None
        sp_ax = cfg.sp_axis if (cfg.sp_axis
                                and cfg.sp_axis in mesh.axis_names) else None
        if tp_ax is not None and sp_ax is not None:
            raise NotImplementedError(
                "pp x tp x sp on one mesh is not supported; pick two")
        if tp_ax is not None:
            tp_size = mesh.shape[tp_ax]
            if cfg.n_head % tp_size or cfg.d_ff % tp_size:
                raise ValueError(
                    f"n_head={cfg.n_head} / d_ff={cfg.d_ff} not divisible "
                    f"by tp={tp_size}")
            x = pipeline_apply(
                lambda act, lp: _block_pp_tp(act, lp, cfg, tp_ax, tp_size),
                params["block"], x, mesh=mesh, pp_axis=cfg.pp_axis,
                num_microbatches=cfg.num_microbatches, tp_axis=tp_ax,
                param_specs=_pp_tp_param_specs(params["block"],
                                               cfg.pp_axis, tp_ax))
        elif sp_ax is not None:
            sp_size = mesh.shape[sp_ax]
            if tokens.shape[1] % sp_size:
                raise ValueError(
                    f"seq {tokens.shape[1]} not divisible by "
                    f"sp={sp_size}")
            x = pipeline_apply(
                lambda act, lp: _block_pp_sp(act, lp, cfg, sp_ax, sp_size),
                params["block"], x, mesh=mesh, pp_axis=cfg.pp_axis,
                num_microbatches=cfg.num_microbatches, sp_axis=sp_ax)
        else:
            # Inside the pipeline body each stage runs single-device math
            # (mesh=None): GSPMD does not reach under the shard_map.
            x = pipeline_apply(
                lambda act, lp: block_fn(act, lp, cfg, None)[0],
                params["block"], x, mesh=mesh, pp_axis=cfg.pp_axis,
                num_microbatches=cfg.num_microbatches)
    else:
        def scan_body(carry, layer_params):
            out, a = block_fn(carry, layer_params, cfg, mesh)
            return out, a

        x, layer_aux = lax.scan(scan_body, x, params["block"])
        aux = jnp.sum(layer_aux)
    x = _rmsnorm(x, params["ln_f_scale"])
    if final_hidden:
        return (x, {"moe_aux": aux}) if return_aux else x
    logits = _project_vocab(x, params["embed"]["kernel"], cfg)
    if return_aux:
        return logits, {"moe_aux": aux}
    return logits


def _project_vocab(x, embed, cfg: GPTConfig):
    """Tied-embedding vocab projection, f32 logits out."""
    return lax.dot_general(
        x.astype(cfg.dtype), embed.astype(cfg.dtype),
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


def _ce_from_logits(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def _chunked_ce(x, embed, targets, cfg: GPTConfig):
    """Cross-entropy over the vocab projection, scanned in sequence
    chunks so only one chunk's f32 logits are ever resident; the chunk
    body is checkpointed, so backward re-projects instead of storing."""
    B, S, d = x.shape
    chunk = cfg.loss_chunk
    n = S // chunk
    tail_loss = jnp.zeros((), jnp.float32)
    if n == 0:
        n, chunk = 1, S
    rem = S - n * chunk

    def body(carry, xt):
        xc, tc = xt  # [B, chunk, d], [B, chunk]
        logits = _project_vocab(xc, embed, cfg)
        return carry + _ce_from_logits(logits, tc) * tc.size, None

    xs = x[:, :n * chunk].reshape(B, n, chunk, d).swapaxes(0, 1)
    ts = targets[:, :n * chunk].reshape(B, n, chunk).swapaxes(0, 1)
    total, _ = lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                        (xs, ts))
    if rem:
        tail = targets[:, n * chunk:]
        tail_loss = _ce_from_logits(
            _project_vocab(x[:, n * chunk:], embed, cfg), tail) * tail.size
    return (total + tail_loss) / (B * S)


def loss_fn(params: Params, batch: Dict[str, jax.Array],
            cfg: GPTConfig, mesh=None
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Next-token cross-entropy. batch: tokens [B, S+1] (or tokens+targets)."""
    if "targets" in batch:
        tokens, targets = batch["tokens"], batch["targets"]
    else:
        tokens, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    if cfg.loss_chunk:
        x, aux = forward(params, tokens, cfg, mesh, return_aux=True,
                         final_hidden=True)
        loss = _chunked_ce(x, params["embed"]["kernel"], targets, cfg)
    else:
        logits, aux = forward(params, tokens, cfg, mesh, return_aux=True)
        loss = _ce_from_logits(logits, targets)
    metrics = {"loss": loss, "perplexity": jnp.exp(loss)}
    if cfg.n_experts > 0:
        loss = loss + cfg.moe_aux_coef * aux["moe_aux"]
        metrics["moe_aux"] = aux["moe_aux"]
    return loss, metrics


# ------------------------------------------------------------- train step
def make_train_step(cfg: GPTConfig, mesh, optimizer=None, *,
                    rules=None, donate: bool = True):
    """Build (init_fn, step_fn) jitted over ``mesh``.

    Where the state is STORED comes from ``rules`` (default
    :data:`ray_tpu.parallel.sharding.LM_RULES`): the batch over
    dp×fsdp; a block's matrices, stacked ``[L, in, out]`` for the layer
    scan, over fsdp and tp on their OWN two dimensions and never on
    ``L``, so every chip holds its part of every layer; Adam's moments
    like their parameters. The partitioner places the collectives, with
    one asked for by name: on an ``fsdp`` axis each layer's matrices
    are cast to ``cfg.dtype`` and ALL-GATHERED inside the scan's body,
    where the layer runs (``_gather_layer``), once in the forward and
    once in the backward pass; the constraint's transpose sums the
    layer's float32 gradient over the chips in the backward body (a
    reduce-scatter on a TPU). That is ZeRO-3's schedule: the TPU-native
    replacement for torch DDP/FSDP wrapping (reference
    ``train_loop_utils.py:158,175``).
    ``parallel.sharding.compiled_collectives(step.lower(...).compile())``
    reads what the compiler made of it.
    """
    import optax

    from ray_tpu.parallel import sharding as shr

    if optimizer is None:
        optimizer = optax.adamw(3e-4, weight_decay=0.01)
    if rules is None:
        pp_mode = cfg.pp_axis and cfg.pp_axis in mesh.axis_names
        rules = shr.PP_LM_RULES if pp_mode else shr.LM_RULES

    def train_init(rng):       # the XLA module: jit_train_init
        params = init_params(rng, cfg)
        opt_state = optimizer.init(params)
        return {"params": params, "opt": opt_state, "step": jnp.zeros((), jnp.int32)}

    abstract = jax.eval_shape(train_init, jax.random.PRNGKey(0))
    param_sh = shr.tree_shardings(abstract["params"], mesh, rules)

    from jax.sharding import NamedSharding, PartitionSpec as P

    # Opt-state leaves that mirror params (adam mu/nu subtrees) carry the
    # param path as a suffix (e.g. "0/mu/block/wq/kernel"), so the same
    # path-regex rules shard them identically; scalars hit the catch-all.
    state_sh = {
        "params": param_sh,
        "opt": shr.tree_shardings(abstract["opt"], mesh, rules),
        "step": NamedSharding(mesh, P()),
    }
    # Tokens stay [B, S+1] (S+1 rarely divides the sp axis); the attention
    # shard_map's in_specs pull activations onto the sp axis and GSPMD
    # propagates that sharding through the surrounding ops.
    batch_sh = shr.batch_sharding(mesh)

    init_jit = jax.jit(train_init, out_shardings=state_sh)

    def train_step(state, batch):      # the XLA module: jit_train_step
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state["params"], batch, cfg, mesh)
        updates, new_opt = optimizer.update(grads, state["opt"],
                                            state["params"])
        new_params = optax.apply_updates(state["params"], updates)
        return ({"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}, metrics)

    step_jit = jax.jit(
        train_step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,) if donate else (),
    )
    return init_jit, step_jit, state_sh, batch_sh
