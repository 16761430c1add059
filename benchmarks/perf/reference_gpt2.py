"""The plain reference: the forward pass of the GPT-2-shaped decoder the
configurations name, in float32 ``jax.numpy`` at
``default_matmul_precision("highest")``. No kernel, no cache, no
batching tricks, and no code shared with ``ray_tpu/models``.

Per layer, as published for GPT-2 / Cerebras-GPT (arXiv:2304.03208):
pre-norm, causal multi-head attention over learned absolute positions,
a GELU (tanh form) feed-forward of width ``n_inner``, residual adds, a
final norm and a head tied to the token embedding.

Departures from the published model, each because the program under
test (``ray_tpu/models/gpt.py``) differs there and the reference checks
the program's arithmetic, not a checkpoint's:

- the norm is RMSNorm with a scale and ``eps = 1e-6`` (published:
  LayerNorm with scale and bias, ``eps = 1e-5``);
- no linear layer has a bias (published: all have);
- Q, K and V are three matrices (published: one fused ``c_attn``);
- the embedding table may hold more rows than the vocabulary (padding
  that is never sampled); logits of those rows are computed and ignored.

Weights come in as a plain dict of float32 arrays with the layer
dimension leading (the layout the seeded weights are made in).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _norm(x, scale):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + 1e-6) * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(weights: dict, tokens, n_head: int):
    """tokens [B, S] int32 -> logits [B, S, rows of the table], float32."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        emb = jnp.asarray(weights["embed"], f32)
        B, S = tokens.shape
        x = emb[tokens] + jnp.asarray(weights["pos_embed"], f32)[:S][None]
        d = x.shape[-1]
        hd = d // n_head
        mask = jnp.tril(jnp.ones((S, S), bool))
        n_layer = weights["wq"].shape[0]
        for l in range(n_layer):
            h = _norm(x, jnp.asarray(weights["ln1_scale"][l], f32))
            q = (h @ jnp.asarray(weights["wq"][l], f32)).reshape(B, S, n_head, hd)
            k = (h @ jnp.asarray(weights["wk"][l], f32)).reshape(B, S, n_head, hd)
            v = (h @ jnp.asarray(weights["wv"][l], f32)).reshape(B, S, n_head, hd)
            att = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
            att = jnp.where(mask, att, -jnp.inf)
            att = jax.nn.softmax(att, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", att, v).reshape(B, S, d)
            x = x + o @ jnp.asarray(weights["wo"][l], f32)
            h = _norm(x, jnp.asarray(weights["ln2_scale"][l], f32))
            h = _gelu_tanh(h @ jnp.asarray(weights["w1"][l], f32))
            x = x + h @ jnp.asarray(weights["w2"][l], f32)
        x = _norm(x, jnp.asarray(weights["ln_f_scale"], f32))
        return x @ emb.T


def loss(weights: dict, tokens, n_head: int, vocab_rows: int = 0):
    """Mean next-token cross-entropy of tokens [B, S+1] over the rows of
    the table the program's loss uses (all of them: ``vocab_rows`` 0)."""
    with jax.default_matmul_precision("highest"):
        logits = forward(weights, tokens[:, :-1], n_head)
        if vocab_rows:
            logits = logits[..., :vocab_rows]
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        return -jnp.mean(ll)


def from_program(params: dict) -> dict:
    """The program's parameter tree, renamed to the flat dict above.
    The only place that knows the program's names."""
    b = params["block"]
    return {"embed": params["embed"]["kernel"],
            "pos_embed": params["pos_embed"],
            "ln1_scale": b["ln1_scale"], "ln2_scale": b["ln2_scale"],
            "wq": b["wq"]["kernel"], "wk": b["wk"]["kernel"],
            "wv": b["wv"]["kernel"], "wo": b["wo"]["kernel"],
            "w1": b["w1"]["kernel"], "w2": b["w2"]["kernel"],
            "ln_f_scale": params["ln_f_scale"]}
