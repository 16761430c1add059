"""The plain reference for Solar-Open2-250B (``model_type``
``solar_open2``;
https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json):
the forward pass in float32 ``jax.numpy`` at
``default_matmul_precision("highest")``. The linear-attention layers
run the recurrence ONE TOKEN AT A TIME and nothing else; the attention
layer is a full causal softmax. No cache, no chunks, no kernel, no
batching trick, and no code shared with the program under test.

``hp`` is a plain dict of the sizes (``heads``, ``kv_heads``,
``head_dim``; ``kda_heads``, ``kda_dim``, ``conv``; ``gqa_layers``;
``eps``; ``top_k``, ``norm_topk``, ``route_scale``; ``experts_held``,
``expert_offset`` and optionally ``weights_offset``: the id of the
first expert in the weight arrays, ``expert_offset`` if absent).
Weights are a flat dict of per-layer lists (``None`` where a layer of
the other kind has no such leaf) in whatever type the program holds
them; each matrix is upcast where it is used, one layer's (one
expert's) at a time, and no float32 copy of the tree is ever held.

The equations (``h`` hidden; block ``l``: ``x += Mixer_l(RMSNorm(x))``,
``x += MoE(RMSNorm(x))``; a final RMSNorm; an untied head; no biases,
no positions):

- KDA (``l`` not in ``gqa_layers``; ``H`` heads of ``D`` keys and
  values): ``q~, k~, v~ = x W_q, x W_k, x W_v``; a causal depthwise
  convolution of width ``conv`` over time, a channel at a time, then
  SiLU: ``q^_t = silu(sum_i c_i * q~_{t-conv+1+i})`` (zeros before the
  start), the same for ``k^``, ``v^``; per head ``q_t = q^_t / |q^_t|
  D^-1/2``, ``k_t = k^_t / |k^_t|`` (``|a| = sqrt(sum a^2 + 1e-6)``),
  ``v_t = v^_t``; the decay per channel ``alpha_t = exp(-exp(A_log)
  softplus((x W_f1) W_f2 + dt_bias))``; the step size ``beta_t = 2
  sigmoid(x W_b)``; from ``S_0 = 0``, a head:
  ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t
  v_t^T``, ``o_t = S_t^T q_t``; ``y_t = W_o [RMSNorm_head(o_t) *
  sigmoid((x W_g1) W_g2)]``.
- GQA (``l`` in ``gqa_layers``): ``q, k, v = x W_q, x W_k, x W_v``;
  query head ``j`` attends causally with KV head ``j // (heads /
  kv_heads)``, scale ``head_dim^-1/2``, no positions; ``y = W_o [a *
  sigmoid(x W_z)]``.
- MoE: ``s = sigmoid(x W_r)`` over all routed experts, the ``top_k``
  highest chosen, ``w_e = route_scale s_e / sum_chosen s``; ``y =
  sum_{e chosen and held} w_e FFN_e(x) + FFN_shared(x)``, ``FFN =
  W_down(silu(x W_gate) * x W_up)``.

Departures from the published model, each listed in the configuration
file under ``assumed``: the router's scores are read as sigmoid with
no selection bias and no groups; the decay and gate projections are
low rank at the head width; the GQA gate is elementwise; only the
experts ``expert_offset .. expert_offset + experts_held`` contribute
(the chip's share: what absent experts would add is left out, here as
in the program); weights are random.

``without`` names ONE mechanism to leave out, for the controls that
show each mechanism is seen by the comparison (``MECHANISMS``);
``"no_positions"`` left out means a rotary reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: what ``without`` may name
MECHANISMS = ("decay", "beta_factor", "short_conv", "qk_l2norm",
              "head_norm_gate", "gqa_gate", "no_positions",
              "shared_expert", "norm_topk", "absent_experts_left_out")


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * jnp.asarray(scale, F32)


def short_conv(x, taps, without=None):
    """x [S, C] -> silu of the causal depthwise convolution over time
    with ``taps`` [width, C] (the last tap meets the current row)."""
    taps = jnp.asarray(taps, F32)
    if without == "short_conv":
        return jax.nn.silu(x)
    width, S = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), F32), x])
    return jax.nn.silu(sum(taps[i] * padded[i:i + S]
                           for i in range(width)))


def kda(x, w, l, hp, without=None):
    """x [S, h] -> the layer's mixer output [S, h], by the recurrence."""
    S = x.shape[0]
    H, D = hp["kda_heads"], hp["kda_dim"]
    h = rms(x, w["ln1"][l], hp["eps"])

    def heads(name, taps):
        return short_conv(h @ jnp.asarray(w[name][l], F32), w[taps][l],
                          without).reshape(S, H, D)

    q, k, v = heads("wq", "conv_q"), heads("wk", "conv_k"), \
        heads("wv", "conv_v")
    if without != "qk_l2norm":
        q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6)
        k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q = q * D ** -0.5
    f = (h @ jnp.asarray(w["wf_down"][l], F32)) \
        @ jnp.asarray(w["wf_up"][l], F32) + jnp.asarray(w["dt_bias"][l], F32)
    alpha = jnp.exp(-jnp.exp(jnp.asarray(w["A_log"][l], F32))[:, None]
                    * jax.nn.softplus(f).reshape(S, H, D))
    if without == "decay":
        alpha = jnp.ones_like(alpha)
    beta = jax.nn.sigmoid(h @ jnp.asarray(w["wb"][l], F32))
    if without != "beta_factor":
        beta = 2.0 * beta
    eye = jnp.eye(D, dtype=F32)

    def step(state, row):
        q, k, v, a, b = row                   # [H, D] each, b [H]
        b = b[:, None, None]
        state = (eye - b * k[:, :, None] * k[:, None, :]) \
            @ (a[:, :, None] * state) + b * k[:, :, None] * v[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    _, o = jax.lax.scan(step, jnp.zeros((H, D, D), F32),
                        (q, k, v, alpha, beta))
    if without != "head_norm_gate":
        gate = jax.nn.sigmoid((h @ jnp.asarray(w["wg_down"][l], F32))
                              @ jnp.asarray(w["wg_up"][l], F32))
        o = rms(o, w["o_norm"][l], hp["eps"]) * gate.reshape(S, H, D)
    return o.reshape(S, H * D) @ jnp.asarray(w["wo"][l], F32)


def rotate(x, theta=10000.0):
    """x [S, heads, d] at positions 0..S-1, halves pairing: what the
    model does NOT do (``without="no_positions"``)."""
    S, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = (jnp.arange(S, dtype=F32)[:, None] * freq)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def gqa(x, w, l, hp, without=None):
    """x [S, h] -> the layer's mixer output [S, h]: full causal
    softmax, grouped key/value heads, no positions, an output gate."""
    S = x.shape[0]
    Hq, Hkv, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    h = rms(x, w["ln1"][l], hp["eps"])
    q = (h @ jnp.asarray(w["wq"][l], F32)).reshape(S, Hq, d)
    k = (h @ jnp.asarray(w["wk"][l], F32)).reshape(S, Hkv, d)
    v = (h @ jnp.asarray(w["wv"][l], F32)).reshape(S, Hkv, d)
    if without == "no_positions":
        q, k = rotate(q), rotate(k)
    k = jnp.repeat(k, Hq // Hkv, axis=1)       # head j <- KV head j // g
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    att = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    att = jnp.where(jnp.tril(jnp.ones((S, S), bool)), att, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(att, axis=-1), v
                   ).reshape(S, Hq * d)
    if w["wz"][l] is not None and without != "gqa_gate":
        a = a * jax.nn.sigmoid(h @ jnp.asarray(w["wz"][l], F32))
    return a @ jnp.asarray(w["wo"][l], F32)


def gated(x, gate, up, down):
    return (jax.nn.silu(x @ jnp.asarray(gate, F32))
            * (x @ jnp.asarray(up, F32))) @ jnp.asarray(down, F32)


def select(x, router, hp, without=None):
    """x [N, h] -> (weight of every routed expert [N, E], 0 where not
    chosen; margins [N]: how far any expert HELD here is from crossing
    the edge of the ``top_k``: a chosen one's score above the first one
    out's, one not chosen below the last one in's. Where only absent
    experts are near the edge, one absent expert goes for another of
    the same score, no held expert comes or goes and the result moves
    smoothly: that sets no margin."""
    s = jax.nn.sigmoid(x @ jnp.asarray(router, F32))
    E, k = s.shape[1], hp["top_k"]
    ranked = jnp.sort(s, axis=-1)
    a, b = ranked[:, E - k][:, None], ranked[:, E - k - 1][:, None]
    chosen = s >= a
    ids = jnp.arange(E)[None]
    held = (ids >= hp["expert_offset"]) \
        & (ids < hp["expert_offset"] + hp["experts_held"])
    margin = jnp.where(held, jnp.where(chosen, s - b, a - s),
                       jnp.inf).min(axis=-1)
    w = jnp.where(chosen, s, 0.0)
    if hp["norm_topk"] and without != "norm_topk":
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * hp["route_scale"], margin


def expert_layer(h, w, l, hp, without=None):
    """h [N, h] (already normed) -> (y [N, h], margins [N])."""
    weight, margin = select(h, w["router"][l], hp, without)
    first = hp.get("weights_offset", hp["expert_offset"])
    held = range(hp["expert_offset"],
                 hp["expert_offset"] + hp["experts_held"])
    if without == "absent_experts_left_out":
        held = range(first, first + w["e_gate"][l].shape[0])
    y = jnp.zeros_like(h)
    for e in held:
        i = e - first
        y = y + weight[:, e][:, None] * gated(
            h, w["e_gate"][l][i], w["e_up"][l][i], w["e_down"][l][i])
    if w["s_gate"][l] is not None and without != "shared_expert":
        y = y + gated(h, w["s_gate"][l], w["s_up"][l], w["s_down"][l])
    return y, margin


def forward_row(weights: dict, tokens, hp: dict, without=None):
    """tokens [S] int32 -> (float32 logits [S, rows held], margins [S]:
    the smallest margin any layer's selection has at that position)."""
    assert without is None or without in MECHANISMS, without
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(weights["embed"], F32)[tokens]
        least = jnp.full(tokens.shape, jnp.inf, F32)
        for l in range(len(weights["ln1"])):
            mixer = gqa if l in hp["gqa_layers"] else kda
            x = x + mixer(x, weights, l, hp, without)
            y, m = expert_layer(rms(x, weights["ln2"][l], hp["eps"]),
                                weights, l, hp, without)
            x, least = x + y, jnp.minimum(least, m)
        x = rms(x, weights["ln_f"], hp["eps"])
        return x @ jnp.asarray(weights["head"], F32), least


def forward(weights: dict, tokens, hp: dict, without=None,
            margins: bool = False):
    """tokens [B, S] int32 -> float32 logits [B, S, rows held]; with
    ``margins`` also [B, S]. ONE SEQUENCE AT A TIME (``lax.map`` over
    the rows, which share nothing), as ``axk1_reference.forward`` and
    for its reasons: a sequence's result cannot depend on how many
    others are beside it, and the pass holds one row's activations
    beside the resident weights."""
    logits, least = jax.lax.map(
        lambda row: forward_row(weights, row, hp, without), tokens)
    return (logits, least) if margins else logits


def loss(weights: dict, tokens, hp: dict):
    """Mean next-token cross-entropy of tokens [B, S + 1]."""
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(forward(weights, tokens[:, :-1], hp), -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))


def from_program(params: dict) -> dict:
    """The program's parameter tree (one tree a layer), renamed to the
    flat dict above. The only place that knows the program's names;
    arrays are passed on as they are held, never copied or upcast."""
    layers = params["layers"]

    def leaf(name, sub=None):
        return [(p[name] if sub is None else p[name][sub])
                if name in p else None for p in layers]

    out = {"embed": params["embed"]["kernel"],
           "head": params["head"]["kernel"],
           "ln_f": params["ln_f_scale"],
           "ln1": leaf("ln1_scale"), "ln2": leaf("ln2_scale"),
           "o_norm": leaf("o_norm_scale"),
           "router": leaf("router", "kernel")}
    for name in ("wq", "wk", "wv", "wz", "wo", "wb", "wf_down", "wf_up",
                 "wg_down", "wg_up"):
        out[name] = leaf(name, "kernel")
    for name in ("conv_q", "conv_k", "conv_v", "A_log", "dt_bias"):
        out[name] = leaf(name)
    for group, short in (("experts", "e"), ("shared", "s")):
        for part in ("gate", "up", "down"):
            out[f"{short}_{part}"] = leaf(group, part)
    return out
