"""The plain reference for granite-4.0-h-small (``model_type``
``granitemoehybrid``;
https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json):
the forward pass in float32 ``jax.numpy`` at
``default_matmul_precision("highest")``. The Mamba-2 mixer runs its
recurrence ONE TOKEN AT A TIME and nothing else (no chunked form); the
attention is a full causal softmax without positions; the expert layer
loops over the experts held, one at a time. No cache, no kernel, no
batching trick, and no code shared with the program under test.

``hp`` is a plain dict: the sizes (``heads``, ``kv_heads``,
``head_dim``; ``ssm_heads``, ``ssm_head_dim``, ``ssm_state``,
``ssm_groups``, ``conv``; ``eps``; ``layer_types``: each layer's mixer,
``"mamba"`` or ``"attention"``; ``top_k``; ``experts_held``,
``expert_offset``: the chip's share of the routed experts, and
optionally ``weights_offset``: the id of the first expert in the weight
arrays, ``expert_offset`` if absent) and the constants under the names
the published config gives them (``embedding_multiplier``,
``residual_multiplier``, ``attention_multiplier``, ``logits_scaling``).
Weights are a dict with a list of per-layer dicts, in whatever type the
program holds them; a matrix is upcast where it is used, one expert's at
a time and the tied table a BLOCK of rows at a time, so that no float32
copy of the tree or of the table is ever held.

The equations (``r = residual_multiplier``; RMSNorm before every
branch and a final one; no bias but the convolution's; the head IS the
table)::

    x_0 = E[token] * embedding_multiplier
    for layer l:
        u  = RMSNorm(x)
        x += r * (Mamba2(u) if layer_types[l] == "mamba" else Attention(u))
        v  = RMSNorm(x)
        x += r * (MoE(v) + SharedMLP(v))
    logits = (RMSNorm(x) E^T) / logits_scaling

- Mamba2 (H heads of P channels, state N wide, G groups of heads that
  share B and C, here one): ``[z | xBC | dt] = u W_in``; ``xBC_t =
  silu(sum_j w[j] xBC_{t-conv+1+j} + b)`` depthwise and causal; ``dt =
  softplus(dt + dt_bias)``; ``a = exp(-exp(A_log) dt)`` a head;
  ``S_t[h] = a_t[h] S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t``; ``y_t[h] =
  S_t[h] C_t + D[h] x_t[h]``; out = ``(RMSNorm(y_t * silu(z_t)) *
  w_norm) W_out``, the norm over a GROUP's channels (one group: all).
- Attention (Hq query heads over Hkv key/value heads, query head j
  with KV head ``j // (Hq / Hkv)``): no positions; scores ``q . k *
  attention_multiplier``; causal softmax; ``W_o``.
- MoE: ``l = v W_r`` over the router's whole width; the ``top_k``
  largest LOGITS; ``w = softmax`` over those; ``sum_{e chosen, e held}
  w_e W_down,e [silu(v W_gate,e) * (v W_up,e)]``. SharedMLP: the same
  gated form, every token.

Readings the published config does not settle are listed in the
configuration file under ``assumed``.

``without`` names ONE mechanism to leave out or swap, for the controls
that show each mechanism is seen by the comparison (``MECHANISMS``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: what ``without`` may name. ``nope``: rotary positions (theta 10000,
#: halves pairing) added to q and k; ``attention_multiplier``: the
#: scores scaled by ``head_dim ** -0.5`` instead; ``softmax_weights``:
#: sigmoid scores of the chosen logits, normalised, in the softmax's
#: place; ``normalised_weights``: the softmax over the router's WHOLE
#: width at the chosen experts, not renormalised; ``norm_groups``: the
#: gated norm in two groups; ``layer_order``: the attention layers
#: moved before the Mamba layers (the first to index 0).
MECHANISMS = (
    "residual_multiplier", "attention_multiplier", "nope",
    "embedding_multiplier", "logits_scaling", "shared_mlp",
    "softmax_weights", "normalised_weights", "conv_bias", "d_skip",
    "gate_z", "norm_groups", "layer_order", "decay", "dt_bias",
    "short_conv")
#: rows of the table upcast at once for the tied head
BLOCK = 8192


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * jnp.asarray(scale, F32)


def mamba(u, w, hp, without=None):
    """u [S, h] (normed) -> the mixer's output [S, h], by the
    recurrence."""
    S = u.shape[0]
    H, P, N, G = hp["ssm_heads"], hp["ssm_head_dim"], hp["ssm_state"], \
        hp["ssm_groups"]
    W, bc = H * P, G * N
    zxbcdt = u @ jnp.asarray(w["in_proj"], F32)
    z, xBC, dt = zxbcdt[:, :W], zxbcdt[:, W:2 * W + 2 * bc], \
        zxbcdt[:, 2 * W + 2 * bc:]
    if without != "short_conv":
        taps = jnp.asarray(w["conv_w"], F32)                 # [conv, C]
        width = taps.shape[0]
        padded = jnp.concatenate([jnp.zeros((width - 1, xBC.shape[1]), F32),
                                  xBC])
        xBC = sum(taps[j] * padded[j:j + S] for j in range(width))
        if without != "conv_bias":
            xBC = xBC + jnp.asarray(w["conv_b"], F32)
    xBC = jax.nn.silu(xBC)
    x = xBC[:, :W].reshape(S, H, P)
    group = jnp.arange(H) // (H // G)
    B = xBC[:, W:W + bc].reshape(S, G, N)[:, group]          # [S, H, N]
    C = xBC[:, W + bc:].reshape(S, G, N)[:, group]
    if without != "dt_bias":
        dt = dt + jnp.asarray(w["dt_bias"], F32)
    dt = jax.nn.softplus(dt)                                 # [S, H]
    a = jnp.exp(-jnp.exp(jnp.asarray(w["A_log"], F32)) * dt)
    if without == "decay":
        a = jnp.ones_like(a)

    def step(state, row):
        x, B, C, dt, a = row              # [H, P], [H, N], [H, N], [H], [H]
        state = a[:, None, None] * state \
            + (dt[:, None] * x)[:, :, None] * B[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, C)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, B, C, dt, a))
    if without != "d_skip":
        y = y + jnp.asarray(w["D_skip"], F32)[:, None] * x
    y = y.reshape(S, W)
    if without != "gate_z":
        y = y * jax.nn.silu(z)
    groups = 2 * G if without == "norm_groups" else G
    y = y.reshape(S, groups, W // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + hp["eps"])
    y = y.reshape(S, W) * jnp.asarray(w["ssm_norm"], F32)
    return y @ jnp.asarray(w["out_proj"], F32)


def rotate(x, theta=10000.0):
    """x [S, heads, d] at positions 0..S-1, halves pairing (the
    ``nope`` control's: the model has no positions)."""
    S, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = (jnp.arange(S, dtype=F32)[:, None] * freq)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def attention(u, w, hp, without=None):
    """u [S, h] (normed) -> the mixer's output [S, h]: full causal
    softmax, grouped key/value heads, no positions, scores scaled by
    ``attention_multiplier``."""
    S = u.shape[0]
    Hq, Hkv, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    q = (u @ jnp.asarray(w["wq"], F32)).reshape(S, Hq, d)
    k = (u @ jnp.asarray(w["wk"], F32)).reshape(S, Hkv, d)
    v = (u @ jnp.asarray(w["wv"], F32)).reshape(S, Hkv, d)
    if without == "nope":
        q, k = rotate(q), rotate(k)
    k = jnp.repeat(k, Hq // Hkv, axis=1)       # head j <- KV head j // g
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    scale = d ** -0.5 if without == "attention_multiplier" \
        else hp["attention_multiplier"]
    att = jnp.einsum("qhd,khd->hqk", q, k) * scale
    att = jnp.where(jnp.tril(jnp.ones((S, S), bool)), att, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(att, axis=-1), v
                      ).reshape(S, Hq * d) @ jnp.asarray(w["wo"], F32)


def gated(x, gate, up, down):
    return (jax.nn.silu(x @ jnp.asarray(gate, F32))
            * (x @ jnp.asarray(up, F32))) @ jnp.asarray(down, F32)


def select(v, router, hp, without=None):
    """v [N, h] -> (weight of every expert of the router [N, E], 0
    where not chosen; margins [N]: how far, in the LOGIT, any expert
    HELD here is from crossing the edge of the top k: a chosen one
    above the first one out, one not chosen below the last one in.
    Where only absent experts are near the edge one goes for another of
    the same logit, neither is computed here, and the held experts'
    weights move continuously: nothing jumps)."""
    logits = v @ jnp.asarray(router, F32)
    N, E = logits.shape
    k = hp["top_k"]
    ranked = jnp.sort(logits, axis=-1)
    a, b = ranked[:, E - k][:, None], ranked[:, E - k - 1][:, None]
    chosen = logits >= a
    ids = jnp.arange(E)[None]
    held = (ids >= hp["expert_offset"]) \
        & (ids < hp["expert_offset"] + hp["experts_held"])
    margin = jnp.where(held, jnp.where(chosen, logits - b, a - logits),
                       jnp.inf).min(axis=-1)
    if without == "softmax_weights":
        s = jnp.where(chosen, jax.nn.sigmoid(logits), 0.0)
        w = s / s.sum(-1, keepdims=True)
    elif without == "normalised_weights":
        w = jnp.where(chosen, jax.nn.softmax(logits, axis=-1), 0.0)
    else:
        w = jax.nn.softmax(jnp.where(chosen, logits, -jnp.inf), axis=-1)
    return w, margin


def expert_layer(v, w, hp, without=None):
    """v [N, h] (normed) -> (routed [N, h]: the held experts' part,
    shared [N, h]: the shared MLP's, margins [N])."""
    weight, margin = select(v, w["router"], hp, without)
    first = hp.get("weights_offset", hp["expert_offset"])
    routed = jnp.zeros_like(v)
    for e in range(hp["expert_offset"],
                   hp["expert_offset"] + hp["experts_held"]):
        i = e - first
        routed = routed + weight[:, e][:, None] * gated(
            v, w["e_gate"][i], w["e_up"][i], w["e_down"][i])
    shared = jnp.zeros_like(v) if without == "shared_mlp" else gated(
        v, w["s_gate"], w["s_up"], w["s_down"])
    return routed, shared, margin


def logits_of(x, table, hp, without=None):
    """x [S, h] (normed) -> [S, rows]: the tied head, a block of the
    table's rows at a time."""
    rows = table.shape[0]
    out = jnp.concatenate(
        [x @ jnp.asarray(table[a:min(a + BLOCK, rows)], F32).T
         for a in range(0, rows, BLOCK)], axis=-1)
    return out if without == "logits_scaling" \
        else out / hp["logits_scaling"]


def forward_row(weights: dict, tokens, hp: dict, without=None):
    """tokens [S] int32 -> (float32 logits [S, rows held], margins [S]:
    the smallest margin any layer's selection has at that position)."""
    assert without is None or without in MECHANISMS, without
    with jax.default_matmul_precision("highest"):
        r = 1.0 if without == "residual_multiplier" \
            else hp["residual_multiplier"]
        x = jnp.asarray(weights["embed"][tokens], F32)
        if without != "embedding_multiplier":
            x = x * hp["embedding_multiplier"]
        order = list(range(len(weights["layers"])))
        if without == "layer_order":
            order.sort(key=lambda l: hp["layer_types"][l] != "attention")
        least = jnp.full(tokens.shape, jnp.inf, F32)
        for l in order:
            w = weights["layers"][l]
            u = rms(x, w["ln1"], hp["eps"])
            mixer = attention if hp["layer_types"][l] == "attention" \
                else mamba
            x = x + r * mixer(u, w, hp, without)
            v = rms(x, w["ln2"], hp["eps"])
            routed, shared, m = expert_layer(v, w, hp, without)
            x = x + r * (routed + shared)
            least = jnp.minimum(least, m)
        x = rms(x, weights["ln_f"], hp["eps"])
        return logits_of(x, weights["embed"], hp, without), least


def forward(weights: dict, tokens, hp: dict, without=None,
            margins: bool = False):
    """tokens [B, S] int32 -> float32 logits [B, S, rows held]; with
    ``margins`` also [B, S] (``forward_row``). ONE SEQUENCE AT A TIME
    (``lax.map`` over the rows, which share nothing), as
    ``solar_open2_reference.forward`` and for its reasons."""
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
    dict above. The only place that knows the program's names; arrays
    are passed on as they are held, never copied or upcast."""
    def layer(p):
        out = {"ln1": p["ln1_scale"], "ln2": p["ln2_scale"],
               "router": p["router"]["kernel"],
               "e_gate": p["experts"]["gate"], "e_up": p["experts"]["up"],
               "e_down": p["experts"]["down"],
               "s_gate": p["shared"]["gate"], "s_up": p["shared"]["up"],
               "s_down": p["shared"]["down"]}
        for name in ("wq", "wk", "wv", "wo", "in_proj", "out_proj"):
            if name in p:
                out[name] = p[name]["kernel"]
        for name in ("conv_w", "conv_b", "dt_bias", "A_log", "D_skip",
                     "ssm_norm"):
            if name in p:
                out[name] = p[name]
        return out

    return {"embed": params["embed"]["kernel"],
            "ln_f": params["ln_f_scale"],
            "layers": [layer(p) for p in params["layers"]]}
