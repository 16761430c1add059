"""The plain reference for A.X-K1 (``model_type`` ``axk1``;
https://huggingface.co/skt/A.X-K1/blob/main/config.json): the forward
pass in float32 ``jax.numpy`` at ``default_matmul_precision("highest")``.
No cache, no kernel, no batching trick, and no code shared with the
program under test.

``hp`` is a plain dict of the sizes (``heads``, ``nope``, ``rope``,
``v``, ``kv_rank``, ``eps``; ``theta``, ``factor``, ``orig_max``,
``beta_fast``, ``beta_slow``, ``mscale_all_dim``; ``n_group``,
``topk_group``, ``top_k``, ``norm_topk``, ``route_scale``;
``experts_held``, ``expert_offset``, and optionally ``weights_offset``:
the id of the first expert in the weight arrays, ``expert_offset`` if
absent). Weights are a flat dict of per-layer lists in whatever type
the program holds them; each matrix is upcast where it is used, one
layer's (one expert's) at a time, and no float32 copy of the tree is
ever held: the check runs beside the resident weights.

The equations (``h`` hidden, ``H`` heads, ``d_n`` ``nope``, ``d_r``
``rope``, ``d_v`` ``v``):

- Block: ``x += Attn(RMSNorm(x))``; ``x += FFN(RMSNorm(x))``; a final
  RMSNorm; an untied head.
- Attention: ``c_q = RMSNorm(x W_qa)``; ``[q_n | q_r] = c_q W_qb`` per
  head; ``[c | k_r] = x W_kva``; ``c = RMSNorm(c)``; ``[k_n | v] = c
  W_kvb`` per head; ``q_r`` and ``k_r`` rotated (one ``k_r`` a token,
  shared by all heads); scores ``(q_n . k_n + q_r . k_r) (d_n +
  d_r)^-0.5 m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``;
  causal softmax; ``o = (p v) W_o``.
- Rotary: YaRN. ``inv_freq_i`` blends ``theta^(-2i/d_r)`` and the same
  over ``factor`` by the linear ramp between the correction dimensions
  of ``beta_fast`` and ``beta_slow`` rotations over ``orig_max``
  positions; the cos/sin factor ``mscale / mscale_all_dim`` is 1.
- Dense FFN: ``W_down(silu(x W_gate) * x W_up)``.
- Expert layer: ``s = sigmoid(x W_r)`` over all routed experts; groups
  of equal size; a group's score is the sum of its two highest ``s``;
  the ``topk_group`` best groups stay; of their experts the ``top_k``
  highest ``s`` are chosen; ``w_e = route_scale s_e / sum_chosen s``;
  ``y = sum_{e chosen and held} w_e FFN_e(x) + FFN_shared(x)``.

Departures from the published model, each listed in the configuration
file under ``assumed``: ``topk_method`` ``"none"`` is read as the
family's group-limited selection WITHOUT a selection bias; the rotary
pairing is by halves (dimension ``i`` with ``i + d_r/2``); only the
experts ``expert_offset .. expert_offset + experts_held`` contribute
(the chip's share: what absent experts would add is left out, here as
in the program); weights are random.

``without`` names ONE mechanism to leave out, for the controls that
show each mechanism is seen by the comparison (``MECHANISMS``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: what ``without`` may name
MECHANISMS = ("rotary", "yarn_blend", "mscale", "latent_norm", "sigmoid",
              "group_limit", "norm_topk", "route_scale", "shared_expert",
              "absent_experts_left_out")


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * jnp.asarray(scale, F32)


def inv_freq(hp, without=None):
    dim = hp["rope"]
    plain = [hp["theta"] ** (-2.0 * i / dim) for i in range(dim // 2)]
    if hp["factor"] <= 1.0 or without == "yarn_blend":
        return jnp.asarray(plain, F32)

    def corr(rot):
        return dim * math.log(hp["orig_max"] / (rot * 2 * math.pi)) \
            / (2 * math.log(hp["theta"]))

    low = max(math.floor(corr(hp["beta_fast"])), 0)
    high = min(math.ceil(corr(hp["beta_slow"])), dim - 1)
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f / hp["factor"] * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, F32)


def rotate(x, hp, without=None):
    """x [B, S, ..., rope] at positions 0..S-1; halves pairing."""
    if without == "rotary":
        return x
    S = x.shape[1]
    ang = jnp.arange(S, dtype=F32)[:, None] * inv_freq(hp, without)
    ang = ang.reshape((1, S) + (1,) * (x.ndim - 3) + (-1,))
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def attention(x, w, l, hp, without=None):
    B, S, _ = x.shape
    H, dn, dr, dv, r = (hp["heads"], hp["nope"], hp["rope"], hp["v"],
                        hp["kv_rank"])
    h = rms(x, w["ln1"][l], hp["eps"])
    cq = rms(h @ jnp.asarray(w["wqa"][l], F32), w["q_norm"][l], hp["eps"])
    q = (cq @ jnp.asarray(w["wqb"][l], F32)).reshape(B, S, H, dn + dr)
    ckv = h @ jnp.asarray(w["wkva"][l], F32)
    c, kr = ckv[..., :r], ckv[..., r:]
    if without != "latent_norm":
        c = rms(c, w["kv_norm"][l], hp["eps"])
    kv = (c @ jnp.asarray(w["wkvb"][l], F32)).reshape(B, S, H, dn + dv)
    qn, qr = q[..., :dn], rotate(q[..., dn:], hp, without)
    kn, v = kv[..., :dn], kv[..., dn:]
    kr = rotate(kr, hp, without)
    m = 1.0
    if hp["factor"] > 1.0 and without != "mscale":
        m = 0.1 * hp["mscale_all_dim"] * math.log(hp["factor"]) + 1.0
    att = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn)
           + jnp.einsum("bqhd,bkd->bhqk", qr, kr)) \
        * ((dn + dr) ** -0.5 * m * m)
    att = jnp.where(jnp.tril(jnp.ones((S, S), bool)), att, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1), v)
    return o.reshape(B, S, H * dv) @ jnp.asarray(w["wo"][l], F32)


def gated(x, gate, up, down):
    return (jax.nn.silu(x @ jnp.asarray(gate, F32))
            * (x @ jnp.asarray(up, F32))) @ jnp.asarray(down, F32)


def select(x, router, hp, without=None):
    """x [N, h] -> (weight of every routed expert [N, E], 0 where not
    chosen; margins [N]: how far the scores are from a selection that
    changes the result BY A JUMP, the smaller of two distances. The
    groups': the last group in against the first one out (another
    group brings other experts, whoever holds them). The experts': how
    far any expert HELD here, in a kept group, is from crossing the
    edge: a chosen one's score above the first one out's, one not
    chosen below the last one in's. Where only absent experts are near
    the edge, one absent expert goes for another of the same score, no
    held expert comes or goes, and the result moves by their
    difference over the sum of the chosen scores, smoothly: that is no
    jump and sets no margin."""
    logits = x @ jnp.asarray(router, F32)
    s = jax.nn.softmax(logits, axis=-1) if without == "sigmoid" \
        else jax.nn.sigmoid(logits)
    N, E = s.shape
    G, kg, k = hp["n_group"], hp["topk_group"], hp["top_k"]
    open_ = jnp.ones((N, E), bool)
    margin = jnp.full((N,), jnp.inf, F32)
    if kg < G and without != "group_limit":
        per = E // G
        two = jnp.sort(s.reshape(N, G, per), axis=-1)[..., -2:].sum(-1)
        ranked = jnp.sort(two, axis=-1)                  # ascending
        margin = ranked[:, G - kg] - ranked[:, G - kg - 1]
        open_ = jnp.repeat(two >= ranked[:, G - kg][:, None], per, axis=1)
    eligible = jnp.where(open_, s, -1.0)
    ranked = jnp.sort(eligible, axis=-1)
    a, b = ranked[:, E - k][:, None], ranked[:, E - k - 1][:, None]
    chosen = eligible >= a
    ids = jnp.arange(E)[None]
    held = (ids >= hp["expert_offset"]) \
        & (ids < hp["expert_offset"] + hp["experts_held"])
    crossing = jnp.where(chosen, eligible - b, a - eligible)
    margin = jnp.minimum(margin, jnp.where(
        held & open_, crossing, jnp.inf).min(axis=-1))
    w = jnp.where(chosen, s, 0.0)
    if hp["norm_topk"] and without != "norm_topk":
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    if without != "route_scale":
        w = w * hp["route_scale"]
    return w, margin


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


def forward_rows(weights: dict, tokens, hp: dict, without=None):
    """tokens [B, S] int32 -> (float32 logits [B, S, rows held],
    margins [B, S]: the smallest margin any expert layer's selection
    has at that position), all rows in one pass."""
    assert without is None or without in MECHANISMS, without
    with jax.default_matmul_precision("highest"):
        B, S = tokens.shape
        x = jnp.asarray(weights["embed"], F32)[tokens]
        least = jnp.full((B * S,), jnp.inf, F32)
        for l in range(len(weights["ln1"])):
            x = x + attention(x, weights, l, hp, without)
            h = rms(x, weights["ln2"][l], hp["eps"])
            if weights["d_gate"][l] is not None:
                y = gated(h, weights["d_gate"][l], weights["d_up"][l],
                          weights["d_down"][l])
            else:
                y, m = expert_layer(h.reshape(B * S, -1), weights, l, hp,
                                    without)
                y, least = y.reshape(B, S, -1), jnp.minimum(least, m)
            x = x + y
        x = rms(x, weights["ln_f"], hp["eps"])
        return x @ jnp.asarray(weights["head"], F32), least.reshape(B, S)


def forward(weights: dict, tokens, hp: dict, without=None,
            margins: bool = False):
    """tokens [B, S] int32 -> float32 logits [B, S, rows held]; with
    ``margins`` also [B, S] (``forward_rows``). ONE SEQUENCE AT A TIME
    (``lax.map`` over the rows, which share nothing): a sequence's
    result then cannot depend on how many others are beside it, and
    the pass holds one row's activations. It is not a matter of taste:
    on a TPU v5e the same pass over all rows at once computed the FIRST
    TWO of nine rows of 223 tokens wrongly (130% off, both at float32
    ``highest``; eight rows of 64 and seventeen of 223 were right, and
    so was the CPU at every shape: builder's chip run, PR 37)."""
    logits, least = jax.lax.map(
        lambda row: forward_rows(weights, row[None], hp, without), tokens)
    logits, least = logits[:, 0], least[:, 0]
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

    def kernels(name):
        return [p[name]["kernel"] for p in layers]

    def part(group, leaf):
        return [p[group][leaf] if group in p else None for p in layers]

    return {"embed": params["embed"]["kernel"],
            "head": params["head"]["kernel"],
            "ln_f": params["ln_f_scale"],
            "ln1": [p["ln1_scale"] for p in layers],
            "ln2": [p["ln2_scale"] for p in layers],
            "q_norm": [p["q_norm_scale"] for p in layers],
            "kv_norm": [p["kv_norm_scale"] for p in layers],
            "wqa": kernels("wqa"), "wqb": kernels("wqb"),
            "wkva": kernels("wkva"), "wkvb": kernels("wkvb"),
            "wo": kernels("wo"),
            "router": [p["router"]["kernel"] if "router" in p else None
                       for p in layers],
            "d_gate": part("ffn", "gate"), "d_up": part("ffn", "up"),
            "d_down": part("ffn", "down"),
            "e_gate": part("experts", "gate"), "e_up": part("experts", "up"),
            "e_down": part("experts", "down"),
            "s_gate": part("shared", "gate"), "s_up": part("shared", "up"),
            "s_down": part("shared", "down")}
