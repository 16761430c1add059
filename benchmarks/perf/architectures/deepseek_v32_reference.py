"""The plain reference for DeepSeek-V3.2-Exp (``model_type``
``deepseek_v32``;
https://huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp/blob/main/config.json;
the equations follow ``inference/model.py`` of that repository): the
forward pass in float32 ``jax.numpy`` at
``default_matmul_precision("highest")``. No cache, no kernel, no
batching trick, and no code shared with the program under test.

``hp`` is a plain dict of the sizes (``heads``, ``nope``, ``rope``,
``v``, ``kv_rank``, ``eps``; ``theta``, ``factor``, ``orig_max``,
``beta_fast``, ``beta_slow``, ``mscale_all_dim``; ``n_group``,
``topk_group``, ``top_k``, ``norm_topk``, ``route_scale``;
``experts_held``, ``expert_offset``, optionally ``weights_offset``;
``index_heads``, ``index_dim``, ``index_topk``). Weights are a flat
dict of per-layer lists in whatever type the program holds them; each
matrix is upcast where it is used, and no float32 copy of the tree is
ever held: the check runs beside the resident weights and the page
pool, so the attention works through a sequence ``QUERY_BLOCK``
queries at a time (every query still sees its WHOLE prefix).

The equations, every layer (``h`` the normed residual of a token):

- Block: ``x += Attn(RMSNorm(x))``; ``x += FFN(RMSNorm(x))``; a final
  RMSNorm; an untied head.
- Latent attention: ``c_q = RMSNorm(h W_qa)``; ``[q_n | q_r] = c_q
  W_qb`` per head; ``[c | k_r] = h W_kva``; ``c = RMSNorm(c)``; ``[k_n
  | v] = c W_kvb`` per head; ``q_r``, ``k_r`` rotated (YaRN); scores
  ``(q_n . k_n + q_r . k_r) (d_n + d_r)^-0.5 m^2``, ``m = 0.1
  mscale_all_dim ln(factor) + 1``.
- Indexer: ``q^I_j = c_q W_iq`` (``index_heads`` heads of
  ``index_dim``), ``k^I = LayerNorm(h W_ik)`` (ONE head, scale and
  bias), both rotated on their first ``d_r`` values; ``w = h W_iw
  index_heads^-0.5 index_dim^-0.5``; ``I_t,s = sum_j w_t,j ReLU(q^I_t,j
  . k^I_s)`` for ``s <= t``; ``S_t`` = the ``min(index_topk, t + 1)``
  positions of largest ``I_t,s``: a literal ``top_k`` over the whole
  prefix at EVERY position.
- The softmax of the latent attention runs over ``s in S_t`` ONLY;
  ``o = (p v) W_o``.
- Dense FFN: ``W_down(silu(x W_gate) * x W_up)``.
- Expert layer: ``s = sigmoid(x W_r)``; groups and experts are chosen
  on ``s + b`` (``b`` the selection bias): a group's score is the sum
  of its two highest, the ``topk_group`` best groups stay, the
  ``top_k`` highest among their experts are chosen; ``w_e = route_scale
  s_e / sum_chosen s`` on the UNBIASED scores; ``y = sum_{e chosen and
  held} w_e FFN_e(x) + FFN_shared(x)``.

Departures from the published model, each listed in the configuration
file under ``assumed``: index keys and queries are not quantised (the
published code holds them in FP8 with a scale a token after a Hadamard
rotation of both, which leaves every product as it was); rotary
pairing by halves in the attention and in the indexer; only the
experts ``expert_offset .. + experts_held`` contribute; the
multi-token-prediction module is not held; weights are random.

``without`` names ONE mechanism to leave out, for the controls
(``MECHANISMS``). Beside the logits the pass gives, for every position,
how far its discrete choices are from their edges (``forward(...,
margins=True)``): the experts' margin, as ``axk1_reference`` has it,
and ``edge_weight``, the largest, over the layers and heads, of the
softmax weight that ALL the cached tokens carry together whose index
score lies within ``hp["index_tie_eps"]`` of the selection's edge on
the side they could cross from.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: what ``without`` may name
MECHANISMS = ("rotary", "yarn_blend", "mscale", "latent_norm", "sigmoid",
              "group_limit", "norm_topk", "route_scale", "shared_expert",
              "selection_bias", "selection", "index_rotary", "index_norm",
              "index_relu", "index_weights")
#: queries the attention works through at once
QUERY_BLOCK = 128


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
    """x [S, ..., rope] at positions 0..S-1; halves pairing."""
    if without == "rotary":
        return x
    S = x.shape[0]
    ang = jnp.arange(S, dtype=F32)[:, None] * inv_freq(hp, without)
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (-1,))
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def rotate_first(x, hp, without=None):
    """Rotary on the first ``rope`` values of x [S, ..., index_dim]."""
    if without == "index_rotary":
        return x
    r = hp["rope"]
    return jnp.concatenate([rotate(x[..., :r], hp, without), x[..., r:]],
                           axis=-1)


def indexer(h, cq, w, l, hp, without=None):
    """h [S, d], c_q [S, q_rank] -> (q^I [S, Hi, Di], k^I [S, Di],
    weights [S, Hi])."""
    S = h.shape[0]
    Hi, Di = hp["index_heads"], hp["index_dim"]
    qi = (cq @ jnp.asarray(w["wiq"][l], F32)).reshape(S, Hi, Di)
    ki = h @ jnp.asarray(w["wik"][l], F32)
    if without != "index_norm":
        ki = ki - jnp.mean(ki, axis=-1, keepdims=True)
        ki = ki / jnp.sqrt(jnp.mean(ki * ki, axis=-1, keepdims=True)
                           + hp["eps"])
        ki = ki * jnp.asarray(w["ik_scale"][l], F32) \
            + jnp.asarray(w["ik_bias"][l], F32)
    wi = (h @ jnp.asarray(w["wiw"][l], F32)) * (Hi ** -0.5 * Di ** -0.5)
    if without == "index_weights":
        wi = jnp.full_like(wi, Hi ** -0.5 * Di ** -0.5)
    return rotate_first(qi, hp, without), rotate_first(ki, hp, without), wi


def attention(x, w, l, hp, without=None):
    """x [S, d] -> (attention's output [S, d], edge_weight [S])."""
    S, _ = x.shape
    H, dn, dr, dv, r = (hp["heads"], hp["nope"], hp["rope"], hp["v"],
                        hp["kv_rank"])
    k_top = hp["index_topk"]
    tie = hp.get("index_tie_eps", 0.0)
    h = rms(x, w["ln1"][l], hp["eps"])
    cq = rms(h @ jnp.asarray(w["wqa"][l], F32), w["q_norm"][l], hp["eps"])
    q = (cq @ jnp.asarray(w["wqb"][l], F32)).reshape(S, H, dn + dr)
    ckv = h @ jnp.asarray(w["wkva"][l], F32)
    c, kr = ckv[..., :r], ckv[..., r:]
    if without != "latent_norm":
        c = rms(c, w["kv_norm"][l], hp["eps"])
    kv = (c @ jnp.asarray(w["wkvb"][l], F32)).reshape(S, H, dn + dv)
    qn, qr = q[..., :dn], rotate(q[..., dn:], hp, without)
    kn, v = kv[..., :dn], kv[..., dn:]
    kr = rotate(kr, hp, without)
    qi, ki, wi = indexer(h, cq, w, l, hp, without)
    m = 1.0
    if hp["factor"] > 1.0 and without != "mscale":
        m = 0.1 * hp["mscale_all_dim"] * math.log(hp["factor"]) + 1.0
    scale = (dn + dr) ** -0.5 * m * m
    Q = min(QUERY_BLOCK, S)
    pad = -S % Q
    keys = jnp.arange(S)

    def block(args):
        t, qn_b, qr_b, qi_b, wi_b = args            # t [Q]: positions
        causal = keys[None] <= t[:, None]                       # [Q, S]
        s = jnp.einsum("qhd,kd->qhk", qi_b, ki)
        if without != "index_relu":
            s = jax.nn.relu(s)
        score = jnp.where(causal, jnp.einsum("qhk,qh->qk", s, wi_b),
                          -jnp.inf)
        att = (jnp.einsum("qhd,khd->hqk", qn_b, kn)
               + jnp.einsum("qhd,kd->hqk", qr_b, kr)) * scale
        picked, near = causal, jnp.zeros_like(causal)
        if S > k_top and without != "selection":
            # a literal top k over the whole prefix; where the prefix
            # is shorter, the -inf it brings along are not causal
            top, idx = jax.lax.top_k(score, k_top + 1)
            picked = jnp.zeros(causal.shape, bool).at[
                jnp.arange(causal.shape[0])[:, None], idx[:, :k_top]
            ].set(True) & causal
            selecting = (t >= k_top)[:, None]
            last_in, first_out = top[:, k_top - 1:k_top], top[:, k_top:]
            near = selecting & causal & jnp.where(
                picked, score - first_out < tie, last_in - score < tie)
        att = jnp.where(picked | near, att, -jnp.inf)
        # every weight over the sum of the PICKED ones: a near token's
        # is what it carries, or would carry on crossing
        e = jnp.exp(att - jnp.max(att, axis=-1, keepdims=True))
        p = e / jnp.sum(jnp.where(picked, e, 0.0), axis=-1, keepdims=True)
        edge = jnp.max(jnp.sum(jnp.where(near, p, 0.0), axis=-1), axis=0)
        o = jnp.einsum("hqk,khd->qhd", jnp.where(picked, p, 0.0), v)
        return o.reshape(o.shape[0], H * dv), edge

    def blocks(a):
        a = jnp.concatenate(
            [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)]) if pad else a
        return a.reshape((-1, Q) + a.shape[1:])

    o, edge = jax.lax.map(block, tuple(
        blocks(a) for a in (keys, qn, qr, qi, wi)))
    o = o.reshape(-1, H * dv)[:S]
    return o @ jnp.asarray(w["wo"][l], F32), edge.reshape(-1)[:S]


def gated(x, gate, up, down):
    return (jax.nn.silu(x @ jnp.asarray(gate, F32))
            * (x @ jnp.asarray(up, F32))) @ jnp.asarray(down, F32)


def select(x, router, bias, hp, without=None):
    """x [N, h] -> (weight of every routed expert [N, E], 0 where not
    chosen; margins [N]), as ``axk1_reference.select`` with the
    selection made on ``s + bias`` and weighted by ``s``: the smaller
    of the groups' margin (the last group in against the first one
    out) and the experts' (how far any expert HELD here, in a kept
    group, is from crossing the edge of the top k), both in the biased
    scores the selection is made on."""
    logits = x @ jnp.asarray(router, F32)
    s = jax.nn.softmax(logits, axis=-1) if without == "sigmoid" \
        else jax.nn.sigmoid(logits)
    sb = s if without == "selection_bias" else s + jnp.asarray(bias, F32)
    N, E = s.shape
    G, kg, k = hp["n_group"], hp["topk_group"], hp["top_k"]
    open_ = jnp.ones((N, E), bool)
    margin = jnp.full((N,), jnp.inf, F32)
    if kg < G and without != "group_limit":
        per = E // G
        two = jnp.sort(sb.reshape(N, G, per), axis=-1)[..., -2:].sum(-1)
        ranked = jnp.sort(two, axis=-1)                  # ascending
        margin = ranked[:, G - kg] - ranked[:, G - kg - 1]
        open_ = jnp.repeat(two >= ranked[:, G - kg][:, None], per, axis=1)
    eligible = jnp.where(open_, sb, -jnp.inf)
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
    weight, margin = select(h, w["router"][l], w["router_bias"][l], hp,
                            without)
    first = hp.get("weights_offset", hp["expert_offset"])

    def add(e, y):
        # one held expert after another, its three matrices upcast
        # where they are used (a loop, not 8 copies of the body: the
        # check's compile is part of every run's set-up)
        i = e - first
        return y + jnp.take(weight, e, axis=1)[:, None] * gated(
            h, w["e_gate"][l][i], w["e_up"][l][i], w["e_down"][l][i])

    y = jax.lax.fori_loop(
        hp["expert_offset"], hp["expert_offset"] + hp["experts_held"], add,
        jnp.zeros_like(h))
    if w["s_gate"][l] is not None and without != "shared_expert":
        y = y + gated(h, w["s_gate"][l], w["s_up"][l], w["s_down"][l])
    return y, margin


def forward_row(weights: dict, tokens, hp: dict, without=None,
                head: bool = True):
    """tokens [S] int32 -> (float32 logits [S, rows held] (None without
    ``head``), margins [S]: the smallest margin any expert layer's
    selection has at that position, edge_weight [S]: the largest
    weight any head of any layer gives the tokens at its selection's
    edge, summed)."""
    assert without is None or without in MECHANISMS, without
    with jax.default_matmul_precision("highest"):
        S = tokens.shape[0]
        x = jnp.asarray(weights["embed"], F32)[tokens]
        least = jnp.full((S,), jnp.inf, F32)
        edge = jnp.zeros((S,), F32)
        for l in range(len(weights["ln1"])):
            a, e = attention(x, weights, l, hp, without)
            x, edge = x + a, jnp.maximum(edge, e)
            h = rms(x, weights["ln2"][l], hp["eps"])
            if weights["d_gate"][l] is not None:
                y = gated(h, weights["d_gate"][l], weights["d_up"][l],
                          weights["d_down"][l])
            else:
                y, m = expert_layer(h, weights, l, hp, without)
                least = jnp.minimum(least, m)
            x = x + y
        if not head:
            return None, least, edge
        x = rms(x, weights["ln_f"], hp["eps"])
        return x @ jnp.asarray(weights["head"], F32), least, edge


def forward(weights: dict, tokens, hp: dict, without=None,
            margins: bool = False):
    """tokens [B, S] int32 -> float32 logits [B, S, rows held]; with
    ``margins`` INSTEAD ``(margins [B, S], edge_weight [B, S])``
    (``forward_row``, the head left out). ONE SEQUENCE AT A TIME
    (``lax.map`` over the rows, which share nothing), as
    ``axk1_reference.forward`` and for its reasons."""
    if margins:
        return jax.lax.map(lambda row: forward_row(
            weights, row, hp, without, head=False)[1:], tokens)
    return jax.lax.map(
        lambda row: forward_row(weights, row, hp, without)[0], tokens)


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
            "wiq": kernels("wiq"), "wik": kernels("wik"),
            "wiw": kernels("wiw"),
            "ik_scale": [p["ik_norm_scale"] for p in layers],
            "ik_bias": [p["ik_norm_bias"] for p in layers],
            "router": part("router", "kernel"),
            "router_bias": part("router", "bias"),
            "d_gate": part("ffn", "gate"), "d_up": part("ffn", "up"),
            "d_down": part("ffn", "down"),
            "e_gate": part("experts", "gate"), "e_up": part("experts", "up"),
            "e_down": part("experts", "down"),
            "s_gate": part("shared", "gate"), "s_up": part("shared", "up"),
            "s_down": part("shared", "down")}
