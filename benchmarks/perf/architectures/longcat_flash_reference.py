"""The plain reference for LongCat-Flash-Chat (``model_type``
``longcat_flash``; https://huggingface.co/meituan-longcat/LongCat-Flash-Chat/blob/main/config.json,
tech report arXiv:2509.01322): the forward pass in float32
``jax.numpy`` at ``default_matmul_precision("highest")``. No cache, no
kernel, no batching trick, and no code shared with the program under
test.

``hp`` is a plain dict of the sizes (``heads``, ``nope``, ``rope``,
``v``, ``kv_rank``, ``eps``, ``theta``; ``top_k``, ``route_scale``,
``n_routed``: the router's first ``n_routed`` scores are routed
experts, the others identity experts; ``experts_held``,
``expert_offset``, and optionally ``weights_offset``: the id of the
first expert in the weight arrays, ``expert_offset`` if absent). Weights are a flat dict of per-layer lists (a layer's two
attentions and two FFNs: lists of two) in whatever type the program
holds them; each matrix is upcast where it is used, one expert's at a
time, and no float32 copy of the tree is ever held: the check runs
beside the resident weights.

The equations (``h`` hidden, ``H`` heads, ``d_n`` ``nope``, ``d_r``
``rope``, ``d_v`` ``v``, ``r_q`` / ``r_kv`` the ranks; RMSNorm
everywhere, no biases, a final RMSNorm, an untied head). One layer::

    x  = x + Attn_0(ln_a0(x))
    u  = ln_f0(x)
    s  = MoE(u)                  # the shortcut branch leaves here ...
    x  = x + FFN_0(u)
    x  = x + Attn_1(ln_a1(x))
    x  = x + FFN_1(ln_f1(x))
    x  = x + s                   # ... and rejoins here

- Attention: ``c_q = RMSNorm(x W_qa)``; ``[q_n | q_r] = (c_q W_qb)
  sqrt(h / r_q)`` per head; ``[c | k_r] = x W_kva``; ``c = RMSNorm(c)
  sqrt(h / r_kv)`` (not ``k_r``); ``[k_n | v] = c W_kvb`` per head;
  ``q_r`` and ``k_r`` rotated (plain rotary, one ``k_r`` a token for
  all heads); scores ``(q_n . k_n + q_r . k_r) (d_n + d_r)^-0.5``;
  causal softmax; ``o = (p v) W_o``.
- FFN: ``W_down(silu(x W_gate) * x W_up)``.
- MoE: ``p = softmax(u W_r)`` over the router's whole width; the
  ``top_k`` ids with the highest ``p + b`` (``b`` the selection bias,
  for selection only); ``w_e = route_scale p_e`` (the unbiased score,
  not renormalised); ``s = sum_{e chosen, e < n_routed, e held} w_e
  FFN_e(u) + sum_{e chosen, e >= n_routed} w_e u``.

Departures from the published model, each listed in the configuration
file under ``assumed``: the rotary pairing is by halves (dimension
``i`` with ``i + d_r/2``); only the routed experts ``expert_offset ..
expert_offset + experts_held`` contribute (the chip's share: what
absent experts would add is left out, here as in the program), while
EVERY identity expert does (it holds nothing and is computed where the
token lives); weights and the selection bias are random.

``without`` names ONE mechanism to leave out, for the controls that
show each mechanism is seen by the comparison (``MECHANISMS``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: what ``without`` may name. ``shortcut``: a plain serial expert layer
#: in the branch's place (it reads ``ln_f1``'s output, the second
#: FFN's input, instead of ``ln_f0``'s).
MECHANISMS = ("zero_experts", "select_bias", "route_scale", "shortcut",
              "second_attention", "q_scale", "kv_scale", "rotary",
              "absent_experts_left_out")


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * jnp.asarray(scale, F32)


def rotate(x, hp, without=None):
    """x [B, S, ..., rope] at positions 0..S-1; halves pairing."""
    if without == "rotary":
        return x
    S, dim = x.shape[1], x.shape[-1]
    freq = jnp.asarray([hp["theta"] ** (-2.0 * i / dim)
                        for i in range(dim // 2)], F32)
    ang = (jnp.arange(S, dtype=F32)[:, None] * freq).reshape(
        (1, S) + (1,) * (x.ndim - 3) + (-1,))
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def attention(x, w, hp, without=None):
    """x [B, S, h] -> Attn(ln_a(x)) for ONE attention's weights ``w``."""
    B, S, hid = x.shape
    H, dn, dr, dv, r = (hp["heads"], hp["nope"], hp["rope"], hp["v"],
                        hp["kv_rank"])
    h = rms(x, w["ln"], hp["eps"])
    cq = rms(h @ jnp.asarray(w["wqa"], F32), w["q_norm"], hp["eps"])
    q = (cq @ jnp.asarray(w["wqb"], F32)).reshape(B, S, H, dn + dr)
    if without != "q_scale":
        q = q * (hid / cq.shape[-1]) ** 0.5
    ckv = h @ jnp.asarray(w["wkva"], F32)
    c, kr = rms(ckv[..., :r], w["kv_norm"], hp["eps"]), ckv[..., r:]
    if without != "kv_scale":
        c = c * (hid / r) ** 0.5
    kv = (c @ jnp.asarray(w["wkvb"], F32)).reshape(B, S, H, dn + dv)
    qn, qr = q[..., :dn], rotate(q[..., dn:], hp, without)
    kn, v = kv[..., :dn], kv[..., dn:]
    kr = rotate(kr, hp, without)
    att = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn)
           + jnp.einsum("bqhd,bkd->bhqk", qr, kr)) * (dn + dr) ** -0.5
    att = jnp.where(jnp.tril(jnp.ones((S, S), bool)), att, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(att, axis=-1), v)
    return o.reshape(B, S, H * dv) @ jnp.asarray(w["wo"], F32)


def gated(x, gate, up, down):
    return (jax.nn.silu(x @ jnp.asarray(gate, F32))
            * (x @ jnp.asarray(up, F32))) @ jnp.asarray(down, F32)


def select(u, router, bias, hp, without=None):
    """u [N, h] -> (weight of every score of the router [N, E], 0 where
    not chosen; margins [N]: how far, in ``p + b``, any expert that
    COUNTS here is from crossing the edge of the top k: a chosen one
    above the first one out, one not chosen below the last one in. The
    experts that count are the routed ones HELD here and every identity
    expert; where only absent routed experts are near the edge one goes
    for another, neither is computed here, and nothing jumps)."""
    p = jax.nn.softmax(u @ jnp.asarray(router, F32), axis=-1)
    sel = p if without == "select_bias" else p + jnp.asarray(bias, F32)
    N, E = p.shape
    k = hp["top_k"]
    ranked = jnp.sort(sel, axis=-1)
    a, b = ranked[:, E - k][:, None], ranked[:, E - k - 1][:, None]
    chosen = sel >= a
    ids = jnp.arange(E)[None]
    counts = (ids >= hp["n_routed"]) | (
        (ids >= hp["expert_offset"])
        & (ids < hp["expert_offset"] + hp["experts_held"]))
    margin = jnp.where(counts, jnp.where(chosen, sel - b, a - sel),
                       jnp.inf).min(axis=-1)
    w = jnp.where(chosen, p, 0.0)
    if without != "route_scale":
        w = w * hp["route_scale"]
    return w, margin


def expert_layer(u, w, hp, without=None):
    """u [N, h] (normed) -> (routed [N, h]: the held experts' part,
    zero [N, h]: the identity experts' part, margins [N])."""
    weight, margin = select(u, w["router"], w["bias"], hp, without)
    first = hp.get("weights_offset", hp["expert_offset"])
    held = range(hp["expert_offset"],
                 hp["expert_offset"] + hp["experts_held"])
    if without == "absent_experts_left_out":
        held = range(first, first + w["e_gate"].shape[0])
    routed = jnp.zeros_like(u)
    for e in held:
        i = e - first
        routed = routed + weight[:, e][:, None] * gated(
            u, w["e_gate"][i], w["e_up"][i], w["e_down"][i])
    zero = weight[:, hp["n_routed"]:].sum(-1, keepdims=True) * u
    if without == "zero_experts":
        zero = jnp.zeros_like(u)
    return routed, zero, margin


def layer(x, w, hp, without=None):
    """x [B, S, h] -> (x', margins [B * S]) through one layer ``w``."""
    B, S, hid = x.shape

    def moe(v):
        routed, zero, m = expert_layer(v.reshape(B * S, hid), w, hp,
                                       without)
        return (routed + zero).reshape(B, S, hid), m

    x = x + attention(x, w["attn"][0], hp, without)
    u = rms(x, w["ffn"][0]["ln"], hp["eps"])
    if without != "shortcut":
        s, m = moe(u)
    f = w["ffn"][0]
    x = x + gated(u, f["gate"], f["up"], f["down"])
    if without != "second_attention":
        x = x + attention(x, w["attn"][1], hp, without)
    f = w["ffn"][1]
    v = rms(x, f["ln"], hp["eps"])
    if without == "shortcut":
        s, m = moe(v)
    return x + gated(v, f["gate"], f["up"], f["down"]) + s, m


def forward_rows(weights: dict, tokens, hp: dict, without=None):
    """tokens [B, S] int32 -> (float32 logits [B, S, rows held],
    margins [B, S]: the smallest margin any expert layer's selection
    has at that position), all rows in one pass."""
    assert without is None or without in MECHANISMS, without
    with jax.default_matmul_precision("highest"):
        B, S = tokens.shape
        x = jnp.asarray(weights["embed"], F32)[tokens]
        least = jnp.full((B * S,), jnp.inf, F32)
        for w in weights["layers"]:
            x, m = layer(x, w, hp, without)
            least = jnp.minimum(least, m)
        x = rms(x, weights["ln_f"], hp["eps"])
        return x @ jnp.asarray(weights["head"], F32), least.reshape(B, S)


def forward(weights: dict, tokens, hp: dict, without=None,
            margins: bool = False):
    """tokens [B, S] int32 -> float32 logits [B, S, rows held]; with
    ``margins`` also [B, S] (``forward_rows``). ONE SEQUENCE AT A TIME
    (``lax.map`` over the rows, which share nothing), as
    ``axk1_reference.forward`` and for its reason: a sequence's result
    cannot depend on how many others are beside it, and the pass holds
    one row's activations."""
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
    dict above. The only place that knows the program's names; arrays
    are passed on as they are held, never copied or upcast."""
    def attn(p):
        return {"ln": p["ln1_scale"], "wqa": p["wqa"]["kernel"],
                "q_norm": p["q_norm_scale"], "wqb": p["wqb"]["kernel"],
                "wkva": p["wkva"]["kernel"], "kv_norm": p["kv_norm_scale"],
                "wkvb": p["wkvb"]["kernel"], "wo": p["wo"]["kernel"]}

    def ffn(p):
        return {"ln": p["ln2_scale"], "gate": p["gate"], "up": p["up"],
                "down": p["down"]}

    return {"embed": params["embed"]["kernel"],
            "head": params["head"]["kernel"],
            "ln_f": params["ln_f_scale"],
            "layers": [{"attn": [attn(a) for a in p["attn"]],
                        "ffn": [ffn(f) for f in p["ffn"]],
                        "router": p["router"]["kernel"],
                        "bias": p["router"]["bias"],
                        "e_gate": p["experts"]["gate"],
                        "e_up": p["experts"]["up"],
                        "e_down": p["experts"]["down"]}
                       for p in params["layers"]]}
