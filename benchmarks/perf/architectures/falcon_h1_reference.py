"""The plain reference for Falcon-H1-34B-Instruct (``model_type``
``falcon_h1``;
https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/config.json):
the forward pass in float32 ``jax.numpy`` at
``default_matmul_precision("highest")``. The state-space mixer runs its
recurrence ONE TOKEN AT A TIME and nothing else (no chunked form); the
attention is a full causal softmax. No cache, no kernel, no batching
trick, and no code shared with the program under test.

``hp`` is a plain dict: the sizes (``heads``, ``kv_heads``,
``head_dim``, ``rope_theta``; ``ssm_heads``, ``ssm_head_dim``,
``ssm_state``, ``ssm_groups``, ``conv``; ``eps``) and the constant
multipliers under the names the published config gives them
(``embedding_multiplier``, ``ssm_in_multiplier``, ``ssm_multipliers``,
``ssm_out_multiplier``, ``attention_in_multiplier``,
``key_multiplier``, ``attention_out_multiplier``, ``mlp_multipliers``,
``lm_head_multiplier``). Weights are a flat dict of per-layer lists in
whatever type the program holds them; a matrix is upcast where it is
used, the wide ones (the MLP's three, the head) a BLOCK of columns at
a time, so that no float32 copy of the tree, of a layer or of the
1.34 G-parameter head is ever held.

The equations, a layer (``u = RMSNorm(x)``; ``h`` hidden; a final
RMSNorm; an untied head; no bias but the convolution's)::

    x0      = E[token] * embedding_multiplier
    -- state-space branch (H heads of P channels, state N wide, G groups)
    [z|xBC|dt] = ((u * ssm_in_multiplier) W_in) * mup
                 mup = ssm_multipliers[0..4] over z(HP) | x(HP) | B(GN) | C(GN) | dt(H)
    xBC_t   = silu(sum_{j<conv} w[j] * xBC_{t-conv+1+j} + b)      depthwise, causal
    dt_t    = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) dt_t)      a head
    S_t[h]  = a_t[h] S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g],  g = h // (H / G)
    y_t[h]  = S_t[h] C_t[g] + D[h] x_t[h]
    y_t     = RMSNorm_groups(y_t * silu(z_t)) * w_norm            G groups of HP / G
    ssm     = (y_t W_out) * ssm_out_multiplier
    -- attention branch
    q = (u * attention_in_multiplier) W_q;  k = (u W_k) * key_multiplier;  v = u W_v
    q, k    = rotary(q, k)          the whole head, halves pairing, theta rope_theta
    att     = (softmax_causal(q k^T / sqrt(head_dim)) v) W_o * attention_out_multiplier
    x      += ssm + att
    -- MLP
    v2 = RMSNorm(x)
    x += (W_down[silu((v2 W_gate) * mlp_multipliers[0]) * (v2 W_up)]) * mlp_multipliers[1]
    logits  = (RMSNorm(x_L) W_head) * lm_head_multiplier

Readings the published config does not settle, each listed in the
configuration file under ``assumed``: ``mamba_use_mlp`` true means the
MLP above follows the two mixers in every layer; the gated norm takes
the gate first (``mamba_norm_before_gate`` false) and normalises
``mamba_n_groups`` groups; the five ``ssm_multipliers`` lie over the
input projection's columns in the order ``z | x | B | C | dt``;
``attn_layer_indices`` null means attention in every layer; weights
are random.

``without`` names ONE mechanism to leave out, for the controls that
show each mechanism is seen by the comparison (``MECHANISMS``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: what ``without`` may name
MECHANISMS = (
    "decay", "dt_bias", "d_skip", "short_conv", "conv_bias", "gate_z",
    "norm_groups", "head_groups", "mup_z", "mup_x", "mup_B", "mup_C",
    "mup_dt", "ssm_in_multiplier", "ssm_out_multiplier", "key_multiplier",
    "rotary", "attention_out_multiplier", "mlp_gate_multiplier",
    "mlp_down_multiplier", "embedding_multiplier", "lm_head_multiplier")
#: columns of a wide matrix upcast at once (the MLP's 21,504, the
#: head's 261,120)
BLOCK = 8192


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * jnp.asarray(scale, F32)


def blocks(n: int):
    return [(a, min(a + BLOCK, n)) for a in range(0, n, BLOCK)]


def mamba(u, w, l, hp, without=None):
    """u [S, h] (normed) -> the branch's part of the residual [S, h],
    by the recurrence."""
    S = u.shape[0]
    H, P, N, G = hp["ssm_heads"], hp["ssm_head_dim"], hp["ssm_state"], \
        hp["ssm_groups"]
    W, bc = H * P, G * N
    mults = [1.0 if without == "mup_" + seg else m for seg, m in
             zip(("z", "x", "B", "C", "dt"), hp["ssm_multipliers"])]
    mup = jnp.concatenate([jnp.full((n,), m, F32) for n, m in
                           zip((W, W, bc, bc, H), mults)])
    if without != "ssm_in_multiplier":
        u = u * hp["ssm_in_multiplier"]
    zxbcdt = (u @ jnp.asarray(w["in_proj"][l], F32)) * mup
    z, xBC, dt = zxbcdt[:, :W], zxbcdt[:, W:2 * W + 2 * bc], \
        zxbcdt[:, 2 * W + 2 * bc:]
    if without != "short_conv":
        taps = jnp.asarray(w["conv_w"][l], F32)              # [conv, C]
        width = taps.shape[0]
        padded = jnp.concatenate([jnp.zeros((width - 1, xBC.shape[1]), F32),
                                  xBC])
        xBC = sum(taps[j] * padded[j:j + S] for j in range(width))
        if without != "conv_bias":
            xBC = xBC + jnp.asarray(w["conv_b"][l], F32)
    xBC = jax.nn.silu(xBC)
    x = xBC[:, :W].reshape(S, H, P)
    B = xBC[:, W:W + bc].reshape(S, G, N)
    C = xBC[:, W + bc:].reshape(S, G, N)
    group = jnp.zeros((H,), jnp.int32) if without == "head_groups" \
        else jnp.arange(H) // (H // G)
    B, C = B[:, group], C[:, group]                          # [S, H, N]
    if without != "dt_bias":
        dt = dt + jnp.asarray(w["dt_bias"][l], F32)
    dt = jax.nn.softplus(dt)                                 # [S, H]
    a = jnp.exp(-jnp.exp(jnp.asarray(w["A_log"][l], F32)) * dt)
    if without == "decay":
        a = jnp.ones_like(a)

    def step(state, row):
        x, B, C, dt, a = row              # [H, P], [H, N], [H, N], [H], [H]
        state = a[:, None, None] * state \
            + (dt[:, None] * x)[:, :, None] * B[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, C)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), F32), (x, B, C, dt, a))
    if without != "d_skip":
        y = y + jnp.asarray(w["D_skip"][l], F32)[:, None] * x
    y = y.reshape(S, W)
    if without != "gate_z":
        y = y * jax.nn.silu(z)
    groups = 1 if without == "norm_groups" else G
    y = y.reshape(S, groups, W // groups)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + hp["eps"])
    y = y.reshape(S, W) * jnp.asarray(w["ssm_norm"][l], F32)
    y = y @ jnp.asarray(w["out_proj"][l], F32)
    return y if without == "ssm_out_multiplier" \
        else y * hp["ssm_out_multiplier"]


def rotate(x, theta):
    """x [S, heads, d] at positions 0..S-1, halves pairing."""
    S, _, d = x.shape
    freq = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = (jnp.arange(S, dtype=F32)[:, None] * freq)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def attention(u, w, l, hp, without=None):
    """u [S, h] (normed) -> the branch's part of the residual [S, h]:
    full causal softmax, grouped key/value heads, rotary."""
    S = u.shape[0]
    Hq, Hkv, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    q = ((u * hp["attention_in_multiplier"])
         @ jnp.asarray(w["wq"][l], F32)).reshape(S, Hq, d)
    k = (u @ jnp.asarray(w["wk"][l], F32)).reshape(S, Hkv, d)
    if without != "key_multiplier":
        k = k * hp["key_multiplier"]
    v = (u @ jnp.asarray(w["wv"][l], F32)).reshape(S, Hkv, d)
    if without != "rotary":
        q, k = rotate(q, hp["rope_theta"]), rotate(k, hp["rope_theta"])
    k = jnp.repeat(k, Hq // Hkv, axis=1)       # head j <- KV head j // g
    v = jnp.repeat(v, Hq // Hkv, axis=1)
    att = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    att = jnp.where(jnp.tril(jnp.ones((S, S), bool)), att, -jnp.inf)
    y = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(att, axis=-1), v
                   ).reshape(S, Hq * d) @ jnp.asarray(w["wo"][l], F32)
    return y if without == "attention_out_multiplier" \
        else y * hp["attention_out_multiplier"]


def mlp(v, w, l, hp, without=None):
    """v [S, h] (normed) -> [S, h], a block of the hidden width at a
    time."""
    m_gate = 1.0 if without == "mlp_gate_multiplier" \
        else hp["mlp_multipliers"][0]
    m_down = 1.0 if without == "mlp_down_multiplier" \
        else hp["mlp_multipliers"][1]
    y = jnp.zeros_like(v)
    for a, b in blocks(w["gate"][l].shape[1]):
        act = jax.nn.silu((v @ jnp.asarray(w["gate"][l][:, a:b], F32))
                          * m_gate) \
            * (v @ jnp.asarray(w["up"][l][:, a:b], F32))
        y = y + act @ jnp.asarray(w["down"][l][a:b], F32)
    return y * m_down


def vocab_blocks(kernel):
    """The table or the head as a list of blocks of vocabulary rows
    (the program's tree may hold either one array or such a list)."""
    return list(kernel) if isinstance(kernel, (list, tuple)) else [kernel]


def table_rows(table, tokens):
    """E[token]: each token's row from the block that holds it."""
    table = vocab_blocks(table)
    rows = table[0].shape[0]
    return sum(jnp.where((tokens // rows == b)[:, None],
                         jnp.asarray(blk[tokens % rows], F32), 0.0)
               for b, blk in enumerate(table))


def forward_row(weights: dict, tokens, hp: dict, without=None):
    """tokens [S] int32 -> float32 logits [S, rows]."""
    assert without is None or without in MECHANISMS, without
    with jax.default_matmul_precision("highest"):
        x = table_rows(weights["embed"], tokens)
        if without != "embedding_multiplier":
            x = x * hp["embedding_multiplier"]
        for l in range(len(weights["ln1"])):
            u = rms(x, weights["ln1"][l], hp["eps"])
            x = x + mamba(u, weights, l, hp, without) \
                + attention(u, weights, l, hp, without)
            x = x + mlp(rms(x, weights["ln2"][l], hp["eps"]), weights, l,
                        hp, without)
        x = rms(x, weights["ln_f"], hp["eps"])
        logits = jnp.concatenate(
            [x @ jnp.asarray(head[:, a:b], F32)
             for head in vocab_blocks(weights["head"])
             for a, b in blocks(head.shape[1])], axis=-1)
        return logits if without == "lm_head_multiplier" \
            else logits * hp["lm_head_multiplier"]


def forward(weights: dict, tokens, hp: dict, without=None):
    """tokens [B, S] int32 -> float32 logits [B, S, rows]. ONE SEQUENCE
    AT A TIME (``lax.map`` over the rows, which share nothing), as
    ``solar_open2_reference.forward`` and for its reasons."""
    return jax.lax.map(
        lambda row: forward_row(weights, row, hp, without), tokens)


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
    out = {"embed": params["embed"]["kernel"],
           "head": params["head"]["kernel"], "ln_f": params["ln_f_scale"],
           "ln1": [p["ln1_scale"] for p in layers],
           "ln2": [p["ln2_scale"] for p in layers]}
    for name in ("wq", "wk", "wv", "wo", "in_proj", "out_proj"):
        out[name] = [p[name]["kernel"] for p in layers]
    for name in ("conv_w", "conv_b", "dt_bias", "A_log", "D_skip",
                 "ssm_norm"):
        out[name] = [p[name] for p in layers]
    for name in ("gate", "up", "down"):
        out[name] = [p["ffn"][name] for p in layers]
    return out
