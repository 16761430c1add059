"""The dummy architecture's plain reference, written out on its own
(float32, precision ``highest``, no code shared with the program or
with ``reference_gpt2.py``): pre-norm decoder, RMSNorm, learned
positions, tanh GELU, tied head."""
import math

import jax
import jax.numpy as jnp


def from_program(params):
    blk = params["block"]
    flat = {k: blk[k]["kernel"] for k in ("wq", "wk", "wv", "wo", "w1",
                                          "w2")}
    flat.update(n1=blk["ln1_scale"], n2=blk["ln2_scale"],
                nf=params["ln_f_scale"], pos=params["pos_embed"],
                table=params["embed"]["kernel"])
    return flat


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g


def forward(w, tokens, heads):
    with jax.default_matmul_precision("highest"):
        w = jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                   w)
        B, S = tokens.shape
        x = w["table"][tokens] + w["pos"][:S]
        hd = x.shape[-1] // heads
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

        def split(a):
            return a.reshape(B, S, heads, hd).transpose(0, 2, 1, 3)

        for l in range(w["wq"].shape[0]):
            h = _rms(x, w["n1"][l])
            q, k, v = (split(h @ w[n][l]) for n in ("wq", "wk", "wv"))
            s = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
            p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
            o = (p @ v).transpose(0, 2, 1, 3).reshape(B, S, -1)
            x = x + o @ w["wo"][l]
            h = _rms(x, w["n2"][l]) @ w["w1"][l]
            x = x + jax.nn.gelu(h, approximate=True) @ w["w2"][l]
        return _rms(x, w["nf"]) @ w["table"].T


def loss(w, tokens, heads):
    with jax.default_matmul_precision("highest"):
        logp = jax.nn.log_softmax(forward(w, tokens[:, :-1], heads), -1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None],
                                             -1))
