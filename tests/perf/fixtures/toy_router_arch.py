"""The smallest architecture with a discrete choice, for the tests of
``perf_reference_check``'s rule of what is left out: one layer of two
experts, top 1 by the router's score, over a causal mean of token
embeddings. The "program" multiplies in bfloat16 and the reference in
float32 at precision ``highest``, so where the two scores are a
near-tie they pick different experts and the logits part by an
expert's whole contribution.

It provides what ``perf_reference_check.serve_check`` asks of an
architecture module (``architectures/gpt2.py`` has the contract):
``vocab``, ``served_logits``, ``reference`` and ``decidable``. The
"engine" holds ``params`` (what the reference is made from) and, for a
planted fault, other weights that the program alone multiplies with."""
import jax
import jax.numpy as jnp
import numpy as np

V, D, F, E = 64, 32, 64, 2


class Engine:
    def __init__(self, params, program_params=None):
        self.params = params
        self.program_params = program_params or params


def init_params(seed: int) -> dict:
    rng = np.random.default_rng(seed)

    def n(*shape, std):
        return rng.normal(0, std, shape).astype(np.float32)

    return {"emb": n(V, D, std=1.0), "router": n(D, E, std=D ** -0.5),
            "up": n(E, D, F, std=D ** -0.5),
            "down": n(E, F, D, std=2 * F ** -0.5)}


def vocab(conf):
    return V, V


def _hidden(w, tokens):
    """[B, S, D]: the token's embedding plus the mean of all embeddings
    up to it (causal), in float32 on both sides."""
    x = jnp.asarray(w["emb"], jnp.float32)[tokens]
    count = jnp.arange(1, tokens.shape[1] + 1, dtype=jnp.float32)
    return x + jnp.cumsum(x, axis=1) / count[None, :, None]


def _forward(w, tokens, dtype, precision):
    """(logits [B, S, V] float32, router scores [B, S, E] float32)."""
    def mm(a, b):
        return jnp.matmul(a.astype(dtype), jnp.asarray(b).astype(dtype),
                          precision=precision,
                          preferred_element_type=jnp.float32)

    h = _hidden(w, tokens)
    scores = mm(h, w["router"])
    pick = jnp.argmax(scores, -1)
    outs = [mm(jax.nn.gelu(mm(h, w["up"][e])), w["down"][e])
            for e in range(E)]
    y = jnp.where((pick == 0)[..., None], outs[0], outs[1])
    # the residual is kept small, so that the expert decides the token
    return mm(0.1 * h + y, jnp.asarray(w["emb"]).T), scores


def served_logits(engine, cfg, seqs, n_prompt, n_steps):
    logits, _ = _forward(engine.program_params,
                         jnp.asarray(seqs[:, :-1]), jnp.bfloat16, None)
    logits = np.asarray(logits, np.float32)
    return {0: logits[:, n_prompt - 1],
            n_steps: logits[:, n_prompt + n_steps - 1]}


def greedy(engine, prompt, n: int):
    """The program's own greedy answer to ``prompt``: ``n`` tokens."""
    seq = [int(t) for t in prompt]
    for _ in range(n):
        logits, _ = _forward(engine.program_params, jnp.asarray([seq]),
                             jnp.bfloat16, None)
        seq.append(int(np.asarray(logits)[0, -1].argmax()))
    return seq[len(prompt):]


def reference(cfg):
    def forward(w, tokens):
        return _forward(w, tokens, jnp.float32, "highest")[0]

    def loss(w, tokens):
        logp = jax.nn.log_softmax(forward(w, tokens[:, :-1]), -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], -1))

    return (lambda params: params), forward, loss


def decidable(cfg, conf):
    """[rows, positions]: the reference's top score clears its runner-up
    by ``correct.tie_eps``. One layer, so a position's logits depend on
    no other position's choice."""
    eps = conf["correct"]["tie_eps"]

    def fn(w, tokens):
        s = jnp.sort(_forward(w, tokens, jnp.float32, "highest")[1], -1)
        return s[..., -1] - s[..., -2] > eps

    return fn


def scores(params, tokens, program: bool):
    """Router scores as the program or the reference computes them."""
    return np.asarray(_forward(
        params, jnp.asarray(tokens),
        jnp.bfloat16 if program else jnp.float32,
        None if program else "highest")[1])


def plant_near_tie(params, tokens, row: int, pos: int,
                   gap: float = 2e-5) -> dict:
    """Weights whose router, at ``tokens[row, pos]``, scores the two
    experts ``gap`` apart in the reference and the other way round in
    the bfloat16 program: the second column moves along the hidden
    vector there, first to an exact tie, then ``gap`` to the side the
    program's rounding does not take."""
    h = np.asarray(_hidden(params, jnp.asarray(tokens)))[row, pos]

    def moved(delta):
        router = params["router"].copy()
        router[:, 1] += delta * h / float(h @ h)
        return dict(params, router=router)

    s = scores(params, tokens, program=False)[row, pos]
    tied = moved(float(s[0] - s[1]))
    p = scores(tied, tokens, program=True)[row, pos]
    side = 1.0 if p[1] > p[0] else -1.0      # where the program leans
    r = scores(tied, tokens, program=False)[row, pos]
    return moved(float(s[0] - s[1]) - float(r[1] - r[0]) - side * gap)
