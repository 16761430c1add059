"""Operations and bytes an algorithm needs, from shapes alone. Kept
with the benchmark: a roofline share divides these by a measured time,
so whoever changes the program cannot change the yardstick.
"""
from __future__ import annotations


def n_params(model: dict) -> int:
    """Parameters of the GPT-2-shaped model as the program holds it:
    token table (rows as held), positions, per layer 4 d*d + 2 d*n_inner
    + 2 norm scales, final norm. No biases (the program has none)."""
    d, f, L = model["n_embd"], model["n_inner"], model["n_layer"]
    rows = model["embedding_rows_held"]
    return rows * d + model["n_positions"] * d \
        + L * (4 * d * d + 2 * d * f + 2 * d) + d


def decode_step_bytes(model: dict, weight_bytes: int, kv_bytes: int,
                      live_tokens: float) -> float:
    """Bytes one decode step has to move: every weight once, as held
    (``weight_bytes`` per parameter), and the keys and values of the
    live tokens (``live_tokens`` summed over the active lanes) in every
    layer. Activations are small beside these and left out, so the
    share this gives is, if anything, low."""
    kv = 2 * model["n_layer"] * model["n_embd"] * kv_bytes * live_tokens
    return n_params(model) * weight_bytes + kv


def train_flops_per_token(model: dict, seq: int) -> float:
    """6 N for the matrix multiplications, forward and backward, plus
    12 L S d for attention; recomputation does not count."""
    return 6 * n_params(model) \
        + 12 * model["n_layer"] * seq * model["n_embd"]
