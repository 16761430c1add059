"""Operations and bytes an algorithm needs, from shapes alone. Kept
with the benchmark: a roofline share divides these by a measured time,
so whoever changes the program cannot change the yardstick. A
roofline's bytes are the fewest ANY program with the configuration's
numerics moves (``compute_dtype``, ``kv_dtype``), never what one
program happens to hold: the share is a lower bound on the time over
the time taken, and no sound program reads over 100.
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


def decode_weight_params(model: dict) -> int:
    """Parameters one decode step multiplies by: the token table (the
    tied head reads every row held), each layer's six matrices and two
    norm scales, the final norm. Not the position table, of which a
    step reads one row a lane."""
    return n_params(model) - model["n_positions"] * model["n_embd"]


def decode_step_bytes(model: dict, weight_bytes: int, kv_bytes: int,
                      live_tokens: float) -> float:
    """Fewest bytes ANY program with these numerics moves in one decode
    step: every weight the step multiplies by once, in the dtype it is
    multiplied in (``weight_bytes`` per parameter: on-chip memory holds
    no 2.6 GB, so each matrix comes from HBM every step), and the keys
    and values of the live tokens (``live_tokens`` summed over the
    active lanes) in every layer. A lower bound, so a share of it
    cannot pass 100: how a program HOLDS its weights (float32 masters,
    a cast once a launch) is overhead on top and shows as distance from
    100, and activations are left out."""
    kv = 2 * model["n_layer"] * model["n_embd"] * kv_bytes * live_tokens
    return decode_weight_params(model) * weight_bytes + kv


def train_flops_per_token(model: dict, seq: int) -> float:
    """6 N for the matrix multiplications, forward and backward, plus
    12 L S d for attention; recomputation does not count."""
    return 6 * n_params(model) \
        + 12 * model["n_layer"] * seq * model["n_embd"]
