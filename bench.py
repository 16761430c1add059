"""Headline benchmark: GPT-2-small training throughput on one chip.

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": ..}``

``vs_baseline`` is measured MFU / 0.40 — the north-star target from
``BASELINE.json`` (≥40% MFU on v5e). >1.0 beats the target.

A device measurement or nothing: with no TPU, or on a chip whose peak
is not on record, this exits non-zero without printing a metric.
"""
from __future__ import annotations

import json
import time


def main() -> None:
    import dataclasses

    from ray_tpu._private import chip

    chip.ensure_compile_cache()
    import jax
    import numpy as np

    from ray_tpu.models import gpt
    from ray_tpu.parallel import create_mesh

    device = chip.require_tpu()
    peak = chip.peak_flops(device["kind"])
    # Tuned on v5e: batch 32 saturates HBM headroom with selective remat
    # + the Pallas flash kernel (block 512); larger batches OOM on the
    # f32 loss logits.
    cfg = dataclasses.replace(gpt.CONFIGS["small"], remat="dots",
                              attn_backend="auto")
    batch, seq = 32, 1024   # loss uses tokens[:, :-1], so seq==max_seq ok

    mesh = create_mesh({"dp": 1}, devices=[jax.devices()[0]])
    init, step, state_sh, batch_sh = gpt.make_train_step(cfg, mesh)
    state = init(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        np.random.randint(0, cfg.vocab_size, (batch, seq + 1), np.int32),
        batch_sh)
    data = {"tokens": tokens}

    # Warmup/compile, then wait for the device before the clock starts.
    for _ in range(3):
        state, metrics = step(state, data)
    float(metrics["loss"])

    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = step(state, data)
    float(metrics["loss"])
    dt = time.perf_counter() - t0

    toks_per_step = batch * seq
    tokens_per_sec = toks_per_step * iters / dt
    # 6N matmul + 12*L*S*d attention flops per token (fwd+bwd).
    flops_per_token = (6 * cfg.num_params()
                       + 12 * cfg.n_layer * seq * cfg.d_model)
    mfu = tokens_per_sec * flops_per_token / peak

    print(json.dumps({
        "metric": "gpt2s_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "attention": gpt.attention_plan(cfg, seq),
        "device": device,
    }))


if __name__ == "__main__":
    main()
