"""FSDP/ZeRO-style sharded LM training benchmark (BASELINE.md:63 —
"FSDP/ZeRO-style sharded 1B LM"; north star ≥40% MFU on v5e-16).

Builds an ``{fsdp: N}`` mesh over every visible device and measures
training throughput + MFU. Model size scales with the device count:
the 1b preset needs its optimizer state sharded across several chips
(adamw f32 master+moments ≈ 17 GB), so a single chip runs the medium
(GPT-2-medium, 350M) preset instead — same code path, same sharding
rules, smaller shapes.

Run: ``python benchmarks/lm_sharded.py [--config 1b] [--batch N]``

A device measurement or nothing: with no TPU, or on a chip whose peak
is not on record, this exits non-zero without printing a metric.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default=None,
                        help="gpt preset (default: by device count)")
    parser.add_argument("--batch", type=int, default=None)
    parser.add_argument("--seq", type=int, default=None)
    parser.add_argument("--iters", type=int, default=10)
    args = parser.parse_args()

    from ray_tpu._private import chip

    chip.ensure_compile_cache()
    import jax
    import numpy as np

    from ray_tpu.models import gpt
    from ray_tpu.parallel import create_mesh

    device = chip.require_tpu()
    peak = chip.peak_flops(device["kind"])
    devs = jax.devices()
    n = len(devs)
    name = args.config or ("1b" if n >= 4 else "medium")
    cfg = dataclasses.replace(gpt.CONFIGS[name], remat="dots",
                              attn_backend="auto")
    batch = args.batch or (8 if name in ("medium", "1b") else 4) * n
    seq = min(args.seq or cfg.max_seq, cfg.max_seq)

    mesh = create_mesh({"fsdp": n}, devices=devs)
    init, step, state_sh, batch_sh = gpt.make_train_step(cfg, mesh)
    state = init(jax.random.PRNGKey(0))
    tokens = jax.device_put(
        np.random.randint(0, cfg.vocab_size, (batch, seq + 1), np.int32),
        batch_sh)
    data = {"tokens": tokens}

    for _ in range(3):
        state, metrics = step(state, data)
    float(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(args.iters):
        state, metrics = step(state, data)
    float(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * args.iters / dt
    flops_per_token = (6 * cfg.num_params()
                       + 12 * cfg.n_layer * seq * cfg.d_model)
    mfu = tokens_per_sec * flops_per_token / (peak * n)
    print(json.dumps({
        "metric": f"gpt_{name}_fsdp{n}_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "params": cfg.num_params(),
        "batch": batch, "seq": seq,
        "mfu": round(mfu, 4),
        "vs_baseline": round(mfu / 0.40, 4),
        "attention": gpt.attention_plan(cfg, seq),
        "device": device,
    }))


if __name__ == "__main__":
    main()
