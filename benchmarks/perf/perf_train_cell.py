"""One run of a training cell. The parent (``run.py``) stays off jax
and starts this file as a child that holds all the cell's chips in one
process; the child writes its result to a file the parent reads.

Steps run back to back on one fixed batch shape made from the seed.
Each step's end is a loss read-back of the step BEFORE the one just
dispatched, so the device always has the next step queued.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import perf_harness as H


def run(found: dict, seed: int, seconds: float, trace: int,
        require_tpu: bool = True, describe: bool = False) -> dict:
    """Parent side: start the child, wait, read what it wrote."""
    cell = found["cell"]
    out = H.out_dir(cell["name"], seed, trace)
    result = os.path.join(out, "train_result.json")
    if os.path.exists(result):
        os.remove(result)
    H.worker_env()
    from ray_tpu._private import chip

    chip.ensure_compile_cache()
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--config-file", os.path.join(H.ROOT, found["config"]["file"]),
           "--mix", cell["traffic"], "--chips", str(cell["chips"]),
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace), "--out", out,
           "--describe", str(int(describe)),
           "--require-tpu", str(int(require_tpu))]
    proc = subprocess.Popen(cmd, cwd=H.ROOT)
    try:
        rc = proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0 or not os.path.exists(result):
        raise H.BenchError(f"training child exited {rc}")
    got = H.load_json(result)
    got["run"]["e2e"]["setup_s"] = got["run"]["t0"] - H.PROCESS_START
    got["run"]["peaks"] = H.peaks(got["device"]["kind"]) \
        if require_tpu else None
    return got


def child(args) -> int:
    from ray_tpu._private import chip

    chip.ensure_compile_cache()
    import jax
    import numpy as np

    import perf_deployment
    import perf_reference_check

    device = chip.require_tpu() if args.require_tpu \
        else chip.device_summary()
    print(f"[pid {os.getpid()}] platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']}",
          flush=True)
    if device["count"] < args.chips:
        print(f"the cell needs {args.chips} chips, jax found "
              f"{device['count']}", file=sys.stderr)
        return 3
    conf = H.load_json(args.config_file)
    mix = H.load_mix(args.mix)
    tr = conf["train"]
    arch = H.load_architecture(conf)
    cfg = arch.model_cfg(conf)
    n = args.chips
    prog = arch.train_program(cfg, conf, jax.devices()[:n])
    step, batch_sh = prog["step"], prog["batch_sharding"]
    t_a = time.monotonic()
    state = prog["init"](jax.random.PRNGKey(args.seed % (2 ** 31)))
    B, S = tr["global_batch"], tr["seq"]
    rng = np.random.default_rng([args.seed & (2 ** 63 - 1), 5])
    host_tokens = rng.integers(0, arch.vocab(conf)[0],
                               (B, S + 1)).astype(np.int32)
    data = {"tokens": jax.device_put(host_tokens, batch_sh)}
    jax.block_until_ready(state)
    t_b = time.monotonic()
    rows = conf["correct"]["reference_rows"]
    ref = perf_reference_check.train_check(
        arch, state["params"],
        jax.device_put(host_tokens[:rows], batch_sh), cfg, prog["loss"])
    ref["ok"] = ref["abs_err"] <= conf["correct"]["loss_abs_tol"]
    losses = []
    for _ in range(int(mix.get("warm_steps", 2))):
        state, metrics = step(state, data)
        losses.append(float(metrics["loss"]))
    t_c = time.monotonic()

    tracer = None
    trace_at = int(mix.get("trace_after_steps", 3))
    trace_n = int(mix.get("trace_steps", 3))
    red = stopped = None
    t0 = time.monotonic()
    ends = []
    state, pending = step(state, data)
    k = 0
    while True:
        if args.trace and k == trace_at:
            float(pending["loss"])          # drain, then trace whole steps
            tracer = perf_deployment.Tracer(
                os.path.join(args.out, "trace"), "perf_train_cell.py",
                "child")
            tracer.start()
        state, nxt = step(state, data)
        losses.append(float(pending["loss"]))
        ends.append(time.monotonic())
        pending = nxt
        k += 1
        if tracer is not None and k == trace_at + trace_n:
            float(pending["loss"])
            tracer.stop()
            stopped, tracer = tracer, None
        if ends[-1] >= t0 + args.seconds:
            break
    float(pending["loss"])
    if args.trace:
        red = stopped.result(describe=bool(args.describe))
    t1 = t0 + args.seconds
    done = [e for e in ends if e < t1]
    span = (done[-1] - t0) if done else 0.0
    steps_ms = [(b - a) * 1e3 for a, b in zip([t0] + ends[:-1], ends)]
    mem = [d.memory_stats() or {} for d in jax.devices()[:n]]
    falling = losses[-1] < losses[0]
    e2e = {"attempted": len(done), "failed": 0,
           "train_tokens_per_s": (len(done) * B * S / span) if span
           else None,
           "steps": len(done)}
    run = {"mix": mix, "conf": conf, "seed": args.seed,
           "seconds": args.seconds, "t0": t0, "t1": t1, "e2e": e2e,
           "step_ms": steps_ms, "losses": losses, "trace": red,
           "device": device, "chips": n,
           "memory_peak_bytes": max(
               perf_deployment.device_peak_bytes(m) for m in mem),
           "memory_stats": {k: int(v) for k, v in mem[0].items()
                            if isinstance(v, (int, float))},
           "timing": {"init_s": t_b - t_a, "reference_s": ref["seconds"],
                      "warm_s": t_c - t_b - ref["seconds"]},
           "reference": ref, "tokens_per_step": B * S}
    dev = H.device_entry(dict(device, count=n), run["memory_peak_bytes"],
                         red)
    print("SETUP " + json.dumps({"timing": run["timing"],
                                 "reference": ref,
                                 "trace_cost": (red or {}).get("cost"),
                                 "memory_stats": run["memory_stats"],
                                 "first_loss": losses[0],
                                 "last_loss": losses[-1]}), flush=True)
    with open(os.path.join(args.out, "train_result.json"), "w") as f:
        json.dump({"run": run, "correct": bool(ref["ok"] and falling),
                   "attempted": len(done), "failed": 0, "device": dev,
                   "breakdown": H.breakdown_entry(red)}, f, default=str)
    return 0


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--config-file")
    ap.add_argument("--mix")
    ap.add_argument("--chips", type=int)
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int)
    ap.add_argument("--describe", type=int, default=0)
    ap.add_argument("--out")
    ap.add_argument("--require-tpu", type=int, default=1)
    a = ap.parse_args()
    H.worker_env()
    sys.exit(child(a))
