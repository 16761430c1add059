"""Batched ResNet-50 serving benchmark (BASELINE.md:63 — "batched
ResNet-50 serving replica (p50 latency)", the reference's headline Serve
config).

One replica hosts a jitted bf16 ResNet-50; ``@serve.batch`` coalesces
concurrent requests and pads each batch to a bucket size so XLA compiles
once per bucket. N closed-loop clients fire requests; we report p50/p99
latency and throughput as JSON lines.

Run: ``python benchmarks/serve_resnet.py [--clients 16] [--secs 10]``
The model is what the arguments say (ResNet-50 at 224 by default); the
driver never touches jax — the replica holds the chip (it asks for one
when the node has one) and reports the device it ran on.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--clients", type=int, default=16)
    parser.add_argument("--secs", type=float, default=10.0)
    parser.add_argument("--max-batch", type=int, default=16)
    parser.add_argument("--depth", type=int, default=50)
    parser.add_argument("--size", type=int, default=224)
    args = parser.parse_args()

    import numpy as np

    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu._private.accelerators import local_chip_count
    from ray_tpu._private.chip import ensure_compile_cache

    ensure_compile_cache()      # before rt.init(): workers inherit it
    rt.init(num_cpus=8, ignore_reinit_error=True)
    serve.start(http_options={"host": "127.0.0.1", "port": 0})
    depth, size = args.depth, args.size

    # One chip for the one replica where the node has chips: a worker
    # that is granted none is confined to the CPU.
    @serve.deployment(max_ongoing_requests=64, ray_actor_options={
        "num_tpus": min(1, local_chip_count())})
    class ResNetReplica:
        def __init__(self, depth: int, size: int, max_batch: int):
            import jax

            from ray_tpu._private.chip import device_summary
            from ray_tpu.models import resnet

            self.device = device_summary()
            self.cfg = resnet.ResNetConfig(depth=depth)
            params = resnet.init_params(jax.random.PRNGKey(0), self.cfg)
            self.predict = resnet.make_predictor(self.cfg, params,
                                                 uint8_input=True)
            self.size = size
            self.max_batch = max_batch
            # Compile every bucket before the replica reports ready
            # (readiness has no deadline), so p50 excludes compiles.
            from ray_tpu.serve.batching import default_buckets

            for b in default_buckets(max_batch):
                np.asarray(self.predict(np.zeros(
                    (b, size, size, 3), np.uint8)))

        def device_summary(self):
            return self.device

        # Class is defined inside main(), so the decorator can take the
        # CLI's batch size — serving and warmup always agree on buckets.
        @serve.batch(max_batch_size=args.max_batch,
                     batch_wait_timeout_s=0.005, pad_to_bucket=True)
        def run_batch(self, images_list):
            batch = np.stack(images_list)
            out = np.asarray(self.predict(batch))
            return [int(row.argmax()) for row in out]

        def __call__(self, _request=None):
            img = np.random.randint(
                0, 256, (self.size, self.size, 3), np.uint8)
            return self.run_batch(img)

    handle = serve.run(
        ResNetReplica.bind(depth, size, args.max_batch),
        name="resnet", route_prefix=None)
    device = handle.options(method_name="device_summary").remote().result()
    handle.remote().result()  # end-to-end warm

    latencies = []
    lock = threading.Lock()
    stop = time.time() + args.secs

    def client():
        while time.time() < stop:
            t0 = time.perf_counter()
            handle.remote().result()
            dt = time.perf_counter() - t0
            with lock:
                latencies.append(dt)

    threads = [threading.Thread(target=client) for _ in range(args.clients)]
    t_start = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.time() - t_start

    latencies.sort()
    n = len(latencies)
    p50 = latencies[n // 2] * 1000
    p99 = latencies[min(n - 1, int(n * 0.99))] * 1000
    model = f"resnet{depth}@{size}px"
    print(json.dumps({"metric": f"serve_{model}_p50_ms",
                      "value": round(p50, 2), "unit": "ms",
                      "clients": args.clients,
                      "p99_ms": round(p99, 2), "device": device}))
    print(json.dumps({"metric": f"serve_{model}_throughput",
                      "value": round(n / wall, 1), "unit": "req/s",
                      "clients": args.clients, "device": device}))
    serve.shutdown()
    rt.shutdown()


if __name__ == "__main__":
    main()
