"""Streaming GPT serving benchmark: a decode-loop replica with bucketed prefill streaming through
Serve (replica generator → handle → chunked HTTP), now with an A/B
chunked-decode mode.

``--chunk`` takes a comma-separated list of decode chunk sizes and runs
the full client load once per size, side by side in one artifact:

- ``1``  — the legacy path: one jitted ``decode_step`` dispatch (and
  one device→host scalar read) per generated token.
- ``k>1`` — the fused path: ``decode_chunk`` runs k steps in a single
  jitted ``lax.scan`` dispatch and the replica streams one per-chunk
  token slice per dispatch.

Per mode, reports per-stream TTFT, amortized per-token latency
(p50/p95/p99), aggregate decoded tokens/s, and — the dispatch
amortization itself — jitted dispatches per generated token counted on
the replica. JSON lines; chunk 1 keeps the legacy metric names.

Run: ``python benchmarks/serve_gpt.py [--clients 4] [--tokens 32]
[--chunk 1,8] [--config nano]``. The model is the ``--config``
argument; no process here looks at its device to pick a size. These
arms are CPU correctness A/Bs (tier-1 spawns their ``--smoke`` forms):
their replicas ask for no chip, so on a TPU node they are confined to
the CPU, and several arms compute their oracle in the driver — the
chip path is ``chip_smoke.py`` until ROADMAP S1's benchmark replaces
this script.

``--overload`` switches to the request-lifecycle A/B instead: offered
load ~3x a 4-slot replica, once with an effectively unbounded admission
queue and once with the bounded queue + 503/BackPressure shedding;
reports shed rate, goodput, and completion p50/p99 per mode.

``--trace`` (ISSUE 4) switches to the observability check: tracing on,
one streamed request through the FULL data plane (HTTP proxy → router →
replica → @serve.batch streaming flush → chunked decode), then dumps
that request's span tree, asserts the stage timings sum to within 10%
of the measured e2e latency, and verifies the serve latency histograms
(`serve_request_e2e_seconds`, `serve_ttft_seconds`,
`serve_tpot_seconds`) reached /metrics with non-zero counts.

``--continuous`` (ISSUE 5) switches to the continuous-batching A/B:
the SAME Poisson arrival schedule with mixed output lengths is driven
twice at equal offered load — once through a static gang-scheduled
``@serve.batch(stream=True)`` deployment (batch forms once, rides out
the whole generation, mid-flight arrivals wait for the next gang) and
once through the slot-pool ``DecodeEngine``
(``@serve.batch(continuous=True)``: admission at chunk boundaries,
slots freed per-request at EOS/max_new). Reports p50/p95 TTFT,
completion latency, total decoded tok/s, and — continuous only — slot
occupancy and dispatches/token from the engine's own accounting.
``--smoke`` shrinks the load so the A/B runs inside tier-1 CI.

``--spec`` (ISSUE 9) switches to the speculative-decoding A/B: the
SAME saturating burst of repetitive-suffix prompts is driven through
three engines built on identical weights — spec off, the n-gram
drafter, and the tied-embedding model drafter — off/ngram driven
back-to-back in every pass with best-of-5 per mode, the same one-sided
noise discipline as ``--continuous``. The workload is
SCREENED: candidate prompts' greedy continuations are simulated once
against the n-gram drafter and the most predictable drive the A/B.
Reports, per mode: decoded tok/s, TTFT p50, TPOT p50/p95, and — spec
modes — accepted-tokens-per-target-forward and the acceptance rate
from the engine's own accounting. ``--smoke`` shrinks it (off vs
n-gram only) for tier-1 CI.

``--disagg`` (ISSUE 14) switches to the disaggregated prefill/decode
A/B: the SAME bursty-prefill Poisson mix — steady long decode streams
plus bursts of long-prompt/2-token requests — is driven through a
colocated 2-replica deployment and a roles-split one (1 prefill + 1
decode) at equal offered load. Colocated, every burst's prefill
dispatch lands between decode chunk dispatches and inflates decode
TPOT; disaggregated, bursts prefill on the prefill replica and reach
the decode engine as a cheap KV import. Reports decode TPOT p50/p95
isolation per mode, handoff latency/bytes from the engines' own
accounting, and asserts ZERO broken streams and NO handoff leaks
(pages free back to baseline, no outstanding leases). ``--smoke``
shrinks it for tier-1 CI.

``--tp N`` (ISSUE 20) switches to the tensor-parallel A/B: the SAME
saturating burst is driven through a single-chip engine and one whose
weights and paged KV are sharded over an N-wide ``tp`` mesh, at equal
offered load. Asserts the exactness contract live — temp-0 token
identity stream for stream, and dispatch accounting equal chunk for
chunk (the mesh moves FLOPs, never driver-loop boundaries) — and
reports TPOT p50 and tok/s per arm. On CPU the mesh is forced host
devices (plumbing + exactness, not speed); the ratio is the headline
only on a real multi-chip host. ``--smoke`` shrinks it for tier-1 CI.

``--chaos`` (ISSUE 7) switches to the crash-safety acceptance run: a
2-replica continuous-engine deployment serves seeded (deterministic)
streams under load while a replica is KILLED mid-stream; every client
stream holds a replay token (``resumable=True``) and must complete
token-identical to its uninterrupted reference — the row asserts ZERO
broken client streams and reports resumes, kills, and the recovery
stall. ``--smoke`` shrinks it for tier-1 CI.
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
    parser.add_argument("--clients", type=int, default=4)
    parser.add_argument("--tokens", type=int, default=32)
    parser.add_argument("--streams", type=int, default=8,
                        help="total streams per client")
    parser.add_argument("--config", default="nano",
                        help="gpt preset, chosen by the caller: no "
                             "process here looks at its device to pick "
                             "a size (the driver of the serve arms must "
                             "stay off jax — a parent that touched it "
                             "holds the chip its replicas need)")
    parser.add_argument("--chunk", default="1,8",
                        help="comma-separated decode chunk sizes to A/B "
                             "(1 = per-token decode_step loop)")
    parser.add_argument("--overload", action="store_true",
                        help="overload A/B instead of the chunk A/B: drive "
                             "the deployment past saturation twice — "
                             "unbounded queue vs bounded queue + shedding — "
                             "and report shed rate, goodput, and completion "
                             "p99 per mode")
    parser.add_argument("--overload-duration", type=float, default=8.0)
    parser.add_argument("--overload-clients", type=int, default=24,
                        help="concurrent clients (~3x a 4-slot replica)")
    parser.add_argument("--trace", action="store_true",
                        help="observability mode: trace one streamed "
                             "request end to end, dump its span tree, "
                             "assert stage sums ≈ e2e, and check the "
                             "serve latency histograms on /metrics")
    parser.add_argument("--continuous", action="store_true",
                        help="continuous-batching A/B: static gang "
                             "@serve.batch vs the slot-pool DecodeEngine "
                             "under the same Poisson arrivals with mixed "
                             "output lengths")
    parser.add_argument("--chaos", action="store_true",
                        help="crash-safety run: kill a replica of a "
                             "2-replica engine deployment mid-load and "
                             "assert zero broken client streams "
                             "(deterministic replay resume)")
    parser.add_argument("--disagg", action="store_true",
                        help="disaggregated prefill/decode A/B "
                             "(ISSUE 14): the same bursty-prefill "
                             "Poisson mix driven through a colocated "
                             "deployment and a roles-split one at "
                             "equal offered load; reports decode TPOT "
                             "p50/p95 isolation, handoff latency, and "
                             "asserts zero broken streams and no "
                             "handoff leaks")
    parser.add_argument("--spec", action="store_true",
                        help="speculative-decoding A/B: spec off vs "
                             "n-gram vs tied-embedding model drafter "
                             "at equal offered load (direct engine "
                             "drive, no serve stack)")
    parser.add_argument("--draft-k", type=int, default=32,
                        help="proposals per verify round for --spec (a "
                             "verify forward's cost is dominated by the "
                             "max_len attention sweep, so wide drafts "
                             "are nearly free and locked-in repetitive "
                             "streams commit k+1 tokens per forward)")
    parser.add_argument("--page-size", type=int, default=8)
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel A/B (ISSUE 20): the same "
                             "saturating burst through a tp=1 engine "
                             "and one sharded over a --tp-wide mesh at "
                             "equal offered load; asserts temp-0 token "
                             "identity and equal dispatch accounting, "
                             "reports TPOT p50 and tok/s per arm (on "
                             "CPU the mesh is forced host devices — "
                             "plumbing and exactness, not speed)")
    parser.add_argument("--smoke", action="store_true",
                        help="shrunk load "
                             "for tier-1 CI (fewer requests, shorter "
                             "outputs)")
    parser.add_argument("--slots", type=int, default=8,
                        help="engine slot count == static max_batch_size")
    parser.add_argument("--rate", type=float, default=0.0,
                        help="Poisson arrival rate in req/s "
                             "(0 = calibrate from a single warm stream)")
    parser.add_argument("--requests", type=int, default=48,
                        help="requests per continuous A/B mode")
    args = parser.parse_args()
    chunks = [int(c) for c in args.chunk.split(",") if c.strip()]

    import numpy as np

    if args.tp > 1:
        # Direct engine drive: the A/B isolates the sharded compute
        # graph (column/row-parallel weights, head-sharded KV) from the
        # serve transport. On a host platform the mesh needs forced
        # devices — set the flag BEFORE jax initializes.
        if "jax" not in sys.modules and \
                "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count="
                f"{max(8, args.tp)}").strip()
        cfg_name = args.config
        run_tp_ab(args, np, cfg_name, f"gpt_{cfg_name}")
        return

    if args.spec:
        # Direct engine drive again: the A/B isolates the dispatch-loop
        # arithmetic (k sequential target steps vs draft + one verify
        # forward) from the serve transport.
        cfg_name = args.config
        run_spec_ab(args, np, cfg_name, f"gpt_{cfg_name}")
        return

    import ray_tpu as rt
    from ray_tpu import serve

    rt.init(num_cpus=8, ignore_reinit_error=True)
    if args.trace:
        from ray_tpu.util import tracing

        tracing.enable()  # before start(): proxies mirror the flag
        serve.start(http_options={"host": "127.0.0.1", "port": 0})
    else:
        serve.start(proxy=False)

    cfg_name = args.config
    max_new = args.tokens

    @serve.deployment(max_ongoing_requests=8)
    class GPTStream:
        """Decode-loop replica. chunk=1: one jitted decode step per
        streamed token. chunk=k: one fused k-step scan per streamed
        per-chunk token slice."""

        def __init__(self, cfg_name: str, max_len: int, chunk_sizes):
            import jax

            from ray_tpu.models import gpt, gpt_decode

            self.cfg = gpt.CONFIGS[cfg_name]
            self.gd = gpt_decode
            self.params = gpt.init_params(jax.random.PRNGKey(0), self.cfg)
            self.max_len = max_len
            self._prefill = jax.jit(gpt_decode.prefill, static_argnums=(2,))
            self._step = jax.jit(gpt_decode.decode_step, static_argnums=(3,))
            self._chunk_steps = {
                k: gpt_decode.jit_decode_chunk(self.cfg, k)
                for k in chunk_sizes if k > 1}
            # Jitted-dispatch accounting for the A/B artifact; locked —
            # up to max_ongoing_requests threads decode concurrently.
            import threading as _threading

            self._stats_lock = _threading.Lock()
            self._dispatches = 0
            self._tokens = 0

        def _count(self, dispatches: int, tokens: int):
            with self._stats_lock:
                self._dispatches += dispatches
                self._tokens += tokens

        def warm(self, prompt_bucket: int, _=None):
            import jax
            import jax.numpy as jnp

            cache = self.gd.init_cache(self.cfg, 1, self.max_len)
            logits, cache = self._prefill(
                self.params, jnp.zeros((1, prompt_bucket), jnp.int32),
                self.cfg, cache)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self._step(self.params, cache, tok, self.cfg)
            rng = jax.random.PRNGKey(0)
            for step in self._chunk_steps.values():
                step(self.params, cache, tok, rng)
            return "warm"

        def reset_stats(self):
            with self._stats_lock:
                self._dispatches = 0
                self._tokens = 0
            return "reset"

        def stats(self):
            with self._stats_lock:
                return {"dispatches": self._dispatches,
                        "tokens": self._tokens}

        def __call__(self, request):
            """request = {"prompt_len", "max_new", "chunk"}; yields one
            token id per step (chunk=1) or one token-id list per fused
            chunk (chunk=k)."""
            import jax.numpy as jnp

            if hasattr(request, "json"):  # HTTP ingress
                request = request.json()
            plen = int(request.get("prompt_len", 16))
            max_new = int(request.get("max_new", 16))
            chunk = int(request.get("chunk", 1))
            prompt = jnp.asarray(
                np.random.randint(0, self.cfg.vocab_size, (1, plen),
                                  dtype=np.int32))
            cache = self.gd.init_cache(self.cfg, 1, self.max_len)
            logits, cache = self._prefill(self.params, prompt, self.cfg,
                                          cache)
            self._count(1, 0)
            if chunk <= 1:
                for _ in range(max_new):
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    self._count(0, 1)
                    yield int(tok[0])
                    logits, cache = self._step(self.params, cache, tok,
                                               self.cfg)
                    self._count(1, 0)
                return
            if max_new <= 0:
                return
            # Unlisted chunk size (e.g. ad-hoc HTTP request): jit on
            # demand instead of dying with a KeyError mid-stream. No
            # lock: dict get/set are GIL-atomic and jit_decode_chunk is
            # lru_cached, so racing threads get the same wrapper.
            step = self._chunk_steps.get(chunk)
            if step is None:
                step = self._chunk_steps[chunk] = \
                    self.gd.jit_decode_chunk(self.cfg, chunk)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            self._count(0, 1)
            yield [int(tok[0])]
            # The library driver IS the measured path: decode_until
            # yields exactly one trimmed slice per fused dispatch.
            for slice_ in self.gd.decode_until(
                    step, self.params, cache, tok, max_new - 1):
                self._count(1, slice_.shape[1])
                yield [int(t) for t in slice_[0]]

    # Cache sized for the worst chunk over-run: the last fused chunk may
    # execute up to (chunk - 1) steps past max_new before truncation.
    max_len = 16 + max_new + max(max(chunks), 8)
    if args.disagg:
        run_disagg_ab(args, serve, np, cfg_name, f"gpt_{cfg_name}")
        serve.shutdown()
        rt.shutdown()
        return
    if args.chaos:
        run_chaos_mode(args, serve, np, cfg_name, f"gpt_{cfg_name}")
        serve.shutdown()
        rt.shutdown()
        return
    if args.continuous:
        run_continuous_ab(args, serve, np, cfg_name, f"gpt_{cfg_name}")
        serve.shutdown()
        rt.shutdown()
        return
    if args.trace:
        run_trace_mode(args, rt, serve, np, cfg_name, max(chunks),
                       f"gpt_{cfg_name}")
        serve.shutdown()
        rt.shutdown()
        return
    if args.overload:
        run_overload_ab(args, serve, GPTStream, cfg_name, max_len, chunks,
                        f"gpt_{cfg_name}")
        serve.shutdown()
        rt.shutdown()
        return
    handle = serve.run(GPTStream.bind(cfg_name, max_len, chunks),
                       name="gpt_stream", route_prefix="/generate")
    assert handle.options(method_name="warm").remote(16).result(
        timeout=600) == "warm"
    # End-to-end warm stream per mode (covers the streaming transport).
    for c in chunks:
        list(handle.options(stream=True).remote(
            {"prompt_len": 16, "max_new": 2, "chunk": c}))

    model = f"gpt_{cfg_name}"

    def run_mode(chunk: int):
        handle.options(method_name="reset_stats").remote().result(
            timeout=60)
        ttfts, tok_lats = [], []
        total_tokens = [0]
        lock = threading.Lock()

        def client():
            for _ in range(args.streams):
                t0 = time.perf_counter()
                gen = handle.options(stream=True).remote(
                    {"prompt_len": 16, "max_new": max_new, "chunk": chunk})
                last = t0
                first = None
                n = 0
                lats = []
                for item in gen:
                    now = time.perf_counter()
                    width = len(item) if isinstance(item, list) else 1
                    if first is None:
                        first = now - t0
                    else:
                        # Amortized per-token latency: a fused chunk
                        # lands j tokens in one arrival.
                        lats.extend([(now - last) / width] * width)
                    last = now
                    n += width
                with lock:
                    ttfts.append(first)
                    tok_lats.extend(lats)
                    total_tokens[0] += n

        threads = [threading.Thread(target=client)
                   for _ in range(args.clients)]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_start

        stats = handle.options(method_name="stats").remote().result(
            timeout=60)
        dpt = stats["dispatches"] / max(stats["tokens"], 1)
        suffix = "" if chunk == 1 else f"_chunk{chunk}"
        ttfts.sort()
        tok_lats.sort()
        print(json.dumps({
            "metric": f"serve_{model}_ttft_p50_ms{suffix}",
            "value": round(ttfts[len(ttfts) // 2] * 1000, 2), "unit": "ms",
            "p95_ms": round(ttfts[int(len(ttfts) * 0.95)] * 1000, 2),
            "clients": args.clients, "chunk": chunk}))
        if tok_lats:
            print(json.dumps({
                "metric": f"serve_{model}_tok_latency_p50_ms{suffix}",
                "value": round(tok_lats[len(tok_lats) // 2] * 1000, 2),
                "unit": "ms",
                "p95_ms": round(tok_lats[int(len(tok_lats) * 0.95)] * 1000,
                                2),
                "p99_ms": round(tok_lats[int(len(tok_lats) * 0.99)] * 1000,
                                2),
                "chunk": chunk}))
        print(json.dumps({
            "metric": f"serve_{model}_decode_throughput{suffix}",
            "value": round(total_tokens[0] / wall, 1), "unit": "tokens/s",
            "clients": args.clients, "streams": args.clients * args.streams,
            "chunk": chunk}))
        print(json.dumps({
            "metric": f"serve_{model}_dispatches_per_token{suffix}",
            "value": round(dpt, 4), "unit": "dispatches/token",
            "dispatches": stats["dispatches"], "tokens": stats["tokens"],
            "chunk": chunk}))
        return {"chunk": chunk,
                "tok_p50_ms": round(
                    tok_lats[len(tok_lats) // 2] * 1000, 2)
                if tok_lats else None,
                "tok_s": round(total_tokens[0] / wall, 1),
                "dispatches_per_token": round(dpt, 4)}

    results = [run_mode(c) for c in chunks]
    _finish_chunk_ab(results, model, serve, rt)


def _finish_chunk_ab(results, model, serve, rt):
    if len(results) > 1:
        base = next((r for r in results if r["chunk"] == 1), results[0])
        best = min(results, key=lambda r: r["dispatches_per_token"])
        print(json.dumps({
            "metric": f"serve_{model}_chunked_decode_ab",
            "value": round(base["dispatches_per_token"]
                           / max(best["dispatches_per_token"], 1e-9), 2),
            "unit": "x_fewer_dispatches", "modes": results}))
    serve.shutdown()
    rt.shutdown()


def make_traced_deployment(serve, np):
    """Batched chunked-decode deployment for --trace: the ingress
    streams per-chunk token slices pulled from a ``@serve.batch``
    streaming handler, so ONE traced request crosses every serve stage
    — proxy admission, router queue, replica dispatch, batch flush, and
    one fused decode dispatch per chunk."""

    @serve.deployment(max_ongoing_requests=4)
    class GPTTraced:
        def __init__(self, cfg_name: str, max_len: int, chunk: int):
            import jax

            from ray_tpu.models import gpt, gpt_decode

            self.cfg = gpt.CONFIGS[cfg_name]
            self.gd = gpt_decode
            self.params = gpt.init_params(jax.random.PRNGKey(0), self.cfg)
            self.max_len = max_len
            self.chunk = chunk
            self._prefill = jax.jit(gpt_decode.prefill,
                                    static_argnums=(2,))
            self._chunk_step = gpt_decode.jit_decode_chunk(self.cfg,
                                                           chunk)

        def _stream_one(self, request):
            import jax.numpy as jnp

            plen = int(request.get("prompt_len", 16))
            max_new = int(request.get("max_new", 16))
            prompt = jnp.asarray(np.random.randint(
                0, self.cfg.vocab_size, (1, plen), dtype=np.int32))
            cache = self.gd.init_cache(self.cfg, 1, self.max_len)
            logits, cache = self._prefill(self.params, prompt, self.cfg,
                                          cache)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            yield [int(tok[0])]
            for slice_ in self.gd.decode_until(
                    self._chunk_step, self.params, cache, tok,
                    max_new - 1):
                yield [int(t) for t in slice_[0]]

        @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.005,
                     stream=True)
        def decode_batch(self, requests):
            # Lockstep drive of the batched per-request generators; a
            # finished caller receives empty slices until the batch
            # drains (single-request trace mode never hits that path).
            gens = [self._stream_one(r) for r in requests]
            done = [False] * len(gens)
            while True:
                out = []
                for i, g in enumerate(gens):
                    if done[i]:
                        out.append([])
                        continue
                    try:
                        out.append(next(g))
                    except StopIteration:
                        done[i] = True
                        out.append([])
                if all(done):
                    return
                yield out

        def warm(self, plen: int = 16):
            list(self._stream_one({"prompt_len": plen,
                                   "max_new": self.chunk + 1}))
            return "warm"

        def __call__(self, request):
            if hasattr(request, "json"):  # HTTP ingress
                request = request.json()
            for slice_ in self.decode_batch(request):
                if slice_:
                    yield slice_

    return GPTTraced


def _span_tree(spans, root):
    """Children-of index for one trace + pretty printer."""
    kids = {}
    for s in spans:
        kids.setdefault(s.get("parent_id"), []).append(s)
    for v in kids.values():
        v.sort(key=lambda s: s["start"])
    lines = []

    def walk(span, depth):
        dur_ms = (span["end"] - span["start"]) * 1000
        lines.append(f"{'  ' * depth}{span['name']}  "
                     f"[{dur_ms:.2f} ms]  kind={span['kind']}")
        for c in kids.get(span["span_id"], []):
            walk(c, depth + 1)

    walk(root, 0)
    return "\n".join(lines)


def run_trace_mode(args, rt, serve, np, cfg_name, chunk, model):
    """One traced streamed request through the full data plane; dump the
    span tree, check the stage partition sums to ~e2e, and confirm the
    latency histograms landed on /metrics."""
    import urllib.request

    from ray_tpu.util import tracing

    # Enough decode work that the measured stages dominate the fixed
    # per-request overheads the partition cannot see (RPC transit,
    # chunk relay) — the 10% tolerance is on e2e.
    max_new = max(args.tokens, 64)
    max_len = 16 + max_new + max(chunk, 8)
    GPTTraced = make_traced_deployment(serve, np)
    handle = serve.run(
        GPTTraced.bind(cfg_name, max_len, chunk),
        name="gpt_trace", route_prefix="/trace")
    assert handle.options(method_name="warm").remote(16).result(
        timeout=600) == "warm"
    port = serve.status()["http"]["port"]

    body = json.dumps({"prompt_len": 16, "max_new": max_new}).encode()
    want = {"proxy.admission", "router.queue_wait", "replica.queue_wait",
            "user_code", "batch.wait", "decode.chunk"}

    def traced_request():
        """One streamed request; returns (its trace, server span,
        client-side e2e, head drop total)."""
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/trace", data=body, method="POST")
        sent_at = time.time()
        t0 = time.perf_counter()
        with urllib.request.urlopen(req, timeout=120) as resp:
            tokens = 0
            for line in resp:
                if line.strip():
                    tokens += len(json.loads(line))
        e2e_client = time.perf_counter() - t0
        assert tokens >= max_new, f"stream returned {tokens} tokens"
        # The proxy flushes spans on a ~1s cadence; wait for the tree.
        deadline = time.time() + 30
        spans = []
        while time.time() < deadline:
            meta = tracing.get_spans(limit=100_000, with_meta=True)
            spans = meta["spans"]
            for s in spans:
                if s["kind"] == "server" and "[stream]" in s["name"] \
                        and s["start"] >= sent_at - 1.0:
                    mine = [x for x in spans
                            if x["trace_id"] == s["trace_id"]]
                    if want <= {x["name"] for x in mine}:
                        return mine, s, e2e_client, meta["dropped_total"]
            time.sleep(0.5)
        raise AssertionError(
            f"incomplete span tree; stages seen: "
            f"{sorted({x['name'] for x in spans})}")

    def dur(trace, name):
        return sum(s["end"] - s["start"] for s in trace
                   if s["name"] == name)

    # Stage partition of the critical path (batch.wait and decode.chunk
    # nest inside user_code): submission overhead + transit + handler
    # stream time should account for ~all of the server-observed e2e.
    # The residue is per-chunk relay overhead, which balloons when the
    # HOST is oversubscribed — take the best of a few attempts so the
    # check measures the instrumentation, not ambient machine load.
    best = None
    for attempt in range(3):
        trace, server, e2e_client, dropped = traced_request()
        e2e = server["end"] - server["start"]
        stage_sum = (dur(trace, "proxy.admission")
                     + dur(trace, "replica.queue_wait")
                     + dur(trace, "user_code"))
        gap = abs(e2e - stage_sum) / max(e2e, 1e-9)
        if best is None or gap < best[0]:
            best = (gap, trace, server, e2e, stage_sum, e2e_client,
                    dropped)
        if gap <= 0.10:
            break
    gap, trace, server, e2e, stage_sum, e2e_client, dropped = best
    print(_span_tree(trace, server))
    n_chunks = sum(1 for s in trace if s["name"] == "decode.chunk")
    print(json.dumps({
        "metric": f"serve_{model}_trace_stage_coverage",
        "value": round(stage_sum / max(e2e, 1e-9), 4),
        "unit": "fraction_of_e2e",
        "e2e_ms": round(e2e * 1000, 2),
        "client_e2e_ms": round(e2e_client * 1000, 2),
        "stage_sum_ms": round(stage_sum * 1000, 2),
        "decode_chunks": n_chunks,
        "spans_in_trace": len(trace),
        "spans_dropped_total": dropped,
    }))
    assert gap <= 0.10, \
        f"stage sum {stage_sum * 1000:.1f} ms deviates " \
        f"{gap:.0%} from e2e {e2e * 1000:.1f} ms (>10%)"
    assert n_chunks >= max_new // chunk, \
        f"expected ≥{max_new // chunk} decode.chunk spans, got {n_chunks}"

    # Histograms reach the head with the ~1s metric flush.
    needed = ["serve_request_e2e_seconds", "serve_ttft_seconds",
              "serve_tpot_seconds"]
    deadline = time.time() + 30
    counts = {}
    while time.time() < deadline:
        text = rt.metrics_text()
        counts = {}
        for n in needed:
            for line in text.splitlines():
                if line.startswith(f"ray_tpu_{n}_count"):
                    counts[n] = counts.get(n, 0.0) + float(line.rsplit(
                        " ", 1)[1])
        if all(counts.get(n, 0) > 0 for n in needed):
            break
        time.sleep(0.5)
    for n in needed:
        assert counts.get(n, 0) > 0, \
            f"{n} has no observations on /metrics: {counts}"
    print(json.dumps({
        "metric": f"serve_{model}_trace_histograms",
        "value": 1, "unit": "ok", "counts": counts}))


def pct(xs, q):
    """Nearest-rank percentile (no interpolation); None on empty."""
    if not xs:
        return None
    xs = sorted(xs)
    return xs[min(int(len(xs) * q), len(xs) - 1)]


def _mk_prompt(rid: int, plen: int, vocab: int):
    """Deterministic per-request prompt, identical across A/B modes."""
    import numpy as _np

    return _np.random.default_rng(1000 + rid).integers(
        0, vocab, (plen,)).astype(_np.int32)


def make_continuous_deployments(serve, np, plen: int, slots: int):
    """The two contenders, built on identical model weights.

    - ``GPTStatic``: the PRE-engine architecture — gang-scheduled
      ``@serve.batch(stream=True)`` with bucketed padding: a batch
      forms once, allocates a fresh KV cache, prefills all lanes
      together, and decodes in lockstep until the LONGEST lane
      finishes (shorter lanes ride along emitting nothing). A request
      arriving mid-generation waits for the next gang.
    - ``GPTContinuous``: the slot-pool engine behind
      ``@serve.batch(continuous=True)`` — persistent KV pool, per-slot
      admission at chunk boundaries, per-slot freeing at max_new.
    """

    @serve.deployment(max_ongoing_requests=128)
    class GPTStatic:
        def __init__(self, cfg_name: str, max_len: int, chunk: int):
            import jax

            from ray_tpu.models import gpt, gpt_decode

            self.cfg = gpt.CONFIGS[cfg_name]
            self.gd = gpt_decode
            self.params = gpt.init_params(jax.random.PRNGKey(0), self.cfg)
            self.max_len = max_len
            self.chunk = chunk
            self._prefill = jax.jit(gpt_decode.prefill,
                                    static_argnums=(2,))

        @serve.batch(max_batch_size=slots, batch_wait_timeout_s=0.02,
                     pad_to_bucket=True, buckets=(slots,),
                     stream=True)
        def decode_batch(self, requests):
            import jax.numpy as jnp

            B = len(requests)        # == slots after padding
            prompts = np.stack([
                _mk_prompt(int(r["rid"]), plen, self.cfg.vocab_size)
                for r in requests])
            mns = [int(r["max_new"]) for r in requests]
            top = max(mns)
            # Fresh per-gang cache: exactly the allocation the engine's
            # persistent pool removes.
            cache = self.gd.init_cache(self.cfg, B, self.max_len)
            logits, cache = self._prefill(
                self.params, jnp.asarray(prompts), self.cfg, cache)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            first = np.asarray(tok)
            sent = [1] * B
            yield [[int(first[i])] if mns[i] >= 1 else []
                   for i in range(B)]
            if top <= 1:
                return
            step = self.gd.jit_decode_chunk(self.cfg, self.chunk)
            for slice_ in self.gd.decode_until(
                    step, self.params, cache, tok, top - 1):
                out = []
                for i in range(B):
                    take = slice_[i][:max(0, mns[i] - sent[i])]
                    sent[i] += len(take)
                    out.append([int(t) for t in take])
                yield out

        def warm(self, max_new: int = 2):
            return "warm"

        def __call__(self, request):
            if hasattr(request, "json"):
                request = request.json()
            return self.decode_batch(request)

    @serve.deployment(max_ongoing_requests=128)
    class GPTContinuous:
        def __init__(self, cfg_name: str, max_len: int, slots: int,
                     chunk: int):
            import jax

            from ray_tpu.models import gpt
            from ray_tpu.serve.engine import DecodeEngine

            self.cfg = gpt.CONFIGS[cfg_name]
            params = gpt.init_params(jax.random.PRNGKey(0), self.cfg)
            self.engine = DecodeEngine(
                params, self.cfg, slots=slots, chunk=chunk,
                max_len=max_len, prompt_buckets=(plen,),
                deployment="gpt_continuous")

        @serve.batch(continuous=True)
        def decode(self, request):
            return self.engine, {
                "prompt": _mk_prompt(int(request["rid"]), plen,
                                     self.cfg.vocab_size),
                "max_new": int(request["max_new"]),
                "seed": int(request["rid"])}

        def warm(self, max_new: int = 2):
            list(self.engine.stream(_mk_prompt(0, plen,
                                               self.cfg.vocab_size),
                                    max_new))
            return "warm"

        def stats(self):
            return self.engine.stats()

        def __call__(self, request):
            if hasattr(request, "json"):
                request = request.json()
            return self.decode(request)

    return GPTStatic, GPTContinuous


def run_continuous_ab(args, serve, np, cfg_name, model):
    """ISSUE 5 acceptance A/B: identical Poisson arrivals + mixed output
    lengths through the static gang and the slot engine; continuous mode
    should beat static on BOTH p50 TTFT and total tok/s."""
    import threading as _th

    slots = max(2, args.slots if not args.smoke else min(args.slots, 4))
    chunk = 8
    plen = 16
    n_req = args.requests if not args.smoke else min(args.requests, 12)
    base = args.tokens if not args.smoke else min(args.tokens, 8)
    # Wide output-length spread — the workload continuous batching
    # exists for: the gang rides every batch out to its LONGEST lane,
    # so its wasted lane-steps scale with max/mean of the mix.
    mix = sorted({max(2, base // 4), base, 2 * base}) if not args.smoke \
        else sorted({max(2, base // 4), max(3, base // 2), base})
    max_len = plen + mix[-1] + chunk
    sched = np.random.default_rng(42)
    max_news = sched.choice(mix, size=n_req)
    mean_new = float(np.mean(max_news))
    GPTStatic, GPTContinuous = make_continuous_deployments(
        serve, np, plen, slots)

    def drive(handle, rate):
        inter = np.random.default_rng(7).exponential(1.0 / rate,
                                                     size=n_req)
        arrivals = np.cumsum(inter)
        ttfts = [None] * n_req
        comps = [None] * n_req
        toks = [0] * n_req
        errs = [None] * n_req
        start = time.perf_counter()

        def one(i):
            delay = start + arrivals[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t0 = time.perf_counter()
            try:
                gen = handle.options(stream=True, timeout_s=300).remote(
                    {"rid": int(i), "max_new": int(max_news[i])})
                first = None
                n = 0
                for item in gen:
                    w = len(item)
                    if w == 0:
                        continue  # gang lane finished early: empty slices
                    if first is None:
                        first = time.perf_counter() - t0
                    n += w
            except Exception as e:  # noqa: BLE001 - report in the assert
                errs[i] = repr(e)
                return
            ttfts[i] = first
            comps[i] = time.perf_counter() - t0
            toks[i] = n

        threads = [_th.Thread(target=one, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - start
        bad = [(i, toks[i], int(max_news[i]), errs[i])
               for i in range(n_req) if toks[i] != max_news[i]]
        assert not bad, f"short/failed streams (i, got, want, err): {bad}"
        return ttfts, comps, wall, sum(toks)

    # Both deployments stay up for the whole A/B and the drive passes
    # INTERLEAVE (static, continuous, static, continuous): this box's
    # throughput drifts minutes-to-minutes, so back-to-back passes keep
    # the modes under the same machine conditions; best-of-N per mode
    # then discards the contention-slowed passes (noise on a shared
    # host is one-sided — it only ever slows a pass down).
    passes = 1 if args.smoke else 2
    handles = {}
    for mode, app in (("static", GPTStatic.bind(cfg_name, max_len, chunk)),
                      ("continuous",
                       GPTContinuous.bind(cfg_name, max_len, slots,
                                          chunk))):
        handle = serve.run(app, name=f"gpt_{mode}",
                           route_prefix=f"/{mode}")
        handle.options(method_name="warm").remote(2).result(timeout=600)
        # Compile the full-width programs before the clock starts.
        warm_threads = [_th.Thread(target=lambda: list(
            handle.options(stream=True).remote(
                {"rid": 0, "max_new": 2}))) for _ in range(slots)]
        for t in warm_threads:
            t.start()
        for t in warm_threads:
            t.join()
        handles[mode] = handle
    rate = args.rate
    if rate <= 0:
        # Calibrate offered load once, from a DEEP saturating burst
        # through the static gang: 3x`slots` UNIFORM-length streams all
        # queued at t=0, so every gang forms full-width (thread-start
        # jitter can't split gangs — the backlog refills them) and has
        # no ride-out waste. The aggregate rate approximates the ideal
        # full-width decode rate at THIS moment on THIS machine (an
        # UNDER-estimate when client-side overhead inflates elapsed
        # time, so err high). Offer 2x of it: both modes run
        # capacity-bound in every machine regime, so tok/s measures
        # architecture (gang ride-out waste vs slot recycling), not the
        # arrival schedule. Identical offered load for both modes.
        n_cal = 3 * slots
        t0 = time.perf_counter()
        burst = [_th.Thread(target=lambda: list(
            handles["static"].options(stream=True, timeout_s=300).remote(
                {"rid": 0, "max_new": int(base)})))
            for _ in range(n_cal)]
        for t in burst:
            t.start()
        for t in burst:
            t.join()
        ideal = n_cal * base / (time.perf_counter() - t0)
        rate = max(2.0, 2.0 * ideal / mean_new)
    runs = {"static": [], "continuous": []}
    for _ in range(passes):
        for mode in ("static", "continuous"):
            runs[mode].append(drive(handles[mode], rate))
    results = {}
    for mode in ("static", "continuous"):
        # Best pass by tok/s; its TTFT/completion percentiles ride along
        # so each reported row is one coherent measurement.
        ttfts, comps, wall, total = max(runs[mode],
                                        key=lambda r: r[3] / r[2])
        row = {
            "metric": f"serve_{model}_{mode}_mode",
            "value": round(total / wall, 1), "unit": "tokens/s",
            "ttft_p50_ms": round(pct(ttfts, 0.50) * 1000, 2),
            "ttft_p95_ms": round(pct(ttfts, 0.95) * 1000, 2),
            "completion_p50_ms": round(pct(comps, 0.50) * 1000, 2),
            "completion_p95_ms": round(pct(comps, 0.95) * 1000, 2),
            "requests": n_req, "passes": passes,
            "offered_rate_req_s": round(rate, 2),
            "offered_tok_s": round(rate * mean_new, 1),
            "tok_s_per_pass": [round(r[3] / r[2], 1) for r in runs[mode]],
            "slots": slots, "chunk": chunk,
            "output_len_mix": [int(m) for m in mix],
        }
        if mode == "continuous":
            st = handles[mode].options(
                method_name="stats").remote().result(timeout=60)
            row["avg_slot_occupancy"] = round(st["avg_occupancy"], 3)
            row["dispatches_per_token"] = round(
                st["dispatches_per_token"], 4)
            row["engine"] = {k: st[k] for k in
                             ("admitted", "completed", "dispatches",
                              "prefills", "tokens")}
        print(json.dumps(row))
        results[mode] = row
        serve.delete(f"gpt_{mode}")
    st, co = results["static"], results["continuous"]
    print(json.dumps({
        "metric": f"serve_{model}_continuous_ab",
        "value": round(co["value"] / max(st["value"], 1e-9), 2),
        "unit": "x_tokens_s_vs_static",
        "ttft_p50_ratio": round(st["ttft_p50_ms"]
                                / max(co["ttft_p50_ms"], 1e-9), 2),
        "continuous_wins_ttft": co["ttft_p50_ms"] < st["ttft_p50_ms"],
        "offered_rate_req_s": co["offered_rate_req_s"],
        "smoke": bool(args.smoke),
    }))


def _drive_burst(eng, prompts, max_new, *, np):
    """Saturating burst shared by the ISSUE 16 arms: every request
    queued at t=0, one thread per request. Returns per-request
    (ttft, completion, tokens) plus the emitted token streams (for the
    kernel arm's token-identity check)."""
    import threading as _th

    n = len(prompts)
    ttfts = [None] * n
    comps = [None] * n
    streams = [None] * n

    def one(i):
        t0 = time.perf_counter()
        first = None
        out = []
        for s in eng.stream(prompts[i], int(max_new), seed=i):
            if first is None:
                first = time.perf_counter() - t0
            out.append(np.asarray(s))
        ttfts[i] = first
        comps[i] = time.perf_counter() - t0
        streams[i] = np.concatenate(out) if out else np.zeros(0, np.int32)

    threads = [_th.Thread(target=one, args=(i,)) for i in range(n)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    short = [(i, streams[i].shape[0]) for i in range(n)
             if streams[i].shape[0] != max_new]
    assert not short, f"short streams (i, got): {short}"
    return ttfts, comps, wall, streams


def run_tp_ab(args, np, cfg_name, model):
    """ISSUE 20 acceptance A/B: the SAME saturating burst through a
    single-chip engine and one whose weights + paged KV are sharded
    over a ``tp``-wide mesh, at equal offered load. The exactness
    contract is checked live: at temperature 0 the sharded arm must
    emit IDENTICAL token streams (psum'd row-parallel partials, not
    approximately-equal ones), and its dispatch accounting must match
    chunk for chunk — the mesh changes where the FLOPs run, never how
    many driver-loop boundaries the stream crosses. On CPU the mesh is
    forced host devices, so the rows prove plumbing and exactness; the
    TPOT/tok-s ratio is the headline only on a real multi-chip host."""
    import jax

    from ray_tpu.models import gpt
    from ray_tpu.serve.engine import DecodeEngine

    cfg = gpt.CONFIGS[cfg_name]
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    ps = args.page_size
    plen = 2 * ps                             # two pages of history
    max_new = 8 if args.smoke else 24
    max_len = plen + max_new + ps
    slots = 2 if args.smoke else 4
    n_req = 2 * slots                         # lanes reuse slots
    rng = np.random.default_rng(20)
    prompts = [rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
               for _ in range(n_req)]

    rows = {}
    token_streams = {}
    accounting = {}
    for tp in (1, args.tp):
        eng = DecodeEngine(
            params, cfg, slots=slots, chunk=4, max_len=max_len,
            prompt_buckets=(plen,), page_size=ps,
            prefix_cache=False, tp=tp, deployment=f"tp{tp}_bench")
        try:
            eng.warm_up()           # the group's prefill program too
            list(eng.stream(prompts[0], max_new, seed=0))   # warm
            ttfts, comps, wall, streams = _drive_burst(
                eng, prompts, max_new, np=np)
            token_streams[tp] = streams
            tpots = [(comps[i] - ttfts[i]) / max(max_new - 1, 1)
                     for i in range(n_req)]
            st = eng.stats()
            accounting[tp] = (st["prefills"], st["dispatches"])
            rows[tp] = {
                "metric": f"serve_{model}_tp{tp}_mode",
                "value": round(pct(tpots, 0.5) * 1000, 3),
                "unit": "tpot_p50_ms",
                "ttft_p50_ms": round(pct(ttfts, 0.5) * 1000, 2),
                "tok_s": round(n_req * max_new / wall, 1),
                "dispatches": st["dispatches"],
                "prefills": st["prefills"],
                "mesh": [["tp", tp]] if tp > 1 else [],
                "requests": n_req, "max_new": max_new,
                "prompt_len": plen,
            }
            print(json.dumps(rows[tp]))
        finally:
            eng.shutdown()
    identical = all(
        np.array_equal(token_streams[1][i], token_streams[args.tp][i])
        for i in range(n_req))
    assert identical, \
        f"tp={args.tp} arm diverged from tp=1 at temp 0"
    assert accounting[1] == accounting[args.tp], (
        f"dispatch accounting diverged: tp=1 {accounting[1]} vs "
        f"tp={args.tp} {accounting[args.tp]} (prefills, dispatches)")
    print(json.dumps({
        "metric": f"serve_{model}_tp_ab",
        "value": round(rows[1]["value"]
                       / max(rows[args.tp]["value"], 1e-9), 2),
        "unit": "x_tpot_tp1_vs_sharded",
        "tp": args.tp,
        "token_identical_temp0": identical,
        "dispatches_equal": accounting[1] == accounting[args.tp],
        "tok_s_tp1": rows[1]["tok_s"],
        "tok_s_sharded": rows[args.tp]["tok_s"],
        "host_mesh": jax.default_backend() == "cpu",
        "smoke": bool(args.smoke),
    }))


def run_spec_ab(args, np, cfg_name, model):
    """ISSUE 9 acceptance A/B: identical saturating bursts of
    repetitive-suffix prompts through three engines on the same
    weights — spec off, n-gram drafter, tied-embedding model drafter —
    INTERLEAVED passes with best-of-N per mode (same discipline as
    --continuous: noise on a shared host is one-sided). The
    workload is the one speculative decoding exists for — locally
    repetitive continuations — and is SCREENED for it: candidate
    repetitive-suffix prompts are generated, their greedy
    continuations simulated once against the n-gram drafter
    (host-side, deterministic), and the most predictable ones drive
    the A/B; the screen's acceptance distribution is reported so the
    selection is visible. Spec modes run with ``spec_threshold=2.5``
    (pool-wide adaptive speculation — on CPU a verify forward costs a
    sizable fraction of a fused chunk, so speculating through
    unpredictable phases would only burn forwards; on
    bandwidth-bound accelerators the threshold belongs at 0). Reports
    per mode: tok/s, TTFT p50, TPOT p50/p95; spec modes add
    accepted-tokens-per-target-forward and acceptance rate from the
    engine's own accounting."""
    import threading as _th

    import jax

    from ray_tpu.models import gpt, gpt_decode
    from ray_tpu.serve.draft import NGramDrafter
    from ray_tpu.serve.engine import DecodeEngine

    cfg = gpt.CONFIGS[cfg_name]
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    chunk = 8
    draft_k = max(1, args.draft_k)
    spec_threshold = 2.5
    # Half the serving default: per-boundary host work is amortized
    # over committed tokens, and the spec path runs ~3x the boundaries
    # (cheaper each) — a leaner pool keeps the A/B measuring dispatch
    # arithmetic rather than python bookkeeping.
    slots = 4 if args.smoke else max(4, args.slots // 2)
    plen = 24
    mix = [12, 24] if args.smoke else [64, 88]
    n_req = 2 * slots if args.smoke else 4 * slots
    n_cand = n_req if args.smoke else 6 * n_req
    max_len = min(cfg.max_seq,
                  plen + mix[-1] + max(chunk, draft_k + 1))
    buckets = (plen,)
    modes = ("off", "ngram") if args.smoke else ("off", "ngram", "model")
    # This box's throughput drifts ~2x minutes-to-minutes (see the
    # --continuous calibration note): off/ngram run back-to-back in
    # EVERY pass and best-of-5 discards the contention-slowed passes
    # (noise on a shared host is one-sided). The model drafter is not
    # the headline — one pass documents it.
    passes = 1 if args.smoke else 5

    def mk_candidate(cid):
        # Repetitive-suffix prompt families: a repeated pattern of
        # period 1, 2, or 4 — the structure prompt-lookup drafting
        # feeds on.
        r = np.random.default_rng(700 + cid)
        kind = cid % 3
        if kind == 0:
            return np.full((plen,),
                           r.integers(0, cfg.vocab_size), np.int32)
        per = 2 if kind == 1 else 4
        pat = r.integers(0, cfg.vocab_size, (per,)).astype(np.int32)
        return np.concatenate([pat] * (plen // per))

    def sim_acceptance(prompt, toks):
        """Rounds of the n-gram drafter against a known greedy stream:
        the deterministic host-side screen (and a preview of what the
        engine's verify rounds will accept)."""
        d = NGramDrafter()
        d.configure(slots=1, max_len=max_len, prompt_buckets=buckets,
                    draft_k=draft_k)
        d.admit(0, prompt, int(toks[0]))
        i, rounds, acc = 1, 0, 0
        active = np.array([True])
        last = np.array([toks[0]], np.int32)
        while i < len(toks):
            props = d.propose(active, last)[0]
            a = 0
            while a < draft_k and i + a < len(toks) \
                    and props[a] == toks[i + a]:
                a += 1
            j = min(a + 1, len(toks) - i)
            d.observe(0, np.asarray(toks[i:i + j]), min(a, j - 1))
            last[0] = toks[i + j - 1]
            i += j
            rounds += 1
            acc += a
        d.free(0)
        return acc / max(rounds, 1)

    # Screen: greedy-decode every candidate once (also warms the
    # library programs) and keep the n_req most n-gram-predictable.
    scores = []
    for cid in range(n_cand):
        p = mk_candidate(cid)
        toks = np.concatenate([s[0] for s in gpt_decode.generate_chunked(
            params, p[None], cfg, mix[-1], chunk=chunk,
            max_len=max_len)]).tolist()
        scores.append((sim_acceptance(p, toks), cid))
    scores.sort(reverse=True)
    chosen = [cid for _score, cid in scores[:n_req]]
    screen = [round(s, 2) for s, _cid in scores[:n_req]]

    def mk_prompt(rid):
        return mk_candidate(chosen[rid % len(chosen)])

    max_news = np.random.default_rng(7).choice(mix, size=n_req)

    def build(mode):
        return DecodeEngine(
            params, cfg, slots=slots, chunk=chunk, max_len=max_len,
            prompt_buckets=buckets, draft_k=draft_k,
            spec_decode=None if mode == "off" else mode,
            spec_threshold=spec_threshold,
            deployment=f"spec_{mode}_bench")

    def drive(eng):
        """Saturating burst: all n_req requests queued at t=0 — equal
        offered load for every mode."""
        ttfts = [None] * n_req
        comps = [None] * n_req
        toks = [0] * n_req

        def one(i):
            t0 = time.perf_counter()
            first = None
            n = 0
            for s in eng.stream(mk_prompt(i), int(max_news[i]), seed=i):
                if first is None:
                    first = time.perf_counter() - t0
                n += s.shape[0]
            ttfts[i] = first
            comps[i] = time.perf_counter() - t0
            toks[i] = n

        threads = [_th.Thread(target=one, args=(i,))
                   for i in range(n_req)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        bad = [(i, toks[i], int(max_news[i]))
               for i in range(n_req) if toks[i] != max_news[i]]
        assert not bad, f"short streams (i, got, want): {bad}"
        # Amortized TPOT per stream: decode time after the first token.
        tpots = [(comps[i] - ttfts[i]) / max(toks[i] - 1, 1)
                 for i in range(n_req)]
        return ttfts, tpots, wall, sum(toks)

    engines = {}
    for mode in modes:
        eng = build(mode)
        # Warm every compile path (prefill bucket, chunk, verify, and
        # the model drafter's own programs) before the clock starts.
        list(eng.stream(mk_prompt(0), max(mix), seed=0))
        engines[mode] = eng
    runs = {m: [] for m in modes}
    try:
        for p in range(passes):
            for mode in modes:
                if mode == "model" and p > 0:
                    continue
                runs[mode].append(drive(engines[mode]))
        results = {}
        for mode in modes:
            ttfts, tpots, wall, total = max(runs[mode],
                                            key=lambda r: r[3] / r[2])
            st = engines[mode].stats()
            row = {
                "metric": f"serve_{model}_spec_{mode}_mode",
                "value": round(total / wall, 1), "unit": "tokens/s",
                "ttft_p50_ms": round(pct(ttfts, 0.50) * 1000, 2),
                "tpot_p50_ms": round(pct(tpots, 0.50) * 1000, 3),
                "tpot_p95_ms": round(pct(tpots, 0.95) * 1000, 3),
                "requests": n_req, "passes": passes,
                "tok_s_per_pass": [round(r[3] / r[2], 1)
                                   for r in runs[mode]],
                "slots": slots, "chunk": chunk,
                "output_len_mix": [int(m) for m in mix],
                "offered_tokens": int(sum(max_news)),
                "dispatches_per_token": round(
                    st["dispatches_per_token"], 4),
            }
            if mode != "off":
                sp = st["spec"]
                row.update({
                    "draft_k": draft_k,
                    "spec_threshold": spec_threshold,
                    "accepted_per_forward": round(
                        sp["accepted_per_forward"], 3),
                    "acceptance_rate": round(sp["acceptance_rate"], 4),
                    "mean_accept_len": round(sp["mean_accept_len"], 3),
                    "verify_rounds": sp["rounds"],
                    "fallback_rounds": sp["fallback_rounds"],
                })
            print(json.dumps(row))
            results[mode] = row
    finally:
        for eng in engines.values():
            eng.shutdown()
    off = results["off"]
    ng = results["ngram"]
    summary = {
        "metric": f"serve_{model}_spec_ab",
        "value": round(ng["value"] / max(off["value"], 1e-9), 2),
        "unit": "x_tokens_s_ngram_vs_off",
        "ngram_accepted_per_forward": ng["accepted_per_forward"],
        "ngram_acceptance_rate": ng["acceptance_rate"],
        "tpot_p50_ratio": round(off["tpot_p50_ms"]
                                / max(ng["tpot_p50_ms"], 1e-9), 2),
        "draft_k": draft_k,
        "spec_threshold": spec_threshold,
        "screen_sim_acceptance": screen,
        "screened_from": n_cand,
        "smoke": bool(args.smoke),
    }
    if "model" in results:
        md = results["model"]
        summary["model_x_tokens_s_vs_off"] = round(
            md["value"] / max(off["value"], 1e-9), 2)
        summary["model_accepted_per_forward"] = \
            md["accepted_per_forward"]
    print(json.dumps(summary))


def run_disagg_ab(args, serve, np, cfg_name, model):
    """ISSUE 14 acceptance: colocated vs disaggregated prefill/decode
    under a bursty-prefill Poisson mix at EQUAL offered load and equal
    replica counts (2 colocated vs 1 prefill + 1 decode).

    Steady decode streams (short prompts, long outputs) share the
    deployment with Poisson BURSTS of prefill-heavy requests (long
    prompts, 2 output tokens). Colocated, every burst prefill dispatch
    lands between the decode engine's chunk dispatches and inflates
    decode TPOT; disaggregated, bursts prefill on the prefill replica
    and reach the decode engine as a cheap KV import. Reports decode
    TPOT p50/p95 per mode, handoff latency/bytes, and asserts ZERO
    broken streams and NO handoff leaks (pages free back to baseline,
    no outstanding leases)."""
    import threading as _th

    import jax

    import ray_tpu as rt
    from ray_tpu.models import gpt
    from ray_tpu.testing import _serve_replica_handles

    # Slots exceed the steady decode lanes so burst admissions always
    # find a free slot — the contention being measured is for the
    # DRIVER's dispatch stream (prefill programs between decode
    # chunks), not for slots.
    slots = 8
    chunk = 4
    plen_dec, plen_burst = 8, 112
    n_dec = 4 if args.smoke else 6
    dec_new = 64 if args.smoke else 96
    burst_size = 6
    burst_gap_s = 0.03
    max_len = 128
    cfg = gpt.CONFIGS[cfg_name]
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)

    @serve.deployment(max_ongoing_requests=64,
                      health_check_period_s=1.0,
                      graceful_shutdown_timeout_s=10.0)
    class DisaggGPT:
        def __init__(self, cfg_name, max_len, slots, chunk, buckets):
            from ray_tpu.models import gpt as _gpt
            from ray_tpu.serve.engine import DecodeEngine

            self.cfg = _gpt.CONFIGS[cfg_name]
            p = _gpt.init_params(jax.random.PRNGKey(0), self.cfg)
            # prefix_cache off: the leak check below wants pages_free
            # to return EXACTLY to baseline, with no cache pins.
            self.engine = DecodeEngine(
                p, self.cfg, slots=slots, chunk=chunk, max_len=max_len,
                prompt_buckets=tuple(buckets), page_size=8,
                prefix_cache=False, deployment="gpt_disagg")

        @serve.batch(continuous=True)
        def decode(self, request):
            return self.engine, {
                "prompt": _mk_prompt(int(request["rid"]),
                                     int(request["plen"]),
                                     self.cfg.vocab_size),
                "max_new": int(request["max_new"]),
                "seed": int(request["rid"])}

        def warm(self, plen: int, max_new: int = 2):
            list(self.engine.stream(
                _mk_prompt(0, plen, self.cfg.vocab_size), max_new))
            return "warm"

        def __call__(self, request):
            return self.decode(request)

    # The uninterrupted run a stream must equal is the same engine's,
    # in this process: generate_chunked multiplies at batch 1 where the
    # pool multiplies at batch `slots`, and where two logits lie within
    # their rounding (prompt 1002, step 47) the two orders part.
    from ray_tpu.serve.engine import DecodeEngine

    ref_eng = DecodeEngine(
        params, cfg, slots=slots, chunk=chunk, max_len=max_len,
        prompt_buckets=(plen_dec, plen_burst), page_size=8,
        prefix_cache=False, deployment="gpt_disagg_ref")
    try:
        refs = {i: np.concatenate(list(ref_eng.stream(
            _mk_prompt(1000 + i, plen_dec, cfg.vocab_size), dec_new,
            seed=1000 + i))) for i in range(n_dec)}
    finally:
        ref_eng.shutdown()

    def run_mode(disagg: bool):
        name = "gpt_disagg"
        dep = DisaggGPT.options(
            name=name,
            num_replicas=None if disagg else 2,
            engine_config={"roles": {"prefill": 1, "decode": 1},
                           "handoff_ttl_s": 15.0} if disagg else None)
        handle = serve.run(dep.bind(cfg_name, max_len, slots, chunk,
                                    (plen_dec, plen_burst)),
                           name=name, route_prefix=None)
        # Warm every replica's programs (prefill buckets + chunk +
        # export/import) before the clock starts.
        for h in _serve_replica_handles(name, name).values():
            for plen in (plen_dec, plen_burst):
                try:
                    rt.get(h.handle_request.remote(
                        "warm", (plen,), {}, {}), timeout=600)
                except Exception:  # noqa: BLE001 - prefill-role engine
                    pass           # warms through the handoff below
        for _ in range(2):
            list(handle.options(stream=True).remote(
                {"rid": 0, "plen": plen_dec, "max_new": 2}))
            list(handle.options(stream=True).remote(
                {"rid": 0, "plen": plen_burst, "max_new": 2}))

        tpot_ms, ttft_ms = [], []
        results = [None] * n_dec
        errors = [None] * n_dec
        done = _th.Event()

        def dec_stream(i):
            try:
                toks = []
                t0 = time.perf_counter()
                last = None
                it = handle.options(stream=True, resumable=True,
                                    timeout_s=300.0).remote(
                    {"rid": 1000 + i, "plen": plen_dec,
                     "max_new": dec_new})
                for item in it:
                    now = time.perf_counter()
                    w = np.asarray(item).ravel()
                    if last is None:
                        ttft_ms.append((now - t0) * 1000)
                    elif len(w):
                        tpot_ms.extend([(now - last) * 1000 / len(w)]
                                       * len(w))
                    last = now
                    toks.extend(int(t) for t in w)
                results[i] = toks
            except Exception as e:  # noqa: BLE001 - counted as broken
                errors[i] = repr(e)

        bursts = {"offered": 0, "errors": 0}

        def burst_client():
            # Poisson bursts of prefill-heavy requests, identical
            # schedule both modes (seeded RNG), until decode finishes.
            import random as _rnd

            r = _rnd.Random(77)
            rid = 5000
            while not done.is_set():
                time.sleep(r.expovariate(1.0 / burst_gap_s))
                ths = []
                for _ in range(burst_size):
                    rid += 1

                    def one(rid=rid):
                        try:
                            list(handle.options(
                                stream=True, timeout_s=120.0).remote(
                                {"rid": rid, "plen": plen_burst,
                                 "max_new": 2}))
                        except Exception:  # noqa: BLE001 - counted
                            bursts["errors"] += 1
                    t = _th.Thread(target=one)
                    t.start()
                    ths.append(t)
                    bursts["offered"] += 1
                for t in ths:
                    t.join()

        t_start = time.perf_counter()
        dec_threads = [_th.Thread(target=dec_stream, args=(i,))
                       for i in range(n_dec)]
        burst_thread = _th.Thread(target=burst_client)
        for t in dec_threads:
            t.start()
            time.sleep(0.02)
        burst_thread.start()
        for t in dec_threads:
            t.join()
        done.set()
        burst_thread.join()
        wall = time.perf_counter() - t_start

        broken = [(i, errors[i]) for i in range(n_dec)
                  if errors[i] is not None
                  or results[i] != [int(t) for t in refs[i]]]

        # Handoff accounting + leak check across the surviving fleet:
        # every lease claimed or swept, every page back on the free
        # list (prefix cache off, so baseline == n_pages).
        handles = _serve_replica_handles(name, name)
        agg = {"exported": 0, "imported": 0, "import_fallbacks": 0,
               "ship_bytes": 0, "leases_outstanding": 0,
               "leases_reclaimed": 0}
        leaks = None
        deadline = time.time() + 20
        while time.time() < deadline:
            agg = {k: 0 for k in agg}
            leaked_pages = 0
            for h in handles.values():
                m = rt.get(h.get_metrics.remote(), timeout=10)
                est = (m.get("engines") or [{}])[0]
                for k in agg:
                    agg[k] += int(est.get("handoff", {}).get(k, 0))
                leaked_pages += int(est.get("pages_used", 0))
            leaks = agg["leases_outstanding"] + leaked_pages
            if leaks == 0:
                break
            time.sleep(0.5)

        mode = "disagg" if disagg else "colocated"
        row = {
            "metric": f"serve_{model}_disagg_{mode}_mode",
            "value": round(pct(tpot_ms, 0.95) or 0.0, 3),
            "unit": "decode_tpot_p95_ms",
            "tpot_p50_ms": round(pct(tpot_ms, 0.5) or 0.0, 3),
            "tpot_p95_ms": round(pct(tpot_ms, 0.95) or 0.0, 3),
            "ttft_p50_ms": round(pct(ttft_ms, 0.5) or 0.0, 1),
            "decode_streams": n_dec,
            "decode_tokens": int(sum(len(r) for r in results
                                     if r is not None)),
            "burst_requests": bursts["offered"],
            "burst_errors": bursts["errors"],
            "broken_streams": len(broken),
            "handoffs_exported": agg["exported"],
            "handoffs_imported": agg["imported"],
            "import_fallbacks": agg["import_fallbacks"],
            "ship_bytes": agg["ship_bytes"],
            "leases_reclaimed": agg["leases_reclaimed"],
            "handoff_leaks": leaks,
            "wall_s": round(wall, 2),
        }
        print(json.dumps(row))
        assert not broken, f"broken decode streams ({mode}): {broken[:3]}"
        serve.delete(name)
        return row

    coloc = run_mode(disagg=False)
    disagg = run_mode(disagg=True)

    # Mean handoff latency from the head-merged histogram (observed by
    # the decode replicas; the bench process cannot see it locally).
    handoff_ms = None
    try:
        total = {"sum": 0.0, "count": 0.0}
        for line in rt.metrics_text().splitlines():
            if line.startswith("ray_tpu_serve_kv_handoff_seconds_sum"):
                total["sum"] += float(line.rsplit(" ", 1)[1])
            elif line.startswith(
                    "ray_tpu_serve_kv_handoff_seconds_count"):
                total["count"] += float(line.rsplit(" ", 1)[1])
        if total["count"]:
            handoff_ms = round(total["sum"] / total["count"] * 1000, 2)
    except Exception:  # noqa: BLE001 - head mid-flush
        pass

    summary = {
        "metric": f"serve_{model}_disagg_ab",
        "value": round(coloc["tpot_p95_ms"]
                       / max(disagg["tpot_p95_ms"], 1e-9), 2),
        "unit": "x_decode_tpot_p95_colocated_vs_disagg",
        "tpot_p50_ratio": round(coloc["tpot_p50_ms"]
                                / max(disagg["tpot_p50_ms"], 1e-9), 2),
        "colocated_tpot_p95_ms": coloc["tpot_p95_ms"],
        "disagg_tpot_p95_ms": disagg["tpot_p95_ms"],
        "handoff_mean_ms": handoff_ms,
        "handoffs_imported": disagg["handoffs_imported"],
        "import_fallbacks": disagg["import_fallbacks"],
        "ship_bytes": disagg["ship_bytes"],
        "broken_streams": coloc["broken_streams"]
        + disagg["broken_streams"],
        "handoff_leaks": (coloc["handoff_leaks"] or 0)
        + (disagg["handoff_leaks"] or 0),
        "burst_requests": [coloc["burst_requests"],
                           disagg["burst_requests"]],
        "smoke": bool(args.smoke),
    }
    print(json.dumps(summary))
    assert summary["handoff_leaks"] == 0, \
        "handoff leaked pages or leases past the run"
    assert disagg["handoffs_imported"] >= 1, \
        "disaggregated mode never imported a handoff"


def run_chaos_mode(args, serve, np, cfg_name, model):
    """ISSUE 7 acceptance: a 2-replica continuous-engine deployment
    serves seeded deterministic streams under load; ONE replica is
    hard-killed mid-load. Every client stream is submitted with
    ``resumable=True`` — a stream cut mid-flight re-routes to the
    survivor with its replay token and must complete TOKEN-IDENTICAL to
    its uninterrupted reference. The row asserts zero broken streams."""
    import threading as _th

    import jax

    import ray_tpu as rt
    from ray_tpu._private.metrics import serve_metrics
    from ray_tpu.models import gpt, gpt_decode
    from ray_tpu.testing import _serve_replica_handles, inject_engine_fault

    slots = 4
    chunk = 8
    plen = 16
    n_req = 10 if args.smoke else min(args.requests, 32)
    base = min(args.tokens, 16) if args.smoke else max(args.tokens, 32)
    max_len = plen + 2 * base + chunk
    cfg = gpt.CONFIGS[cfg_name]
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    max_news = np.random.default_rng(7).integers(base, 2 * base + 1,
                                                 size=n_req)

    @serve.deployment(num_replicas=2, max_ongoing_requests=64,
                      health_check_period_s=0.5,
                      graceful_shutdown_timeout_s=10.0)
    class ChaosGPT:
        def __init__(self, cfg_name, max_len, slots, chunk, plen):
            from ray_tpu.models import gpt as _gpt
            from ray_tpu.serve.engine import DecodeEngine

            self.cfg = _gpt.CONFIGS[cfg_name]
            p = _gpt.init_params(jax.random.PRNGKey(0), self.cfg)
            self.plen = plen
            self.engine = DecodeEngine(
                p, self.cfg, slots=slots, chunk=chunk, max_len=max_len,
                prompt_buckets=(plen,), deployment="gpt_chaos")

        @serve.batch(continuous=True)
        def decode(self, request):
            rid = int(request["rid"])
            return self.engine, {
                "prompt": _mk_prompt(rid, self.plen,
                                     self.cfg.vocab_size),
                "max_new": int(request["max_new"]), "seed": rid}

        def warm(self, max_new: int = 2):
            list(self.engine.stream(
                _mk_prompt(0, self.plen, self.cfg.vocab_size), max_new))
            return "warm"

        def __call__(self, request):
            if hasattr(request, "json"):
                request = request.json()
            return self.decode(request)

    handle = serve.run(
        ChaosGPT.bind(cfg_name, max_len, slots, chunk, plen),
        name="gpt_chaos", route_prefix="/chaos")
    handle.options(method_name="warm").remote(2).result(timeout=600)
    # Compile both replicas' programs before the clock starts.
    warm_threads = [_th.Thread(target=lambda: list(
        handle.options(stream=True).remote({"rid": 0, "max_new": 2})))
        for _ in range(4)]
    for t in warm_threads:
        t.start()
    for t in warm_threads:
        t.join()
    # Throttle the engines so the kill reliably lands while streams are
    # mid-flight. The smoke run carries far fewer tokens, so it needs a
    # heavier per-chunk stall to stay airborne past the kill (the total
    # dispatch count times the throttle must comfortably exceed the
    # time it takes the first third of the streams to yield a token).
    inject_engine_fault("gpt_chaos", "ChaosGPT", kind="driver_slow",
                        wedge_s=0.05 if args.smoke else 0.02)

    refs = {int(i): gpt_decode.generate_chunked(
        params, _mk_prompt(int(i), plen, cfg.vocab_size)[None], cfg,
        int(max_news[i]), chunk=chunk, max_len=max_len)
        for i in range(n_req)}
    refs = {i: np.concatenate([s[0] for s in r]) for i, r in refs.items()}

    resumes0 = sum(v for _k, v in
                   serve_metrics()["stream_resumes"].collect())
    first_tokens = _th.Semaphore(0)
    results = [None] * n_req
    errors = [None] * n_req
    stalls = [0.0] * n_req

    def one(i):
        try:
            toks = []
            last = time.perf_counter()
            it = handle.options(stream=True, resumable=True,
                                timeout_s=300.0).remote(
                {"rid": int(i), "max_new": int(max_news[i])})
            for item in it:
                now = time.perf_counter()
                stalls[i] = max(stalls[i], now - last)
                last = now
                w = np.asarray(item).ravel()
                if not toks:
                    first_tokens.release()
                toks.extend(int(t) for t in w)
            results[i] = np.asarray(toks, np.int32)
        except Exception as e:  # noqa: BLE001 - counted as broken
            errors[i] = repr(e)

    def launch():
        for i in range(n_req):
            results[i], errors[i], stalls[i] = None, None, 0.0
        ths = [_th.Thread(target=one, args=(i,)) for i in range(n_req)]
        for t in ths:
            t.start()
            time.sleep(0.02)       # staggered arrivals
        return ths

    def count_resumes():
        return sum(v for _k, v in
                   serve_metrics()["stream_resumes"].collect()) - resumes0

    handles = _serve_replica_handles("gpt_chaos", "ChaosGPT")
    t_start = time.perf_counter()
    threads = launch()

    # Arm a deterministic mid-stream kill on the BUSIER replica once a
    # third of the streams are flowing: the engine hard-exits the
    # replica process at the NEXT delivered token, so the kill lands
    # while a stream is delivering BY CONSTRUCTION — an outside-in
    # rt.kill races stream completion on a loaded box.
    for _ in range(max(2, n_req // 3)):
        first_tokens.acquire(timeout=60)
    busiest, busiest_slots, busiest_toks = None, -1, 0
    for rid_, h in handles.items():
        try:
            m = rt.get(h.get_metrics.remote(), timeout=10)
            est = (m.get("engines") or [{}])[0]
            act = est.get("active_slots", 0)
        except Exception:  # noqa: BLE001
            act, est = 0, {}
        if act > busiest_slots:
            busiest, busiest_slots = rid_, act
            busiest_toks = int(est.get("tokens", 0))
    busiest = busiest if busiest is not None else next(iter(handles))
    rt.get(handles[busiest].inject_engine_fault.remote(
        "kill_process", busiest_toks + 1, 0.0), timeout=10)
    t_kill = time.perf_counter()
    kills = 1

    for t in threads:
        t.join()
    rounds = 1
    if not any(errors) and count_resumes() == 0:
        # Every stream outran the armed kill (tiny smoke loads on a
        # contended box): the one-shot fault is STILL armed and fires
        # at the armed replica's next delivered token — one more
        # identical round guarantees a mid-stream kill.
        rounds = 2
        threads = launch()
        t_kill = time.perf_counter()
        for t in threads:
            t.join()
    wall = time.perf_counter() - t_start

    broken = []
    for i in range(n_req):
        if errors[i] is not None:
            broken.append((i, errors[i]))
        elif results[i] is None or len(results[i]) != len(refs[i]) \
                or not (results[i] == refs[i]).all():
            broken.append((i, f"token mismatch: got "
                              f"{None if results[i] is None else len(results[i])}"
                              f" want {len(refs[i])}"))
    resumes = count_resumes()
    completed = sum(r is not None for r in results)
    # Runtime-sanitizer verdict from the SURVIVING replicas (ISSUE 13):
    # under RT_SAN=1 every replica engine carries a sanitizer block in
    # stats(); a chaos run that recovered cleanly must also have zero
    # runtime findings (no lock-order cycles, no blocking-under-lock).
    from ray_tpu.testing import engine_sanitizer_findings

    san_findings = engine_sanitizer_findings("gpt_chaos", "ChaosGPT")
    row = {
        "metric": f"serve_{model}_chaos_recovery",
        "value": len(broken), "unit": "broken_streams",
        "broken_streams": len(broken),
        "requests": n_req, "completed": completed,
        "kills": kills, "killed_replica": busiest,
        "rounds": rounds,
        "active_slots_at_kill": busiest_slots,
        "stream_resumes": int(resumes),
        "max_stall_ms": round(max(stalls) * 1000, 1),
        "stall_p50_ms": round(sorted(stalls)[len(stalls) // 2] * 1000, 1),
        "kill_at_s": round(t_kill - t_start, 2),
        "wall_s": round(wall, 2),
        "tokens_total": int(sum(len(r) for r in results
                                if r is not None)),
        "output_tokens": [int(m) for m in max_news],
        "sanitizer_findings": san_findings,
        "smoke": bool(args.smoke),
    }
    print(json.dumps(row))
    assert not broken, f"broken client streams after replica kill: " \
                       f"{broken[:4]}"
    assert resumes >= 1, \
        "the kill interrupted no stream — chaos run proved nothing"
    assert san_findings in (None, 0), \
        f"rtsan found {san_findings} runtime findings during chaos"
    serve.delete("gpt_chaos")


def run_overload_ab(args, serve, GPTStream, cfg_name, max_len, chunks,
                    model):
    """Overload A/B (ISSUE 2 CI satellite): offered load ~3x a 4-slot
    replica, once with an effectively unbounded admission queue and once
    with the bounded queue + shedding. Reports shed rate, goodput
    (completed tokens/s), and completion p50/p99 of ACCEPTED streams per
    mode — the bounded mode should hold p99 roughly at the service time
    of a full pipeline while the unbounded mode's p99 grows with the
    queue."""
    from ray_tpu.serve import BackPressureError, RequestDeadlineExceeded

    chunk = max(chunks)
    max_new = min(args.tokens, 8)
    timeout_s = 10.0
    summary = []
    for mode, max_queued in (("unshed", 1_000_000), ("shed", 4)):
        handle = serve.run(
            GPTStream.options(num_replicas=1, max_ongoing_requests=4,
                              max_queued_requests=max_queued)
            .bind(cfg_name, max_len, chunks),
            name="gpt_overload", route_prefix="/overload")
        handle.options(method_name="warm").remote(16).result(timeout=600)
        list(handle.options(stream=True).remote(
            {"prompt_len": 16, "max_new": 2, "chunk": chunk}))

        lock = threading.Lock()
        stats = {"offered": 0, "completed": 0, "shed": 0, "expired": 0,
                 "errors": 0, "tokens": 0}
        completion_s = []
        stop_at = time.perf_counter() + args.overload_duration

        def client():
            while time.perf_counter() < stop_at:
                with lock:
                    stats["offered"] += 1
                t0 = time.perf_counter()
                try:
                    gen = handle.options(
                        stream=True, timeout_s=timeout_s).remote(
                        {"prompt_len": 16, "max_new": max_new,
                         "chunk": chunk})
                    n = 0
                    for item in gen:
                        n += len(item) if isinstance(item, list) else 1
                    with lock:
                        stats["completed"] += 1
                        stats["tokens"] += n
                        completion_s.append(time.perf_counter() - t0)
                except BackPressureError:
                    with lock:
                        stats["shed"] += 1
                    time.sleep(0.05)  # honor the backoff contract
                except (RequestDeadlineExceeded, TimeoutError):
                    with lock:
                        stats["expired"] += 1
                except Exception:  # noqa: BLE001
                    with lock:
                        stats["errors"] += 1

        threads = [threading.Thread(target=client)
                   for _ in range(args.overload_clients)]
        t_start = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t_start
        completion_s.sort()
        p50 = completion_s[len(completion_s) // 2] if completion_s else None
        p99 = completion_s[int(len(completion_s) * 0.99)] \
            if completion_s else None
        row = {
            "metric": f"serve_{model}_overload_{mode}",
            "value": round(stats["tokens"] / wall, 1),
            "unit": "goodput_tokens_s",
            "offered": stats["offered"], "completed": stats["completed"],
            "shed": stats["shed"], "expired": stats["expired"],
            "errors": stats["errors"],
            "shed_rate": round(stats["shed"] / max(stats["offered"], 1), 3),
            "completion_p50_s": round(p50, 3) if p50 else None,
            "completion_p99_s": round(p99, 3) if p99 else None,
            "clients": args.overload_clients,
            "max_queued_requests": max_queued,
        }
        print(json.dumps(row))
        summary.append(row)
        serve.delete("gpt_overload")
    if len(summary) == 2:
        unshed, shed = summary
        print(json.dumps({
            "metric": f"serve_{model}_overload_ab_p99_ratio",
            "value": round((unshed["completion_p99_s"] or 0)
                           / max(shed["completion_p99_s"] or 1e-9, 1e-9), 2),
            "unit": "x_p99_unshed_vs_shed",
            "goodput_ratio": round(shed["value"]
                                   / max(unshed["value"], 1e-9), 2)}))


if __name__ == "__main__":
    main()
