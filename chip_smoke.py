"""Prove on demand that the serving and training paths start on the chip.

``python chip_smoke.py`` — no arguments, no size switch — drives the
system's main paths once through the entry points a user would call, at
the full width of the models the repo supports (weights random, from a
seed), and checks what comes out by the repo's own means. It claims no
speed: the only seconds it prints are compile seconds, as set-up time.

This process never imports jax: a parent that touched it would hold the
chip its children need. Each phase that needs the chip runs in a process
of its own, the next starts only after the last has exited, and every
chip-holding process reports ``platform`` / ``device_kind`` / device
count from inside and fails unless the platform is ``tpu``. Any phase
failing makes the exit code non-zero (with the tail of the failing
worker's log) and no result line is printed; on success the last line of
stdout is ``{"ok": true, "device": {...}}``.

The phase bodies are importable functions taking a configuration name,
so tier-1 runs them at ``nano`` on the CPU (``require_tpu=False``) and a
builder can debug the command before spending chip time; only
``__main__`` is chip-only.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: One bf16 ulp, relative: 8 significand bits.
BF16_ULP = 2.0 ** -8
#: A phase that has not finished by then is killed with its whole tree
#: (the whole smoke has 1200 s).
PHASE_BUDGET_S = 600


# --------------------------------------------------------------- helpers
def _cache_counter():
    """Count this process's persistent-compile-cache traffic (jax's own
    monitoring events). Call before the first compile."""
    import jax

    counts = {"requests": 0, "hits": 0}
    names = {"/jax/compilation_cache/compile_requests_use_cache":
             "requests", "/jax/compilation_cache/cache_hits": "hits"}

    def listen(event, **_):
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listen)
    return counts


def _holder_report(require_tpu: bool) -> dict:
    """What a chip-holding process says about itself; raises, naming
    what it found, unless the platform is ``tpu``."""
    from ray_tpu import _native
    from ray_tpu._private import chip

    dev = chip.require_tpu() if require_tpu else chip.device_summary()
    print(f"[pid {os.getpid()}] platform={dev['platform']} "
          f"device_kind={dev['kind']!r} count={dev['count']}", flush=True)
    return {"device": dev, "pid": os.getpid(),
            "native_codec": _native.load() is not None,
            "compile_cache_dir": os.environ.get(
                "JAX_COMPILATION_CACHE_DIR")}


def _init_params(cfg, seed: int):
    """Seeded weights, built under ONE jit so the cold start is one
    compile (and one persistent-cache entry), not one per tensor."""
    import jax

    from ray_tpu.models import gpt

    return jax.block_until_ready(jax.jit(
        lambda key: gpt.init_params(key, cfg))(jax.random.PRNGKey(seed)))


def _engine_shape(max_seq: int) -> dict:
    """The smoke's engine at a config's full context: two explicit
    prompt buckets, eight slots, the paged pool with the Pallas kernel."""
    return dict(slots=8, chunk=8, max_len=max_seq,
                prompt_buckets=(max_seq // 16, max_seq // 4),
                page_size=16, attn_kernel="pallas")


def _requests(max_seq: int):
    """Mixed prompt and output lengths as ``(prompt_len, max_new)``:
    both buckets are hit, and the third and last are the same request
    (asserted identical at temperature 0)."""
    b0, b1 = _engine_shape(max_seq)["prompt_buckets"]
    return [(b0 * 3 // 4, 24), (b1 * 3 // 5, 17), (b0 // 2, 40),
            (b1 - 1, 9), (b0 - 3, 33), (b1 * 4 // 5, 21), (b0 // 2, 40)]


def _prompt(vocab_size: int, n: int):
    """The prompt of length n (one per length: equal lengths are the
    same request)."""
    import numpy as np

    return np.random.default_rng(1000 + n).integers(
        0, vocab_size, (n,)).astype(np.int32)


def _device_bytes() -> list:
    """Per-device bytes in use, as the runtime reports them."""
    import jax

    return [int((d.memory_stats() or {}).get("bytes_in_use", -1))
            for d in jax.devices()]


# ----------------------------------------------------------- serve phase
def _deployment(cfg_name: str, require_tpu: bool, tp: int, num_tpus: int,
                num_replicas: int):
    """An ordinary ``@serve.deployment`` around a DecodeEngine, exposed
    through ``@serve.batch(continuous=True)`` exactly as README
    "Continuous batching" shows."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=num_replicas,
                      ray_actor_options={"num_tpus": num_tpus})
    class SmokeGPT:
        def __init__(self):
            counts = _cache_counter()
            t0 = time.monotonic()
            self.info = _holder_report(require_tpu)
            import jax

            from ray_tpu.models import gpt
            from ray_tpu.serve.engine import DecodeEngine

            self.cfg = gpt.CONFIGS[cfg_name]
            params = _init_params(self.cfg, seed=0)
            self.info["weights_s"] = round(time.monotonic() - t0, 2)
            self.engine = DecodeEngine(params, self.cfg, tp=tp,
                                       **_engine_shape(self.cfg.max_seq))
            self.info.update(
                # A confined process numbers its own devices from 0:
                # the chips it was BOUND to are what tells two
                # replicas apart (None: the whole node, unconfined).
                chips=os.environ.get("TPU_VISIBLE_CHIPS"),
                device_ids=[d.id for d in jax.devices()],
                # What the driver (which stays off jax, so off the
                # model modules too) needs to build its requests.
                max_seq=self.cfg.max_seq, vocab_size=self.cfg.vocab_size)
            self.counts = counts

        @serve.batch(continuous=True)
        def decode(self, request):
            return self.engine, {"prompt": request["prompt"],
                                 "max_new": request["max_new"]}

        def __call__(self, request):
            return self.decode(request)

        def report(self) -> dict:
            out = dict(self.info)
            out["stats"] = self.engine.stats()
            out["cache"] = dict(self.counts)
            out["device_bytes"] = _device_bytes()
            return out

        def tp_check(self) -> dict:
            return _tp_logits_check(self.engine)

    return SmokeGPT


def _tp_logits_check(engine) -> dict:
    """First-step logits of the engine's own sharded weights against
    tp=1, plus where the shards actually live. Runs inside the replica
    (the one process that holds the chips)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private.jax_compat import decode_mesh, shard_map
    from ray_tpu.models import gpt_decode as gd

    cfg, tp = engine.cfg, engine.tp
    P = jax.sharding.PartitionSpec
    token = jnp.asarray([11, 12], jnp.int32)
    active = jnp.ones((2,), jnp.bool_)

    # a dense two-slot cache: one 32-position page per slot
    pt = jnp.arange(2, dtype=jnp.int32)[:, None]

    def first_logits(params, n):
        cache = gd.init_paged_cache(cfg, 2, 2, 32, tp=n)
        if n == 1:
            fn = jax.jit(functools.partial(gd._slot_decode_step_paged,
                                           cfg=cfg, page_size=32))
        else:
            inner = functools.partial(gd._slot_decode_step_paged, cfg=cfg,
                                      page_size=32, tp_axis="tp")
            cs = gd._tp_cache_specs(cache)
            fn = jax.jit(shard_map(
                inner, mesh=decode_mesh(n),
                in_specs=(gd._tp_param_specs(params), cs, P(), P(), P()),
                out_specs=(P(), cs)))
        return np.asarray(fn(params, cache, token, active, pt)[0],
                          np.float32)

    sharded = first_logits(engine._params_dev, tp)
    single = first_logits(engine.params, 1)
    # bf16 activations, f32 partial sums psum-reduced in another order
    # than one device adds them: each layer's rounding can land one bf16
    # ulp apart and the residual stream carries it through every layer.
    # Allow 8 ulp of the largest logit; token identity is the engine
    # tests' business, on a CPU, where the order is fixed.
    tol = 8 * BF16_ULP * float(np.abs(single).max())
    err = float(np.abs(sharded - single).max())
    per_dev = {}
    for leaf in jax.tree_util.tree_leaves(
            (engine._params_dev, engine._cache)):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    return {"logits_max_abs_err": err, "tol": tol, "ok": err <= tol,
            "argmax_equal": bool((sharded.argmax(-1)
                                  == single.argmax(-1)).all()),
            "shard_bytes_per_device": per_dev,
            "device_bytes": _device_bytes()}


def _worker_log_tails(session_dir: str, lines: int = 40) -> str:
    import glob

    out = []
    for path in sorted(glob.glob(os.path.join(session_dir, "logs",
                                              "worker-*.log"))):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        out.append(f"----- {path}\n{''.join(tail)}")
    return "\n".join(out)


def phase_serve(cfg_name: str, require_tpu: bool = True, tp: int = 1,
                num_replicas: int = 1) -> dict:
    """``rt.init()`` → ``serve.run`` → stream requests through the
    handle → ``serve.shutdown()`` / ``rt.shutdown()``. The calling
    process is the DRIVER: it stays off jax; the replicas hold the
    chips. ``tp > 1`` gives the one replica ``tp`` chips and checks the
    sharding; ``num_replicas > 1`` gives each replica one chip and
    checks the devices are distinct."""
    import numpy as np

    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu._private.utils import process_exited

    had_jax = "jax" in sys.modules      # tier-1 runs this under pytest
    chips = tp if (require_tpu or tp > 1) else 0
    core = rt.init(num_cpus=8, num_tpus=None if require_tpu
                   else chips * num_replicas)
    pids = []
    try:
        t0 = time.monotonic()
        handle = serve.run(
            _deployment(cfg_name, require_tpu, tp, chips,
                        num_replicas).bind(), _proxy=False)
        ready_s = round(time.monotonic() - t0, 2)

        def reports() -> dict:
            # The router spreads calls: ask until every replica has
            # answered once.
            got = {}
            for _ in range(20 * num_replicas):
                r = handle.report.remote().result()
                got[r["pid"]] = r
                if len(got) == num_replicas:
                    return got
            raise AssertionError(
                f"{len(got)} of {num_replicas} replicas answered")

        first = reports()
        pids = sorted(first)
        facts = next(iter(first.values()))
        max_seq, vocab = facts["max_seq"], facts["vocab_size"]
        reqs = _requests(max_seq)
        outs = {}

        def one(i):
            n, max_new = reqs[i]
            outs[i] = np.concatenate([np.asarray(s).reshape(-1) for s in
                                      handle.options(stream=True).remote(
                {"prompt": _prompt(vocab, n), "max_new": max_new})])

        # One request per bucket alone, then the rest together so that
        # a chunk carries several live lanes.
        one(0)
        one(1)
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(2, len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, (n, max_new) in enumerate(reqs):
            assert i in outs, f"request {i} raised (see replica log)"
            assert len(outs[i]) == max_new, \
                f"request {i}: {len(outs[i])} tokens, wanted {max_new}"
            assert ((outs[i] >= 0) & (outs[i] < vocab)).all(), \
                f"request {i}: token id out of range"
        assert (outs[2] == outs[len(reqs) - 1]).all(), \
            "the repeated request diverged at temperature 0"

        final = reports()
        assert sorted(final) == pids, \
            f"a replica was replaced: {pids} -> {sorted(final)}"
        mode = "compiled" if require_tpu else "interpret"
        b0, b1 = _engine_shape(max_seq)["prompt_buckets"]
        assert any(n <= b0 for n, _ in reqs) and \
            any(b0 < n <= b1 for n, _ in reqs)
        totals = {"prefills": 0, "dispatches": 0,
                  "attn_kernel_dispatches": 0}
        for r in final.values():
            st = r["stats"]
            assert not require_tpu or r["device"]["count"] == tp, r
            assert st["driver_restarts"] == 0 and st["resumed"] == 0 \
                and st["preempted"] == 0, st
            assert st["warm_up"]["attn_kernel_mode"] == mode, st["warm_up"]
            # a bucket's program for one prompt, and a pair of buckets'
            # for the group of one chunk boundary where both are short
            # enough
            from ray_tpu.serve.engine import (PREFILL_GROUP,
                                              PREFILL_GROUP_BUCKETS,
                                              PREFILL_GROUP_ROWS)

            wide = [b for b in (b0, b1) if PREFILL_GROUP * b
                    <= PREFILL_GROUP_ROWS][-PREFILL_GROUP_BUCKETS:]
            assert set(st["warm_up"]["programs"]) == {
                f"prefill_{b0}", f"prefill_{b1}", "chunk"} | {
                f"prefill_{a}+{b}" for a in wide for b in wide
                if a >= b}, st["warm_up"]
            for k in totals:
                totals[k] += st[k]
        # One prefill per request: nothing was retried or replayed.
        assert totals["prefills"] == len(reqs), totals
        assert totals["dispatches"] > 0 and \
            totals["attn_kernel_dispatches"] > 0, totals
        ids = [r["chips"] for r in final.values()]
        assert not require_tpu or len(set(ids)) == num_replicas, \
            f"replicas share chips: {ids}"
        assert had_jax or "jax" not in sys.modules, \
            "the serve driver touched jax"
        result = {
            "ready_s": ready_s,
            "replicas": [{k: r[k] for k in (
                "device", "pid", "chips", "device_ids", "native_codec",
                "weights_s", "cache", "device_bytes",
                "compile_cache_dir")} | {
                "warm_up": r["stats"]["warm_up"],
                "stats": {k: r["stats"][k] for k in (
                    "prefills", "dispatches", "tokens", "peak_active",
                    "attn_kernel_dispatches", "driver_restarts",
                    "resumed", "tp")}} for r in final.values()],
            "tokens": {i: len(o) for i, o in outs.items()},
            "repeat_identical": True,
            # Set-up time per replica: seeded weights + program set.
            "compile_s": [round(r["weights_s"]
                                + r["stats"]["warm_up"]["total_s"], 2)
                          for r in final.values()],
            "cache": [r["cache"] for r in final.values()],
        }
        if tp > 1:
            check = handle.tp_check.remote().result()
            assert check["ok"], check
            shard = check["shard_bytes_per_device"]
            assert len(shard) == tp and \
                max(shard.values()) < 2 * min(shard.values()), \
                f"shards are not spread: {shard}"
            result["tp_check"] = check
    except BaseException:
        print(_worker_log_tails(core.session_dir), flush=True)
        raise
    finally:
        serve.shutdown()
        rt.shutdown()
    deadline = time.monotonic() + 30
    while not all(process_exited(p) for p in pids):
        assert time.monotonic() < deadline, \
            f"replica processes {pids} outlived shutdown"
        time.sleep(0.1)
    return result


# --------------------------------------------------------- kernel phase
def _ragged_pool(cfg, quant: bool, seed: int):
    """A hand-built paged pool at a config's shapes: slot 0 full, the
    others ragged with unmapped page-table columns, every length ending
    mid-page, pages mapped out of order."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import gpt_decode as gd

    rng = np.random.default_rng(seed)
    H, hd, ps, B = cfg.n_head, cfg.head_dim, 16, 8
    max_pages = cfg.max_seq // ps
    n_pages = B * max_pages + 7
    shape = (n_pages, ps, H, hd)
    if quant:
        kc = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        vc = jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
        ks = jnp.asarray(rng.uniform(.005, .02, (n_pages, H)), jnp.float32)
        vs = jnp.asarray(rng.uniform(.005, .02, (n_pages, H)), jnp.float32)
    else:
        kc = jnp.asarray(rng.standard_normal(shape), cfg.dtype)
        vc = jnp.asarray(rng.standard_normal(shape), cfg.dtype)
        ks = vs = None
    q = jnp.asarray(rng.standard_normal((B, 1, H, hd)), cfg.dtype)
    pt = np.full((B, max_pages), gd.PT_SENTINEL, np.int32)
    pos = np.zeros((B,), np.int32)
    perm, off = rng.permutation(n_pages), 0
    for b in range(B):
        n = max_pages if b == 0 else int(rng.integers(1, max_pages + 1))
        pt[b, :n] = perm[off:off + n]
        off += n
        pos[b] = (n - 1) * ps + int(rng.integers(0, ps - 1))
    return q, kc, vc, jnp.asarray(pt), jnp.asarray(pos), ks, vs


def phase_kernels(cfg_name: str, require_tpu: bool = True,
                  flash_cfgs=None) -> dict:
    """The two Pallas kernels against their XLA references, outside the
    engine, in one process: values compared, not sampled tokens."""
    counts = _cache_counter()
    info = _holder_report(require_tpu)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu._private import chip
    from ray_tpu.models import gpt
    from ray_tpu.models import gpt_decode as gd
    from ray_tpu.ops.flash_attention import flash_attention

    cfg = gpt.CONFIGS[cfg_name]
    mosaic = require_tpu
    out = {"holder": info, "paged": {}, "flash": {}}
    t_compile = 0.0

    def f32(x):
        return np.asarray(x.astype(jnp.float32))

    for quant in (False, True):
        args = _ragged_pool(cfg, quant, seed=7)

        def run(kernel):
            fn = jax.jit(lambda q, kc, vc, pt, pos, ks, vs:
                         gd.paged_attention(q, kc, vc, pt, pos,
                                            page_size=16, kernel=kernel,
                                            ks=ks, vs=vs))
            t0 = time.monotonic()
            low = fn.lower(*args).as_text()
            res = f32(jax.block_until_ready(fn(*args)))
            return res, chip.compiled_by_mosaic(low), time.monotonic() - t0

        ref, _, s0 = run("gather")
        got, by_mosaic, s1 = run("pallas")
        t_compile += s0 + s1
        # The kernel rounds its probabilities before the division by
        # their sum, the gather path after it, and each rounds its f32
        # result once: the written bound of the kernel's contract, in
        # ulps of the largest output (gpt_decode.ATTN_KERNEL_ULPS; read
        # on a v5e: 0.5-1.1 fp, 1.1-1.5 int8).
        tol = gd.ATTN_KERNEL_ULPS * BF16_ULP * float(np.abs(ref).max())
        err = float(np.abs(got - ref).max())
        out["paged"]["int8" if quant else "fp"] = {
            "max_abs_err": err, "tol": tol, "mosaic": by_mosaic}
        assert np.isfinite(got).all() and err <= tol, out["paged"]
        assert by_mosaic == mosaic, "paged kernel: wrong build mode"

    for name in flash_cfgs or (cfg_name,):
        c = gpt.CONFIGS[name]
        B, S, H, hd = 2, c.max_seq, c.n_head, c.head_dim
        rng = np.random.default_rng(11)
        q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, hd)), c.dtype)
                   for _ in range(3))
        w = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.float32)

        def vg(attn):
            return jax.jit(jax.value_and_grad(
                lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32)
                                        * w), argnums=(0, 1, 2)))

        flash = vg(lambda q, k, v: flash_attention(q, k, v, causal=True))
        xla = vg(lambda q, k, v: gpt._attention_xla(q, k, v, c))
        t0 = time.monotonic()
        by_mosaic = chip.compiled_by_mosaic(flash.lower(q, k, v).as_text())
        _, gf = jax.block_until_ready(flash(q, k, v))
        _, gx = jax.block_until_ready(xla(q, k, v))
        of = f32(flash_attention(q, k, v, causal=True))
        ox = f32(gpt._attention_xla(q, k, v, c))
        t_compile += time.monotonic() - t0
        # Forward: one bf16 rounding of differently-ordered f32 sums,
        # as above — 2 ulp of the largest output. Backward: the kernel
        # recomputes p from the saved logsumexp and rounds p and ds to
        # bf16 before each MXU dot, where XLA's autodiff rounds at
        # other points; S products per element average that out, and a
        # few ulp of the largest gradient remain — allow 4.
        rec = {"shape": [B, S, H, hd], "mosaic": by_mosaic,
               "fwd": [float(np.abs(of - ox).max()),
                       2 * BF16_ULP * float(np.abs(ox).max())]}
        for nm, a, b in zip("qkv", gf, gx):
            rec["d" + nm] = [float(np.abs(f32(a) - f32(b)).max()),
                             4 * BF16_ULP * float(np.abs(f32(b)).max())]
        out["flash"][name] = rec
        assert by_mosaic == mosaic, "flash kernel: wrong build mode"
        for key in ("fwd", "dq", "dk", "dv"):
            assert rec[key][0] <= rec[key][1], (name, key, rec)

    # Token identity between the two attention paths through the
    # engine: REPORTED, not gated — with random weights the arg-max
    # moves on rounding.
    from ray_tpu.serve.engine import DecodeEngine

    params = _init_params(cfg, seed=0)
    streams = {}
    for kernel in ("gather", "pallas"):
        eng = DecodeEngine(params, cfg, **{
            **_engine_shape(cfg.max_seq), "attn_kernel": kernel})
        try:
            streams[kernel] = [np.concatenate(list(eng.stream(
                _prompt(cfg.vocab_size, n), 16)))
                for n, _ in _requests(cfg.max_seq)[:3]]
        finally:
            eng.shutdown()
            del eng             # its pool goes before the next is built
    out["token_identity_pallas_vs_gather"] = [
        bool((a == b).all()) for a, b in zip(streams["gather"],
                                             streams["pallas"])]
    out["compile_s"] = round(t_compile, 2)
    out["cache"] = dict(counts)
    return out


# ----------------------------------------------------------- train phase
def phase_train(cfg_name: str, require_tpu: bool = True, mesh_axes=None,
                batch: int = 32, steps: int = 12) -> dict:
    """``gpt.make_train_step`` at ``bench.py``'s settings (``remat=
    "dots"``, ``attn_backend="auto"``, full context) on one fixed batch:
    the loss must be finite at every step and lower at the end."""
    counts = _cache_counter()
    info = _holder_report(require_tpu)
    import dataclasses

    import jax
    import numpy as np

    from ray_tpu.models import gpt
    from ray_tpu.parallel import create_mesh

    cfg = dataclasses.replace(gpt.CONFIGS[cfg_name], remat="dots",
                              attn_backend="auto")
    seq = cfg.max_seq
    plan = gpt.attention_plan(cfg, seq)
    if require_tpu:
        assert plan == {"backend": "flash", "mode": "compiled"}, plan
    axes = mesh_axes or {"dp": 1}
    n = int(np.prod(list(axes.values())))
    mesh = create_mesh(axes, devices=jax.devices()[:n])
    init, step, _, batch_sh = gpt.make_train_step(cfg, mesh)
    t0 = time.monotonic()
    state = jax.block_until_ready(init(jax.random.PRNGKey(0)))
    init_s = time.monotonic() - t0
    tokens = jax.device_put(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32), batch_sh)
    losses = []
    t0 = time.monotonic()
    for i in range(steps):
        state, metrics = step(state, {"tokens": tokens})
        losses.append(float(metrics["loss"]))
        if i == 0:
            first_step_s = time.monotonic() - t0
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    return {"holder": info, "attention": plan, "mesh": axes,
            "batch": batch, "seq": seq, "losses": losses,
            "compile_s": round(init_s + first_step_s, 2),
            "device_bytes": _device_bytes(), "cache": dict(counts)}


def phase_device(require_tpu: bool = True) -> dict:
    """The first, smallest phase: which device a process here gets."""
    return {"holder": _holder_report(require_tpu)}


def phase_dryrun(n: int) -> dict:
    """``dryrun_multichip(n)`` over the first n REAL devices: the
    dp/fsdp/tp, sp, pp and ep plans."""
    info = _holder_report(True)
    os.environ["RT_DRYRUN_REAL_DEVICES"] = "1"
    from __graft_entry__ import dryrun_multichip

    dryrun_multichip(n)
    return {"holder": info, "plans": "dp/fsdp/tp, sp, pp, ep"}


# ------------------------------------------------------------------ main
def _one_chip_phases():
    return [("device", phase_device),
            ("serve", lambda: phase_serve("1b")),
            ("kernels", lambda: phase_kernels(
                "1b", flash_cfgs=("small", "1b"))),
            ("train", lambda: phase_train("small"))]


def _four_chip_phases():
    return [("serve_tp4", lambda: phase_serve("1b", tp=4)),
            ("train_fsdp4", lambda: phase_train(
                "1b", mesh_axes={"fsdp": 4}, batch=4, steps=4)),
            ("dryrun4", lambda: phase_dryrun(4)),
            # at `1b`: the kernel fetches whole [page, H, hd] pages by
            # DMA, and `small`'s heads (12 of 64) are not whole tiles
            ("replicas4", lambda: phase_serve("1b", num_replicas=4))]


def _run_child(name: str) -> dict:
    """One phase in a process of its own (its own session, so that a
    timeout takes its whole tree with it)."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        stdout=subprocess.PIPE, text=True, start_new_session=True)
    watchdog = threading.Timer(PHASE_BUDGET_S, os.killpg, (proc.pid, 9))
    watchdog.start()
    result = None
    try:
        for line in proc.stdout:
            if line.startswith("PHASE_RESULT "):
                result = json.loads(line[len("PHASE_RESULT "):])
            else:
                sys.stdout.write(f"[{name}] {line}")
        rc = proc.wait()
    finally:
        watchdog.cancel()
        try:
            os.killpg(proc.pid, 9)      # whatever the phase left behind
        except ProcessLookupError:
            pass
    if rc != 0 or result is None:
        raise SystemExit(f"phase {name} failed (exit code {rc})")
    return result


def main() -> int:
    from ray_tpu._private.accelerators import local_chip_count
    from ray_tpu._private.chip import ensure_compile_cache

    if len(sys.argv) == 3 and sys.argv[1] == "--phase":
        fn = dict(_one_chip_phases() + _four_chip_phases())[sys.argv[2]]
        print("PHASE_RESULT " + json.dumps(fn()), flush=True)
        return 0
    cache_dir = ensure_compile_cache()      # children inherit it
    chips = local_chip_count()
    print(f"chip_smoke: {chips} chip(s) by the host's census; compile "
          f"cache at {cache_dir}", flush=True)
    phases = _one_chip_phases()
    if chips >= 4:
        phases += _four_chip_phases()
    else:
        print("four-chip phases (tp=4 serving, fsdp=4 training, "
              "dryrun_multichip(4), four one-chip replicas): "
              "one chip: not run", flush=True)
    results = {}
    for name, _ in phases:
        t0 = time.monotonic()
        results[name] = _run_child(name)
        print(f"[{name}] ok in {time.monotonic() - t0:.0f}s: "
              f"{json.dumps(results[name])}", flush=True)
    holders = [r["holder"] for r in results.values() if "holder" in r] \
        + [x for r in results.values() for x in r.get("replicas", ())]
    kinds = {(h["device"]["platform"], h["device"]["kind"])
             for h in holders}
    assert len(kinds) == 1, f"phases saw different devices: {kinds}"
    # What an unconfined process sees is what jax reports for the
    # machine — and what the host-side census must have counted.
    device = results["device"]["holder"]["device"]
    assert device["count"] == chips, (device, chips)
    print("compile seconds (set-up time; cold where hits == 0): "
          + json.dumps({n: {k: r[k] for k in ("compile_s", "cache")}
                        for n, r in results.items() if "cache" in r}),
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
