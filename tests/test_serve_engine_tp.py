"""Tensor-parallel decode (ISSUE 20): one DecodeEngine spanning a
multi-chip mesh.

- ``DecodeEngine(tp=2)`` on a REAL 2-device host-platform mesh
  (conftest forces 8 virtual CPU devices) is TOKEN-IDENTICAL to the
  single-chip engine at temperature 0 AND seeded temperature > 0,
  fp and int8 pages, with speculative decoding on — the sharded compute
  graph (column/row-parallel weights, head-sharded KV, psum'd
  partials) commits the same tokens the canonical graph does.
- The compiled-program set stays ``len(prompt_buckets) + 3`` PER MESH
  SHAPE: the tp=2 wrappers are distinct cache keys from tp=1, and an
  admission storm adds zero programs to either.
- The KV handoff plane is a resharding boundary: an N-way exporter
  gathers to the canonical host layout, an M-way importer scatters
  into its own mesh, the digest rides the layout-independent bytes —
  and a non-canonical layout stamp degrades to the counted local
  re-prefill, never a wrongly-scattered cache.
- Crash-resume works unchanged on sharded state: a mid-stream driver
  kill on a tp=2 engine resumes token-identically via the replay
  token.
"""
import threading
import time

import numpy as np
import pytest


@pytest.fixture(scope="module")
def nano():
    from ray_tpu.models import gpt

    return gpt.CONFIGS["nano"]


@pytest.fixture(scope="module")
def nano_params(nano):
    import jax

    from ray_tpu.models import gpt

    return gpt.init_params(jax.random.PRNGKey(0), nano)


def _make_engine(nano, nano_params, **kw):
    from ray_tpu.serve.engine import DecodeEngine

    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_buckets", (8, 16))
    return DecodeEngine(nano_params, nano, **kw)


def _drain(lane):
    from ray_tpu.serve.batching import _EngineStream

    return np.concatenate(list(_EngineStream(lane)))


def _mk_prompt(rid: int, vocab: int, n: int = 7):
    return np.random.default_rng(2000 + rid).integers(
        0, vocab, (n,)).astype(np.int32)


# ------------------------------------------------------- token identity
@pytest.mark.parametrize("kv_dtype,temperature,attn_kernel",
                         [("int8", 0.0, "gather"), ("fp", 0.0, "gather"),
                          ("int8", 1.0, "gather"), ("fp", 1.0, "gather"),
                          ("fp", 0.0, "pallas")])
def test_tp2_token_identity(nano, nano_params, kv_dtype, temperature,
                            attn_kernel):
    """tp=2 output == tp=1 output, stream for stream, at temp 0 and
    seeded temp>0, fp and int8 pages (a page's scale is per head, and
    the mesh cuts between heads) — concurrent mixed-length requests
    through both meshes. The pallas row pins the kernel under the tp
    shard_map (ISSUE 21: it did not trace there — pallas_call's output
    has no vma annotation)."""
    prompts = [_mk_prompt(i, nano.vocab_size, n)
               for i, n in enumerate((5, 8, 11, 16))]
    max_news = [10, 7, 12, 3]

    def run(tp):
        eng = _make_engine(nano, nano_params, page_size=8,
                           kv_dtype=kv_dtype, attn_kernel=attn_kernel,
                           temperature=temperature, tp=tp)
        try:
            outs = {}

            def consume(i):
                outs[i] = np.concatenate(list(eng.stream(
                    prompts[i], max_news[i], seed=100 + i)))

            threads = [threading.Thread(target=consume, args=(i,))
                       for i in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert eng.stats()["tp"] == tp
            return outs
        finally:
            eng.shutdown()

    ref, got = run(1), run(2)
    for i in range(4):
        assert (got[i] == ref[i]).all(), (i, got[i], ref[i])


def test_tp2_spec_decode_identity(nano, nano_params):
    """Speculative decoding on a sharded pool: the tp=2 verify program
    commits exactly what tp=1 commits (draft, verify, and the
    correction token all replicate through the mesh)."""
    prompt = np.tile(np.arange(4, dtype=np.int32) % nano.vocab_size, 2)

    def run(tp):
        eng = _make_engine(nano, nano_params, page_size=8,
                           spec_decode="ngram", draft_k=4, tp=tp)
        try:
            out = np.concatenate(list(eng.stream(prompt, 16, seed=1)))
            st = eng.stats()
            assert st["spec"]["rounds"] >= 1
            return out
        finally:
            eng.shutdown()

    ref, got = run(1), run(2)
    assert (got == ref).all(), (got, ref)


# --------------------------------------------------- program budget
def test_tp_recompile_guard(nano, nano_params):
    """The per-mesh compiled-program budget: a tp=2 engine compiles a
    prefill per prompt bucket and one per pair of buckets (the group of
    one chunk boundary: ``warm_up()`` runs them all) + 1 chunk + 2 handoff
    programs on ITS OWN
    wrappers (distinct lru keys from tp=1), and an admission storm adds
    zero programs."""
    from ray_tpu.models.gpt_decode import (jit_decode_chunk_slots_paged,
                                           jit_prefill_into_slot_paged)

    eng = _make_engine(nano, nano_params, slots=3, max_len=48,
                       prompt_buckets=(8, 16), tp=2)
    try:
        rng = np.random.default_rng(7)

        def storm(n, lens):
            threads = []
            for i in range(n):
                p = rng.integers(0, nano.vocab_size,
                                 (int(lens[i % len(lens)]),)
                                 ).astype(np.int32)
                mn = int(rng.integers(1, 12))
                t = threading.Thread(
                    target=lambda p=p, mn=mn: list(eng.stream(p, mn)))
                t.start()
                threads.append(t)
            for t in threads:
                t.join()

        # twice: a program takes the pool as the init placed it the
        # first time and as another program's output the second
        eng.warm_up()
        eng.warm_up()
        storm(4, [5, 16])             # warm pass: touch both buckets
        pre_prefill = eng._prefill._cache_size()
        pre_step = eng._step._cache_size()
        assert pre_prefill >= 5       # a bucket's, and a pair's
        storm(12, [1, 3, 7, 8, 9, 12, 15, 16])
        assert eng._prefill._cache_size() == pre_prefill
        assert eng._step._cache_size() == pre_step
        # Mesh shape is part of the wrapper key: the tp=2 engine shares
        # the tp=2 wrapper, never the tp=1 one.
        assert jit_prefill_into_slot_paged(
            nano, 16, 0.0, "fp", 2) is eng._prefill
        assert jit_prefill_into_slot_paged(nano, 16, 0.0) \
            is not eng._prefill
        assert jit_decode_chunk_slots_paged(
            nano, 4, 16, 0.0, -1, "fp", "gather", 2) is eng._step
    finally:
        eng.shutdown()


def test_tp_validation_and_config_plane(nano, nano_params):
    """Bad meshes fail at construction; ensure_tp is idempotent,
    rebuilds an unused engine, and refuses a live one."""
    with pytest.raises(ValueError, match="tp"):
        _make_engine(nano, nano_params, tp=3)   # 3 does not divide 2 heads
    eng = _make_engine(nano, nano_params, auto_start=False)
    assert eng.tp == 1
    eng.ensure_tp(2)
    assert eng.tp == 2 and eng.stats()["tp"] == 2
    eng.ensure_tp(2)                            # idempotent no-op
    eng.apply_config(tp=1)                      # config-plane routing
    assert eng.tp == 1
    eng.start()
    try:
        list(eng.stream(_mk_prompt(9, nano.vocab_size), 4))
        with pytest.raises(ValueError, match="live"):
            eng.ensure_tp(2)
    finally:
        eng.shutdown()


# ------------------------------------------------ resharding handoff
@pytest.mark.parametrize("src_tp,dst_tp,src_ps,dst_ps",
                         [(2, 1, 16, 8), (1, 2, 8, 8), (2, 4, 8, 16)])
def test_handoff_resharding_roundtrip(nano, nano_params, src_tp, dst_tp,
                                      src_ps, dst_ps):
    """N-way prefill -> M-way decode: the exporter gathers to the
    canonical host layout, the importer scatters into its own mesh, the
    digest verifies the layout-independent bytes, and the continued
    stream is token-identical to an uninterrupted tp=1 run."""
    import dataclasses

    import jax

    from ray_tpu.models import gpt

    params = nano_params
    if max(src_tp, dst_tp) > nano.n_head:
        # nano has 2 heads; the 2-way -> 4-way leg needs a mesh axis
        # that divides the head count, so widen the model for it.
        nano = dataclasses.replace(nano, n_head=4)
        params = gpt.init_params(jax.random.PRNGKey(0), nano)
    pre = _make_engine(nano, params, role="prefill", tp=src_tp,
                       page_size=src_ps)
    dec = _make_engine(nano, params, role="decode", tp=dst_tp,
                       page_size=dst_ps)
    ref_eng = _make_engine(nano, params)
    try:
        prompt = _mk_prompt(3, nano.vocab_size)
        ref = np.concatenate(list(ref_eng.stream(prompt, 12, seed=9)))
        desc = pre.handoff(prompt, 12, seed=9)
        assert desc["digest"]
        out = _drain(dec.admit_prefilled(desc))
        assert (out == ref).all(), (out, ref)
        assert pre.stats()["handoff"]["exported"] == 1
        hd = dec.stats()["handoff"]
        assert hd["imported"] == 1 and hd["import_fallbacks"] == 0
    finally:
        pre.shutdown()
        dec.shutdown()
        ref_eng.shutdown()


def test_handoff_layout_mismatch_counted_fallback(nano, nano_params):
    """A payload stamped with a non-canonical KV layout is REJECTED
    (its bytes would scatter wrong into the importer's mesh) and
    degrades to the counted local re-prefill — token-identical, zero
    broken streams, visible in serve_prefill_fallbacks_total."""
    from ray_tpu._private.metrics import serve_metrics
    from ray_tpu.serve.handoff import payload_digest

    pre = _make_engine(nano, nano_params, role="prefill", tp=2)
    dec = _make_engine(nano, nano_params, role="decode", tp=2,
                       deployment="tp_layout_probe")
    ref_eng = _make_engine(nano, nano_params)
    try:
        prompt = _mk_prompt(4, nano.vocab_size)
        ref = np.concatenate(list(ref_eng.stream(prompt, 10, seed=5)))
        desc = pre.handoff(prompt, 10, seed=5)
        # A foreign exporter shipping mesh-local bytes: internally
        # consistent (digest covers the stamp), wrong for this plane.
        desc["payload"]["layout"] = "tp2-local"
        desc["payload"]["digest"] = payload_digest(desc["payload"])
        desc["digest"] = desc["payload"]["digest"]
        out = _drain(dec.admit_prefilled(desc))
        assert (out == ref).all(), (out, ref)
        hd = dec.stats()["handoff"]
        assert hd["imported"] == 0 and hd["import_fallbacks"] == 1
        fb = dict(serve_metrics()["prefill_fallbacks"].collect())
        key = (("deployment", "tp_layout_probe"), ("where", "engine"))
        assert fb.get(key, 0) >= 1
    finally:
        pre.shutdown()
        dec.shutdown()
        ref_eng.shutdown()


def test_handoff_digest_canonical_across_meshes(nano, nano_params):
    """The digest is a function of the canonical bytes, not the
    exporter's mesh: the same (prompt, seed) exported from a tp=1 and
    a tp=2 engine hashes identically."""
    one = _make_engine(nano, nano_params, role="prefill", tp=1)
    two = _make_engine(nano, nano_params, role="prefill", tp=2)
    try:
        prompt = _mk_prompt(6, nano.vocab_size)
        d1 = one.handoff(prompt, 8, seed=2)
        d2 = two.handoff(prompt, 8, seed=2)
        assert d1["digest"] == d2["digest"]
        assert "layout" not in d2["payload"]   # canonical ships unstamped
    finally:
        one.shutdown()
        two.shutdown()


# ------------------------------------------------------- crash resume
def test_tp_driver_kill_resume_identity(nano, nano_params):
    """Mid-stream driver death on a sharded pool: the supervisor
    rebuilds the tp=2 pool (sharded params, sharded cache, same
    compiled programs), and the replay token resumes the stream
    bit-exactly against an uninterrupted tp=1 reference."""
    from ray_tpu.serve.engine import EngineRestartError

    ref_eng = _make_engine(nano, nano_params, temperature=1.0)
    eng = _make_engine(nano, nano_params, temperature=1.0, tp=2,
                       wedge_timeout_s=2.0)
    try:
        prompt = _mk_prompt(8, nano.vocab_size)
        ref = np.concatenate(list(ref_eng.stream(prompt, 24, seed=11)))
        eng.inject_fault("driver_die", at_tokens=8)
        toks = []
        try:
            for c in eng.stream(prompt, 24, seed=11):
                toks.extend(int(t) for t in np.asarray(c).ravel())
        except EngineRestartError:
            pass
        assert 0 < len(toks) < 24, toks
        # The replica's health probe path: keep probing until the
        # supervisor observes the death and restarts (the lanes fail
        # before the old thread finishes dying, so an early probe can
        # still see it alive and not restart yet).
        deadline = time.monotonic() + 10.0
        while eng.stats()["driver_restarts"] == 0:
            assert eng.supervise()
            assert time.monotonic() < deadline, "supervisor never restarted"
            time.sleep(0.05)
        tail = list(eng.stream(prompt, 24, seed=11,
                               resume_from=len(toks)))
        toks.extend(int(t) for t in np.concatenate(tail))
        assert toks == [int(t) for t in ref], (toks, ref)
        assert eng.stats()["driver_restarts"] == 1
        assert eng.stats()["tp"] == 2
    finally:
        ref_eng.shutdown()
        eng.shutdown()


# ------------------------------------------------------- benchmark CI
def test_tp_smoke_benchmark():
    """Satellite CI hook: the benchmark's --tp 2 --smoke A/B runs end
    to end (tp=1 and sharded arms under the same saturating burst) and
    the summary line certifies temp-0 token identity and equal
    dispatch accounting on the forced host mesh."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "serve_gpt.py"),
         "--tp", "2", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env, cwd=root)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.strip().startswith("{")]
    ab = [r for r in rows if r["metric"].endswith("tp_ab")]
    assert ab, rows
    assert ab[0]["smoke"] is True and ab[0]["value"] > 0
    assert ab[0]["token_identical_temp0"] is True
    assert ab[0]["dispatches_equal"] is True
    modes = {r["metric"]: r for r in rows}
    assert any(m.endswith("tp1_mode") for m in modes)
    assert any(m.endswith("tp2_mode") for m in modes)


# --------------------------------------------------- flight recorder
def test_shard_dispatch_event_and_stats(nano, nano_params, tmp_path):
    """The sharded dispatch path leaves a post-mortem breadcrumb: one
    ``shard.dispatch`` event (mesh shape + program key) per chunk
    boundary, next to the ``engine.dispatch`` it annotates."""
    from ray_tpu._private import events as ev

    ev._reset_for_tests()
    try:
        ev.init(str(tmp_path), proc="tp-test")
        eng = _make_engine(nano, nano_params, tp=2, page_size=8)
        try:
            list(eng.stream(_mk_prompt(10, nano.vocab_size), 8))
        finally:
            eng.shutdown()
        rec = ev.recorder()
        rec.flush()
        ring = ev.read_ring(rec.path)
        shard = [e for e in ring["events"]
                 if e["kind"] == "shard.dispatch"]
        assert shard, [e["kind"] for e in ring["events"]]
        assert [list(ax) for ax in shard[0]["attrs"]["mesh"]] \
            == [["tp", 2]]
        assert shard[0]["attrs"]["program"] == "chunk_paged"
    finally:
        ev._reset_for_tests()
