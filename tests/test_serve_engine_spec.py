"""Speculative decoding in the DecodeEngine (ISSUE 9): draft-k-verify-
once with per-slot variable advance.

- At temperature 0 spec-decoded streams are token-identical to
  ``generate_chunked`` for ANY drafter — n-gram, model, and an
  adversarial always-wrong drafter (acceptance 0, output still exact).
- Seeded temperature>0 streams are reproducible and ``resume_from``
  replay through a mid-stream driver kill (chaos harness) delivers the
  exact uninterrupted stream.
- The compiled-program set stays ``len(prompt_buckets) + 1 + 1`` (one
  extra verify program) across a mixed admission storm — zero
  retraces.
- ``spec_decode``/``draft_k`` ride the existing config plane
  (``@serve.batch(continuous=True, ...)``, schema ``engine:`` block).
"""
import sys
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


def _ref_chunked(params, prompt, cfg, max_new, **kw):
    from ray_tpu.models import gpt_decode

    return np.concatenate([s[0] for s in gpt_decode.generate_chunked(
        params, np.asarray(prompt)[None], cfg, max_new, **kw)])


def _make_engine(nano, nano_params, **kw):
    from ray_tpu.serve.engine import DecodeEngine

    kw.setdefault("slots", 2)
    kw.setdefault("chunk", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("prompt_buckets", (8, 16))
    kw.setdefault("spec_decode", "ngram")
    kw.setdefault("draft_k", 4)
    return DecodeEngine(nano_params, nano, **kw)


def _always_wrong_drafter(prompts, refs, vocab):
    """Adversarial drafter, wrong by construction: it holds the
    reference streams and proposes, at every position, a token that
    DIFFERS from the one the target will commit there, so nothing is
    ever accepted — the committed stream must STILL be exact (the
    correction token is the target's own sample)."""
    from ray_tpu.serve.draft import Drafter

    class AlwaysWrongDrafter(Drafter):
        name = "always_wrong"

        def configure(self, **kw):
            super().configure(**kw)
            self._ref, self._n = {}, {}

        def admit(self, slot, prompt, first_token):
            self._ref[slot] = next(
                r for p, r in zip(prompts, refs)
                if p.shape == prompt.shape and (p == prompt).all())
            self._n[slot] = 1            # tokens committed so far

        def observe(self, slot, tokens, accepted):
            self._n[slot] += len(tokens)

        def propose(self, active, last):
            out = np.zeros((self.slots, self.draft_k), np.int32)
            for i in np.flatnonzero(active):
                ref, n = self._ref[i], self._n[i]
                for j in range(self.draft_k):
                    nxt = ref[n + j] if n + j < len(ref) else 0
                    out[i, j] = (int(nxt) + 1) % vocab
            return out

    return AlwaysWrongDrafter()


def _drive_concurrent(eng, prompts, max_news):
    outs = {}

    def consume(i):
        outs[i] = np.concatenate(list(eng.stream(prompts[i],
                                                 max_news[i])))

    threads = [threading.Thread(target=consume, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outs


@pytest.mark.parametrize("drafter", ["ngram", "model", "adversarial"])
def test_spec_greedy_identity_any_drafter(nano, nano_params, drafter):
    """Temp-0 token identity holds for ANY drafter — acceptance only
    changes how many verify forwards the stream takes, never its
    tokens. The adversarial drafter pins the acceptance-0 edge."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, nano.vocab_size, (n,)).astype(np.int32)
               for n in (5, 8, 16)]
    max_news = [10, 14, 7]
    refs = [_ref_chunked(nano_params, p, nano, mn, chunk=4, max_len=64)
            for p, mn in zip(prompts, max_news)]
    spec = _always_wrong_drafter(prompts, refs, nano.vocab_size) \
        if drafter == "adversarial" else drafter
    eng = _make_engine(nano, nano_params, spec_decode=spec)
    try:
        outs = _drive_concurrent(eng, prompts, max_news)
        for i, r in enumerate(refs):
            assert (outs[i] == r).all(), (drafter, i, outs[i], r)
        st = eng.stats()
        assert st["completed"] == 3
        sp = st["spec"]
        assert sp["drafter"] == (
            "always_wrong" if drafter == "adversarial" else drafter)
        assert sp["rounds"] > 0 and sp["proposed"] > 0
        if drafter == "adversarial":
            assert sp["accepted"] == 0
            assert sp["accepted_per_forward"] == 1.0
        # Every round commits at least the correction/bonus token.
        assert sp["accepted_per_forward"] >= 1.0
    finally:
        eng.shutdown()


def test_spec_temperature_determinism_and_resume(nano, nano_params):
    """Seeded temp>0 spec streams are reproducible (PRNG consumption is
    static per verify round) and a fresh engine replays them for
    ``resume_from`` with the delivered prefix suppressed bit-exactly."""
    prompt = np.random.default_rng(1).integers(
        0, nano.vocab_size, (8,)).astype(np.int32)

    def build():
        return _make_engine(nano, nano_params, prompt_buckets=(8,),
                            temperature=1.0)

    e1 = build()
    try:
        a = np.concatenate(list(e1.stream(prompt, 20, seed=7)))
        b = np.concatenate(list(e1.stream(prompt, 20, seed=7)))
        c = np.concatenate(list(e1.stream(prompt, 20, seed=8)))
        assert (a == b).all()
        assert not (a == c).all()
    finally:
        e1.shutdown()
    e2 = build()
    try:
        tail = np.concatenate(list(
            e2.stream(prompt, 20, seed=7, resume_from=9)))
        assert (tail == a[9:]).all(), (tail, a[9:])
        assert e2.stats()["resumed"] == 1
    finally:
        e2.shutdown()


def test_spec_adaptive_threshold(nano, nano_params):
    """``spec_threshold > 0`` gates speculation POOL-WIDE on the
    drafters' mean self-assessed acceptance EMA: unpredictable phases
    ride plain chunk boundaries (fallback_rounds > 0, ONE dispatch per
    boundary — a split pool would pay both programs and always lose),
    verify boundaries run only on predictable phases, token identity
    holds through every mode switch, and resume_from replay stays
    exact (greedy streams are PRNG-free, so pool-dependent decisions
    cannot perturb them). Sampling engines must refuse the knob."""
    # Constant-token prompts steer greedy decoding into repetitive
    # attractors — the predictable phase the gate must detect.
    prompts = [np.full((24,), np.random.default_rng(700 + s).integers(
        0, nano.vocab_size), np.int32) for s in range(3)]
    refs = [_ref_chunked(nano_params, p, nano, 40, chunk=8, max_len=128)
            for p in prompts]
    kw = dict(chunk=8, max_len=128, prompt_buckets=(24,), draft_k=8,
              spec_threshold=1.0)
    eng = _make_engine(nano, nano_params, **kw)
    try:
        outs = _drive_concurrent(eng, prompts, [40, 40, 40])
        for i, r in enumerate(refs):
            assert (outs[i] == r).all(), (i, outs[i], r)
        sp = eng.stats()["spec"]
        assert sp["threshold"] == 1.0
        assert sp["fallback_rounds"] > 0, sp   # unpredictable phases
        assert sp["rounds"] > 0, sp            # predictable phases
        # The gate only verifies when it expects to win: mean accept
        # within verify rounds clears the threshold comfortably.
        assert sp["mean_accept_len"] >= 1.0, sp
    finally:
        eng.shutdown()
    # resume_from through mode switches: greedy replay is exact even
    # though the replaying pool gates on different pool-mates.
    e2 = _make_engine(nano, nano_params, **kw)
    try:
        tail = np.concatenate(list(
            e2.stream(prompts[0], 40, resume_from=13)))
        assert (tail == refs[0][13:]).all(), (tail, refs[0][13:])
    finally:
        e2.shutdown()
    # Pool-wide gating on a sampling engine would break replay; the
    # constructor and the config plane both refuse it.
    with pytest.raises(ValueError, match="temperature 0"):
        _make_engine(nano, nano_params, temperature=1.0, **kw)
    e3 = _make_engine(nano, nano_params, temperature=1.0,
                      spec_decode="ngram")
    try:
        with pytest.raises(ValueError, match="temperature 0"):
            e3.ensure_spec(spec_threshold=1.0)
    finally:
        e3.shutdown()


def test_spec_resume_through_driver_kill(rt_cluster, nano, nano_params):
    """Chaos harness, spec on, seeded temp>0: the engine driver dies
    mid-stream; the client resumes on the other replica and the
    concatenation — delivered prefix plus replayed tail — is bit-exact
    against an uninterrupted run."""
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.testing import (_serve_replica_handles,
                                 inject_engine_fault)

    name = "chaos_spec"
    serve.start(proxy=False)
    try:
        @serve.deployment(num_replicas=2, max_ongoing_requests=8,
                          health_check_period_s=0.3,
                          graceful_shutdown_timeout_s=10.0)
        class SpecChaosGPT:
            def __init__(self):
                import jax

                from ray_tpu.models import gpt
                from ray_tpu.serve.engine import DecodeEngine

                self.cfg = gpt.CONFIGS["nano"]
                params = gpt.init_params(jax.random.PRNGKey(0), self.cfg)
                self.engine = DecodeEngine(
                    params, self.cfg, slots=2, chunk=4, max_len=64,
                    prompt_buckets=(8,), deployment=name,
                    temperature=1.0, spec_decode="ngram", draft_k=4,
                    wedge_timeout_s=2.0)
                # Compile before the replica registers (health probes
                # start at registration).
                list(self.engine.stream(
                    np.arange(8, dtype=np.int32) % self.cfg.vocab_size,
                    6, seed=0))

            @serve.batch(continuous=True)
            def decode(self, request):
                import numpy as _np

                return self.engine, {
                    "prompt": _np.asarray(request["prompt"], _np.int32),
                    "max_new": int(request["max_new"]),
                    "seed": int(request["rid"])}

            def __call__(self, request):
                return self.decode(request)

        handle = serve.run(SpecChaosGPT.options(name=name).bind(),
                           name=name, route_prefix=None)
        prompt = np.random.default_rng(905).integers(
            0, nano.vocab_size, (8,)).astype(np.int32)
        req = {"rid": 5, "max_new": 32, "prompt": prompt.tolist()}
        # Uninterrupted spec stream = the reference (temp>0 PRNG
        # consumption differs from the non-spec path by design).
        ref = np.concatenate([np.asarray(x).ravel() for x in
                              handle.options(stream=True).remote(req)])
        handles = _serve_replica_handles(name, name)
        assert len(handles) == 2
        inject_engine_fault(name, name, kind="driver_slow", wedge_s=0.03)

        def killer():
            for r, st in _engine_stats(handles, rt).items():
                if st.get("active_slots", 0) > 0:
                    rt.get(handles[r].inject_engine_fault.remote(
                        "driver_die", int(st["tokens"]), 0.0),
                        timeout=10)

        fired = False
        toks = []
        it = handle.options(stream=True, resumable=True,
                            timeout_s=60.0).remote(req)
        for item in it:
            toks.extend(int(t) for t in np.asarray(item).ravel())
            if not fired and len(toks) >= 6:
                fired = True
                killer()
        assert fired, "stream finished before the fault could fire"
        assert toks == [int(t) for t in ref], (toks, ref)
        total_resumed = sum(
            st.get("resumed", 0)
            for st in _engine_stats(handles, rt).values())
        assert total_resumed >= 1
        serve.delete(name)
    finally:
        serve.shutdown()


def _engine_stats(handles, rt):
    out = {}
    for r, h in handles.items():
        try:
            m = rt.get(h.get_metrics.remote(), timeout=10)
            out[r] = (m.get("engines") or [{}])[0]
        except Exception:  # noqa: BLE001 - replica dead (chaos test!)
            pass
    return out


def test_spec_recompile_guard(nano, nano_params):
    """With spec on, a mixed admission storm compiles exactly
    ``len(prompt_buckets) + 1 + 1`` programs — the usual prefill-per-
    bucket + one chunk program + ONE verify program — and a storm of
    varied prompts/lengths adds ZERO retraces. Unique static knobs
    (max_len=56, draft_k=5) isolate this engine's programs from the
    shared lru wrappers' other users."""
    from ray_tpu.models.gpt_decode import (jit_decode_chunk_slots_paged,
                                           jit_prefill_into_slot_paged,
                                           jit_verify_chunk_slots_paged)

    buckets = (8, 24)
    pf = jit_prefill_into_slot_paged(nano, 16, 0.0)
    n_pf0 = pf._cache_size()
    eng = _make_engine(nano, nano_params, slots=3, max_len=56,
                       prompt_buckets=buckets, draft_k=5)
    try:
        assert eng._prefill is pf
        assert eng._step is jit_decode_chunk_slots_paged(
            nano, 4, 16, 0.0, -1)
        assert eng._verify is jit_verify_chunk_slots_paged(
            nano, 5, 16, 0.0)
        rng = np.random.default_rng(6)

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
                if i % 3 == 0:
                    time.sleep(0.01)  # stagger: mid-stream admissions
            for t in threads:
                t.join()

        storm(4, [5, 24])             # warm pass: touch both buckets
        pre_pf = pf._cache_size()
        pre_step = eng._step._cache_size()
        pre_vf = eng._verify._cache_size()
        # Exactly one program per bucket + 1 chunk + 1 verify for THIS
        # engine's unique (max_len, draft_k) knobs.
        assert pre_pf - n_pf0 == len(buckets)
        assert pre_vf == 1
        storm(12, [1, 3, 7, 8, 9, 12, 20, 24])
        assert pf._cache_size() == pre_pf
        assert eng._step._cache_size() == pre_step
        assert eng._verify._cache_size() == pre_vf
        assert eng.stats()["spec"]["rounds"] > 0
    finally:
        eng.shutdown()


def test_spec_model_drafter_program_set_bounded(nano, nano_params):
    """The model drafter's own compiled-program set is bounded too:
    one prefill per prompt bucket plus the k-step draft chunk plus the
    1-token lazy ingest — regardless of traffic or acceptance."""
    eng = _make_engine(nano, nano_params, spec_decode="model")
    try:
        rng = np.random.default_rng(9)
        prompts = [rng.integers(0, nano.vocab_size, (n,)
                                ).astype(np.int32) for n in (5, 8, 16)]
        _drive_concurrent(eng, prompts, [8, 12, 6])
        d = eng._drafter
        assert d._step._cache_size() == 1          # draft chunk (k)
        assert d._ingest._cache_size() <= 1        # lazy ingest (k=1)
        assert d._prefill._cache_size() >= 1
        # Tied embedding: the drafter SHARES the target's arrays.
        assert d.params["embed"] is nano_params["embed"]
        assert d.params["pos_embed"] is nano_params["pos_embed"]
    finally:
        eng.shutdown()


# What the parent's drafter (a dense [slots, max_len] cache of its own,
# PR 33's tree) proposed for this prompt and seed when its cursor upload
# did not race its prefill; the greedy rows are also the reference
# model's own continuation, since this drafter IS the target.
_DRAFTED = {
    0.0: ([2, 91, 2, 330, 2, 100, 335, 218, 330, 330, 330, 330, 330, 330,
           335, 260],
          [[91, 2, 330, 2], [335, 218, 330, 330], [330, 330, 330, 335]]),
    1.0: ([254, 59, 101, 433, 154, 14, 221, 494, 453, 80, 360, 48, 39,
           421, 191, 331],
          [[2, 22, 132, 2], [95, 22, 132, 16], [218, 132, 132, 132],
           [22, 132, 330, 325], [496, 22, 132, 220], [22, 132, 22, 132],
           [445, 324, 445, 461], [95, 22, 218, 132], [2, 22, 218, 171],
           [22, 218, 155, 22], [132, 16, 58, 458], [155, 22, 218, 22],
           [240, 446, 22, 132], [22, 446, 22, 446], [22, 218, 132, 330]]),
}


@pytest.mark.parametrize("temperature", [0.0, 1.0])
def test_model_drafter_proposals_on_identity_table(nano, nano_params,
                                                   temperature):
    """The model drafter's cache is the page pool under the fixed table
    ``pt[s] = [s]`` (one ``max_len`` page a slot). Drafting with the
    target's own weights, it proposes token for token what the dense
    cache it replaced proposed: every round accepted at temperature 0
    (the lazy ingest after a full accept), every round rolled back at
    seeded temperature 1 (the cursor rollback)."""
    from ray_tpu.serve.draft import ModelDrafter

    stream, proposals = _DRAFTED[temperature]
    prompt = np.random.default_rng(11).integers(
        0, nano.vocab_size, (8,)).astype(np.int32)
    drafter = ModelDrafter(nano_params, nano)
    eng = _make_engine(nano, nano_params, spec_decode=drafter,
                       temperature=temperature)
    try:
        assert drafter._pt.tolist() == [[0], [1]]
        assert drafter._cache["k"].shape[1:3] == (2, 64)
        seen = []
        propose = drafter.propose

        def recording(active, last):
            out = propose(active, last)
            seen.append(out[np.flatnonzero(active)[0]].tolist())
            return out

        drafter.propose = recording
        got = np.concatenate(list(eng.stream(prompt, 16, seed=7)))
        assert got.tolist() == stream
        assert seen == proposals
        sp = eng.stats()["spec"]
        assert sp["accepted"] == (12 if temperature == 0.0 else 0)
    finally:
        eng.shutdown()


def test_spec_metrics_observed(nano, nano_params):
    """The verify loop observes the new spec counters/histogram into
    the serve metric set, labeled by deployment."""
    from ray_tpu._private.metrics import serve_metrics

    eng = _make_engine(nano, nano_params, deployment="spec_probe")
    try:
        prompt = np.arange(8, dtype=np.int32) % nano.vocab_size
        list(eng.stream(prompt, 12))
        sm = serve_metrics()
        key = (("deployment", "spec_probe"),)
        proposed = dict(sm["engine_spec_proposed"].collect())
        accept_len = dict(sm["engine_spec_accept_len"].collect())
        assert proposed.get(key, 0) > 0
        assert key in accept_len and accept_len[key][-1] > 0
        # accepted may legitimately be zero; the counter must still
        # exist with a prometheus-lintable name.
        assert "engine_spec_accepted" in sm
    finally:
        eng.shutdown()


def test_spec_config_plumbing(nano, nano_params):
    """spec_decode/draft_k ride the existing engine config plane: the
    continuous decorator and schema accept them, non-continuous use is
    a decorate-time error, and a LIVE engine refuses the change."""
    from ray_tpu import serve
    from ray_tpu.serve.schema import DeploymentSchema

    with pytest.raises(ValueError, match="continuous"):
        @serve.batch(spec_decode="ngram")
        def bad(items):
            return items

    with pytest.raises(ValueError, match="continuous"):
        @serve.batch(draft_k=4)
        def worse(items):
            return items

    s = DeploymentSchema.from_dict(
        {"name": "d", "engine": {"spec_decode": "ngram", "draft_k": 4,
                                 "spec_threshold": 1.5}})
    assert s.engine["spec_decode"] == "ngram"
    assert s.engine["spec_threshold"] == 1.5
    with pytest.raises(ValueError, match="unknown engine config"):
        DeploymentSchema.from_dict(
            {"name": "d", "engine": {"spec": True}})

    eng = _make_engine(nano, nano_params, spec_decode=None)
    try:
        assert eng._verify is None
        eng.apply_config(spec_decode="ngram", draft_k=3)
        assert eng._drafter is not None and eng.draft_k == 3
        assert eng._verify is not None
        # Matching re-application is a no-op, even after traffic.
        prompt = np.arange(8, dtype=np.int32) % nano.vocab_size
        list(eng.stream(prompt, 6))
        eng.apply_config(spec_decode="ngram", draft_k=3)
        # A mismatch on a live engine refuses.
        with pytest.raises(ValueError, match="live engine"):
            eng.ensure_spec(draft_k=5)
        with pytest.raises(ValueError, match="live engine"):
            eng.ensure_spec(spec_decode=False)
        with pytest.raises(ValueError, match="live engine"):
            eng.ensure_spec(spec_threshold=2.0)
        with pytest.raises(ValueError, match="unknown engine config"):
            eng.apply_config(bogus=1)
        with pytest.raises(ValueError, match="draft_k"):
            eng.ensure_spec(draft_k=0)
    finally:
        eng.shutdown()


def test_spec_eos_frees_slot(nano, nano_params):
    """EOS inside a committed verify row trims the stream AT the EOS
    and frees the slot for the queued request — same contract as the
    chunk path, now through variable advance."""
    prompt = np.random.default_rng(2).integers(
        0, nano.vocab_size, (8,)).astype(np.int32)
    ref = _ref_chunked(nano_params, prompt, nano, 16, chunk=4,
                       max_len=64)
    eos = int(ref[5])
    stop = int(np.argmax(ref == eos))
    eng = _make_engine(nano, nano_params, slots=1, eos_token=eos)
    try:
        p2 = np.random.default_rng(3).integers(
            0, nano.vocab_size, (8,)).astype(np.int32)
        ref2 = _ref_chunked(nano_params, p2, nano, 6, chunk=4,
                            max_len=64, eos_token=eos)
        out = {}

        def consume(key, p, mn):
            out[key] = np.concatenate(list(eng.stream(p, mn)))

        t1 = threading.Thread(target=consume, args=("a", prompt, 16))
        t2 = threading.Thread(target=consume, args=("b", p2, 6))
        t1.start()
        time.sleep(0.05)
        t2.start()
        t1.join()
        t2.join()
        assert out["a"].shape[0] == stop + 1
        assert int(out["a"][-1]) == eos
        assert (out["a"] == ref[:stop + 1]).all()
        assert (out["b"] == ref2).all()
        assert eng.stats()["completed"] == 2
    finally:
        eng.shutdown()


def test_spec_walk_defers_through_the_chunk_walks_helper(nano,
                                                        nano_params):
    """ISSUE 43: the verify round's walk is the chunk round's
    (``_advance_lanes``), so what it owes the lanes is kept and handed
    over behind the NEXT verify's enqueue: no ``q.put`` of its own, and
    while a lane keeps running every put rides a program in flight."""
    import inspect

    from ray_tpu.serve.engine import DecodeEngine

    for fn in (DecodeEngine._dispatch_spec, DecodeEngine._dispatch_chunk):
        src = inspect.getsource(inspect.unwrap(fn))
        assert "self._advance_lanes(" in src and "q.put" not in src
        assert src.index('step("enqueue")') < src.index('step("flush")') \
            < src.index('step("wait")')
    eng = _make_engine(nano, nano_params)
    try:
        calls = []
        inner = eng._advance_lanes

        def walk(rows, rngs, acc, *a, **kw):
            before = len(eng._kept)
            inner(rows, rngs, acc, *a, **kw)
            calls.append((acc is not None, len(eng._kept) - before))

        eng._advance_lanes = walk
        prompt = np.random.default_rng(4).integers(
            0, nano.vocab_size, (8,)).astype(np.int32)
        ref = _ref_chunked(nano_params, prompt, nano, 20, chunk=4,
                           max_len=64)
        out = np.concatenate(list(eng.stream(prompt, 20)))
        assert (out == ref).all()
        time.sleep(0.12)
        st = eng.stats()
        assert calls and all(spec for spec, _ in calls)
        assert all(kept >= 1 for _, kept in calls)
        assert st["deliver_puts"] == sum(kept for _, kept in calls)
        # all but the last round's slice and the end rode a verify
        assert st["deliver_puts"] - st["deliver_puts_overlapped"] == 2
        assert st["driver_ns_decode_flush"] > 0
    finally:
        eng.shutdown()


def test_spec_smoke_benchmark():
    """Satellite CI hook: the benchmark's --spec --smoke A/B runs end
    to end (spec off vs the n-gram drafter under the same burst) and
    emits the A/B summary row with acceptance accounting."""
    import json
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "serve_gpt.py"),
         "--spec", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env, cwd=root)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.strip().startswith("{")]
    ab = [r for r in rows if r["metric"].endswith("spec_ab")]
    assert ab, rows
    assert ab[0]["smoke"] is True
    assert ab[0]["ngram_accepted_per_forward"] >= 1.0
    modes = {r["metric"] for r in rows}
    assert any("spec_off_mode" in m for m in modes)
    assert any("spec_ngram_mode" in m for m in modes)
