"""Disaggregated prefill/decode with a crash-safe KV handoff protocol
(ISSUE 14).

- A prefill-role engine exports a prefilled slot (K/V + pos + first
  token + PRNG lane) under an epoch-stamped lease; a decode-role engine
  byte-verifies and imports it — the continued stream is
  TOKEN-IDENTICAL to a colocated run for every pairing of page sizes, at
  temperature 0 AND seeded temperature > 0.
- The compiled-program set stays bounded: the whole handoff plane adds
  exactly one export + one import program per engine.
- Every failure degrades to a cheap re-prefill, never a broken stream:
  corrupt/missing payloads fall back locally, unclaimed leases are
  swept on the prefill driver's lease clock (orphaned pages freed),
  and killing EITHER side mid-flight leaves every client stream
  token-identical (chaos below + ``serve_gpt.py --disagg``).
- Router satellites: role-aware two-hop routing with locality, drain
  marks that do NOT self-expire while the controller lists a replica
  as draining, and role groups reconciled/drained independently by the
  controller.
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


def _mk_prompt(rid: int, vocab: int, n: int = 7):
    return np.random.default_rng(1400 + rid).integers(
        0, vocab, (n,)).astype(np.int32)


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


# ------------------------------------------------------------ engine level
@pytest.mark.parametrize("src_ps,dst_ps,prefix_cache,temperature",
                         [(8, 16, True, 0.0), (16, 8, True, 0.0),
                          (8, 8, False, 0.0), (8, 8, True, 0.0),
                          (8, 8, False, 1.0), (8, 8, True, 1.0)])
def test_handoff_identity(nano, nano_params, src_ps, dst_ps,
                          prefix_cache, temperature):
    """Export on one engine, import on another: the decode-side stream
    (first token included) is token-identical to an uninterrupted
    colocated run — equal and unequal page sizes (fp K/V ships in
    position order, whatever pages held it), the prefix cache on and
    off, greedy AND seeded sampling — and the handoff counters
    balance."""
    import jax

    pre = _make_engine(nano, nano_params, role="prefill",
                       page_size=src_ps, prefix_cache=prefix_cache,
                       temperature=temperature)
    dec = _make_engine(nano, nano_params, role="decode",
                       page_size=dst_ps, prefix_cache=prefix_cache,
                       temperature=temperature)
    try:
        prompt = _mk_prompt(1, nano.vocab_size)
        kw = {"chunk": 4, "max_len": 64}
        if temperature:
            kw.update(temperature=1.0, rng=jax.random.PRNGKey(9))
        ref = _ref_chunked(nano_params, prompt, nano, 12, **kw)
        desc = pre.handoff(prompt, 12, seed=9)
        assert desc["lease_id"] and desc["digest"]
        assert desc["pos"] == prompt.shape[0]
        out = _drain(dec.admit_prefilled(desc))
        assert (out == ref).all(), (out, ref)
        hp, hd = pre.stats()["handoff"], dec.stats()["handoff"]
        assert hp["exported"] == 1 and hp["ship_bytes"] > 0
        assert hd["imported"] == 1 and hd["import_fallbacks"] == 0
        assert pre.stats()["role"] == "prefill"
        assert dec.stats()["role"] == "decode"
        # The prefill engine holds no slot-pool steady state.
        assert pre.stats()["active_slots"] == 0
    finally:
        pre.shutdown()
        dec.shutdown()


def test_handoff_resume_from_suppression(nano, nano_params):
    """``admit_prefilled(resume_from=n)`` — the decode-death failover
    replay — suppresses the already-delivered prefix, including the
    shipped first token."""
    pre = _make_engine(nano, nano_params, role="prefill")
    dec = _make_engine(nano, nano_params, role="decode")
    try:
        prompt = _mk_prompt(2, nano.vocab_size)
        ref = _ref_chunked(nano_params, prompt, nano, 10, chunk=4,
                           max_len=64)
        desc = pre.handoff(prompt, 10, seed=3)
        out = _drain(dec.admit_prefilled(desc, resume_from=4))
        assert (out == ref[4:]).all(), (out, ref)
        assert dec.stats()["resumed"] == 1
    finally:
        pre.shutdown()
        dec.shutdown()


def test_handoff_recompile_guard(nano, nano_params):
    """The handoff plane adds exactly ONE export and ONE import
    program; a storm of varied prompt/output lengths adds ZERO more
    (and no extra prefill/chunk programs either)."""
    pre = _make_engine(nano, nano_params, role="prefill")
    dec = _make_engine(nano, nano_params, role="decode", slots=3)
    try:
        rng = np.random.default_rng(3)
        for n, mn in ((5, 6), (13, 9)):       # warm both buckets
            p = rng.integers(0, nano.vocab_size, (n,)).astype(np.int32)
            _drain(dec.admit_prefilled(pre.handoff(p, mn, seed=n)))
        counts = (pre._export._cache_size(), dec._import._cache_size(),
                  pre._prefill._cache_size(), dec._step._cache_size())
        # The wrappers are shared per static-knob tuple across engines
        # (other tests may have compiled other pool shapes): what is
        # bounded is ONE program per pool shape — a storm of varied
        # prompts/lengths below must add ZERO.
        assert counts[0] >= 1 and counts[1] >= 1
        for i in range(10):
            n = int(rng.integers(1, 17))
            mn = int(rng.integers(1, 12))
            p = rng.integers(0, nano.vocab_size, (n,)).astype(np.int32)
            _drain(dec.admit_prefilled(pre.handoff(p, mn, seed=i)))
        assert (pre._export._cache_size(), dec._import._cache_size(),
                pre._prefill._cache_size(),
                dec._step._cache_size()) == counts
    finally:
        pre.shutdown()
        dec.shutdown()


def test_lease_expiry_sweeps_orphans(nano, nano_params):
    """A handoff nobody claims (decode replica died between grant and
    claim) is reclaimed on the prefill driver's lease clock: leases
    drop to zero, the reclaim is counted, and the prefill engine's
    pages are all free — a crash can never pin the pool."""
    pre = _make_engine(nano, nano_params, role="prefill",
                       page_size=8, prefix_cache=False,
                       handoff_ttl_s=0.3)
    try:
        base_free = pre.stats()["pages_free"]
        prompt = _mk_prompt(4, nano.vocab_size)
        for seed in (1, 2):
            pre.handoff(prompt, 8, seed=seed)   # never claimed
        assert pre.stats()["handoff"]["leases_outstanding"] == 2
        # The transient prefill slots already freed their pages.
        assert pre.stats()["pages_free"] == base_free
        deadline = time.time() + 10
        while time.time() < deadline:
            ho = pre.stats()["handoff"]
            if ho["leases_reclaimed"] >= 2:
                break
            time.sleep(0.05)
        ho = pre.stats()["handoff"]
        assert ho["leases_reclaimed"] == 2 and \
            ho["leases_outstanding"] == 0, ho
        assert pre.stats()["pages_free"] == base_free
    finally:
        pre.shutdown()


def test_corrupt_payload_falls_back_token_identical(nano, nano_params):
    """Byte verification: a descriptor whose shipped K/V was corrupted
    in flight fails the digest and degrades to a LOCAL prefill of the
    descriptor's prompt+seed — the stream is still token-identical,
    and the fallback is counted."""
    pre = _make_engine(nano, nano_params, role="prefill")
    dec = _make_engine(nano, nano_params, role="decode")
    try:
        prompt = _mk_prompt(5, nano.vocab_size)
        ref = _ref_chunked(nano_params, prompt, nano, 9, chunk=4,
                           max_len=64)
        desc = pre.handoff(prompt, 9, seed=5)
        bad = dict(desc)
        bad["payload"] = dict(desc["payload"])
        bad["payload"]["k"] = np.array(bad["payload"]["k"])
        bad["payload"]["k"][0, 0] = 0
        out = _drain(dec.admit_prefilled(bad))
        assert (out == ref).all()
        ho = dec.stats()["handoff"]
        assert ho["import_fallbacks"] == 1 and ho["imported"] == 0
        # An INTERNALLY-consistent payload that differs from the
        # descriptor's RPC-plane digest (stale/clobbered object) is
        # caught by the cross-plane check and falls back the same way.
        from ray_tpu.serve.handoff import payload_digest

        swapped = dict(desc)
        swapped["payload"] = dict(desc["payload"])
        swapped["payload"]["k"] = np.array(swapped["payload"]["k"])
        swapped["payload"]["k"][0, 0] = 0
        swapped["payload"]["digest"] = payload_digest(swapped["payload"])
        out_sw = _drain(dec.admit_prefilled(swapped))
        assert (out_sw == ref).all()
        assert dec.stats()["handoff"]["import_fallbacks"] == 2
        # A descriptor with NO payload at all (lease reclaimed, no
        # runtime to pull a ref through) falls back the same way.
        gone = {k: v for k, v in desc.items() if k != "payload"}
        out2 = _drain(dec.admit_prefilled(gone))
        assert (out2 == ref).all()
        assert dec.stats()["handoff"]["import_fallbacks"] == 3
    finally:
        pre.shutdown()
        dec.shutdown()


def test_role_gates(nano, nano_params):
    """Role gating: prefill engines reject decode submissions, decode
    engines reject exports, and a role cannot change under traffic."""
    pre = _make_engine(nano, nano_params, role="prefill")
    dec = _make_engine(nano, nano_params, role="decode")
    try:
        prompt = _mk_prompt(6, nano.vocab_size)
        with pytest.raises(ValueError, match="prefill-role"):
            pre.submit(prompt, 4)
        with pytest.raises(ValueError, match="decode-role"):
            dec.handoff(prompt, 4)
        with pytest.raises(ValueError, match="unknown engine role"):
            _make_engine(nano, nano_params, role="router")
        # ensure_role flips a FRESH engine, refuses a used one.
        dec.ensure_role(role="decode")          # no-op
        list(dec.stream(prompt, 3))
        with pytest.raises(ValueError, match="live engine"):
            dec.ensure_role(role="both")
        pre.handoff(prompt, 3, seed=0)
        with pytest.raises(ValueError, match="live engine"):
            pre.ensure_role(role="both")
    finally:
        pre.shutdown()
        dec.shutdown()


# ------------------------------------------------------------ router level
def test_router_draining_marks_do_not_self_expire():
    """ISSUE 14 satellite: a ReplicaDrainingError pushback keeps the
    replica out of the pick set PAST the saturation mark's expiry, and
    a controller snapshot listing it as draining pins the mark until a
    later snapshot clears it — unlike ``note_overloaded``, which
    self-expires."""
    from ray_tpu.serve.handle import Router

    r = Router.__new__(Router)      # no controller / waiter thread
    r.app_name, r.deployment_name = "a", "d"
    r.closed = False
    r._cond = threading.Condition()
    r._replicas = {"r1": object(), "r2": object()}
    r._replica_nodes = {}
    r._replica_roles = {}
    r._ongoing = {"r1": 0, "r2": 0}
    r._saturated = {}
    r._draining_marks = {}
    r._version = 7
    r._local_node = None
    r._max_ongoing = 4
    r._max_queued = 8
    r._pending = 0
    from collections import OrderedDict

    r._model_affinity = OrderedDict()

    def picks(k=6):
        # Mirror _acquire's in-flight increment so load-balancing
        # spreads picks across the WHOLE candidate set.
        with r._cond:
            saved = dict(r._ongoing)
            got = set()
            for _ in range(k):
                rid = r._pick_locked()
                if rid is None:
                    break
                got.add(rid)
                r._ongoing[rid] += 1
            r._ongoing = saved
            return got

    assert picks() == {"r1", "r2"}
    # Pushback: the local mark outlives the saturation window.
    r.note_draining("r1")
    assert picks() == {"r2"}
    time.sleep(Router.SATURATION_MARK_S + 0.05)
    assert picks() == {"r2"}, \
        "drain mark must not self-expire like a saturation mark"
    # Controller confirms the drain: the mark becomes indefinite.
    info = {"version": 7, "replicas": dict(r._replicas),
            "draining": ["r1"]}
    r._apply_membership(info)
    assert r._draining_marks["r1"] == float("inf")
    assert picks() == {"r2"}
    # Controller stops listing it (same version poll): mark heals.
    r._apply_membership({"version": 7, "replicas": dict(r._replicas),
                         "draining": []})
    assert picks() == {"r1", "r2"}
    # Membership change drops marks for departed replicas.
    r.note_draining("r2")
    r._apply_membership({"version": 8, "max_ongoing_requests": 4,
                         "replicas": {"r1": object()},
                         "replica_nodes": {}, "draining": []})
    assert r._draining_marks == {}


def test_router_role_filtering_and_locality():
    """Role-aware picks: explicit role filters the candidate set
    ("both" serves either), roles-active defaults plain traffic to
    decode-capable replicas, and ``prefer_node`` narrows to the node
    holding the shipped bytes."""
    from ray_tpu.serve.handle import Router

    r = Router.__new__(Router)
    r._cond = threading.Condition()
    r._replicas = {"p1": object(), "d1": object(), "b1": object()}
    r._replica_nodes = {"p1": "nA", "d1": "nB", "b1": "nA"}
    r._replica_roles = {"p1": "prefill", "d1": "decode", "b1": "both"}
    r._ongoing = {"p1": 0, "d1": 0, "b1": 0}
    r._saturated = {}
    r._draining_marks = {}
    r._local_node = None
    r._max_ongoing = 4
    from collections import OrderedDict

    r._model_affinity = OrderedDict()

    def picks(role="", prefer_node=None, k=8):
        with r._cond:
            saved = dict(r._ongoing)
            got = set()
            for _ in range(k):
                rid = r._pick_locked("", role, prefer_node)
                if rid is None:
                    break
                got.add(rid)
                r._ongoing[rid] += 1
            r._ongoing = saved
            return got

    assert r._roles_active()
    assert picks(role="prefill") == {"p1", "b1"}
    assert picks(role="decode") == {"d1", "b1"}
    # Plain traffic (no explicit role) avoids prefill-only replicas.
    assert picks() == {"d1", "b1"}
    # Locality: decode hop prefers the shipped bytes' node while the
    # local candidate has capacity (k below max_ongoing)...
    assert picks(role="decode", prefer_node="nA", k=3) == {"b1"}
    assert picks(role="decode", prefer_node="nB", k=3) == {"d1"}
    # ...and spills to remote candidates once the local one saturates.
    assert picks(role="decode", prefer_node="nA", k=8) == {"b1", "d1"}
    # A momentarily EMPTY decode group (its replicas just died) must
    # mean "wait for the controller to respawn", never "spill decode
    # streams onto prefill-only replicas that reject them".
    r._replicas = {"p1": object()}
    r._ongoing = {"p1": 0}
    assert not r._roles_active()        # two-hop impossible right now
    assert r._prefill_present()         # ...but the filter must hold
    assert picks() == set()
    # No prefill replicas -> roles inactive -> everything serves.
    r._replicas = {"p1": object(), "d1": object(), "b1": object()}
    r._ongoing = {"p1": 0, "d1": 0, "b1": 0}
    r._replica_roles = {"p1": "both", "d1": "both", "b1": "both"}
    assert not r._roles_active()
    assert picks() == {"p1", "d1", "b1"}


# ------------------------------------------------------------- serve level
def _disagg_deployment(serve, *, deployment, roles, ttl_s=30.0,
                       num_replicas=None):
    @serve.deployment(num_replicas=num_replicas or
                      sum(roles.values()),
                      max_ongoing_requests=16,
                      health_check_period_s=0.5,
                      graceful_shutdown_timeout_s=10.0,
                      engine_config={"roles": dict(roles),
                                     "handoff_ttl_s": ttl_s})
    class DisaggGPT:
        def __init__(self, deployment: str):
            import jax

            from ray_tpu.models import gpt
            from ray_tpu.serve.engine import DecodeEngine

            self.cfg = gpt.CONFIGS["nano"]
            params = gpt.init_params(jax.random.PRNGKey(0), self.cfg)
            self.engine = DecodeEngine(
                params, self.cfg, slots=2, chunk=4, max_len=64,
                prompt_buckets=(8,), deployment=deployment,
                page_size=8)

        @serve.batch(continuous=True)
        def decode(self, request):
            import numpy as _np

            return self.engine, {
                "prompt": _np.asarray(request["prompt"], _np.int32),
                "max_new": int(request["max_new"]),
                "seed": int(request["rid"])}

        def __call__(self, request):
            return self.decode(request)

    return DisaggGPT.options(name=deployment).bind(deployment)


def _req(rid: int, max_new: int, vocab: int) -> dict:
    return {"rid": rid, "max_new": max_new,
            "prompt": _mk_prompt(rid, vocab).tolist()}


def _engine_stats(handles) -> dict:
    import ray_tpu as rt

    out = {}
    for r, h in handles.items():
        try:
            m = rt.get(h.get_metrics.remote(), timeout=10)
            out[r] = (m.get("engines") or [{}])[0]
        except Exception:  # noqa: BLE001 - replica dead (chaos!)
            pass
    return out


def test_disagg_two_hop_deployment(rt_cluster, nano, nano_params):
    """One deployment, heterogeneous role groups: the controller
    reconciles 1 prefill + 2 decode replicas, streams route two-hop
    (prefill export -> decode import, lease claimed), output is
    token-identical, and the handoff block aggregates into
    serve.status(). Draining the prefill role independently degrades
    new streams to local prefill — still token-identical."""
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.serve.config import SERVE_CONTROLLER_NAME
    from ray_tpu.testing import _serve_replica_handles

    name = "disagg_roles"
    serve.start(proxy=False)
    try:
        handle = serve.run(
            _disagg_deployment(serve, deployment=name,
                               roles={"prefill": 1, "decode": 2}),
            name=name, route_prefix=None)
        ctrl = rt.get_actor(SERVE_CONTROLLER_NAME, timeout=10)
        info = rt.get(ctrl.get_replicas.remote(name, name), timeout=10)
        roles = info["replica_roles"]
        assert sorted(roles.values()) == ["decode", "decode", "prefill"]
        prefill_rid = next(r for r, ro in roles.items()
                           if ro == "prefill")

        rid, max_new = 3, 12
        req = _req(rid, max_new, nano.vocab_size)
        ref = _ref_chunked(nano_params, _mk_prompt(rid, nano.vocab_size),
                           nano, max_new, chunk=4, max_len=64)
        for _ in range(2):
            out = np.concatenate([np.asarray(x).ravel() for x in
                                  handle.options(stream=True).remote(req)])
            assert (out == ref).all(), (out, ref)

        handles = _serve_replica_handles(name, name)
        stats = _engine_stats(handles)
        assert stats[prefill_rid]["handoff"]["exported"] >= 2
        assert stats[prefill_rid]["role"] == "prefill"
        assert sum(s["handoff"]["imported"]
                   for s in stats.values()) >= 2
        # Claims land asynchronously after each stream's first item.
        deadline = time.time() + 10
        while time.time() < deadline:
            claimed = _engine_stats(handles)[prefill_rid][
                "handoff"]["leases_claimed"]
            if claimed >= 2:
                break
            time.sleep(0.1)
        assert claimed >= 2

        # Controller aggregation into serve.status().
        deadline = time.time() + 15
        agg = {}
        while time.time() < deadline:
            st = serve.status()
            agg = st["applications"][name]["deployments"][name] \
                .get("engine") or {}
            if agg.get("handoff", {}).get("exported", 0) >= 2:
                break
            time.sleep(0.3)
        assert agg["handoff"]["imported"] >= 2, agg

        # Drain the prefill role INDEPENDENTLY (mark-and-drain): the
        # controller lists it as draining, the router pins it out, and
        # new streams fall back to a local prefill on a decode replica
        # — token-identical, counted as a router fallback.
        from ray_tpu._private.metrics import serve_metrics

        fb0 = sum(v for _k, v in
                  serve_metrics()["prefill_fallbacks"].collect())
        drained = rt.get(ctrl.drain_role.remote(name, name, "prefill",
                                                False), timeout=30)
        assert drained == [prefill_rid]
        info = rt.get(ctrl.get_replicas.remote(name, name), timeout=10)
        assert info["draining"] == [prefill_rid]
        out = np.concatenate([np.asarray(x).ravel() for x in
                              handle.options(stream=True).remote(req)])
        assert (out == ref).all()
        fb = sum(v for _k, v in
                 serve_metrics()["prefill_fallbacks"].collect())
        assert fb > fb0, "fallback to local prefill was not counted"
        serve.delete(name)
    finally:
        serve.shutdown()


def test_role_transition_reaps_stray_replicas(rt_cluster, nano,
                                              nano_params):
    """Redeploying a plain deployment WITH a roles block (same payload,
    new config) must converge membership to the role groups: the old
    role-less replicas are drained away, not stranded outside every
    per-role count — and traffic keeps flowing token-identically
    through the transition's endpoints."""
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.serve.config import SERVE_CONTROLLER_NAME

    name = "disagg_transition"
    serve.start(proxy=False)
    try:
        app_roles = _disagg_deployment(serve, deployment=name,
                                       roles={"prefill": 1,
                                              "decode": 1})
        # SAME class (→ same payload bytes), different config: the
        # redeploy below must take the config-change path, where only
        # _reap_stray_roles can retire the role-less replicas.
        plain = app_roles.deployment.options(num_replicas=2,
                                             engine_config={})
        handle = serve.run(plain.bind(name), name=name,
                           route_prefix=None)
        ctrl = rt.get_actor(SERVE_CONTROLLER_NAME, timeout=10)
        info = rt.get(ctrl.get_replicas.remote(name, name), timeout=10)
        assert sorted(info["replica_roles"].values()) == ["both",
                                                          "both"]
        rid, max_new = 7, 8
        req = _req(rid, max_new, nano.vocab_size)
        ref = _ref_chunked(nano_params, _mk_prompt(rid, nano.vocab_size),
                           nano, max_new, chunk=4, max_len=64)
        out = np.concatenate([np.asarray(x).ravel() for x in
                              handle.options(stream=True).remote(req)])
        assert (out == ref).all()
        # Redeploy with roles (same payload): the two plain replicas
        # are strays the reconcile loop must drain away.
        serve.run(app_roles, name=name, route_prefix=None)
        deadline = time.time() + 60
        roles = {}
        while time.time() < deadline:
            info = rt.get(ctrl.get_replicas.remote(name, name),
                          timeout=10)
            roles = dict(info["replica_roles"])
            if sorted(roles.values()) == ["decode", "prefill"]:
                break
            time.sleep(0.3)
        assert sorted(roles.values()) == ["decode", "prefill"], roles
        out = np.concatenate([np.asarray(x).ravel() for x in
                              handle.options(stream=True).remote(req)])
        assert (out == ref).all()
        serve.delete(name)
    finally:
        serve.shutdown()


def test_disagg_chaos_kill_either_side(rt_cluster, nano, nano_params):
    """The acceptance chaos: kill the prefill replica mid-handoff AND a
    decode replica mid-stream. Zero broken client streams, every
    stream token-identical to its uninterrupted reference, >= 1
    mid-stream resume, and >= 1 lease reclaimed (a grant orphaned by
    the dying consumer expires on the lease clock)."""
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu._private.metrics import serve_metrics
    from ray_tpu.serve.request import HANDOFF_KEY
    from ray_tpu.testing import _serve_replica_handles, inject_engine_fault

    name = "disagg_chaos"
    serve.start(proxy=False)
    try:
        handle = serve.run(
            _disagg_deployment(serve, deployment=name,
                               roles={"prefill": 2, "decode": 2},
                               ttl_s=2.0),
            name=name, route_prefix=None)
        handles = _serve_replica_handles(name, name)
        assert len(handles) == 4
        import ray_tpu as _rt
        from ray_tpu.serve.config import SERVE_CONTROLLER_NAME

        ctrl = _rt.get_actor(SERVE_CONTROLLER_NAME, timeout=10)
        roles = rt.get(ctrl.get_replicas.remote(name, name),
                       timeout=10)["replica_roles"]
        prefills = [r for r, ro in roles.items() if ro == "prefill"]
        decodes = [r for r, ro in roles.items() if ro == "decode"]

        n_req, max_new = 6, 16
        reqs = [_req(100 + i, max_new, nano.vocab_size)
                for i in range(n_req)]
        refs = [_ref_chunked(nano_params,
                             _mk_prompt(100 + i, nano.vocab_size),
                             nano, max_new, chunk=4, max_len=64)
                for i in range(n_req)]
        # Warm every program (and both role groups).
        out = np.concatenate([np.asarray(x).ravel() for x in
                              handle.options(stream=True).remote(reqs[0])])
        assert (out == refs[0]).all()

        resumes0 = sum(v for _k, v in
                       serve_metrics()["stream_resumes"].collect())
        # Throttle decode chunks so streams are reliably mid-flight.
        inject_engine_fault(name, name, kind="driver_slow",
                            wedge_s=0.03)

        # (a) prefill death mid-handoff: one prefill replica hard-exits
        # at its next exported token; in-flight/following prefill hops
        # retry on the survivor or fall back — streams never break.
        stats = _engine_stats(handles)
        victim_p = prefills[0]
        rt.get(handles[victim_p].inject_engine_fault.remote(
            "kill_process", int(stats[victim_p].get("tokens", 0)) + 1,
            0.0), timeout=10)
        # (b) decode death mid-stream: one decode replica hard-exits
        # after two more delivered tokens; its resumable streams replay
        # on the surviving decode replica.
        victim_d = decodes[0]
        rt.get(handles[victim_d].inject_engine_fault.remote(
            "kill_process", int(stats[victim_d].get("tokens", 0)) + 2,
            0.0), timeout=10)

        results = [None] * n_req
        errors = [None] * n_req

        def one(i):
            try:
                toks = []
                it = handle.options(stream=True, resumable=True,
                                    timeout_s=120.0).remote(reqs[i])
                for item in it:
                    toks.extend(int(t) for t in np.asarray(item).ravel())
                results[i] = toks
            except Exception as e:  # noqa: BLE001 - counted as broken
                errors[i] = repr(e)

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(n_req)]
        for t in threads:
            t.start()
            time.sleep(0.05)
        for t in threads:
            t.join(timeout=180)
        assert not any(t.is_alive() for t in threads)

        broken = [(i, errors[i]) for i in range(n_req)
                  if errors[i] is not None
                  or results[i] != [int(t) for t in refs[i]]]
        assert not broken, f"broken streams after kills: {broken[:3]}"

        # Survivor accounting: both kills landed (the dead replicas
        # fail their metrics RPC), and >= 1 stream resumed mid-flight.
        alive = _engine_stats(handles)
        assert victim_p not in alive and victim_d not in alive, \
            "a kill did not land"
        resumes = sum(v for _k, v in
                      serve_metrics()["stream_resumes"].collect()) \
            - resumes0
        assert resumes >= 1, "no stream was interrupted mid-flight"

        # Lease reclaim: grant a handoff on the SURVIVING prefill
        # replica and never claim it — the consumer that would have
        # claimed is exactly the replica we killed. The prefill
        # driver's lease clock sweeps it.
        survivor_p = next(r for r in prefills if r in alive)
        desc = rt.get(handles[survivor_p].handle_request.remote(
            "__call__", (reqs[0],), {}, {HANDOFF_KEY: "export"}),
            timeout=30)
        assert desc["lease_id"]
        deadline = time.time() + 15
        reclaimed = 0
        while time.time() < deadline:
            ho = _engine_stats(handles)[survivor_p]["handoff"]
            reclaimed = ho["leases_reclaimed"]
            if reclaimed >= 1 and ho["leases_outstanding"] == 0:
                break
            time.sleep(0.2)
        assert reclaimed >= 1, "orphaned lease was not swept"
        serve.delete(name)
    finally:
        serve.shutdown()


def test_disagg_smoke_benchmark():
    """Satellite CI hook: ``benchmarks/serve_gpt.py --disagg --smoke``
    A/Bs colocated vs disaggregated under a bursty-prefill mix and
    asserts zero broken streams and no handoff leaks."""
    import json
    import os
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "serve_gpt.py"),
         "--disagg", "--smoke"],
        capture_output=True, text=True, timeout=420, env=env, cwd=root)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.strip().startswith("{")]
    ab = [r for r in rows if r["metric"].endswith("disagg_ab")]
    assert ab, rows
    row = ab[0]
    assert row["smoke"] is True
    assert row["broken_streams"] == 0
    assert row["handoff_leaks"] == 0
    assert row["handoffs_imported"] >= 1
