"""Admission by the group (ISSUE 54): the head requests one chunk
boundary can admit share ONE prefill launch (``engine._admit_head``,
``PREFILL_GROUP``): FIFO order within the group, a deferral at the
second prompt, the epoch guard over every prompt's pages, the counters
(``prefill_launches`` beside ``prefills``, ``prefill_ns_sum`` once a
launch), who goes alone, and the lone prompt's program, which is the
one it always was.

A boundary is driven by hand here: the engine is built without its
driver thread, requests are queued, and ``_admit_pending`` runs on the
test's thread under a phase clock of its own, so that what one boundary
admits is what the test queued, not what a race left there. CPU,
``nano``: counts, orders and equalities, never a speed.
"""
import hashlib

import numpy as np
import pytest

from ray_tpu.serve.batching import _EngineStream
from ray_tpu.util import tracing


@pytest.fixture(scope="module")
def nano():
    from ray_tpu.models import gpt

    return gpt.CONFIGS["nano"]


@pytest.fixture(scope="module")
def nano_params(nano):
    import jax

    from ray_tpu.models import gpt

    return gpt.init_params(jax.random.PRNGKey(0), nano)


@pytest.fixture
def make(nano, nano_params):
    from ray_tpu.serve.engine import DecodeEngine

    made = []

    def _make(**kw):
        kw.setdefault("slots", 4)
        kw.setdefault("chunk", 4)
        kw.setdefault("max_len", 64)
        kw.setdefault("page_size", 8)
        kw.setdefault("prompt_buckets", (8, 16, 32))
        kw.setdefault("auto_start", False)
        made.append(DecodeEngine(nano_params, nano, **kw))
        return made[-1]

    yield _make
    for eng in made:
        eng.shutdown()


def _prompt(nano, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, nano.vocab_size, (n,)).astype(np.int32)


def _boundary(eng, epoch=-1):
    """One chunk boundary's admission pass, on this thread."""
    if eng._phases is None:
        eng._phases = tracing.PhaseClock("engine", eng._driver_ns)
    with eng._phases.phase("admit"):
        eng._admit_pending(epoch)


def _spy(eng):
    """Record what every prefill launch is called with: ``(the buckets
    of its prompts, the shape of ``length``, the prompts' live
    tokens)``. One prompt: an array ``[1, bucket]`` and scalars; a
    group: a tuple of them, widest first, and vectors."""
    calls, real = [], eng._prefill

    def prefill(params, cache, tokens, length, *rest):
        group = tokens if isinstance(tokens, tuple) else (tokens,)
        assert all(t.shape[0] == 1 for t in group)
        calls.append((tuple(t.shape[1] for t in group), np.shape(length),
                      [t[0, :n].tolist() for t, n in
                       zip(group, np.atleast_1d(length))]))
        return real(params, cache, tokens, length, *rest)

    eng._prefill = prefill
    return calls


def _streams(eng, lanes):
    return [np.concatenate(list(_EngineStream(ln))) for ln in lanes]


def _reference(make, prompts, max_new, **kw):
    """Each request alone, one after another: the single program."""
    ref = make(auto_start=True, **kw)
    calls = _spy(ref)
    out = [np.concatenate(list(ref.stream(p, max_new, seed=i)))
           for i, p in enumerate(prompts)]
    assert all(len(shape) == 1 and ln == () for shape, ln, _ in calls)
    return out


@pytest.mark.parametrize("temperature", [0.0, 0.9])
def test_a_boundary_admits_in_fifo_order_two_a_launch(make, nano,
                                                      temperature):
    """Three queued prompts of three buckets, four free slots: the
    first two (the two widest buckets: they group) share one launch,
    each in its own bucket, the wider one first in the program's
    operands; the third (the narrowest bucket) goes alone; slots and
    first tokens follow the QUEUE's order, and every stream is what the
    request gets alone."""
    from ray_tpu.serve.engine import PREFILL_GROUP

    assert PREFILL_GROUP == 2
    prompts = [_prompt(nano, n, i) for i, n in enumerate((11, 20, 5))]
    want = _reference(make, prompts, 9, temperature=temperature)
    eng = make(temperature=temperature)
    calls = _spy(eng)
    lanes = [eng.submit(p, 9, seed=i) for i, p in enumerate(prompts)]
    _boundary(eng)
    assert [(shape, ln) for shape, ln, _ in calls] == [
        ((32, 16), (2,)), ((8,), ())]
    assert calls[0][2] == [prompts[1].tolist(), prompts[0].tolist()]
    assert calls[1][2] == [prompts[2].tolist()]
    assert [s.req.prompt.tolist() if s else None for s in eng._state] == [
        p.tolist() for p in prompts] + [None]
    st = eng.stats()
    assert (st["prefills"], st["prefill_launches"], st["admitted"]) == (
        3, 2, 3)
    assert not eng._pending
    # the first token of each reached its lane at the boundary
    assert [ln.q.qsize() for ln in lanes] == [1, 1, 1]
    got = _streams_started(eng, lanes)
    assert [g.tolist() for g in got] == [w.tolist() for w in want]


def test_a_deferral_at_the_second_prompt_launches_the_first_alone(make,
                                                                  nano):
    """A pool that holds the first prompt's pages and not the second's:
    the group closes at the request that cannot get pages, which stays
    at the queue's head; the first still launches, as the single
    program."""
    eng = make(max_len=32, n_pages=4, prefix_cache=False)
    calls = _spy(eng)
    a, b = _prompt(nano, 20, 1), _prompt(nano, 20, 2)
    eng.submit(a, 4)
    eng.submit(b, 4)
    _boundary(eng)
    assert [(shape, ln) for shape, ln, _ in calls] == [((32,), ())]
    st = eng.stats()
    assert (st["prefills"], st["prefill_launches"],
            st["admissions_deferred"]) == (1, 1, 1)
    assert [r.prompt.tolist() for r in eng._pending] == [b.tolist()]
    assert eng._state[0] is not None and eng._state[1] is None
    assert eng._pool.available() == 1


def test_a_stale_driver_hands_back_every_prompts_pages(make, nano):
    """The supervisor restarts past a driver whose group launch is
    stuck on the device: the result is dropped, and the pages, the
    pinned shared pages and the pinned COW source of EVERY prompt of the
    group go back to the pool snapshot they came from; the queue is the
    new driver's and is not popped."""
    eng = make()
    base = _prompt(nano, 12, 9)            # 1 whole page + 4 tokens
    eng.submit(base, 1)                    # done at its first token:
    _boundary(eng)                         # the cache keeps its pages
    assert eng.stats()["completed"] == 1
    free0 = eng._pool.available()
    refs0 = list(eng._pool.refs)
    cache0 = eng._cache
    real = eng._prefill

    def stuck(*args):
        out = real(*args)
        eng._epoch += 1                     # the restart, mid-launch
        return out

    eng._prefill = stuck
    epoch = eng._epoch
    hit = np.concatenate([base, _prompt(nano, 10, 3)])   # COW fork
    eng.submit(_prompt(nano, 20, 4), 4)
    eng.submit(hit, 4)
    a = eng.stats()
    _boundary(eng, epoch)
    assert eng._pool.available() == free0
    assert eng._pool.refs == refs0
    assert len(eng._pending) == 2
    assert all(s is None for s in eng._state)
    b = eng.stats()
    assert (b["prefills"], b["prefill_launches"], b["cow_copies"]) == (
        a["prefills"], a["prefill_launches"], a["cow_copies"])
    assert eng._cache is cache0 or eng._cache is not None


def _streams_started(eng, lanes):
    eng.start()
    return _streams(eng, lanes)


def test_a_launchs_time_is_counted_once_and_its_prompts_each(make, nano):
    """``prefill_ns_sum`` grows by a launch's span ONCE and ``prefills``
    by its prompts (so their ratio is the lanes' wait a prompt);
    ``prefill_launches`` counts launches; both requests carry the
    launch's stamps; the phase and the request's span carry the group's
    size."""
    eng = make()
    tracing.drain()
    tracing.enable()
    try:
        ctx = {"trace_id": "ab" * 16, "span_id": "cd" * 8}
        eng.submit(_prompt(nano, 9, 1), 4, trace_ctx=ctx)
        eng.submit(_prompt(nano, 20, 2), 4)
        reqs = []
        real = eng._enter_steady_state

        def tail(req, *rest):
            reqs.append(req)
            return real(req, *rest)

        eng._enter_steady_state = tail
        _boundary(eng)
        spans = tracing.local_spans()
    finally:
        tracing.disable()
        tracing.drain()
    st = eng.stats()
    assert (st["prefills"], st["prefill_launches"]) == (2, 1)
    assert len(reqs) == 2
    assert len({(r.granted_ns, r.first_ns) for r in reqs}) == 1
    assert st["prefill_ns_sum"] == reqs[0].first_ns - reqs[0].granted_ns > 0
    assert st["prefill_tokens_sum"] == 29
    assert st["dispatches_per_token"] == 1 / 2      # one launch, 2 tokens
    assert st["driver_ns_prefill"] >= st["prefill_ns_sum"]
    mine = [s for s in spans if s["name"] == "engine.prefill"
            and s["trace_id"] == ctx["trace_id"]]
    assert [s["attrs"]["group"] for s in mine] == [2]
    assert mine[0]["attrs"]["bucket"] == 16       # the request's own
    drv = [s for s in spans if s["name"] == "engine.prefill"
           and s["kind"] == "driver"]
    assert [(s["attrs"]["group"], s["attrs"]["bucket"]) for s in drv] == [
        (2, 32)]                                  # the widest of the two


def test_a_prompt_that_would_hit_its_groupmates_pages_goes_next(make,
                                                                nano):
    """Two queued prompts that share two pages nobody has cached yet:
    grouped, the second's lookup would miss what the first is about to
    write. It closes the group, goes in the next launch of the same
    boundary and hits, as it does behind the first alone. A pair that
    shares only what the cache already holds stays a group."""
    eng = make()
    calls = _spy(eng)
    shared = _prompt(nano, 16, 5)
    a = np.concatenate([shared, _prompt(nano, 4, 6)])
    b = np.concatenate([shared, _prompt(nano, 7, 7)])
    want = _reference(make, [a, b], 6)
    lanes = [eng.submit(p, 6, seed=i) for i, p in enumerate((a, b))]
    _boundary(eng)
    assert [(shape, ln) for shape, ln, _ in calls] == [
        ((32,), ()), ((8,), ())]          # 23 tokens, 16 of them a hit
    st = eng.stats()
    assert (st["prefix_hits"], st["prefix_tokens_reused"]) == (1, 16)
    # now the cache holds ``shared``: two more prompts behind it share
    # nothing that is not there, and share a launch
    c = np.concatenate([shared, _prompt(nano, 9, 8)])
    d = np.concatenate([shared, _prompt(nano, 12, 9)])
    del calls[:]
    for p in (c, d):
        eng.submit(p, 2)
    _boundary(eng)
    assert [(shape, ln) for shape, ln, _ in calls] == [((16, 16), (2,))]
    assert eng.stats()["prefix_hits"] == 3
    got = _streams_started(eng, lanes)
    assert [g.tolist() for g in got] == [w.tolist() for w in want]


@pytest.mark.parametrize("case", [
    ((8, 3), (8, 11), 8, 3, True),        # common 8 of two longer: a page
    ((8, 3), (8, 11), 8, 8, False),       # ... the mate maps it already
    ((5, 3), (5, 11), 8, 0, False),       # short of a page boundary
    ((12,), (12,), 8, 0, True),           # identical: the exact entry
    ((12,), (12,), 8, 11, False),         # ... all but a token cached
    ((16,), (16, 4), 8, 8, True),         # the mate's whole second page
])
def test_shares_pages(nano, case):
    from ray_tpu.serve.engine import _shares_pages

    parts_a, parts_b, ps, hist, want = case
    common = _prompt(nano, parts_a[0], 1)
    assert parts_b[0] == parts_a[0]
    a = np.concatenate([common] + [_prompt(nano, n, 2) for n in parts_a[1:]])
    b = np.concatenate([common] + [_prompt(nano, n, 3) for n in parts_b[1:]])
    assert _shares_pages(a, b, hist, ps) is want


def test_who_goes_alone(make, nano, nano_params):
    """An export (a prefill-role handoff) and a prompt the drafter
    prefills too keep the single path: one launch each, scalar
    operands."""
    eng = make(spec_decode="ngram", draft_k=2)
    calls = _spy(eng)
    for i in range(3):
        eng.submit(_prompt(nano, 10 + i, i), 4)
    _boundary(eng)
    assert [(shape, ln) for shape, ln, _ in calls] == [((16,), ())] * 3
    st = eng.stats()
    assert st["prefills"] == st["prefill_launches"] == 3

    pre = make(role="prefill")
    calls = _spy(pre)
    import threading

    out = []
    threads = [threading.Thread(
        target=lambda i=i: out.append(pre.handoff(_prompt(nano, 12, i), 4)))
        for i in range(2)]
    for t in threads:
        t.start()
    while pre.queue_depth() < 2:
        pass
    _boundary(pre)
    for t in threads:
        t.join()
    assert [(shape, ln) for shape, ln, _ in calls] == [((16,), ())] * 2
    assert len(out) == 2 and pre.stats()["handoff"]["exported"] == 2


def test_which_buckets_share_a_launch(make, nano, monkeypatch):
    """``PREFILL_GROUP_ROWS`` and ``PREFILL_GROUP_BUCKETS``: the widest
    two of the buckets that, ``PREFILL_GROUP`` times over, stay within
    the rows group; a prompt whose suffix takes another goes alone,
    first or second in the queue; only the pairs that group are
    warmed."""
    from ray_tpu.serve import engine as E

    assert E.PREFILL_GROUP * 512 <= E.PREFILL_GROUP_ROWS < \
        E.PREFILL_GROUP * 2048
    assert E.PREFILL_GROUP_BUCKETS == 2
    eng = make(slots=7, prompt_buckets=(4, 8, 16, 32))
    assert [b for b in eng.prompt_buckets if eng._groups(b)] == [16, 32]
    monkeypatch.setattr(E, "PREFILL_GROUP_ROWS", 32)     # 32 is too wide
    assert [b for b in eng.prompt_buckets if eng._groups(b)] == [8, 16]
    calls = _spy(eng)
    for i, n in enumerate((20, 5, 9, 20, 3, 7, 6)):
        eng.submit(_prompt(nano, n, i), 3)
    _boundary(eng)
    assert [(shape, ln) for shape, ln, _ in calls] == [
        ((32,), ()), ((16, 8), (2,)), ((32,), ()), ((4,), ()),
        ((8, 8), (2,))]
    warm = make(auto_start=True, page_size=2)
    assert {n for n in warm.warm_up()["programs"] if "+" in n} == {
        "prefill_8+8", "prefill_16+8", "prefill_16+16"}


def test_warm_up_compiles_the_group_program_a_bucket(make, nano):
    """``warm_up()`` runs every bucket's program and every pair of
    buckets'; the traffic behind it, alone or by the group, compiles
    nothing."""
    # a page size no other engine of this process has: the jit wrappers
    # are shared by their knobs, and this one's cache counts this pool's
    eng = make(auto_start=True, slots=3, prompt_buckets=(8, 16),
               page_size=32)            # two buckets: both group
    report = eng.warm_up()
    assert set(report["programs"]) == {
        "prefill_8", "prefill_16", "prefill_8+8", "prefill_16+8",
        "prefill_16+16", "chunk"}
    assert eng._prefill._cache_size() == 5
    a = eng.stats()
    assert all(s is None for s in eng._state)
    lanes = [eng.submit(_prompt(nano, n, n), 5) for n in (3, 9, 14, 6, 16)]
    _streams(eng, lanes)
    b = eng.stats()
    assert eng._prefill._cache_size() == 5
    assert b["compiles"] == a["compiles"]
    assert b["prefills"] - a["prefills"] == 5
    # a drafter's engine has no group to warm
    spec = make(auto_start=True, spec_decode="ngram", draft_k=2,
                prompt_buckets=(8, 16))
    assert not any("+" in name for name in spec.warm_up()["programs"])


#: sha256 (16 hex) of ``jit_prefill_into_slot_paged(...).lower(...)
#: .as_text()`` for ONE prompt (``tokens`` ``[1, 8]``, scalar operands) at
#: ``nano``, page 4, 4 slots of 24 pages, on the CPU, as commit 56195a9
#: (the parent of ISSUE 54) lowers it: the lone prompt's program is the
#: one it was before there were groups. Whoever changes a prefill's
#: arithmetic on purpose reads the new values off this test's failure.
PARENT_TEXT = {
    ("gpt", "fp"): "631a6f1c7deb4552",
    ("gpt", "int8"): "7e9dbf2364841ccf",
    ("mla_moe", "fp"): "c643aca1dc411603",
    ("scmoe", "fp"): "aad294ac66cca770",
    ("kda_moe", "fp"): "92ae57f1de5ced9c",
    ("ssm_hybrid", "fp"): "31a6d34e04b5d0fb",
}


@pytest.mark.parametrize("name,kv", list(PARENT_TEXT))
def test_a_lone_prompt_lowers_to_the_parents_text(name, kv):
    import jax

    from ray_tpu.models import (gpt, gpt_decode, kda_moe, mla_moe, scmoe,
                                ssm_hybrid)
    from ray_tpu.models.serving import PT_SENTINEL

    desc = {"gpt": gpt_decode, "mla_moe": mla_moe, "scmoe": scmoe,
            "kda_moe": kda_moe, "ssm_hybrid": ssm_hybrid}[name]
    cfg = (gpt if name == "gpt" else desc).CONFIGS["nano"]
    params = (gpt if name == "gpt" else desc).init_params(
        jax.random.PRNGKey(0), cfg)
    cache = desc.init_paged_cache(cfg, 4, 96, 4, kv)
    prog = desc.jit_prefill_into_slot_paged(cfg, 4, 0.0, kv)
    text = prog.lower(
        params, cache, np.zeros((1, 8), np.int32), np.int32(1), np.int32(0),
        np.full((24,), PT_SENTINEL, np.int32), np.int32(PT_SENTINEL),
        np.int32(0), jax.random.PRNGKey(0)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_TEXT[name, kv]
    # and the group's is another program under the same name
    group = prog.lower(
        params, cache, (np.zeros((1, 8), np.int32),) * 2,
        np.ones((2,), np.int32),
        np.zeros((2,), np.int32), np.full((2, 24), PT_SENTINEL, np.int32),
        np.full((2,), PT_SENTINEL, np.int32), np.arange(2, dtype=np.int32),
        np.zeros((2, 2), np.uint32)).as_text()
    assert group != text
    assert "jit_prefill_into_slot_paged" in group.splitlines()[0]
