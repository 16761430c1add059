"""Continuous-batching decode engine: one driver thread, one persistent
KV page pool (ISSUE 5 tentpole; paged since ISSUE 6, the only pool
since ISSUE 34).

``@serve.batch(stream=True)`` gang-schedules: a batch forms once, runs
its whole generation off a freshly allocated KV cache, and a request
arriving mid-generation waits for the NEXT batch (or spawns a competing
per-batch stream thread that contends for the one device). The engine
replaces gang scheduling with **slot scheduling** — the standard
continuous-batching design of production inference stacks, mapped onto
TPU-friendly static shapes:

- ONE long-lived pool of KV pages (``[L, n_pages, page_size, H, hd]``,
  :func:`~ray_tpu.models.gpt_decode.init_paged_cache`) allocated at
  construction. No per-request ``init_cache``; slots are recycled by
  re-prefilling in place.
- A single driver thread owns every device dispatch, so concurrent
  requests never contend for the device — request threads only enqueue
  (device-concurrency discipline per the TPU concurrency study in
  PAPERS.md).
- Admission happens at **chunk boundaries**:
  :func:`~ray_tpu.models.gpt_decode.prefill_into_slot_paged` writes
  the prompt's K/V into a free slot's pages (one compiled program per
  prompt bucket; the TRUE length is traced, so any length within a
  bucket shares the program) and the first sampled token streams out
  immediately — TTFT is one prefill dispatch away from admission, not
  one full gang generation. The head requests ONE boundary can admit
  (a free slot each, pages granted one after another in FIFO order,
  up to ``PREFILL_GROUP``) share one launch
  (``prefill_group_into_slots_paged`` of the model's description,
  under the same program name; each prompt in its own bucket, its
  rows end to end with the others'): every weight is read once for the
  group, an expert layer sees the rows of all its prompts, and the
  running lanes wait for one dispatch and one blocking read instead of
  one a prompt. What is per prompt stays per prompt (its attention and
  cached prefix, its recurrent state, its pages, its sample and key),
  so a request's tokens do not depend on who shared its launch. The
  first request that cannot get pages closes the group and defers; a
  handoff import, an export, a prompt the drafter prefills too, a
  prompt whose bucket is not among the ``PREFILL_GROUP_BUCKETS`` widest
  that keep a launch within ``PREFILL_GROUP_ROWS`` rows, and a prompt whose prefix lookup would hit pages a groupmate
  is about to write go alone. A lone prompt runs the single program
  unchanged.
- :func:`~ray_tpu.models.gpt_decode.decode_chunk_slots_paged` then
  decodes ALL active slots in one fused k-step dispatch; a slot frees
  the moment its lane samples EOS, exhausts ``max_new``, passes its
  deadline, or its consumer walks away — instead of riding out the
  batch.

Static-shape discipline: the compiled-program set is exactly
``len(prompt_buckets)`` prefill programs for one prompt, at most one
more for every pair of buckets a group can hold (each prompt in its
own bucket; the ``PREFILL_GROUP_BUCKETS`` widest that keep a launch
within ``PREFILL_GROUP_ROWS`` rows: three programs) + 1 chunk program,
bounded for ANY
admission pattern (see the recompile guard in
``tests/test_serve_engine.py``); ``warm_up()`` runs every one of them
before traffic.

Results stream back through the same :class:`~.batching._StreamLane`
queues the batched streaming path uses, so replicas, handles, and the
HTTP proxy need no new transport: ``engine.submit(...)`` returns a lane,
``engine.stream(...)`` an iterator of per-chunk ``np.int32[j]`` slices.
A launch's slices reach their lanes behind the NEXT decode enqueue
(``_flush_kept``): the consumers they wake run under the interpreter
lock while the driver is blocked on the device, not between two
launches; where no launch follows they go at once.

**KV pages + shared-prefix reuse** (ISSUE 6 tentpole): reserving
``max_len`` KV per slot up front would cap concurrency by the
WORST-CASE sequence even when every live request is short. The pool
holds fixed-size pages handed out by a host-side allocator; its default
budget (``n_pages=0``) is the bytes of ``slots * max_len`` fp
positions, so a default engine can hold every slot at full length:

- Each slot carries a page-table row (``[max_pages]`` int32, sentinel
  padded) that the device programs gather/scatter through — the table
  is traced DATA, so any mapping runs the same compiled programs.
- Pages are allocated **on advance**: a slot takes its next page only
  when ``pos`` is about to cross a page boundary (checked at chunk
  boundaries, where admission already happens). Out of pages is a
  *defined* backpressure path: admission defers (FIFO kept, and freed
  pages flow to parked lanes BEFORE new admissions) and a running slot
  parks out of the dispatch mask until a page frees — never a silent
  clamped write into someone else's page. If EVERY occupied slot is
  parked (allocation deadlock), the youngest lane is preempted **by
  recompute**: its pages free, its request requeues at the head, and on
  re-admission the deterministic per-request PRNG lane replays the
  exact same tokens with the already-delivered prefix suppressed — the
  consumer sees a stall, never an error or a duplicate token.
- A **prefix cache** (the default wherever the model's description can
  have one; ``prefix_cache=True`` for one that cannot, a model with
  per-slot state, raises its reason) hashes prompt
  prefixes at page granularity: a request whose prompt prefix is
  already resident maps the cached pages into its table (refcounted),
  prefills only the suffix, and — when the cached prefix ends mid-page
  — forks that one page copy-on-write inside the same prefill program.
  TTFT for a cached system prompt becomes a page-table copy plus a
  short-suffix prefill. Cache entries are evicted LRU when the
  allocator runs dry.

Streams are asserted token-identical to
:func:`~ray_tpu.models.gpt_decode.generate_chunked` (temp 0 AND seeded
temp > 0) in ``tests/test_serve_engine_paged.py``.

**Crash-safe streaming** (ISSUE 7 tentpole): the recompute-preemption
replay above generalizes ACROSS engines — a stream is fully determined
by (prompt, sampling knobs, seed, delivered-token count), so any engine
holding the same weights can reconstruct a lane killed elsewhere:
``submit(resume_from=n)`` replays the generation and suppresses the
first ``n`` tokens (on an engine whose prefix cache holds the
prompt, the replay prefill is near-free). The serve layers lean on it
three ways:

- the driver thread stamps a **heartbeat** per dispatch loop;
  :meth:`supervise` (called from the replica's ``check_health``)
  detects a dead or wedged driver, fails current lanes with the
  *retryable* :class:`EngineRestartError` (clients resume on another
  replica via ``resume_from``), and restarts the driver ONCE before
  reporting unhealthy — replica replacement is the escalation, not the
  first response;
- :meth:`drain` winds an engine down gracefully: admissions stop
  (``submit`` raises the retryable :class:`EngineShutdownError`, so the
  router re-picks), running lanes finish, stragglers fail retryably at
  the deadline;
- :meth:`inject_fault` arms the chaos harness (driver death / wedge /
  process kill at token N) driven by ``tests/test_serve_chaos.py`` and
  ``benchmarks/serve_gpt.py --chaos``.

**Speculative decoding** (ISSUE 9 tentpole, ``spec_decode=..``): the
chunk path above pays one full target forward per generated token —
decode stays memory-bandwidth-bound on weights/KV per token. With a
drafter configured (``spec_decode="ngram"`` / ``"model"`` / a
:class:`~.draft.Drafter` instance; ``draft_k`` proposals per round),
the driver interleaves **draft → verify** per chunk boundary instead:

- the drafter proposes ``draft_k`` tokens per active slot (host-side
  n-gram table, or a small GPT on its own slot pool — see
  :mod:`~.draft`);
- ONE batched target forward
  (:func:`~ray_tpu.models.gpt_decode.verify_chunk_slots_paged`)
  scores all ``draft_k + 1`` logit rows, computes each
  slot's accepted length with exact rejection sampling (greedy match
  at temperature 0; point-mass residual resampling above it — the
  committed stream is the target's own distribution for ANY drafter,
  and bitwise the greedy stream at temperature 0), samples the
  bonus/correction token, and rolls each slot's KV write cursor back
  past its rejected positions in-program;
- each slot advances by its OWN ``accepted + 1`` — the variable
  per-slot advance rides the same EOS/deadline/freeing/``resume_from``
  replay logic as the fixed-k path (replay tokens count DELIVERED
  tokens, so crash-resume stays token-identical through any acceptance
  pattern).

The compiled-program set grows by exactly ONE verify program per
``draft_k`` (``len(prompt_buckets) + 1 + 1`` with the n-gram drafter,
whose admissions never group);
accepted-token throughput multiplies by the mean committed tokens per
verify forward (``1 + mean_accept_len``) while the per-forward cost
stays one weight sweep. Wired through the config plane as
``@serve.batch(continuous=True, spec_decode=.., draft_k=..)`` and the
deployment schema's ``engine:`` block; A/B'd in
``benchmarks/serve_gpt.py --spec``.

**Disaggregated prefill/decode** (ISSUE 14 tentpole, ``role=..``):
prefill is compute-bound and bursty, decode bandwidth-bound and steady
— colocated they fight for the one driver dispatch slot and prefill
bursts inflate decode TPOT. ``role="prefill"`` turns an engine into a
prefill-only front: :meth:`handoff` runs the prompt into a transient
slot, samples the first token, EXPORTS the slot's K/V into a contiguous
ship buffer (:func:`~ray_tpu.models.gpt_decode.export_slot_kv_paged`;
trimmed to the true prompt length so the bytes are identical whichever
page layout produced them), frees the slot
immediately — no slot-pool steady state — and returns a descriptor
under an epoch-stamped **lease** (:mod:`~.handoff`). ``role="decode"``
engines own the slot pools: :meth:`admit_prefilled` resolves the
descriptor (inline or an object-plane chunked pull), BYTE-VERIFIES the
shipped pages against the stamped digest, and imports them into a free
slot's pages
(:func:`~ray_tpu.models.gpt_decode.import_slot_kv_paged`), so the first
decode chunk continues bit-exactly where the prefill engine stopped. Every failure mode degrades to a cheap re-prefill, never a
broken stream: a missing/corrupt payload falls back to a local prefill
from the descriptor's prompt+seed (token-identical by determinism); a
decode side that never claims lets the lease expire, and the prefill
driver's sweep reclaims the shipped pages — a crash can never pin the
pool. The handoff plane adds exactly TWO compiled programs per engine
(export + import); ``role="both"`` (the default) serves all paths.
"""
from __future__ import annotations

import collections
import functools
import hashlib
import itertools
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from .._private import events as _events
from .._private.events import driver_emit as _driver_emit
from ..util import tracing
from .batching import (_STREAM_END, _EngineStream, _StreamLane,
                       default_buckets)
from .request import (RequestDeadlineExceeded, deadline_expired,
                      get_request_id)


#: The driver loop's phases (``stats()["driver_ns_<phase>"]``): blocked
#: on the empty queue; admission (prefix lookup, page allocation and
#: eviction, lease sweep); prefill (dispatch to the first token's read,
#: every flavour); page coverage (park / preempt); decode (chunk or
#: verify dispatch to the tokens' read); delivery (per-slot routing, EOS
#: trimming, frees); and the rest of the loop.
_PHASES = ("idle", "admit", "prefill", "cover", "decode", "deliver",
           "other")
#: The counted parts of a phase (``stats()["driver_ns_<phase>_<step>"]``,
#: inside the phase's self time): a prefill's request key, the call into
#: the compiled program and the first token's read; a decode (or verify)
#: launch's call into the compiled program from the phase's start to its
#: return, the previous launch's tokens handed to their lanes behind it
#: (``flush``: the consumers they wake run while the device does), the
#: wait for the device, and the results' read.
_STEPS = ("prefill.key", "prefill.dispatch", "prefill.read",
          "decode.enqueue", "decode.flush", "decode.wait", "decode.read")
#: The phases in which the driver holds no dispatch, so that wall minus
#: CPU time (``stats()["driver_cpu_ns_<phase>"]``) is the thread waiting
#: for the interpreter lock or a processor, not for the device.
_HOST_PHASES = ("admit", "cover", "deliver", "other")
#: How many launch records the engine keeps (``launch_log()``): a 40 s
#: window of 54 ms launches and its drain; many times the window a
#: stall is judged by.
LAUNCH_RING = 2048
#: A launch is a STALL where its period (``gap`` + ``phase``: from the
#: previous launch's read to its own) outside what its prompts usually
#: take exceeds this many times the median of that among the records
#: LIKE it (``_stall``) of the ``LAUNCH_STALL_WINDOW`` before it. A
#: launch period moves by 1.6x with the host's work and a collection in
#: its gap, a standstill by 5-300x; until half the window is like it
#: (a driver run's first launches, a ramp) a launch classifies nothing.
LAUNCH_STALL_FACTOR = 4
LAUNCH_STALL_WINDOW = 32
#: One launch record (``launch_log()``, the ``engine.dispatch`` and
#: ``engine.stall`` events): ints, every time ``time.monotonic_ns()``.
#: ``t0``: the ``decode`` phase's start, ``phase`` its length. ``gap``:
#: since the previous launch's tokens were read, if a lane stayed
#: occupied (else 0), and what the ``prefill``, ``idle`` and ``deliver``
#: phases took of it (the previous launch's state pass and whatever was
#: handed over with no launch to ride behind); the rest of it is the
#: host's (admission, coverage, the loop). ``prompts`` /
#: ``prefill_launches``: admitted since the previous record closed. The
#: four counted steps of THIS launch. ``deliver``: the state pass after
#: it, where the record closes. ``hold``: how long the messages its
#: ``flush`` handed over had been kept (0: nothing was kept). ``gc`` /
#: ``cpu_proc``: collections of the process and CPU time of all its
#: threads over ``wall``, which runs from the previous record's close
#: (with no ``gap``: from ``t0``) to this one's, so that the records of
#: a busy engine tile its time. ``lanes`` live; ``kind`` chunk (0) or
#: verify (1).
LAUNCH_FIELDS = ("t0", "phase", "gap", "gap_prefill", "gap_idle",
                 "gap_deliver", "prompts", "prefill_launches", "enqueue",
                 "flush", "wait", "read", "deliver", "hold", "gc",
                 "cpu_proc", "wall", "lanes", "kind")
_LAUNCH_KINDS = ("chunk", "verify")
_F = {name: i for i, name in enumerate(LAUNCH_FIELDS)}
_PHASE, _GAP, _DELIVER = _F["phase"], _F["gap"], _F["deliver"]
_GAP_PREFILL, _PROMPTS, _LANES = _F["gap_prefill"], _F["prompts"], _F["lanes"]
#: Where a stalled launch's excess fell
#: (``stats()["launch_stall_ns_<part>"]``): the parts of its period.
_STALL_PARTS = ("gap_prefill", "gap_host", "gap_idle", "enqueue", "flush",
                "wait", "read", "deliver")


class _Tables(NamedTuple):
    """The driver's tables at one moment (``at``, monotonic ns): what a
    launch record's differences are taken from. The phases ``prefill``,
    ``idle`` and ``deliver``; the four steps of ``decode``; the prompts
    and prefill launches admitted; the process's collections and its
    CPU time."""
    at: int
    prefill: int
    idle: int
    deliver: int
    enqueue: int
    flush: int
    wait: int
    read: int
    prompts: int
    prefill_launches: int
    gc: int
    cpu: int


def _launch_dict(rec: tuple) -> dict:
    out = dict(zip(LAUNCH_FIELDS, rec))
    out["kind"] = _LAUNCH_KINDS[out["kind"]]
    out["period"] = out["gap"] + out["phase"]
    return out


def _stall_parts(rec: tuple) -> tuple:
    """A record's period by ``_STALL_PARTS``: the gap's prefills, its
    host time outside the state pass, its idle time, the launch's four
    steps, and the state pass that lay in the gap."""
    g = rec[_GAP]
    pre, idle, dl = (rec[_GAP_PREFILL], rec[_F["gap_idle"]],
                     rec[_F["gap_deliver"]])
    return (pre, g - pre - idle - dl, idle, rec[_F["enqueue"]],
            rec[_F["flush"]], rec[_F["wait"]], rec[_F["read"]], dl)


def _stall(rec: tuple, recent) -> Optional[Tuple[int, int, tuple]]:
    """Whether the launch ``rec`` stalled, judged by ``recent``, the
    records of its driver run before it (``LAUNCH_STALL_WINDOW`` at
    most): None, or its excess, the usual period it is measured from,
    and the excess by ``_STALL_PARTS``. Two things that are work and no
    standstill are kept out. A launch is judged only by records LIKE
    it, of half to twice its lanes (a full engine's launch is not
    stalled for being longer than a lone lane's), and only where they
    are half the window or more. And the prefills in its gap count only
    for what they took beyond their prompts' due, the median time a
    prompt among those records (no prompt among them: not judged), so a
    burst of admissions in one gap counts as none and a prefill that
    stood still does. The usual period is the median, among the like,
    of the period outside prefills."""
    lanes = rec[_LANES]
    like = [r for r in recent
            if r[_LANES] <= 2 * lanes and lanes <= 2 * r[_LANES]]
    if 2 * len(like) < LAUNCH_STALL_WINDOW:
        return None
    mid, pre = len(like) // 2, rec[_GAP_PREFILL]
    each = sorted(r[_GAP_PREFILL] // r[_PROMPTS] for r in like
                  if r[_PROMPTS]) if pre else ()
    over = max(pre - rec[_PROMPTS] * each[len(each) // 2], 0) if each else 0
    usual = sorted(r[_GAP] - r[_GAP_PREFILL] + r[_PHASE] for r in like)[mid]
    judged = rec[_GAP] - pre + rec[_PHASE] + over
    if judged <= LAUNCH_STALL_FACTOR * usual:
        return None
    usual_parts = (sorted(col)[mid] for col in zip(*map(_stall_parts, like)))
    parts = [max(v - u, 0) for v, u in zip(_stall_parts(rec), usual_parts)]
    parts[0] = over
    return judged - usual, usual, tuple(parts)


def default_prompt_buckets(max_len: int) -> List[int]:
    """Powers of two from 8 up to (and including) max_len."""
    return sorted(b for b in default_buckets(max_len) if b >= 8) \
        or [max_len]


def _node_id():
    """This process's node id (handoff locality hint), or None outside
    a running runtime."""
    try:
        from ..core.worker import CoreWorker

        core = CoreWorker._current
        return getattr(core, "node_id", None) if core is not None \
            else None
    except Exception:  # noqa: BLE001 - no runtime in this process
        return None


@dataclass
class _EngineRequest:
    """One queued admission: everything the driver needs to prefill a
    slot and route its stream."""

    prompt: np.ndarray            # [S] int32
    max_new: int
    lane: _StreamLane
    deadline_s: Optional[float]
    trace_ctx: Optional[dict]
    seed: int
    #: ``time.monotonic_ns()`` when the request was queued (reset when
    #: a preemption requeues it), when a slot and its pages were
    #: granted, and when the first token reached the host. The engine
    #: sums their differences into ``stats()``; a traced request gets
    #: them as the ``engine.admission`` and ``engine.prefill`` spans.
    enq_ns: int
    granted_ns: int = 0
    first_ns: int = 0
    #: Tokens already delivered before a recompute preemption: the
    #: replay regenerates them (identical — the per-request PRNG lane
    #: is deterministic) and suppresses this many from the stream.
    skip: int = 0
    #: Prefill-role handoff export: admit = prefill + export + free,
    #: the lane receives ONE item (the handoff descriptor), no slot
    #: steady state.
    export: bool = False
    #: Decode-role import: a verified handoff payload whose K/V is
    #: scattered into the slot instead of prefilling. Kept on the
    #: request so a recompute preemption re-imports (cheaper than a
    #: re-prefill, identical by construction).
    handoff: Optional[dict] = None
    #: Export-side lease TTL override (0 = the engine's default).
    ttl_s: float = 0.0
    #: Flight-recorder correlation id: the router-stamped request id
    #: read from the replica's contextvar at submit time (falls back to
    #: a local ``eng-<n>`` id for bare in-process engine use), stamped
    #: on every event this request's slot produces.
    req_id: str = ""


@dataclass
class _Slot:
    """Host-side state of one occupied slot between chunk boundaries."""

    lane: _StreamLane
    remaining: int                # tokens still owed to the caller
    deadline_s: Optional[float]
    trace_ctx: Optional[dict]
    req: Optional[_EngineRequest] = None   # for recompute preemption
    emitted: int = 1              # tokens DELIVERED to the lane
    admitted_t: float = field(default_factory=time.time)
    # -------- page bookkeeping
    pos: int = 0                  # virtual write position (mirrors device)
    pages: List[int] = field(default_factory=list)
    parked: bool = False          # out of pages: excluded from dispatch
    skip: int = 0                 # replay tokens left to suppress


#: Prompts one prefill launch holds at most: the head requests a chunk
#: boundary can admit share ONE launch (``_admit_head``), so their
#: weights are read once and the lanes wait for one dispatch and one
#: blocking read. Two: the closed-loop cells free 1.9-2.3 slots a
#: launch (PERF.md section 5), and every size is one more program a
#: bucket to compile and keep. A lone prompt runs the single program.
PREFILL_GROUP = 2
#: Rows one group launch holds at most (prompts x their bucket): a
#: prompt whose bucket is wider goes alone. Past a thousand rows a
#: prefill is bound by its arithmetic whoever shares it, so a second
#: prompt saves no read of the weights worth having, while the launch's
#: temporaries (the scores are quadratic in the bucket) would outgrow
#: what the pool was sized beside: the widest SINGLE prompt's.
PREFILL_GROUP_ROWS = 1024
#: Buckets whose prompts may share a launch: the widest two of those
#: :data:`PREFILL_GROUP_ROWS` admits. A pair of buckets is a program of
#: its own (each prompt in its own bucket: nothing is padded to
#: another's), so one bucket more adds as many programs as there are
#: buckets below it, each traced, loaded and run once before a replica
#: reports ready (3 s apiece at seven layers of 7,168: PERF.md section
#: 6, PR 54), for the prompts whose launches are the shortest.
PREFILL_GROUP_BUCKETS = 2


@dataclass
class _Grant:
    """What the host half of a paged admission took for one request
    (``_grant_pages``), held until its launch is read: the slot, the
    prefix hit and its COW source (pinned), the pages (shared first),
    the suffix's bucket and the entries evicted for it."""

    req: _EngineRequest
    slot: int
    hist: int
    cow_src: int
    pages: List[int]
    bucket: int
    evicted: int


@functools.lru_cache(maxsize=1024)
def _request_key(seed: int) -> np.ndarray:
    """``jax.random.PRNGKey(seed)`` as the host array a prefill is
    handed. Making a key is a device dispatch of its own (1.2 ms of
    the driver's thread at every admission with the device idle:
    PERF.md section 6, PR 54); requests that share a seed (the default,
    0) share the one made first."""
    import jax

    key = np.asarray(jax.random.PRNGKey(seed))
    key.setflags(write=False)
    return key


def _shares_pages(prompt: np.ndarray, mate: np.ndarray, hist: int,
                  page_size: int) -> bool:
    """Whether ``prompt``'s prefix lookup would hit pages that ``mate``,
    granted but not yet prefilled, is about to write: the two share a
    prefix that reaches a page boundary (or all of both), and what a
    lookup would map of it (one token short of the prompt at most)
    reaches past what ``mate`` itself maps from the cache (``hist``):
    after ``mate``'s launch registers its pages the lookup finds what
    it cannot find now."""
    n = min(len(prompt), len(mate))
    diff = np.nonzero(prompt[:n] != mate[:n])[0]
    common = int(diff[0]) if len(diff) else n
    hit = common if common == len(prompt) == len(mate) \
        else common // page_size * page_size
    return min(hit, len(prompt) - 1) > hist


class EngineShutdownError(RuntimeError):
    """The engine stopped while this request was queued or decoding.

    Retryable: the request state (prompt, knobs, seed, delivered count)
    fully determines the stream, so the router re-picks another replica
    — mid-stream via ``resume_from`` replay — instead of surfacing a
    hard failure or marking the replica dead."""

    retryable = True


class EngineRestartError(EngineShutdownError):
    """The engine's driver thread died or wedged; its lanes were failed
    and the driver restarted (or is awaiting replica replacement).
    Retryable like :class:`EngineShutdownError` — resumed streams replay
    deterministically on whichever replica admits them next."""


class _PagePool:
    """Host-side page allocator: a free list plus per-page refcounts.
    Shared-prefix pages are mapped into several page tables at once (and
    pinned by prefix-cache entries); a page returns to the free list
    when its LAST reference drops. Driver-thread only — no locking."""

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self.refs = [0] * n_pages
        # Pop from the end → low page indices hand out first (stable
        # layouts in tests/benchmarks).
        self.free = list(range(n_pages - 1, -1, -1))

    def available(self) -> int:
        return len(self.free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh pages at refcount 1, or None (caller defers/parks)."""
        if n > len(self.free):
            return None
        out = [self.free.pop() for _ in range(n)]
        for p in out:
            self.refs[p] = 1
        return out

    def ref(self, pages: Sequence[int]):
        for p in pages:
            self.refs[p] += 1

    def unref(self, pages: Sequence[int]):
        for p in pages:
            self.refs[p] -= 1
            assert self.refs[p] >= 0, f"page {p} over-freed"
            if self.refs[p] == 0:
                self.free.append(p)


class _PrefixCache:
    """Prompt-prefix → resident-pages map, page-granular with an
    exact-length tail entry.

    Keys are content hashes of the token prefix at every page boundary
    plus the full prompt length; entries pin their pages with a pool
    reference so a cached prefix survives the lane that produced it.
    Lookup probes the query's page boundaries longest-first (plus its
    exact length), verifies tokens byte-for-byte (hashes only index),
    and returns ``(hist_len, pages)`` — ``hist_len`` capped one token
    short of the query so the suffix prefill always has a token to
    sample from. Page-aligned hits share pages directly; an exact-length
    hit ends mid-page and the engine forks that page copy-on-write.
    LRU: entries are evicted (unpinning their pages) when the allocator
    runs dry."""

    def __init__(self, pool: _PagePool, page_size: int):
        self._pool = pool
        self._ps = page_size
        # key -> (n_tokens, prefix_bytes, pages tuple)
        self._entries: "collections.OrderedDict[bytes, Tuple[int, bytes, Tuple[int, ...]]]" = \
            collections.OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self):
        return len(self._entries)

    @staticmethod
    def _key(tokens: np.ndarray, n: int) -> bytes:
        return hashlib.sha1(
            np.ascontiguousarray(tokens[:n]).tobytes()).digest()

    def lookup(self, tokens: np.ndarray) -> Tuple[int, List[int]]:
        """Longest cached prefix of ``tokens`` (< len(tokens)); returns
        ``(hist_len, pages_covering_hist)`` or ``(0, [])``."""
        P = len(tokens)
        probes = sorted({n for n in
                         list(range(self._ps, P + 1, self._ps)) + [P]},
                        reverse=True)
        for n in probes:
            ent = self._entries.get(self._key(tokens, n))
            if ent is None:
                continue
            n_cached, raw, pages = ent
            if n_cached != n or raw != tokens[:n].tobytes():
                continue                     # hash collision: skip
            hist = min(n, P - 1)
            if hist <= 0:
                continue
            self._entries.move_to_end(self._key(tokens, n))
            self.hits += 1
            n_cover = -(-hist // self._ps)   # ceil
            return hist, list(pages[:n_cover])
        self.misses += 1
        return 0, []

    def insert(self, tokens: np.ndarray, pages: Sequence[int]):
        """Register a freshly prefilled prompt's pages: one entry per
        covered page boundary plus the exact prompt length. Existing
        keys just refresh their LRU position."""
        P = len(tokens)
        bounds = list(range(self._ps, P + 1, self._ps))
        if P not in bounds:
            bounds.append(P)
        for n in bounds:
            key = self._key(tokens, n)
            if key in self._entries:
                self._entries.move_to_end(key)
                continue
            n_cover = -(-n // self._ps)
            ent_pages = tuple(pages[:n_cover])
            self._pool.ref(ent_pages)
            self._entries[key] = (n, tokens[:n].tobytes(), ent_pages)

    def evict_lru(self) -> bool:
        """Drop the oldest entry whose eviction actually FREES a page
        (some page at refcount 1 — held by the cache alone). False when
        no eviction can free anything: entries pinned by live lanes stay
        resident and keep serving hits rather than being wiped for an
        allocation that would fail anyway. Liveness: with no lane pins,
        a prompt's maximal entry holds its tail page exclusively, so a
        non-empty cache always has an evictable entry."""
        for key, (_n, _raw, pages) in self._entries.items():
            if any(self._pool.refs[p] == 1 for p in pages):
                del self._entries[key]
                self._pool.unref(pages)
                self.evictions += 1
                return True
        return False

    def clear(self):
        """Unpin and drop EVERY entry, shared or not (cache teardown —
        eviction's frees-a-page filter does not apply)."""
        while self._entries:
            _, (_n, _raw, pages) = self._entries.popitem(last=False)
            self._pool.unref(pages)
            self.evictions += 1


class DecodeEngine:
    """Slot-based continuous-batching engine for the chunked GPT decode
    path.

    Usage (inside a deployment; or see ``@serve.batch(continuous=True)``
    for the decorator form)::

        engine = DecodeEngine(params, cfg, slots=8, chunk=8,
                              max_len=256, eos_token=eos)
        for slice_ in engine.stream(prompt_ids, max_new=64):
            ...                       # np.int32 [j] per chunk, first j=1

    All device work runs on the engine's single driver thread;
    ``submit``/``stream`` only enqueue and are safe from any thread.
    At ``temperature == 0`` each stream is token-identical to
    :func:`~ray_tpu.models.gpt_decode.generate_chunked` for the same
    prompt (asserted in ``tests/test_serve_engine.py``).
    """

    def __init__(self, params, cfg, *, slots: int = 4, chunk: int = 8,
                 max_len: int = 0, temperature: float = 0.0,
                 eos_token: int = -1,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 deployment: str = "", auto_start: bool = True,
                 paged: bool = True, page_size: int = 16,
                 n_pages: int = 0, prefix_cache: Optional[bool] = None,
                 wedge_timeout_s: float = 30.0,
                 max_driver_restarts: int = 1,
                 spec_decode=None, draft_k: int = 4,
                 spec_threshold: float = 0.0,
                 role: str = "both", handoff_ttl_s: float = 30.0,
                 attn_kernel: str = "gather", kv_dtype: str = "fp",
                 tp: int = 1):
        from ..models import serving
        from .draft import make_drafter
        from .handoff import LeaseTable

        self.params = params
        self.cfg = cfg
        # The model's DESCRIPTION (models/serving.py): the paged
        # programs, the cache spec and what the model does not take
        # all come from the config object; no model module is bound
        # here by name.
        model = self._model = serving.decode_programs(cfg)
        self.slots = int(slots)
        self.chunk = int(chunk)
        self.max_len = int(max_len or model.max_positions(cfg))
        self.temperature = float(temperature)
        self.eos_token = int(eos_token)
        self.deployment = deployment or "engine"
        if self.slots < 1 or self.chunk < 1:
            raise ValueError("slots and chunk must be >= 1")
        if self.max_len > model.max_positions(cfg):
            raise ValueError(f"max_len {self.max_len} exceeds model "
                             f"max_seq {model.max_positions(cfg)}")
        buckets = sorted(set(int(b) for b in (
            prompt_buckets or default_prompt_buckets(self.max_len))))
        if not buckets or buckets[0] < 1:
            raise ValueError(f"invalid prompt_buckets {buckets}")
        if buckets[-1] > self.max_len:
            raise ValueError(
                f"largest prompt bucket {buckets[-1]} exceeds cache "
                f"length {self.max_len}")
        self.prompt_buckets = buckets
        # ---- disaggregation role (ISSUE 14): "prefill" engines only
        # export handoffs (no slot-pool steady state), "decode" engines
        # additionally import them; "both" serves every path. The lease
        # table exists for every role — ensure_role may flip a fresh
        # engine before traffic, and an empty table costs nothing.
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown engine role {role!r}; expected "
                             f"'prefill', 'decode', or 'both'")
        self._refuse("roles", role != "both", f"role={role!r}")
        self._refuse("spec_decode", spec_decode is not None,
                     f"spec_decode={spec_decode!r}")
        self._refuse("int8", kv_dtype == "int8", "kv_dtype='int8'")
        self._refuse("tp", int(tp) > 1, f"tp={tp}")
        self._refuse("prefix_cache", prefix_cache is True,
                     "prefix_cache=True")
        if prefix_cache is None:
            # the default follows what the model can have
            prefix_cache = "prefix_cache" not in model.UNSUPPORTED
        self.role = role
        self._leases = LeaseTable(ttl_s=float(handoff_ttl_s))
        # ---- speculative decoding (ISSUE 9): an optional drafter turns
        # the dispatch loop into draft -> verify; draft_k is the
        # chunk-static proposal width (one verify program per value).
        # spec_threshold > 0 enables POOL-WIDE adaptive speculation: a
        # boundary verifies only while the drafters' self-assessed mean
        # expected acceptance clears the threshold, else it runs ONE
        # plain chunk dispatch — all-or-nothing, because the chunk
        # program's cost is paid once for the whole pool, so a mixed
        # boundary would pay both programs and always lose. Pool-wide
        # decisions depend on pool COMPOSITION, which is only
        # replay-safe when sampling consumes no randomness — hence
        # greedy engines only (enforced below); temperature > 0 keeps
        # threshold 0 (always verify), whose per-slot PRNG chains are
        # independent of pool-mates.
        self.draft_k = int(draft_k)
        self.spec_threshold = float(spec_threshold)
        if self.draft_k < 1:
            raise ValueError("draft_k must be >= 1")
        if self.spec_threshold > 0.0 and self.temperature > 0.0:
            raise ValueError(
                "spec_threshold > 0 (adaptive speculation) requires "
                "temperature 0: the pool-wide verify-or-chunk decision "
                "depends on which lanes share the pool, and a sampled "
                "stream replayed on another pool would consume a "
                "different PRNG chain — breaking crash-resume replay")
        self._drafter = make_drafter(spec_decode, params, cfg)
        if self._drafter is not None:
            self._drafter.configure(
                slots=self.slots, max_len=self.max_len,
                prompt_buckets=self.prompt_buckets,
                draft_k=self.draft_k)
        # ---- paged-attention kernel + quantized KV (ISSUE 16): both
        # are ENGINE-STATIC knobs baked into the compiled programs at
        # pool build — never retrace triggers. Stored before _build_pool
        # (which reads them) and re-read verbatim on driver restart.
        if attn_kernel not in model.ATTN_KERNELS:
            raise ValueError(
                f"unknown attn_kernel {attn_kernel!r}; expected one of "
                f"{model.ATTN_KERNELS}")
        if kv_dtype not in model.KV_DTYPES:
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r}; expected one of "
                f"{model.KV_DTYPES}")
        if not paged:
            raise ValueError(
                "paged=False: the flat slot pool is gone; the engine "
                "has one KV pool, the page pool (page_size, n_pages)")
        self.attn_kernel = attn_kernel
        self.kv_dtype = kv_dtype
        # ---- tensor parallelism (ISSUE 20): ENGINE-STATIC mesh width.
        # tp=N shards weights over heads/ffn and the KV pool over the
        # head dim; validated eagerly so a bad (cfg, tp) pair fails at
        # construction, not first dispatch. _tp_mesh also raises when
        # fewer than N devices are visible — on CPU, force host devices
        # via XLA_FLAGS before importing jax.
        self.tp = int(tp)
        model.check_tp(cfg, self.tp)
        # Guards the put-vs-final-drain race: once _fail_all flips
        # _draining under this lock, no new submission can land in a
        # queue nobody will ever read again. Created BEFORE the pool so
        # every _build_pool call site can hold it (its holds= contract).
        self._admit_lock = threading.Lock()
        with self._admit_lock:
            self._build_pool(page_size, n_pages, prefix_cache)
        # Per-slot host state; index i mirrors pool row i. ``_token`` /
        # ``_rngs`` are the host copies uploaded with each dispatch
        # (tiny against the chunk compute; keeping them host-side avoids
        # per-admission scatter programs).
        self._state: List[Optional[_Slot]] = [None] * self.slots
        self._token = np.zeros((self.slots,), np.int32)
        self._rngs = np.zeros((self.slots, 2), np.uint32)
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        # Driver-local FIFO fed from the submit queue; the head defers
        # in place when admission runs out of pages, preserving
        # arrival order across the backpressure boundary.
        self._pending: "collections.deque[_EngineRequest]" = \
            collections.deque()
        self._draining = False
        self._fail_lock = threading.Lock()
        # What the last launch's state pass would have put on its lanes
        # (slices, ends, deadline errors), in order, until the next
        # program is enqueued: see _flush_kept. The driver appends; one
        # thread at a time hands over (the lock), so that a lane's
        # messages stay in order and a thread failing the lanes puts
        # its error BEHIND the kept slices.
        self._kept: "collections.deque[Tuple[_StreamLane, tuple]]" = \
            collections.deque()
        self._kept_lock = threading.Lock()
        self._stats_lock = threading.Lock()
        self._stats = {"admitted": 0, "completed": 0, "expired": 0,
                       "abandoned": 0, "prefills": 0,
                       "prefill_launches": 0, "dispatches": 0,
                       "tokens": 0, "occupancy_sum": 0.0,
                       "peak_active": 0, "prefix_hits": 0,
                       "prefix_tokens_reused": 0, "cow_copies": 0,
                       "admissions_deferred": 0, "lane_parks": 0,
                       "preempted": 0, "resumed": 0, "driver_restarts": 0,
                       "spec_rounds": 0, "spec_proposed": 0,
                       "spec_accepted": 0, "spec_fallback_rounds": 0,
                       "spec_lanes": 0,
                       "handoffs_exported": 0, "handoffs_imported": 0,
                       "handoff_import_fallbacks": 0,
                       "handoff_ship_bytes": 0,
                       "attn_kernel_dispatches": 0,
                       # messages handed to lanes by the deferred
                       # delivery, and those of them handed over with
                       # a device program in flight
                       "deliver_puts": 0, "deliver_puts_overlapped": 0,
                       # and how long each had been kept by then,
                       # summed (ns, over `deliver_puts`)
                       "deliver_hold_ns_sum": 0,
                       # request lifecycle, summed where it happens
                       # (monotonic ns): queued -> slot granted over
                       # `admitted`; slot granted -> first token on the
                       # host (a launch's span ONCE, whatever prompts
                       # it holds: `prefills` counts prompts,
                       # `prefill_launches` launches, so the sum over
                       # `prefills` is the lanes' wait a prompt) and the
                       # suffix tokens prefilled over `prefills`; and,
                       # before each decode/verify
                       # dispatch, the time since the previous one's
                       # tokens were read while a lane stayed occupied,
                       # and the part of it spent in prefill phases
                       "admission_wait_ns_sum": 0, "prefill_ns_sum": 0,
                       "prefill_tokens_sum": 0, "decode_gap_ns_sum": 0,
                       "decode_gap_prefill_ns_sum": 0,
                       # the stalls among the launch records,
                       # classified as each closed: their excess over
                       # the usual period, the wall their `gc` and
                       # `cpu_proc` cover, and those two
                       "launch_stalls": 0, "launch_stall_ns_sum": 0,
                       "launch_stall_wall_ns_sum": 0,
                       "launch_stall_gc_ns_sum": 0,
                       "launch_stall_cpu_ns_sum": 0}
        # the excess by where it fell: each part less its usual
        self._stats.update(dict.fromkeys(
            (f"launch_stall_ns_{p}" for p in _STALL_PARTS), 0))
        # Counters the model's chunk program returns with its tokens
        # (an expert layer's load), summed per dispatch.
        self._stats.update(dict.fromkeys(model.STEP_COUNTERS, 0))
        # What the driver thread is doing, by phase (self time, ns) and
        # by counted step of a phase, and the thread's CPU time in the
        # host's phases (``cpu.<phase>``): written by the driver alone
        # through its PhaseClock, read racily by stats(). Outlives
        # driver restarts.
        self._driver_ns = dict.fromkeys(
            _PHASES + ("total",) + _STEPS
            + tuple(f"cpu.{ph}" for ph in _HOST_PHASES), 0)
        self._phases: Optional[tracing.PhaseClock] = None
        # monotonic ns at which the last decode/verify dispatch's tokens
        # were read, while a lane has stayed occupied since; else None.
        self._decode_read_ns: Optional[int] = None
        self._compiles = tracing.compile_counts()
        self._gc = tracing.gc_counts()
        # One record a decode/verify launch (LAUNCH_FIELDS), appended by
        # the driver as the launch's state pass ends. Beside it what the
        # driver carries from one launch to the next: the tables as the
        # last record closed, what _note_decode_gap left at this
        # launch's start, this driver run's last records (a stall is
        # judged by them: _stall), and the read stamp of the launch
        # whose messages are kept.
        self._launches: "collections.deque[tuple]" = collections.deque(
            maxlen=LAUNCH_RING)
        self._launch_closed = self._tables(0)
        self._launch_open: tuple = ()
        self._recent: "collections.deque[tuple]" = collections.deque(
            maxlen=LAUNCH_STALL_WINDOW)
        self._kept_read_ns = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # ---- driver supervision (ISSUE 7): the driver stamps _beat at
        # every loop iteration; supervise() treats a stale beat from a
        # live thread as a wedge (stuck dispatch / stuck user fault) and
        # a dead thread as a crash. Each driver run gets an epoch — a
        # wedged thread that wakes after a restart finds the epoch moved
        # and drops its results instead of corrupting the new pool.
        self.wedge_timeout_s = float(wedge_timeout_s)
        self.max_driver_restarts = int(max_driver_restarts)
        self._beat = time.monotonic()
        self._epoch = 0
        self._shutdown = False
        self._supervise_lock = threading.Lock()
        #: Chaos-harness fault armed via inject_fault() (testing only).
        self._fault: Optional[dict] = None
        self._throttle_s = 0.0
        # Fallback flight-recorder ids for bare in-process submissions
        # (no router upstream to stamp the contextvar).
        self._req_uid = 0
        # A pending warm_up() request the driver picks up at its next
        # loop boundary, and the report of the last one that ran.
        self._warm: Optional[dict] = None
        self._warm_report: Optional[dict] = None
        if auto_start:
            self.start()

    # THE engine program budget (rtflow RT109, ISSUE 15): one prefill
    # program per prompt bucket (the true prompt length is traced, so
    # every length within a bucket shares its program), at most one
    # more per PAIR of buckets (the two prompts of one chunk boundary in
    # one launch, each in its own bucket, widest first: n (n + 1) / 2
    # of the n = PREFILL_GROUP_BUCKETS buckets that group, bounded here
    # by the square of all there are) + 1 fused chunk program +
    # the 2 KV-handoff programs (export + import). The verify program
    # is budgeted separately in _bind_verify. rtflow audits this bound
    # against every factory call and dispatch shape reachable from
    # here; the budget-vs-actual test pins it to the jit cache sizes on
    # nano CPU.
    # rtlint: program-budget: len(prompt_buckets) * len(prompt_buckets) + len(prompt_buckets) + 3
    def _build_pool(self, page_size: int, n_pages: int,
                    prefix_cache: bool):  # rtlint: holds=_admit_lock
        """Allocate THE persistent page pool and bind its jitted
        programs. Called once at construction, by
        :meth:`ensure_paging` on a never-used engine, and by
        :meth:`_restart_driver` — EVERY call site holds ``_admit_lock``
        (rtlint RT101 real finding: the restart path used to swap
        ``_pool``/``_prefix``/``_cache`` under only ``_fail_lock``,
        racing a concurrent ``ensure_paging`` config push)."""
        cfg = self.cfg
        # The dispatch-side weights: placed once per pool build (a
        # NamedSharding scatter when tp > 1, the raw host pytree when
        # tp == 1 — shard_params is an identity there). The drafter
        # keeps ``self.params``: it runs its own single-chip programs.
        self._params_dev = self._model.shard_params(
            self.params, cfg, self.tp)
        self.page_size = int(page_size)
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        self.max_pages = -(-self.max_len // self.page_size)   # ceil
        # Default budget: the KV **bytes** of [slots, max_len] fp
        # positions (every slot at full length), re-cut into pages of
        # the configured kv_dtype — an int8 pool's page is ~half the
        # bytes, so the same budget holds ~2x the pages (the ISSUE 16
        # sizing fix: counting pages in positions instead of bytes left
        # half an int8 engine's HBM budget unused).
        fp_bytes = self._model.kv_bytes_per_page(cfg, self.page_size)
        kv_bytes = self._model.kv_bytes_per_page(cfg, self.page_size,
                                                self.kv_dtype)
        self.n_pages = int(n_pages) or \
            (self.slots * self.max_pages * fp_bytes) // kv_bytes
        if self.n_pages < self.max_pages:
            raise ValueError(
                f"n_pages {self.n_pages} cannot hold one max_len "
                f"sequence ({self.max_pages} pages of {self.page_size})")
        self._pool = _PagePool(self.n_pages)
        self._prefix = _PrefixCache(self._pool, self.page_size) \
            if prefix_cache else None
        self._pt = np.full((self.slots, self.max_pages),
                           self._model.PT_SENTINEL, np.int32)
        self._prefill = self._model.jit_prefill_into_slot_paged(
            cfg, self.page_size, self.temperature, self.kv_dtype,
            self.tp)
        self._step = self._model.jit_decode_chunk_slots_paged(
            cfg, self.chunk, self.page_size, self.temperature,
            self.eos_token, self.kv_dtype, self.attn_kernel, self.tp)
        # Whether that program holds a fused attention kernel: the
        # description's word (models/serving.py).
        self._attn_fused = bool(self._model.decode_attention_fused(
            cfg, self.page_size, self.attn_kernel))
        self._export = self._import = None
        if "roles" not in self._model.UNSUPPORTED:
            self._export = self._model.jit_export_slot_kv_paged(
                cfg, self.page_size, self.kv_dtype, self.tp)
            self._import = self._model.jit_import_slot_kv_paged(
                cfg, self.page_size, self.kv_dtype, self.tp)
        self._cache = self._model.init_paged_cache(
            cfg, self.slots, self.n_pages, self.page_size,
            self.kv_dtype, self.tp)
        self._bind_verify()

    # rtlint: program-budget: 1
    def _bind_verify(self):  # rtlint: holds=_admit_lock
        """(Re)bind the verify program to the current pool layout and
        drafter — ONE compiled program per (pool shape, draft_k), or
        None with speculative decoding off. Called from
        :meth:`_build_pool` and :meth:`ensure_spec`, both of which hold
        ``_admit_lock``."""
        if self._drafter is None:
            self._verify = None
        else:
            self._verify = self._model.jit_verify_chunk_slots_paged(
                self.cfg, self.draft_k, self.page_size,
                self.temperature, self.kv_dtype, self.tp)

    def _refuse(self, capability: str, asked: bool, what: str):
        """Raise, with the model's own reason, where ``asked`` is for
        something the served model's description lists under
        ``UNSUPPORTED``."""
        reason = self._model.UNSUPPORTED.get(capability)
        if asked and reason is not None:
            raise ValueError(
                f"{type(self.cfg).__name__} cannot be served with "
                f"{what}: {reason}")

    def ensure_paging(self, page_size: Optional[int] = None,
                      prefix_cache: Optional[bool] = None,
                      n_pages: Optional[int] = None,
                      attn_kernel: Optional[str] = None,
                      kv_dtype: Optional[str] = None):
        """Idempotently apply paging knobs from the config plane
        (``@serve.batch(continuous=True, page_size=..)`` or the
        deployment schema's ``engine:`` block). A matching engine is a
        no-op; a mismatched engine is rebuilt IF it has never admitted a
        request, else this raises — pool shape is load-bearing state,
        not something to swap under live lanes. ``attn_kernel`` /
        ``kv_dtype`` follow the same discipline: they are baked into
        the pool's compiled programs (and, for ``kv_dtype``, its byte
        layout), so a mismatch triggers the same rebuild-if-unused
        path."""
        want_ps = int(page_size) if page_size is not None else None
        if want_ps is not None and want_ps < 1:
            raise ValueError("page_size must be >= 1")
        if attn_kernel is not None and \
                attn_kernel not in self._model.ATTN_KERNELS:
            raise ValueError(
                f"unknown attn_kernel {attn_kernel!r}; expected one of "
                f"{self._model.ATTN_KERNELS}")
        self._refuse("int8", kv_dtype == "int8", "kv_dtype='int8'")
        self._refuse("prefix_cache", prefix_cache is True,
                     "prefix_cache=True")
        if kv_dtype is not None and kv_dtype not in self._model.KV_DTYPES:
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r}; expected one of "
                f"{self._model.KV_DTYPES}")
        with self._admit_lock:
            knob_change = (
                (attn_kernel is not None and
                 attn_kernel != self.attn_kernel) or
                (kv_dtype is not None and kv_dtype != self.kv_dtype))
            if want_ps is None:
                want_ps = self.page_size   # as constructed
            need_rebuild = (
                want_ps != self.page_size or
                (n_pages is not None and int(n_pages) != self.n_pages) or
                knob_change)
            if need_rebuild:
                with self._stats_lock:
                    used = self._stats["admitted"]
                if used or self._queue.qsize() or self._pending or \
                        any(s is not None for s in self._state):
                    raise ValueError(
                        f"cannot repage a live engine (page_size="
                        f"{self.page_size} -> {want_ps}); construct it "
                        f"with the knobs or apply the config before "
                        f"traffic")
                if attn_kernel is not None:
                    self.attn_kernel = attn_kernel
                if kv_dtype is not None:
                    self.kv_dtype = kv_dtype
                self._build_pool(want_ps, int(n_pages or 0),
                                 prefix_cache if prefix_cache is not None
                                 else self._prefix is not None)
            elif prefix_cache is not None:
                if prefix_cache and self._prefix is None:
                    self._prefix = _PrefixCache(self._pool,
                                                self.page_size)
                elif not prefix_cache and self._prefix is not None:
                    self._prefix.clear()
                    self._prefix = None
        return self

    def ensure_spec(self, spec_decode=None, draft_k: Optional[int] = None,
                    spec_threshold: Optional[float] = None):
        """Idempotently apply the speculative-decoding knobs from the
        config plane (``@serve.batch(continuous=True, spec_decode=..)``
        or the deployment schema's ``engine:`` block). A matching
        engine is a no-op; a mismatched engine is reconfigured IF it
        has never admitted a request, else this raises — the drafter's
        per-slot state and the verify program are load-bearing, not
        something to swap under live lanes."""
        from .draft import make_drafter

        if draft_k is not None and int(draft_k) < 1:
            raise ValueError("draft_k must be >= 1")
        self._refuse("spec_decode", spec_decode is not None,
                     f"spec_decode={spec_decode!r}")
        with self._admit_lock:
            want_k = int(draft_k) if draft_k is not None else self.draft_k
            cur = self._drafter
            if spec_decode is None:
                want = cur
            elif isinstance(spec_decode, str) and cur is not None \
                    and cur.name == spec_decode:
                want = cur
            elif spec_decode is True and cur is not None:
                want = cur
            else:
                want = make_drafter(spec_decode, self.params, self.cfg)
            want_thr = float(spec_threshold) \
                if spec_threshold is not None else self.spec_threshold
            if want_thr > 0.0 and self.temperature > 0.0:
                raise ValueError(
                    "spec_threshold > 0 (adaptive speculation) "
                    "requires temperature 0 — see DecodeEngine")
            if want is cur and want_k == self.draft_k \
                    and want_thr == self.spec_threshold:
                return self
            with self._stats_lock:
                used = self._stats["admitted"]
            if used or self._queue.qsize() or self._pending or \
                    any(s is not None for s in self._state):
                raise ValueError(
                    "cannot change spec_decode/draft_k on a live "
                    "engine; construct it with the knobs or apply the "
                    "config before traffic")
            self.draft_k = want_k
            self.spec_threshold = want_thr
            self._drafter = want
            if want is not None:
                want.configure(slots=self.slots, max_len=self.max_len,
                               prompt_buckets=self.prompt_buckets,
                               draft_k=self.draft_k)
            self._bind_verify()
        return self

    def ensure_role(self, role: Optional[str] = None,
                    handoff_ttl_s: Optional[float] = None):
        """Idempotently apply the disaggregation knobs from the config
        plane (the deployment schema's ``engine: role:`` assignment —
        the controller stamps each replica's role when reconciling a
        ``roles:`` block). A matching engine is a no-op; a mismatched
        engine is re-roled IF it has never admitted or exported, else
        this raises — the role gates which queues exist, not something
        to flip under live lanes."""
        if role is not None and role not in ("both", "prefill",
                                             "decode"):
            raise ValueError(f"unknown engine role {role!r}")
        self._refuse("roles", role not in (None, "both"), f"role={role!r}")
        with self._admit_lock:
            if role is not None and role != self.role:
                with self._stats_lock:
                    used = self._stats["admitted"] \
                        + self._stats["handoffs_exported"]
                if used or self._queue.qsize() or self._pending or \
                        any(s is not None for s in self._state):
                    raise ValueError(
                        f"cannot change engine role ({self.role} -> "
                        f"{role}) on a live engine; construct it with "
                        f"the role or apply the config before traffic")
                self.role = role
            if handoff_ttl_s is not None:
                self._leases.ttl_s = float(handoff_ttl_s)
        return self

    def ensure_tp(self, tp: Optional[int] = None):
        """Idempotently apply the tensor-parallel width from the config
        plane (the deployment schema's ``engine: tp:`` knob). A
        matching engine is a no-op; a mismatched engine is rebuilt IF
        it has never admitted a request, else this raises — the mesh is
        baked into every compiled program AND the pool's device layout,
        so flipping it under live lanes would orphan the sharded
        cache."""
        if tp is None:
            return self
        want = int(tp)
        with self._admit_lock:
            if want == self.tp:
                return self
            with self._stats_lock:
                used = self._stats["admitted"]
            if used or self._queue.qsize() or self._pending or \
                    any(s is not None for s in self._state):
                raise ValueError(
                    f"cannot change tp ({self.tp} -> {want}) on a "
                    f"live engine; construct it with tp= or apply the "
                    f"config before traffic")
            # Validate (divisibility + visible devices) BEFORE mutating.
            self._model.check_tp(self.cfg, want)
            self.tp = want
            self._build_pool(self.page_size, self.n_pages,
                             self._prefix is not None)
        return self

    #: Config-plane knob split for :meth:`apply_config`.
    _PAGE_KEYS = ("page_size", "prefix_cache", "n_pages",
                  "attn_kernel", "kv_dtype")
    _SPEC_KEYS = ("spec_decode", "draft_k", "spec_threshold")
    _ROLE_KEYS = ("role", "handoff_ttl_s")
    _TP_KEYS = ("tp",)

    def apply_config(self, **knobs):
        """Route a deployment ``engine:`` config block to the right
        idempotent applier: paged-KV knobs to :meth:`ensure_paging`,
        speculative-decoding knobs to :meth:`ensure_spec`,
        disaggregation knobs to :meth:`ensure_role`. Unknown keys
        raise (the schema validates too — this guards direct callers).
        """
        known = set(self._PAGE_KEYS) | set(self._SPEC_KEYS) \
            | set(self._ROLE_KEYS) | set(self._TP_KEYS)
        unknown = set(knobs) - known
        if unknown:
            raise ValueError(
                f"unknown engine config keys {sorted(unknown)}; known: "
                f"{sorted(known)}")
        page = {k: v for k, v in knobs.items()
                if k in self._PAGE_KEYS and v is not None}
        spec = {k: v for k, v in knobs.items()
                if k in self._SPEC_KEYS and v is not None}
        rolek = {k: v for k, v in knobs.items()
                 if k in self._ROLE_KEYS and v is not None}
        tpk = {k: v for k, v in knobs.items()
               if k in self._TP_KEYS and v is not None}
        # tp first: a repage after the mesh flip lands on the already-
        # sharded pool, while the reverse would rebuild twice.
        if tpk:
            self.ensure_tp(**tpk)
        if page:
            self.ensure_paging(**page)
        if spec:
            self.ensure_spec(**spec)
        if rolek:
            self.ensure_role(**rolek)
        return self

    # ------------------------------------------------------------- admission
    def _validate_admission(self, prompt, max_new: int):
        """Shared admission-time validation for every entry point that
        prefills from a prompt (``submit`` and ``handoff``):
        canonicalize the prompt, check that a compile bucket holds it,
        and bound the generation against the cache. Returns the
        prompt."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        S = prompt.shape[0]
        if S < 1:
            raise ValueError("empty prompt")
        if S > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt length {S} exceeds largest prompt bucket "
                f"{self.prompt_buckets[-1]}")
        if S + max_new > self.max_len:
            raise ValueError(
                f"prompt ({S}) + max_new ({max_new}) exceeds cache "
                f"length {self.max_len}")
        return prompt

    def _new_req_id(self) -> str:
        """Flight-recorder correlation id for this admission: the
        router-stamped id when one rode the request context here, else
        a local ``eng-<pid>-<n>`` id so bare in-process streams still
        correlate across their own events."""
        rid = get_request_id()
        if rid:
            return rid
        with self._admit_lock:
            self._req_uid += 1
            return f"eng-{os.getpid():x}-{self._req_uid}"

    def submit(self, prompt, max_new: int, *,
               deadline_s: Optional[float] = None,
               trace_ctx: Optional[dict] = None,
               seed: int = 0, resume_from: int = 0) -> _StreamLane:
        """Enqueue one request; returns its stream lane immediately. The
        driver admits it at the next chunk boundary with a free slot.
        Safe from any thread.

        ``resume_from=n`` is the mid-stream failover replay token: the
        caller already holds the first ``n`` tokens of this exact
        (prompt, knobs, seed) stream — delivered by another replica
        before it died — so the engine replays the generation (the
        per-request PRNG lane is deterministic; the engine's prefix
        cache makes the prompt prefill near-free) and suppresses the
        first ``n`` tokens from the lane."""
        if self.role == "prefill":
            raise ValueError(
                "prefill-role engine only exports handoffs (use "
                "handoff()); decode streams need a decode-capable "
                "engine")
        prompt = self._validate_admission(prompt, max_new)
        resume_from = int(resume_from)
        if resume_from < 0 or resume_from > max_new:
            raise ValueError(
                f"resume_from {resume_from} outside [0, max_new="
                f"{max_new}] — the replay token counts tokens this "
                f"stream already delivered")
        lane = _StreamLane()
        if max_new <= 0:
            lane.q.put((_STREAM_END, None))
            return lane
        req_id = self._new_req_id()
        with self._admit_lock:
            # _draining (not thread-aliveness) is the admission gate: a
            # not-yet-started engine (auto_start=False) queues work for
            # start(), while a shut-down, draining, or crashed driver —
            # which flipped _draining in _fail_all — rejects (retryably:
            # the router re-picks) instead of accepting submissions
            # nobody will ever read.
            if self._draining:
                raise EngineShutdownError(
                    "engine is not accepting requests (draining or shut "
                    "down); resubmit on another replica")
            self._queue.put(_EngineRequest(
                prompt=prompt, max_new=int(max_new),
                lane=lane, deadline_s=deadline_s, trace_ctx=trace_ctx,
                seed=int(seed), enq_ns=time.monotonic_ns(), skip=resume_from,
                req_id=req_id))
        if resume_from:
            self._count(resumed=1)
            _events.emit("engine.resume", request=req_id,
                         resume_from=int(resume_from),
                         epoch=self._epoch)
        return lane

    def stream(self, prompt, max_new: int, **kw):
        """``submit`` + drain: an iterator of np.int32 ``[j]`` chunk
        slices (first slice is the prefill token alone). ``close()``
        marks the lane abandoned even before the first pull."""
        return _EngineStream(self.submit(prompt, max_new, **kw))

    # --------------------------------------------------- disaggregation
    def handoff(self, prompt, max_new: int, *, seed: int = 0,
                deadline_s: Optional[float] = None,
                trace_ctx: Optional[dict] = None,
                ttl_s: Optional[float] = None) -> dict:
        """Prefill ``prompt`` into a transient slot, sample the first
        token, EXPORT the slot's K/V, and return a leased handoff
        descriptor (ISSUE 14). The slot frees before this returns — a
        prefill-role engine never holds slot-pool steady state.

        The descriptor carries the lease (``lease_id``/``epoch``/
        ``expires_at``), the byte-verification ``digest``, the shipped
        payload (inline, or an object-plane ``ref`` the decode side
        pulls through the chunked-transfer path), and the full replay
        identity (``prompt``/``seed``/``max_new``) — so ANY
        decode-capable engine can either import the bytes or, if they
        are gone, re-prefill the identical stream from scratch.

        Blocks the calling thread until the driver exports (bounded by
        ``deadline_s``); safe from any thread."""
        if self.role == "decode":
            raise ValueError(
                "decode-role engine cannot export handoffs; use a "
                "prefill or both-role engine")
        prompt = self._validate_admission(prompt, max_new)
        if max_new < 1:
            raise ValueError("handoff needs max_new >= 1 (the first "
                             "token is sampled at prefill)")
        lane = _StreamLane()
        req_id = self._new_req_id()
        with self._admit_lock:
            if self._draining:
                raise EngineShutdownError(
                    "engine is not accepting requests (draining or "
                    "shut down); resubmit on another replica")
            self._queue.put(_EngineRequest(
                prompt=prompt, max_new=int(max_new),
                lane=lane, deadline_s=deadline_s, trace_ctx=trace_ctx,
                seed=int(seed), enq_ns=time.monotonic_ns(), export=True,
                ttl_s=float(ttl_s or 0.0), req_id=req_id))
        # Synchronous drain: ONE item (the descriptor), then END. The
        # wait is deadline-bounded so a wedged driver surfaces as the
        # deadline error instead of a hang.
        from .request import remaining_s
        while True:
            rem = remaining_s(deadline_s)
            try:
                kind, val = lane.q.get(
                    timeout=rem if rem is not None else 120.0)
            except queue.Empty:
                lane.closed = True
                raise RequestDeadlineExceeded(
                    "handoff export did not complete before the "
                    "request deadline") from None
            if kind == "err":
                raise val
            if kind is _STREAM_END:
                raise EngineShutdownError(
                    "handoff export lane closed without a descriptor")
            return val

    def claim_handoff(self, lease_id: str, epoch: int) -> bool:
        """Decode-side acknowledgement that a shipped payload was
        imported: releases the lease (and the pin on the shipped
        object) before its expiry. Unknown/stale leases return False —
        the sweep already reclaimed them, which is also fine: the
        claimer holds the bytes it needs. Safe from any thread."""
        ok = self._leases.claim(lease_id, int(epoch))
        _events.emit("handoff.claim", lease=lease_id,
                     epoch=int(epoch), released=ok)
        return ok

    def admit_prefilled(self, desc: dict, *,
                        deadline_s: Optional[float] = None,
                        trace_ctx: Optional[dict] = None,
                        resume_from: int = 0) -> _StreamLane:
        """Admit a handed-off stream: resolve the descriptor's payload
        (inline, or a chunked object-plane pull), BYTE-VERIFY it, and
        enqueue an import admission — the driver scatters the shipped
        K/V into a free slot/pages and decoding continues bit-exactly
        from the prefill engine's state. Any resolution or verification
        failure degrades to a LOCAL prefill of the descriptor's
        prompt+seed (token-identical by determinism), counted as a
        fallback. Returns the stream lane; safe from any thread."""
        from .handoff import HandoffError, resolve_payload, verify_payload
        from .request import remaining_s

        if self.role == "prefill":
            raise ValueError(
                "prefill-role engine cannot import handoffs; use a "
                "decode or both-role engine")
        prompt = np.asarray(desc["prompt"], np.int32).reshape(-1)
        max_new = int(desc["max_new"])
        seed = int(desc["seed"])
        resume_from = int(resume_from)
        payload = None
        try:
            rem = remaining_s(deadline_s)
            payload = resolve_payload(
                desc, timeout_s=min(rem, 30.0) if rem is not None
                else 30.0)
            # Cross-plane check FIRST: the descriptor's digest traveled
            # over the RPC plane, independently of the object-plane
            # payload — a stale or wrong payload that is internally
            # consistent would pass verify_payload alone.
            if desc.get("digest") and \
                    payload.get("digest") != desc["digest"]:
                raise HandoffError(
                    "shipped payload digest does not match the "
                    "descriptor's (stale or clobbered object)")
            verify_payload(payload)
            if int(payload["pos"]) + max_new > self.max_len:
                raise HandoffError(
                    f"shipped pos {payload['pos']} + max_new "
                    f"{max_new} exceeds cache length {self.max_len}")
            want = self._model.cache_spec(self.cfg, self.kv_dtype) \
                .token_shape("k", int(payload["pos"]))
            if tuple(payload["k"].shape) != want \
                    or tuple(payload["v"].shape) != want:
                raise HandoffError(
                    f"shipped KV shape {tuple(payload['k'].shape)} "
                    f"does not fit this engine's model ({want})")
            # Layout identity (ISSUE 16): quantized payloads only land
            # on an engine with the SAME kv_dtype and page_size — int8
            # codes are meaningless without their page-aligned scales,
            # and scales are page-granular. Any mismatch (int8->fp,
            # fp->int8, or a different page cut) degrades to the local
            # re-prefill, which is token-identical by determinism.
            ship_dt = payload.get("kv_dtype", "fp")
            if ship_dt != self.kv_dtype:
                raise HandoffError(
                    f"shipped kv_dtype {ship_dt!r} does not match this "
                    f"engine's ({self.kv_dtype!r})")
            if ship_dt == "int8" and \
                    int(payload.get("page_size", 0)) != self.page_size:
                raise HandoffError(
                    f"shipped page_size {payload.get('page_size')} "
                    f"does not match this engine's ({self.page_size})")
            # tp-layout identity (ISSUE 20): the handoff plane ships
            # CANONICAL host-order KV only — the exporter gathers its
            # mesh and the importer's jit scatters into its own, so an
            # N-way prefill feeds an M-way decode with no negotiation.
            # A payload stamped with any other layout came from a
            # foreign/newer protocol; its bytes would scatter wrong, so
            # it degrades to the counted local re-prefill below.
            ship_layout = payload.get("layout", "canonical")
            if ship_layout != "canonical":
                raise HandoffError(
                    f"shipped KV layout {ship_layout!r} is not the "
                    f"canonical host layout; refusing to scatter into "
                    f"a tp={self.tp} mesh")
        except HandoffError:
            payload = None
        if payload is None:
            # Degraded path: the bytes are gone or bad — re-prefill the
            # SAME deterministic stream locally. Counted so the A/B and
            # the chaos harness can see who paid what.
            self._count(handoff_import_fallbacks=1)
            from .._private.metrics import serve_metrics
            serve_metrics()["prefill_fallbacks"].inc(
                labels={"deployment": self.deployment,
                        "where": "engine"})
            return self.submit(prompt, max_new, seed=seed,
                               deadline_s=deadline_s,
                               trace_ctx=trace_ctx,
                               resume_from=resume_from)
        if resume_from < 0 or resume_from > max_new:
            raise ValueError(
                f"resume_from {resume_from} outside [0, max_new="
                f"{max_new}]")
        lane = _StreamLane()
        req_id = self._new_req_id()
        with self._admit_lock:
            if self._draining:
                raise EngineShutdownError(
                    "engine is not accepting requests (draining or "
                    "shut down); resubmit on another replica")
            self._queue.put(_EngineRequest(
                prompt=prompt, max_new=max_new,
                lane=lane, deadline_s=deadline_s, trace_ctx=trace_ctx,
                seed=seed, enq_ns=time.monotonic_ns(), skip=resume_from,
                handoff={"payload": payload,
                         "created_t": desc.get("created_t")},
                req_id=req_id))
        if resume_from:
            self._count(resumed=1)
        return lane

    def warm_up(self) -> dict:
        """Compile the engine's WHOLE program set now — every prompt
        bucket's prefill, the chunk program, the verify program when a
        drafter is bound, and the handoff program a disaggregated role
        uses — instead of leaving each to the first request that
        happens to need it. The replica calls this before it reports
        ready: a cold compile at real width then lands in start-up,
        where nothing times it, and never inside a supervised dispatch
        (the driver stamps its heartbeat only between dispatches, so a
        first-call compile would read as a wedge) or a client's
        time-to-first-token. A compiler error is raised HERE, to the
        caller, with the compiler's own message.

        Every program runs once on the driver thread with inputs that
        change nothing a later admission reads (no active lane, an
        all-sentinel page table); the per-program seconds are set-up
        time, kept in ``stats()["warm_up"]`` together with HOW the
        attention kernel was built — read off the lowered chunk
        program, not inferred from the platform. Blocks until done;
        call it before traffic."""
        req = {"done": threading.Event(), "error": None}
        self.start()
        self._warm = req
        req["done"].wait()
        if req["error"] is not None:
            raise req["error"]
        return self._warm_report

    # rtlint: owner=driver
    def _run_warm_up(self, req: dict):
        import jax

        gd = self._model
        secs = {}

        def timed(name, fn, *args):
            t0 = time.monotonic()
            # rtlint: sync-ok=warm-up start-up compile, before traffic
            out = jax.block_until_ready(fn(*args))
            secs[name] = round(time.monotonic() - t0, 3)
            return out

        try:
            key = _request_key(0)       # as an admission hands it over
            active = np.zeros((self.slots,), bool)
            # The programs' paging operands: no history, an
            # all-sentinel page table (every write drops), no COW.
            none = np.full((self.max_pages,), gd.PT_SENTINEL, np.int32)
            mid = (np.int32(0), none, np.int32(gd.PT_SENTINEL))
            for b in self.prompt_buckets:
                _, self._cache, _ = timed(
                    f"prefill_{b}", self._prefill, self._params_dev,
                    self._cache, np.zeros((1, b), np.int32), np.int32(1),
                    *mid, np.int32(0), key)
            # ... and the program of every pair of buckets a chunk
            # boundary can group (_admit_head; widest first, as
            # _prefill_paged hands a pair over). Its prompts land in no
            # page, but a model with per-slot state writes the slots it
            # is given: the first ones, distinct, which hold no lane
            # now and are rebuilt by the prefill that admits one.
            G = min(PREFILL_GROUP, self.slots)
            if G > 1 and self._drafter is None and self.role != "prefill":
                ones = np.ones((G,), np.int32)
                wide = sorted(filter(self._groups, self.prompt_buckets),
                              reverse=True)
                for bs in itertools.combinations_with_replacement(wide, G):
                    _, self._cache, _ = timed(
                        "prefill_" + "+".join(map(str, bs)), self._prefill,
                        self._params_dev, self._cache,
                        tuple(np.zeros((1, b), np.int32) for b in bs),
                        ones, 0 * ones, np.stack([none] * G),
                        ones * gd.PT_SENTINEL, np.arange(G, dtype=np.int32),
                        np.stack([key] * G))
            step_args = (self._params_dev, self._cache, self._token,
                         self._rngs, active, self._pt)
            mode = None
            if self._attn_fused:
                from .._private.chip import compiled_by_mosaic

                mode = "compiled" if compiled_by_mosaic(
                    self._step.lower(*step_args).as_text()) \
                    else "interpret"
            self._cache = timed("chunk", self._step, *step_args)[1]
            if self._verify is not None:
                _, _, self._cache, _ = timed(
                    "verify", self._verify, self._params_dev, self._cache,
                    self._token,
                    np.zeros((self.slots, self.draft_k), np.int32),
                    self._rngs, active, self._pt)
            if self.role == "prefill":
                timed("export", self._export, self._cache, none)
            self._warm_report = {"programs": secs,
                                 "total_s": round(sum(secs.values()), 3),
                                 "attn_kernel_mode": mode}
        except Exception as e:  # noqa: BLE001 - handed to warm_up()'s caller
            req["error"] = e
        finally:
            self._warm = None
            req["done"].set()

    def queue_depth(self) -> int:
        """Requests accepted but not yet admitted to a slot (submit
        queue + the driver's deferred FIFO). THE offline-pipeline
        throttle signal (ISSUE 11): a saturated pool wants this small
        but nonzero — zero risks an idle boundary, unbounded means the
        admission queue is absorbing the whole dataset. Exported as the
        ``serve_engine_queue_depth`` gauge once per driver loop.
        Safe from any thread (both reads are approximate by nature)."""
        return self._queue.qsize() + len(self._pending)

    # ------------------------------------------------------------- lifecycle
    def start(self):
        if self._thread is not None and self._thread.is_alive():
            return
        with self._admit_lock:
            self._draining = False
        self._shutdown = False
        self._stop = threading.Event()
        self._beat = time.monotonic()
        self._thread = threading.Thread(
            target=self._run, args=(self._stop, self._epoch),
            daemon=True, name=f"rt-serve-engine-{self.deployment}")
        self._thread.start()

    def shutdown(self, timeout_s: float = 5.0):
        """Stop the driver and fail EVERY queued or in-flight lane with
        :class:`EngineShutdownError` — unconditionally. The driver's own
        exit path fails lanes too, but only if it is alive to run it; a
        never-started driver (``auto_start=False``) or one that died at
        startup would otherwise leave queued submissions hanging
        forever, so the drain repeats here (idempotent: the queue is
        drained once, double error puts on a lane are inert)."""
        self._shutdown = True
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
        # A driver that outlived the join (stuck in a long dispatch /
        # first-call compile) still owns the slot structures and the
        # page pool: fail the lanes but leave the bookkeeping to its
        # own exit path, or freed pages would be double-unref'd.
        alive = self._thread is not None and self._thread.is_alive()
        self._fail_all(EngineShutdownError("engine shut down"),
                       free_state=not alive)

    def drain(self, timeout_s: float = 5.0) -> bool:
        """Graceful wind-down (replica teardown path): stop admissions
        NOW — ``submit`` raises the retryable
        :class:`EngineShutdownError`, so routers re-pick another replica
        — fail queued-but-unstarted requests the same way (they have no
        delivered state; the retry is a fresh stream), let RUNNING lanes
        finish, and fail stragglers retryably at the deadline (clients
        resume elsewhere via ``resume_from``). Returns True when every
        lane finished inside the budget. The driver keeps running — the
        caller tears the replica down afterwards."""
        with self._admit_lock:
            self._draining = True
        deadline = time.monotonic() + max(float(timeout_s), 0.0)
        # rtsan RS104 audit (ISSUE 13): a 10 ms poll, NOT a condition —
        # the state it watches (_state/_pending) is driver-thread-owned
        # by contract, so a condition here would need the driver to
        # notify under a lock it deliberately never takes on its hot
        # loop. Deadline-bounded, and no lock is held across the sleep.
        while time.monotonic() < deadline:
            if not any(s is not None for s in self._state) \
                    and not self._queue.qsize() and not self._pending:
                return True
            time.sleep(0.01)
        alive = self._thread is not None and self._thread.is_alive()
        self._fail_all(
            EngineShutdownError(
                f"engine drained with lanes still running after "
                f"{timeout_s:.1f}s; resubmit to resume"),
            free_state=not alive)
        return False

    def supervise(self) -> bool:
        """Driver health verdict, with a one-shot recovery: True while
        the driver is alive and beating (or deliberately stopped); on
        the FIRST death/wedge, fail current lanes with the retryable
        :class:`EngineRestartError` (clients resume on another replica),
        restart the driver, and still report True — the replica stays.
        A second death/wedge reports False, escalating to
        controller-driven replica replacement. Called from the replica's
        ``check_health``; safe from any thread."""
        with self._supervise_lock:
            t = self._thread
            if t is None or self._shutdown or self._warm is not None:
                # Never started (auto_start=False), deliberately shut
                # down, or compiling its program set on request: not a
                # health signal.
                return True
            alive = t.is_alive()
            beat_age = time.monotonic() - self._beat
            wedged = alive and beat_age > self.wedge_timeout_s
            if alive and not wedged:
                return True
            with self._stats_lock:
                restarts = self._stats["driver_restarts"]
            if restarts >= self.max_driver_restarts:
                return False
            self._restart_driver(
                f"driver wedged (no heartbeat for {beat_age:.1f}s)"
                if wedged else "driver thread died")
            return True

    def _restart_driver(self, reason: str):
        """Supervisor recovery: retire the current driver epoch (a
        wedged thread that later wakes drops its results at the epoch
        guards), fail its lanes retryably, rebuild EVERY pool structure
        fresh — the old thread may still hold the old ones mid-dispatch
        — and start a new driver."""
        exc = EngineRestartError(
            f"engine driver restarted ({reason}); resubmit to resume")
        old_stop = self._stop
        old_stop.set()            # the old thread exits when it wakes
        with self._fail_lock:
            # Lanes error retryably; state/pages are NOT freed into the
            # old structures (the wedged thread may still be touching
            # them) — the rebuild below replaces them wholesale.
            self._fail_all_locked(exc, free_state=False)
            self._epoch += 1
            # The rebuild holds _admit_lock too (lock order: fail →
            # admit, same as _fail_all_locked): ensure_paging reads and
            # swaps the pool structures under _admit_lock, and a config
            # push racing this restart must see either the old pool or
            # the new one — never a half-built mix.
            with self._admit_lock:
                self._build_pool(self.page_size, self.n_pages,
                                 self._prefix is not None)
                if self._drafter is not None:
                    # The pool was rebuilt from scratch and every lane
                    # failed; per-slot drafter state must follow.
                    self._drafter.reset()
                self._state = [None] * self.slots
                self._token = np.zeros((self.slots,), np.int32)
                self._rngs = np.zeros((self.slots, 2), np.uint32)
                self._pending = collections.deque()
                self._queue = queue.SimpleQueue()
        self._count(driver_restarts=1)
        from .._private.metrics import serve_metrics
        serve_metrics()["engine_driver_restarts"].inc(
            labels={"deployment": self.deployment})
        _events.emit("engine.driver_restart", epoch=self._epoch,
                     deployment=self.deployment, reason=reason)
        self._thread = None
        self.start()

    def inject_fault(self, kind: str = "driver_die", at_tokens: int = 0,
                     wedge_s: float = 0.0):
        """Arm ONE chaos fault on the driver (testing only), triggered
        at the next loop boundary once ``at_tokens`` tokens have been
        delivered:

        - ``kind="driver_die"``: the driver thread raises — lanes fail
          with the retryable :class:`EngineRestartError`, clients resume
          elsewhere, and :meth:`supervise` restarts the driver once.
        - ``kind="driver_wedge"`` (with ``wedge_s``): the driver stalls
          without heartbeating, simulating a stuck dispatch; supervise
          detects the stale beat and recovers as above.
        - ``kind="kill_process"``: hard ``os._exit`` — the whole replica
          worker dies mid-stream, exercising the actor-death retry path.
        - ``kind="driver_slow"`` (with ``wedge_s``): a PERSISTENT
          per-loop stall of ``wedge_s`` seconds (heartbeat still beats)
          — simulates a heavily loaded device so chaos tests can
          interleave kills with a stream that is reliably mid-flight.
        """
        if kind not in ("driver_die", "driver_wedge", "kill_process",
                        "driver_slow"):
            raise ValueError(f"unknown fault kind {kind!r}")
        if kind == "driver_slow":
            self._throttle_s = float(wedge_s)
            return
        self._fault = {"kind": kind, "at_tokens": int(at_tokens),
                       "wedge_s": float(wedge_s)}

    def _check_fault(self):
        """Driver-loop fault point (no-op unless armed; one-shot except
        the persistent ``driver_slow`` throttle)."""
        throttle = getattr(self, "_throttle_s", 0.0)
        if throttle:
            time.sleep(throttle)
        f = self._fault
        if f is None:
            return
        with self._stats_lock:
            toks = self._stats["tokens"]
        if toks < f["at_tokens"]:
            return
        self._fault = None
        if f["kind"] == "driver_wedge":
            # Stall WITHOUT beating: supervise() sees a live thread with
            # a stale heartbeat — the wedge signature.
            time.sleep(f["wedge_s"])
        elif f["kind"] == "kill_process":
            os._exit(43)
        else:
            raise RuntimeError(
                f"injected engine driver death at {toks} tokens")

    def stats(self) -> dict:
        with self._stats_lock:
            out = dict(self._stats)
        out["active_slots"] = sum(s is not None for s in self._state)
        out["slots"] = self.slots
        out["queue_depth"] = out["queued"] = self.queue_depth()
        d = max(out["dispatches"], 1)
        out["avg_occupancy"] = out.pop("occupancy_sum") / d
        out["dispatches_per_token"] = (
            (out["dispatches"] + out["prefill_launches"])
            / max(out["tokens"], 1))
        out.update({f"driver_cpu_ns_{ph[4:]}" if ph.startswith("cpu.")
                    else f"driver_ns_{ph.replace('.', '_')}": ns
                    for ph, ns in self._driver_ns.items()})
        out["compiles"] = self._compiles["n"]
        out["compile_ns"] = self._compiles["ns"]
        out["gc_pauses"] = self._gc["n"]
        out["gc_pause_ns_sum"] = self._gc["ns"]
        out["gc2_pauses"] = self._gc["n2"]
        out["gc2_pause_ns_sum"] = self._gc["ns2"]
        out["paged"] = True     # one pool; _controller.py folds the key
        out["deployment"] = self.deployment
        out["tp"] = self.tp
        sp_r = out.pop("spec_rounds")
        sp_p = out.pop("spec_proposed")
        sp_a = out.pop("spec_accepted")
        sp_f = out.pop("spec_fallback_rounds")
        sp_l = out.pop("spec_lanes")
        if self._drafter is not None:
            out["spec"] = {
                "drafter": self._drafter.name,
                "draft_k": self.draft_k,
                "threshold": self.spec_threshold,
                "rounds": sp_r, "proposed": sp_p, "accepted": sp_a,
                "lanes": sp_l, "fallback_rounds": sp_f,
                "acceptance_rate": sp_a / max(sp_p, 1),
                # Per LANE per verify forward (the literature's
                # numbers): a lane commits its accepted prefix PLUS
                # the correction/bonus token every round it verifies.
                "mean_accept_len": sp_a / max(sp_l, 1),
                "accepted_per_forward": (sp_a + sp_l) / max(sp_l, 1),
            }
        # ---- disaggregation (ISSUE 14): always surfaced — a zero
        # block on a colocated engine is itself the signal that no
        # handoffs happened.
        out["role"] = self.role
        ls = self._leases.stats()
        out["handoff"] = {
            "exported": out.pop("handoffs_exported"),
            "imported": out.pop("handoffs_imported"),
            "import_fallbacks": out.pop("handoff_import_fallbacks"),
            "ship_bytes": out.pop("handoff_ship_bytes"),
            "leases_outstanding": ls["outstanding"],
            "leases_claimed": ls["claimed"],
            "leases_reclaimed": ls["reclaimed"],
        }
        t = self._thread
        out["warm_up"] = self._warm_report
        out["driver_alive"] = bool(t is not None and t.is_alive())
        out["heartbeat_age_s"] = round(time.monotonic() - self._beat, 3)
        out["draining"] = self._draining
        # Flight-recorder health (ISSUE 19): ring fill fraction and
        # per-kind rate-cap drops for THIS process's recorder — rides
        # the replica metrics pull up into serve.status().
        out["events"] = _events.stats()
        # Runtime-sanitizer block (ISSUE 13): only when tools/rtsan is
        # already loaded AND active in this process — checked via
        # sys.modules so ray_tpu never imports the analyzer tree into
        # workers on its own (same boundary as the rtlint metrics
        # lint). Chaos benchmarks assert findings == 0 here.
        import sys as _sys
        _rtsan = _sys.modules.get("tools.rtsan")
        if _rtsan is not None and _rtsan.is_active():
            out["sanitizer"] = _rtsan.stats_block("serve/")
        out["page_size"] = self.page_size
        out["n_pages"] = self.n_pages
        out["pages_free"] = self._pool.available()
        out["pages_used"] = self.n_pages - self._pool.available()
        out["parked_slots"] = sum(
            s is not None and s.parked for s in self._state)
        if self._prefix is not None:
            out["prefix_evictions"] = self._prefix.evictions
        out["attn_kernel"] = self.attn_kernel
        out["kv_dtype"] = self.kv_dtype
        spec = self._model.cache_spec(self.cfg, self.kv_dtype)
        out["kv_bytes_per_token"] = \
            spec.bytes_per_page(self.page_size) / self.page_size
        # What a sequence keeps in its SLOT whatever its length (a
        # recurrent state; 0 for a model that keeps all in pages), and
        # what the pool gives to it rather than to pages.
        out["state_bytes_per_slot"] = spec.bytes_per_slot()
        out["state_bytes"] = self.slots * out["state_bytes_per_slot"]
        return out

    def _count(self, **deltas):
        with self._stats_lock:
            for k, v in deltas.items():
                self._stats[k] += v

    def launch_log(self, since_ns: int = 0) -> List[dict]:
        """The last ``LAUNCH_RING`` decode and verify launches, oldest
        first, each a dict of ``LAUNCH_FIELDS`` (``kind`` by name) with
        its ``period``; ``since_ns``: those whose ``t0`` is later. The
        stamps are ``time.monotonic_ns()``: the clock of the driver's
        spans (``mono_ns``), of the flight recorder's ``mono`` and,
        through ``Tracer.handoff``'s ``sync_host_ns``, of a
        ``jax.profiler`` trace. Not part of ``stats()``."""
        while True:
            try:
                recs = list(self._launches)
                break
            except RuntimeError:    # the driver appended meanwhile
                continue
        return [_launch_dict(r) for r in recs if r[0] > since_ns]

    # ---------------------------------------------------------- driver loop
    # THE driver loop: everything it calls below dispatches against
    # pool structures only this thread (or a supervisor that already
    # fenced it off by epoch) may touch. entry=driver: the thread that
    # enters _run IS the driver — rtsan (tools/rtsan) registers it here
    # and asserts every other owner=driver method runs on it (a
    # supervisor restart re-registers automatically on the new thread's
    # first loop).
    # rtlint: owner=driver entry=driver
    def _run(self, stop: threading.Event, epoch: int):
        # One phase clock per driver run (its spans share a trace id);
        # the table it adds to is the engine's.
        self._phases = tracing.PhaseClock(
            "engine", self._driver_ns, epoch=epoch,
            deployment=self.deployment)
        self._decode_read_ns = None
        self._recent.clear()
        try:
            while not stop.is_set():
                # The whole iteration is phase "other"; what it opens
                # inside is charged to its own phase. The body stays in
                # THIS frame: profilers label the driver by function.
                with self._phases.phase("other") as loop:
                    # Heartbeat BEFORE any work: supervise() reads its
                    # age to tell a wedged dispatch from a live idle
                    # loop.
                    self._beat = time.monotonic()
                    warm = self._warm
                    if warm is not None:
                        self._flush_now(epoch)  # not behind a compile
                        self._run_warm_up(warm)
                        continue
                    self._check_fault()
                    if stop.is_set():
                        # Woke from a wedge (fault sleep / stuck
                        # dispatch) to find the supervisor restarted
                        # past this run: exit before touching the
                        # rebuilt structures.
                        break
                    with self._phases.phase("admit"):
                        self._admit_pending(epoch)
                        self._sweep_leases()
                    self._observe_queue_depth()
                    if not any(s is not None for s in self._state):
                        # no lane left: no launch to ride behind
                        self._flush_now(epoch)
                        if self._pending:
                            # Deferred head with an empty pool and ZERO
                            # running lanes cannot happen (n_pages holds
                            # a full max_len sequence and the prefix
                            # cache evicts first) — but never busy-spin
                            # on it.
                            time.sleep(0.001)
                            continue
                        # Idle: block briefly for the next arrival
                        # instead of spinning; the timeout bounds
                        # shutdown latency.
                        with self._phases.phase("idle"):
                            try:
                                self._pending.append(
                                    self._queue.get(timeout=0.05))
                            except queue.Empty:
                                # nothing to do, twenty times a second:
                                # counted, not recorded as spans
                                loop.muted = True
                        continue  # boundary: admission pass first
                    if self._drafter is not None:
                        self._dispatch_spec(epoch)
                    else:
                        self._dispatch_chunk(epoch)
            self._fail_all(EngineShutdownError("engine shut down"),
                           epoch=epoch)
        except BaseException as e:  # noqa: BLE001 - driver died: fan out
            # An unexpected driver death is RECOVERABLE for the lanes —
            # their streams replay deterministically elsewhere — so they
            # fail with the retryable restart error, not the raw cause.
            if not isinstance(e, EngineShutdownError):
                exc: BaseException = EngineRestartError(
                    f"engine driver died: {e!r}; resubmit to resume")
                exc.__cause__ = e
            else:
                exc = e
            self._fail_all(exc, epoch=epoch)
            raise

    def _fail_all(self, exc: BaseException, free_state: bool = True,
                  epoch: Optional[int] = None):
        """Fail every queued / in-flight lane with ``exc``.

        ``free_state=False`` (shutdown racing a still-alive driver)
        only PUTS errors — slot state, the pending deque, and the page
        pool stay driver-owned, so refcounts drop exactly once when the
        driver's own exit path runs this with ``free_state=True``.
        ``epoch`` (driver exit paths) makes the call a no-op when the
        supervisor already retired that driver's run — a late exit must
        not fail the RESTARTED engine's lanes. Double error puts on a
        lane are inert."""
        # Serialized: shutdown() calls this unconditionally (covering a
        # dead/never-started driver) and may race the dying driver's own
        # exit path — page refcounts must only drop once per slot.
        with self._fail_lock:
            if epoch is not None and epoch != self._epoch:
                return           # stale driver: its lanes already moved
            self._fail_all_locked(exc, free_state)

    def _fail_all_locked(self, exc: BaseException, free_state: bool):
        with self._admit_lock:
            self._draining = True    # no put can land past this point
        # The kept slices BEFORE the error: a client that resubmits
        # counts what it received, and so must have received what the
        # state pass counted as delivered.
        self._flush_kept(in_flight=False)
        for i, st in enumerate(self._state):
            if st is not None:
                st.lane.q.put(("err", exc))
                if free_state:
                    # Ownership transferred: free_state=True means the
                    # driver is confirmed dead (_fail_all's contract),
                    # so the failing thread IS the owner — the same
                    # dead-owner rebind rtsan's RS103 grants at runtime.
                    # rtlint: disable=RT110 ownership transfer (above)
                    self._free_slot(i)
        if free_state:
            while self._pending:
                self._pending.popleft().lane.q.put(("err", exc))
        else:
            for req in list(self._pending):
                req.lane.q.put(("err", exc))
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                return
            req.lane.q.put(("err", exc))

    def _flush_kept(self, in_flight: bool, epoch: int = -1,
                    now_ns: int = 0) -> int:
        """Hand the last launch's kept messages to their lanes, in the
        order the state pass kept them. ``in_flight``: the next decode
        (or verify) program has just been enqueued and the driver is
        about to block on it for the whole of a launch (where it needs
        no interpreter lock), so the consumers these puts wake run
        while the device does and not between two launches. Not behind
        a prefill's enqueue: its wait is shorter than the consumers'
        work at 256 lanes, and what spills contends with the driver
        before the next launch (``PERF.md`` section 6, PR 43: both
        rules measured). A stale driver (``epoch`` moved on) hands
        nothing over: the supervisor already did, before its error.
        Returns how long the messages had been kept: ``now_ns`` (the
        caller's stamp as it starts; absent, one is taken) less the read
        stamp of the launch that made them; 0 where nothing was."""
        kept = self._kept
        if not kept or (epoch >= 0 and epoch != self._epoch):
            return 0
        hold = max((now_ns or time.monotonic_ns()) - self._kept_read_ns, 0)
        n = 0
        with self._kept_lock:
            while kept:
                lane, msg = kept.popleft()
                lane.q.put(msg)
                n += 1
        self._count(deliver_puts=n,
                    deliver_puts_overlapped=n if in_flight else 0,
                    deliver_hold_ns_sum=hold * n)
        return hold if n else 0

    def _flush_now(self, epoch: int):  # rtlint: owner=driver
        """Nothing will be enqueued for the kept messages to ride
        behind (no lane left or none runnable, the loop going idle):
        hand them over at once, as delivery the device does not hide."""
        if self._kept:
            with self._phases.phase("deliver") as dl:
                self._flush_kept(in_flight=False, epoch=epoch,
                                 now_ns=dl.t0)

    # Ownership transfers to the failing thread only once the driver is
    # confirmed dead — see _fail_all's free_state contract.
    # rtlint: owner=driver
    def _free_slot(self, i: int):
        """Release slot i: page references drop (pages whose last ref
        was this slot return to the free list; prefix-cached pages stay
        resident) and the page-table row resets to sentinel."""
        st = self._state[i]
        if st is not None and st.pages:
            self._pool.unref(st.pages)
            self._pt[i, :] = self._model.PT_SENTINEL
        if st is not None and self._drafter is not None:
            self._drafter.free(i)
        self._state[i] = None

    def _alloc_pages(self, n: int, pool: Optional[_PagePool] = None,
                     prefix: Optional[_PrefixCache] = None
                     ) -> Optional[List[int]]:
        """Allocate n pages, evicting LRU prefix-cache entries while
        short. None = genuinely out (every page pinned by live lanes) —
        the caller defers or parks, never clamps. ``pool``/``prefix``
        let an in-flight admission keep ONE consistent snapshot across
        a supervisor restart (default: the engine's current ones)."""
        pool = self._pool if pool is None else pool
        prefix = self._prefix if prefix is None else prefix
        while pool.available() < n:
            if prefix is None or not prefix.evict_lru():
                return None
            _driver_emit("engine.page_evict", epoch=self._epoch,
                         wanted=n, free=pool.available())
        pages = pool.alloc(n)
        if pages is not None:
            _driver_emit("engine.page_alloc", epoch=self._epoch,
                         n=n, free=pool.available())
        return pages

    def _observe_pages(self, sm=None):
        if sm is None:
            from .._private.metrics import serve_metrics
            sm = serve_metrics()
        sm["engine_pages_free"].set(
            self._pool.available(), labels={"deployment": self.deployment})

    def _sweep_leases(self):  # rtlint: owner=driver
        """Reclaim expired handoff leases once per driver loop
        (ISSUE 14): dropping each orphan's pin frees the shipped pages
        on the object plane, so a decode replica (or router) that died
        between grant and claim can never pin the pool."""
        if not len(self._leases):
            return
        n = self._leases.sweep()
        if n:
            from .._private.metrics import serve_metrics

            serve_metrics()["handoff_leases_reclaimed"].inc(
                n, labels={"deployment": self.deployment})
            _driver_emit("handoff.reclaim", count=n,
                         epoch=self._epoch,
                         outstanding=len(self._leases))

    def _observe_queue_depth(self):  # rtlint: owner=driver
        """Export the admission backlog once per driver loop (gauge
        semantics want one writer: the driver, same as the page
        gauges)."""
        from .._private.metrics import serve_metrics

        serve_metrics()["engine_queue_depth"].set(
            self.queue_depth(), labels={"deployment": self.deployment})

    def _admit_pending(self, epoch: int = -1):  # rtlint: owner=driver
        """Chunk-boundary admission: fill every free slot in FIFO order.
        Expired / abandoned requests are failed out without spending a
        prefill; an admission that cannot get pages DEFERS — it
        stays at the queue head (order preserved) and retries next
        boundary, by which time a lane may have freed pages."""
        if epoch >= 0 and epoch != self._epoch:
            # Stale driver (the supervisor restarted past it while it
            # was blocked mid-iteration): every structure it can see is
            # the NEW driver's — touching them would race the live
            # admission pass or discard its requests.
            return
        while True:
            try:
                self._pending.append(self._queue.get_nowait())
            except queue.Empty:
                break
        if self._draining and self._pending:
            # Draining: queued-but-unstarted requests fail retryably NOW
            # (no delivered state — the retry is a fresh stream on
            # another replica) while running lanes ride to completion.
            exc = EngineShutdownError(
                "engine draining; resubmit on another replica")
            while self._pending:
                self._pending.popleft().lane.q.put(("err", exc))
            return
        # Cull dead entries EVERYWHERE in the deque first — deferral
        # under page pressure must not delay a deadline error that
        # costs nothing to deliver. In-place rotation keeps FIFO order.
        for _ in range(len(self._pending)):
            req = self._pending.popleft()
            if req.lane.closed:
                self._count(abandoned=1)
                continue
            if deadline_expired(req.deadline_s):
                from .._private.metrics import serve_metrics
                self._count(expired=1)
                serve_metrics()["requests_expired"].inc(
                    labels={"where": "engine",
                            "deployment": self.deployment})
                req.lane.q.put(("err", RequestDeadlineExceeded(
                    "request expired while queued for engine admission")))
                continue
            self._pending.append(req)
        if any(s is not None and s.parked for s in self._state):
            # Page pressure: freed pages must reach the (older) parked
            # lanes before new admissions may take them — otherwise a
            # preempted lane's pages would be re-pinned immediately and
            # the pool would thrash prefills instead of progressing.
            return
        while self._pending and any(s is None for s in self._state):
            admitted, deferred = self._admit_head(epoch)
            if epoch >= 0 and epoch != self._epoch:
                # The supervisor restarted past this driver WHILE its
                # prefill was blocked on the device: the deque now holds
                # the new driver's requests — popping would silently
                # discard one (its lane would hang to its deadline).
                return
            for _ in range(admitted):
                self._pending.popleft()
            if deferred:
                self._count(admissions_deferred=1)
                return               # out of pages: keep FIFO, back off

    # rtlint: owner=driver
    def _admit_head(self, epoch: int = -1) -> Tuple[int, bool]:
        """Admit the head of the FIFO into free slots with ONE launch:
        ``(requests admitted, whether the next one deferred)``. The
        head requests that have a free slot and get their pages, one
        after another exactly as a lone admission takes them (prefix
        lookup, pins, allocation with LRU eviction), up to
        :data:`PREFILL_GROUP`, share one prefill launch
        (:meth:`_prefill_paged`): one read of the weights, one dispatch
        and one blocking read for the group. The first request that
        cannot get pages closes the group and defers as ever (what
        stood before it still launches); so does one that must go
        alone: a handoff import (no prefill at all), an export, a
        prompt the drafter prefills too, a prompt whose bucket shares
        no launch (:meth:`_groups`), and a prompt whose prefix lookup
        would hit pages a groupmate is about to write (it goes next,
        alone or first of its own group, and hits). Lane-closed /
        expired checks happen in :meth:`_admit_pending` before any
        resources are taken. A stale driver (the supervisor restarted
        past it while its prefill was stuck on the device) drops the
        result at the epoch guard instead of writing into the rebuilt
        pool."""
        from .._private.metrics import serve_metrics

        sm = serve_metrics()
        free = [i for i, s in enumerate(self._state) if s is None]
        head = self._pending[0]
        if head.handoff is not None:
            done = self._admit_import(head, free[0], sm, epoch)
            return int(done), not done
        # ONE pool/prefix snapshot for the whole launch: a supervisor
        # restart swaps self._pool wholesale, and page accounting split
        # across two pool objects would corrupt both free lists.
        pool, prefix = self._pool, self._prefix
        group: List[_Grant] = []
        deferred = False
        for req, slot in zip(self._pending, free[:PREFILL_GROUP]):
            hist, shared = prefix.lookup(req.prompt) \
                if prefix is not None else (0, [])
            bucket = next(b for b in self.prompt_buckets
                          if b >= req.prompt.shape[0] - hist)
            alone = req.export or req.handoff is not None \
                or self._drafter is not None or not self._groups(bucket)
            if group and (alone or prefix is not None and any(
                    _shares_pages(req.prompt, g.req.prompt, g.hist,
                                  self.page_size) for g in group)):
                break
            grant = self._grant_pages(req, slot, hist, shared, bucket,
                                      pool, prefix)
            if grant is None:
                deferred = True
                break
            group.append(grant)
            if alone:
                break
        if not group:
            return 0, True
        firsts = self._prefill_paged(group, pool, prefix, sm, epoch)
        if firsts is None:
            return 0, False              # stale: the caller's guard
        for g, first in zip(group, firsts):
            req, slot, P = g.req, g.slot, g.req.prompt.shape[0]
            fresh = req.skip == 0 and not req.export
            self._note_admission(req, slot, sm, fresh)
            if req.trace_ctx is not None:
                tracing.record_span("engine.prefill",
                                    mono_ns=(req.granted_ns, req.first_ns),
                                    parent_ctx=req.trace_ctx, slot=slot,
                                    bucket=g.bucket, hist_len=g.hist,
                                    pages=len(g.pages), group=len(group),
                                    deployment=self.deployment)
            self._count(prefills=1, admitted=1 if fresh else 0,
                        prefill_tokens_sum=P - g.hist)
            self._token[slot] = first
            if req.export:
                with self._phases.phase("prefill", export=True):
                    self._finish_export(req, slot, P, g.pages, first, sm)
            else:
                self._enter_steady_state(req, slot, first, P, g.pages, sm)
        return len(group), deferred

    # rtlint: owner=driver
    def _note_admission(self, req: _EngineRequest, slot: int, sm,
                        fresh: bool, **attrs):
        """A slot (and its pages) was granted at ``req.granted_ns``:
        the wait since the request was queued goes to the histogram, to
        ``admission_wait_ns_sum`` (for requests that count as
        ``admitted``: a replay's requeue is not a client's wait) and,
        for a traced request, to its ``engine.admission`` span."""
        wait_ns = max(req.granted_ns - req.enq_ns, 0)
        sm["engine_admission_wait"].observe(
            wait_ns / 1e9, labels={"deployment": self.deployment})
        if fresh:
            self._count(admission_wait_ns_sum=wait_ns)
        if req.trace_ctx is not None:
            tracing.record_span("engine.admission",
                                mono_ns=(req.enq_ns, req.granted_ns),
                                parent_ctx=req.trace_ctx, slot=slot,
                                deployment=self.deployment, **attrs)

    # rtlint: owner=driver
    def _enter_steady_state(self, req: _EngineRequest, slot: int,
                            first: int, P: int, pages: List[int],
                            sm) -> bool:
        """Shared admission tail for every path that lands a first
        token in a slot (local prefill AND handoff import): deliver or
        replay-suppress the first token, close out single-token/EOS
        requests, otherwise install the slot's steady state and seed
        the drafter. The replay bookkeeping (``emitted``/``skip``)
        must stay bit-equal between the two entry paths or a resumed
        stream diverges by one token."""
        _driver_emit("engine.admit", request=req.req_id, slot=slot,
                     epoch=self._epoch, prompt_len=P,
                     max_new=req.max_new, resume_from=req.skip)
        skip = req.skip
        if skip > 0:
            skip -= 1            # replay: the first token was delivered
        else:                    # before the preemption/failover
            self._count(tokens=1)
            sm["engine_tokens"].inc(
                labels={"deployment": self.deployment})
            req.lane.q.put(("item", np.asarray([first], np.int32)))
        if req.max_new <= 1 or (self.eos_token >= 0
                                and first == self.eos_token):
            req.lane.q.put((_STREAM_END, None))
            self._count(completed=1)
            self._pool.unref(pages)
            self._pt[slot, :] = self._model.PT_SENTINEL
            self._observe_pages(sm)
            return True
        self._state[slot] = _Slot(
            lane=req.lane, remaining=req.max_new - 1,
            deadline_s=req.deadline_s, trace_ctx=req.trace_ctx,
            req=req, emitted=1 if req.skip == 0 else req.skip,
            pos=P, pages=pages, skip=skip)
        if self._drafter is not None:
            # Deterministic per-slot drafter state from the prompt +
            # first token — a resume_from replay rebuilds it bit-equal.
            self._drafter.admit(slot, req.prompt, first)
        self._observe_pages(sm)
        return True

    def _groups(self, bucket: int) -> bool:
        """Whether a prompt whose suffix takes ``bucket`` may share its
        launch: the bucket is one of the widest
        :data:`PREFILL_GROUP_BUCKETS` of which :data:`PREFILL_GROUP`
        prompts stay within :data:`PREFILL_GROUP_ROWS`."""
        wide = [b for b in self.prompt_buckets
                if PREFILL_GROUP * b <= PREFILL_GROUP_ROWS]
        return bucket in wide[-PREFILL_GROUP_BUCKETS:]

    # rtlint: owner=driver
    def _grant_pages(self, req: _EngineRequest, slot: int, hist: int,
                     shared_pages: List[int], bucket: int,
                     pool: _PagePool, prefix: Optional[_PrefixCache]
                     ) -> Optional["_Grant"]:
        """The host half of a paged admission, behind the prefix lookup
        (``hist`` tokens of cached prefix on ``shared_pages``): pin the
        cached pages it maps (refcounted, the COW source too if the
        prefix ends mid-page) and allocate fresh pages for the suffix,
        evicting LRU prefix entries while short. Returns None (nothing
        taken) when pages are unavailable even after eviction."""
        gd = self._model
        ps = self.page_size
        shared_full = hist // ps
        partial = hist % ps
        cow_src = shared_pages[shared_full] if partial else \
            gd.PT_SENTINEL
        shared = shared_pages[:shared_full]
        # Pin everything we read BEFORE eviction-driven allocation can
        # free it from under us.
        pool.ref(shared)
        if partial:
            pool.ref([cow_src])
        n_fresh = -(-req.prompt.shape[0] // ps) - shared_full
        evicted0 = prefix.evictions if prefix is not None else 0
        fresh = self._alloc_pages(n_fresh, pool, prefix)
        if fresh is None:
            pool.unref(shared)
            if partial:
                pool.unref([cow_src])
            return None
        return _Grant(
            req=req, slot=slot, hist=hist, cow_src=cow_src,
            pages=shared + fresh, bucket=bucket,
            evicted=(prefix.evictions - evicted0)
            if prefix is not None else 0)

    # rtlint: owner=driver
    def _prefill_paged(self, group: List["_Grant"], pool: _PagePool,
                       prefix: Optional[_PrefixCache], sm,
                       epoch: int = -1) -> Optional[List[int]]:
        """The device half of a paged admission, for the prompts one
        chunk boundary granted (:meth:`_admit_head`): prefill ONLY each
        prompt's suffix, all of them in ONE launch (a lone prompt with
        the scalar operands of the program it always was; a group as a
        tuple of suffixes, each in its own bucket, widest first), ONE
        blocking read of their first tokens and keys, then register
        every prompt's pages in the prefix cache. Returns the first
        tokens — or None when a supervisor restart retired this
        driver's epoch while its prefill ran (the stale result must not
        touch the rebuilt pool; every page the group took is handed
        back to ``pool``, the snapshot it was granted from)."""
        gd = self._model
        G = len(group)
        # the program's order: widest bucket first, so that a pair of
        # buckets is ONE program whichever prompt came first
        order = sorted(group, key=lambda g: -g.bucket)
        clock = self._phases
        with clock.phase(
                "prefill", bucket=order[0].bucket, hist_len=group[0].hist,
                pages_evicted=sum(g.evicted for g in group),
                group=G) as ph:
            tokens, pt_rows = [], []
            for g in order:
                suffix = g.req.prompt[g.hist:]
                padded = np.zeros((1, g.bucket), np.int32)
                padded[0, :len(suffix)] = suffix
                pt_row = np.full((self.max_pages,), gd.PT_SENTINEL,
                                 np.int32)
                pt_row[:len(g.pages)] = g.pages
                self._pt[g.slot] = pt_row
                tokens.append(padded)
                pt_rows.append(pt_row)
            with clock.step("key"):
                rngs = [_request_key(g.req.seed) for g in order]
            operands = [np.asarray(v, np.int32) for v in zip(*(
                (g.req.prompt.shape[0] - g.hist, g.hist, g.cow_src, g.slot)
                for g in order))]
            if G == 1:
                length, hist, cow_src, slot = (v[0] for v in operands)
                tokens, pt_rows, rngs = tokens[0], pt_rows[0], rngs[0]
            else:
                length, hist, cow_src, slot = operands
                tokens, pt_rows = tuple(tokens), np.stack(pt_rows)
                rngs = np.stack(rngs)
            with clock.step("dispatch"):
                tok, cache, key = self._prefill(
                    self._params_dev, self._cache, tokens, length, hist,
                    pt_rows, cow_src, slot, rngs)
            # One transfer per launch — THE TTFT point. The read
            # stays in this frame (and the step annotation is no
            # function of this file): profilers that label the driver
            # by function see the wait here.
            with clock.step("read"):
                # rtlint: sync-ok=ttft first token streams from the host
                firsts = np.asarray(tok).reshape(G).tolist()
            first = {g.slot: t for g, t in zip(order, firsts)}
        for g in group:
            g.req.granted_ns, g.req.first_ns = ph.t0, ph.t1
        if epoch >= 0 and epoch != self._epoch:
            # Stale driver: drop the result AND hand back every page
            # this launch's prompts took — against the SAME pool
            # snapshot, so the accounting stays balanced whether the
            # restart replaced the pool before or during the admission
            # (a leak here would shrink the free list forever).
            for g in group:
                pool.unref(g.pages)
                if g.hist % self.page_size:
                    pool.unref([g.cow_src])
            return None
        self._cache = cache
        self._count(prefill_launches=1, prefill_ns_sum=ph.t1 - ph.t0)
        # Host mirror of the slots' PRNG lanes (tiny [2] uint32 each).
        # rtlint: sync-ok=prng-mirror re-uploaded per dispatch
        keys = np.asarray(key).reshape(G, 2)
        for g, k in zip(order, keys):
            self._rngs[g.slot] = k
            if g.hist % self.page_size:
                # The fork read src synchronously inside the dispatch
                # above; its pin is no longer needed.
                pool.unref([g.cow_src])
                self._count(cow_copies=1)
                sm["engine_cow_copies"].inc(
                    labels={"deployment": self.deployment})
            if g.hist:
                self._count(prefix_hits=1, prefix_tokens_reused=g.hist)
                sm["engine_prefix_hits"].inc(
                    labels={"deployment": self.deployment})
        if prefix is not None:
            for g in group:         # the cache's LRU order is FIFO's
                prefix.insert(g.req.prompt, g.pages)
        return [first[g.slot] for g in group]

    # rtlint: owner=driver
    def _finish_export(self, req: _EngineRequest, slot: int, P: int,
                       pages: List[int], first: int, sm) -> bool:
        """Handoff export tail (ISSUE 14), run right after the prefill
        landed in the transient slot: extract the slot's K/V into ship
        order, trim to the true prompt length on the host, free the
        slot's pages, grant the lease, and deliver the descriptor on
        the request's lane. The slot never enters steady state — a
        prefill-role engine's pool is a staging area, not a residence.
        """
        from . import handoff as _ho

        quant = self.kv_dtype == "int8"
        ks = vs = None
        if quant:
            k_dev, v_dev, ks_dev, vs_dev = self._export(
                self._cache, self._pt[slot])
        else:
            k_dev, v_dev = self._export(self._cache, self._pt[slot])
        # Trim to pos BEFORE hashing/shipping: positions past P hold
        # pad/stale garbage the mask never read — shipping them would
        # make the digest depend on pool history.
        # The export IS the handoff payload: the bytes must reach the
        # host to digest and ship — one round-trip per export.
        # rtlint: sync-ok=ship handoff payload leaves through the host
        k = np.asarray(k_dev)[:, :P].copy()
        # rtlint: sync-ok=ship second half of the same payload
        v = np.asarray(v_dev)[:, :P].copy()
        if quant:
            # int8 ships CODES (trimmed like fp — the merge writes
            # canonical zeros past pos, so page bytes are a pure
            # function of held tokens) plus the per-page scales for
            # the covering pages. The digest covers both.
            n_cover = -(-P // self.page_size)
            # rtlint: sync-ok=ship per-page K scales ride the payload
            ks = np.asarray(ks_dev)[:, :n_cover].copy()
            # rtlint: sync-ok=ship per-page V scales ride the payload
            vs = np.asarray(vs_dev)[:, :n_cover].copy()
        rng = np.asarray(self._rngs[slot], np.uint32).copy()
        self._pool.unref(pages)
        self._pt[slot, :] = self._model.PT_SENTINEL
        payload = _ho.build_payload(k=k, v=v, prompt=req.prompt, pos=P,
                                    first=first, rng=rng, seed=req.seed,
                                    max_new=req.max_new, ks=ks, vs=vs,
                                    kv_dtype=self.kv_dtype if quant
                                    else None,
                                    page_size=self.page_size if quant
                                    else None)
        fields, nbytes = _ho.ship_payload(payload)
        lease_id, expires = self._leases.grant(
            epoch=self._epoch, pin=fields.get("ref"), nbytes=nbytes,
            ttl_s=req.ttl_s or None)
        desc = dict(fields)
        desc.update({
            "lease_id": lease_id, "epoch": self._epoch,
            "expires_at": expires, "digest": payload["digest"],
            "prompt": req.prompt, "pos": P, "first": first,
            "seed": req.seed, "max_new": req.max_new,
            "created_t": time.time(), "nbytes": nbytes,
            "node_id": _node_id(), "deployment": self.deployment})
        # tokens counts the sampled first token, so the chaos fault
        # points (kill/throttle at token N) work on prefill engines.
        self._count(handoffs_exported=1, handoff_ship_bytes=nbytes,
                    tokens=1)
        _driver_emit("handoff.grant", request=req.req_id,
                     lease=lease_id, epoch=self._epoch, nbytes=nbytes,
                     ttl_s=req.ttl_s or self._leases.ttl_s)
        _driver_emit("engine.export", request=req.req_id, slot=slot,
                     epoch=self._epoch, prompt_len=P, nbytes=nbytes)
        sm["kv_ship_bytes"].inc(
            nbytes, labels={"deployment": self.deployment})
        req.lane.q.put(("item", desc))
        req.lane.q.put((_STREAM_END, None))
        self._observe_pages(sm)
        return True

    # rtlint: owner=driver
    def _admit_import(self, req: _EngineRequest, slot: int, sm,
                      epoch: int = -1) -> bool:
        """Handoff import admission (ISSUE 14): scatter the verified
        ship buffer into freshly mapped pages, restore the slot's PRNG
        lane and fed token, and enter steady-state decode exactly where
        the prefill engine stopped. Returns False to defer (no pages).
        A recompute preemption re-enqueues the request WITH its
        payload, so the replay is a re-import, not a re-prefill."""
        payload = req.handoff["payload"]
        P = int(payload["pos"])
        gd = self._model
        L, _, H, hd = gd.cache_spec(self.cfg, self.kv_dtype).token_shape(
            "k", 0)
        dt = payload["k"].dtype
        req.granted_ns = time.monotonic_ns()
        ps = self.page_size
        # ONE pool snapshot for the whole admission (see
        # _prefill_paged): a supervisor restart must never split
        # page accounting across two pool objects.
        pool = self._pool
        prefix = self._prefix
        n_cover = -(-P // ps)
        pages = self._alloc_pages(n_cover, pool, prefix)
        if pages is None:
            return False          # out of pages: defer, keep FIFO
        pt_row = np.full((self.max_pages,), gd.PT_SENTINEL, np.int32)
        pt_row[:len(pages)] = pages
        self._pt[slot] = pt_row
        k_pad = np.zeros((L, self.max_pages * ps, H, hd), dt)
        v_pad = np.zeros((L, self.max_pages * ps, H, hd), dt)
        k_pad[:, :P] = payload["k"]
        v_pad[:, :P] = payload["v"]
        scales = ()
        if self.kv_dtype == "int8":
            # Quantized handoff: the codes pad/reshape exactly like
            # fp K/V; the per-page scales pad to the full table
            # width and scatter under the same page mask.
            ks_pad = np.zeros((L, self.max_pages, H), np.float32)
            vs_pad = np.zeros((L, self.max_pages, H), np.float32)
            ks_pad[:, :n_cover] = payload["ks"]
            vs_pad[:, :n_cover] = payload["vs"]
            scales = (ks_pad, vs_pad)
        cache = self._import(
            self._cache,
            k_pad.reshape(L, self.max_pages, ps, H, hd),
            v_pad.reshape(L, self.max_pages, ps, H, hd),
            *scales, pt_row, np.int32(slot), np.int32(P))
        if epoch >= 0 and epoch != self._epoch:
            pool.unref(pages)     # stale driver: hand pages back
            return True
        # Shipped pages cover the WHOLE prompt: register them so
        # later local admissions of the same prompt prefix map the
        # imported pages instead of re-prefilling.
        if prefix is not None and P == req.prompt.shape[0]:
            prefix.insert(req.prompt, pages)
        self._cache = cache
        first = int(payload["first"])
        self._token[slot] = first
        self._rngs[slot] = np.asarray(payload["rng"], np.uint32)
        self._note_admission(req, slot, sm, req.skip == 0, imported=True)
        created = req.handoff.get("created_t")
        if created:
            # Export stamp -> successful import: THE handoff latency.
            # Wall-clock across processes, like the deadline it rides
            # with.
            sm["kv_handoff"].observe(
                max(time.time() - float(created), 0.0),
                labels={"deployment": self.deployment})
        self._count(handoffs_imported=1,
                    admitted=1 if req.skip == 0 else 0)
        _driver_emit("engine.import", request=req.req_id, slot=slot,
                     epoch=self._epoch, pos=P)
        return self._enter_steady_state(req, slot, first, P, pages, sm)

    def _cover_pages(self) -> bool:  # rtlint: owner=driver
        """Allocate-on-advance (chunk boundary): every
        occupied slot must have pages mapped through the positions this
        chunk will write (``pos + min(chunk, remaining)``). A slot that
        cannot be covered PARKS — it keeps its state and pages but sits
        out the dispatch mask until a page frees. Returns True if at
        least one lane can run; False means every occupied slot was
        parked and the youngest lane has been preempted by recompute
        to break the deadlock."""
        ps = self.page_size
        # Cull dead PARKED lanes first: a parked slot sits out the
        # dispatch mask, so it never reaches the post-dispatch
        # closed/deadline checks — a consumer that walked away (or a
        # deadline that already passed) would otherwise pin its pages
        # forever and could force recompute-preemption of a healthy
        # lane. Freed pages are immediately available to the coverage
        # loop below.
        culled = False
        for i, st in enumerate(self._state):
            if st is None or not st.parked:
                continue
            if st.lane.closed:
                self._free_slot(i)
                self._count(abandoned=1)
                culled = True
            elif deadline_expired(st.deadline_s):
                from .._private.metrics import serve_metrics
                st.lane.q.put(("err", RequestDeadlineExceeded(
                    "request deadline passed while parked for pages")))
                self._free_slot(i)
                self._count(expired=1)
                serve_metrics()["requests_expired"].inc(
                    labels={"where": "engine",
                            "deployment": self.deployment})
                culled = True
        if culled:
            self._observe_pages()
            if not any(s is not None for s in self._state):
                return False         # nothing left to dispatch
        runnable = 0
        for i, st in enumerate(self._state):
            if st is None:
                continue
            if self._drafter is not None:
                # Verify writes K/V at pos..pos+draft_k (the fed token
                # plus every proposal); writes past the covered pages
                # drop, which is only safe for positions a CONTINUING
                # lane can never commit — i.e. beyond remaining. Under
                # adaptive speculation the slot may instead run a chunk
                # round this boundary, so cover the max of both modes.
                need = st.pos + max(
                    min(self.draft_k, st.remaining) + 1,
                    min(self.chunk, st.remaining))
            else:
                need = st.pos + min(self.chunk, st.remaining)
            while len(st.pages) * ps < need:
                got = self._alloc_pages(1)
                if got is None:
                    break
                self._pt[i, len(st.pages)] = got[0]
                st.pages.extend(got)
            short = len(st.pages) * ps < need
            if short and not st.parked:
                self._count(lane_parks=1)
            st.parked = short
            runnable += not short
        if runnable:
            return True
        # Deadlock: every occupied slot is parked and nothing will free
        # a page on its own. Preempt the youngest lane (least sunk
        # work) BY RECOMPUTE: free its pages, requeue its request at
        # the head, and let the replay suppress the already-delivered
        # tokens — the consumer sees a stall, never an error or a
        # duplicate. Each preemption strictly shrinks the lane set, and
        # one lane always fits (n_pages >= max_pages), so this
        # terminates.
        youngest = max(
            (i for i, s in enumerate(self._state) if s is not None),
            key=lambda i: self._state[i].admitted_t)
        st = self._state[youngest]
        req = st.req
        req.skip = st.emitted
        req.enq_ns = time.monotonic_ns()
        self._free_slot(youngest)
        self._pending.appendleft(req)
        self._count(preempted=1)
        _driver_emit("engine.preempt", request=req.req_id,
                     slot=youngest, epoch=self._epoch,
                     delivered=st.emitted)
        self._observe_pages()
        return False

    # rtlint: owner=driver
    def _dispatch_chunk(self, epoch: int = -1, cover: bool = True):
        """ONE fused device dispatch decoding every active slot, then
        per-slot routing/trimming and boundary frees. A stale driver —
        one whose dispatch was stuck on the device while the supervisor
        restarted past it — drops the whole result at the post-dispatch
        epoch guard: its lanes were already failed retryably and the
        pool rebuilt.

        ``cover=False`` serves adaptive speculation: the spec
        dispatcher already ran the coverage pass for this boundary
        before deciding to fall back to a chunk round."""
        import jax

        from .._private.metrics import serve_metrics

        if epoch >= 0 and epoch != self._epoch:
            # Stale driver: _cover_pages parks/preempts lanes — running
            # it against the NEW driver's pool would preempt a healthy
            # restarted lane.
            return
        if cover:
            with self._phases.phase("cover"):
                runnable = self._cover_pages()
            if not runnable:
                self._flush_now(epoch)    # no launch to ride behind
                return                # re-run admission/coverage pass
        active = np.array([s is not None and not s.parked
                           for s in self._state], bool)
        n_active = int(active.sum())
        clock = self._phases
        with clock.phase("decode", slots_active=n_active) as ph:
            with clock.step("enqueue"):
                self._note_decode_gap(ph.t0)
                toks, cache, _done, rngs, *more = self._step(
                    self._params_dev, self._cache, self._token,
                    self._rngs, active, self._pt)
                # the copies to the host start when the program ends,
                # not when the wait below has returned
                for arr in (toks, rngs, *more[:1]):
                    arr.copy_to_host_async()
            # The previous launch's tokens reach their lanes HERE: the
            # consumers they wake take the interpreter lock while this
            # thread is blocked in the wait below.
            with clock.step("flush") as fl:
                hold = self._flush_kept(in_flight=True, epoch=epoch,
                                        now_ns=fl.t0)
            # The wait and the reads stay in this frame, as the
            # prefill's do: profilers that label the driver by function
            # see the device's part here, told from the host's by step.
            with clock.step("wait"):
                # rtlint: sync-ok=chunk-boundary the device's part, alone
                jax.block_until_ready(toks)
            # ONE transfer per fused k-step chunk — the engine's
            # designed streaming granularity.
            with clock.step("read"):
                # rtlint: sync-ok=chunk-boundary one transfer per chunk
                toks_np = np.asarray(toks)
                # rtlint: sync-ok=chunk-boundary PRNG lanes ride the same sync
                rngs_np = np.asarray(rngs)
                # rtlint: sync-ok=chunk-boundary the model's counters, same sync
                counted = [int(c) for c in np.asarray(more[0])] \
                    if more else []
        if epoch >= 0 and epoch != self._epoch:
            return                    # stale driver: drop on the floor
        with self._phases.phase("deliver", slots_active=n_active) as dl:
            self._cache = cache
            sm = serve_metrics()
            sm["engine_slot_occupancy"].observe(
                n_active / self.slots,
                labels={"deployment": self.deployment})
            sm["engine_dispatches"].inc(
                labels={"deployment": self.deployment})
            self._count(dispatches=1, occupancy_sum=n_active / self.slots,
                        **dict(zip(self._model.STEP_COUNTERS, counted)))
            if self.tp > 1:
                # Post-mortem breadcrumb for sharded dispatch: which mesh
                # shape ran which compiled program. Same rate cap as
                # engine.dispatch — one pair per chunk boundary.
                _driver_emit("shard.dispatch", epoch=self._epoch,
                             mesh=[("tp", self.tp)],
                             program="chunk_paged")
            if self._attn_fused:
                # One fused-kernel dispatch per chunk program launch (the
                # kernel runs k times per layer inside it).
                sm["engine_attn_kernel_dispatches"].inc(
                    labels={"deployment": self.deployment})
                self._count(attn_kernel_dispatches=1)
            with self._stats_lock:
                self._stats["peak_active"] = max(self._stats["peak_active"],
                                                 n_active)
            self._advance_lanes(toks_np, rngs_np, None, ph, n_active, sm)
        self._note_decode_read(ph, dl, hold, n_active, 0,
                               chunk=self.chunk)

    # rtlint: owner=driver
    def _advance_lanes(self, rows, rngs, acc, ph, n_active: int, sm):
        """The STATE pass over the lanes a decode or verify launch
        advanced, inside its ``deliver`` phase: the host's lane state
        brought up to date (the fed token, the PRNG lane, ``pos``,
        ``remaining``, the EOS cut, the replay's ``skip``, the frees,
        the counters) before admission and coverage read it. What a
        lane is owed (its slice, its end, a deadline error) is KEPT, in
        order, for ``_flush_kept`` behind the next enqueue. ``rows[i]``
        holds lane i's tokens; ``acc[i]`` how many proposals its verify
        accepted (the lane advances by one more: the correction or
        bonus token), None after a chunk launch, where every lane
        advances by ``chunk``."""
        labels = {"deployment": self.deployment}
        keep = self._kept.append
        self._kept_read_ns = ph.t1      # what is kept below is held from
        emitted = 0
        for i, st in enumerate(self._state):
            if st is None or st.parked:
                continue                     # parked: nothing advanced
            if acc is None:
                na, adv = -1, self.chunk     # -1: nothing was proposed
            else:
                na = int(acc[i])
                adv = na + 1
                sm["engine_spec_accept_len"].observe(na, labels=labels)
            self._rngs[i] = rngs[i]
            st.pos += adv                    # mirrors the device pos
            if st.lane.closed:               # consumer left: free now
                self._free_slot(i)
                self._count(abandoned=1)
                continue
            if deadline_expired(st.deadline_s):
                keep((st.lane, ("err", RequestDeadlineExceeded(
                    "request deadline passed mid-generation"))))
                self._free_slot(i)
                self._count(expired=1)
                sm["requests_expired"].inc(
                    labels={"where": "engine",
                            "deployment": self.deployment})
                continue
            row = rows[i]
            j = min(adv, st.remaining)
            finished = st.remaining <= adv
            if self.eos_token >= 0:
                hits = np.flatnonzero(row[:j] == self.eos_token)
                if hits.size:                # free at the EOS, not the
                    j = int(hits[0]) + 1     # end of the gang batch
                    finished = True
            self._token[i] = row[j - 1]      # last DELIVERED token
            if st.trace_ctx is not None:
                tracing.record_span("decode.chunk",
                                    mono_ns=(ph.t0, ph.t1),
                                    parent_ctx=st.trace_ctx, slot=i,
                                    active_slots=n_active, tokens=j,
                                    deployment=self.deployment,
                                    **({} if acc is None
                                       else {"accepted": na}))
            # Recompute replay: the first ``skip`` regenerated tokens
            # were already delivered before the preemption — suppress
            # them, stream only the new tail. It counts DELIVERED
            # tokens: a variable advance changes nothing about it.
            cut = min(st.skip, j)
            st.skip -= cut
            if j > cut:
                keep((st.lane, ("item", row[cut:j].copy())))
                st.emitted += j - cut
                emitted += j - cut
            st.remaining -= j
            if finished:
                keep((st.lane, (_STREAM_END, None)))
                self._free_slot(i)           # drafter.free rides along
                self._count(completed=1)
            elif self._drafter is not None:
                # Keep the drafter's history (and its self-assessment)
                # current, after an adaptive fallback's chunk round too.
                self._drafter.observe(i, row[:j], na)
        if emitted:
            sm["engine_tokens"].inc(emitted, labels=labels)
            self._count(tokens=emitted)
        self._observe_pages(sm)

    def _tables(self, at_ns: int) -> "_Tables":
        ns, st = self._driver_ns, self._stats
        return _Tables(
            at_ns, ns["prefill"], ns["idle"], ns["deliver"],
            ns["decode.enqueue"], ns["decode.flush"], ns["decode.wait"],
            ns["decode.read"], st["prefills"], st["prefill_launches"],
            self._gc["ns"], time.process_time_ns())

    # rtlint: owner=driver
    def _note_decode_gap(self, t0: int):
        """At the start of a decode/verify dispatch (inside its
        ``enqueue``, before the table has any of this launch): the time
        since the previous one's tokens were read, if a lane stayed
        occupied all the while — what running lanes lost to whatever
        came between (delivery, admission, another request's prefill) —
        and the parts of it in which the driver was in a prefill, an
        idle or a deliver phase (the previous launch's state pass, and
        what was handed over since with no launch to ride behind). What
        the record of THIS launch is differenced from is left in
        ``_launch_open``."""
        now, last = self._tables(t0), self._launch_closed
        if self._decode_read_ns is not None:
            gap = (t0 - self._decode_read_ns, now.prefill - last.prefill,
                   now.idle - last.idle, now.deliver - last.deliver
                   + self._launches[-1][_DELIVER])
            self._count(decode_gap_ns_sum=gap[0],
                        decode_gap_prefill_ns_sum=gap[1])
            since = last
        else:
            # nobody waited: what the record says of the process starts
            # here, not at the close of a launch before the lull
            gap, since = (0, 0, 0, 0), now
        self._launch_open = (now, since, gap + (
            now.prompts - last.prompts,
            now.prefill_launches - last.prefill_launches))

    # rtlint: owner=driver
    def _note_decode_read(self, ph, dl, hold: int, lanes: int, kind: int,
                          **attrs):
        """A decode/verify launch closes, its state pass (``dl``) just
        ended: ONE record to the ring (``LAUNCH_FIELDS``), every field a
        stamp the phases took or a difference of the tables; a stall
        classified and counted where it fell; the launch's
        flight-recorder event with the record's fields (``attrs``
        beside them); and where the launch's tokens were read, if a
        lane is still occupied: what the next gap is counted from."""
        opened, since, head = self._launch_open
        now = self._launch_closed = self._tables(dl.t1)
        rec = (ph.t0, ph.t1 - ph.t0) + head + (
            now.enqueue - opened.enqueue, now.flush - opened.flush,
            now.wait - opened.wait, now.read - opened.read,
            now.deliver - opened.deliver, hold, now.gc - since.gc,
            now.cpu - since.cpu, now.at - since.at, lanes, kind)
        self._decode_read_ns = ph.t1 if any(
            s is not None for s in self._state) else None
        stall = _stall(rec, self._recent)
        self._launches.append(rec)
        self._recent.append(rec)
        if stall is not None:
            excess, usual, parts = stall
            stall = {"excess": excess, "median": usual}
            self._count(
                launch_stalls=1, launch_stall_ns_sum=excess,
                launch_stall_wall_ns_sum=rec[_F["wall"]],
                launch_stall_gc_ns_sum=rec[_F["gc"]],
                launch_stall_cpu_ns_sum=rec[_F["cpu_proc"]],
                **{f"launch_stall_ns_{p}": v
                   for p, v in zip(_STALL_PARTS, parts)})
        if stall is not None or _events.enabled():
            fields = _launch_dict(rec)
            fields["launch"] = fields.pop("kind")   # an event's own word
            # Rate-capped: under a dispatch-per-token storm the cap drops
            # the excess (counted) instead of flooding the ring.
            _driver_emit("engine.dispatch", epoch=self._epoch, **attrs,
                         **fields)
            if stall is not None:
                # what an operator searches a timeline for
                _driver_emit("engine.stall", epoch=self._epoch, **stall,
                             **fields)
                self._phases.span("stall", ph.t0 - rec[_GAP], ph.t1,
                                  {**stall, **fields})

    def _dispatch_spec(self, epoch: int = -1):  # rtlint: owner=driver
        """Draft-k-verify-once twin of :meth:`_dispatch_chunk`
        (ISSUE 9): the drafter proposes ``draft_k`` tokens per active
        slot, ONE batched target forward verifies them all, and each
        slot advances by its OWN ``accepted + 1`` (the target's
        correction/bonus token rides along) — variable per-slot advance
        flowing through the same EOS/deadline/freeing/``resume_from``
        replay logic as the fixed-k path. A stale driver drops the
        whole result at the post-dispatch epoch guard.

        ``spec_threshold > 0`` makes speculation POOL-WIDE adaptive:
        the boundary verifies only when the drafters' mean
        self-assessed acceptance over the runnable lanes clears the
        threshold, and runs ONE plain chunk dispatch otherwise. The
        decision is all-or-nothing because the chunk program's cost is
        paid once for the whole pool — a boundary that dispatched both
        programs for a split pool would always commit fewer tokens per
        wall-second than chunking everyone. Greedy engines only (the
        constructor enforces it): the decision depends on pool
        composition, which is replay-safe only when sampling consumes
        no randomness."""
        import jax

        from .._private.metrics import serve_metrics

        if epoch >= 0 and epoch != self._epoch:
            return
        with self._phases.phase("cover"):
            runnable = self._cover_pages()
        if not runnable:
            self._flush_now(epoch)        # no launch to ride behind
            return                    # re-run admission/coverage pass
        active = np.array([s is not None and not s.parked
                           for s in self._state], bool)
        n_active = int(active.sum())
        if self.spec_threshold > 0.0:
            ests = [self._drafter.estimate(i)
                    for i in range(self.slots) if active[i]]
            if not any(e is None for e in ests) \
                    and sum(ests) / n_active < self.spec_threshold:
                # Unpredictable pool: one chunk dispatch beats a verify
                # that would mostly commit correction tokens. The
                # drafter still observes (chunk path) so its estimate
                # recovers the moment streams turn repetitive.
                self._count(spec_fallback_rounds=1)
                self._dispatch_chunk(epoch, cover=False)
                return
        draft = self._drafter.propose(active, self._token)
        clock = self._phases
        with clock.phase("decode", slots_active=n_active,
                         spec=True) as ph:
            with clock.step("enqueue"):
                self._note_decode_gap(ph.t0)
                committed, n_acc, cache, rngs = self._verify(
                    self._params_dev, self._cache, self._token, draft,
                    self._rngs, active, self._pt)
                for arr in (committed, n_acc, rngs):
                    arr.copy_to_host_async()
            with clock.step("flush") as fl:   # as _dispatch_chunk's
                hold = self._flush_kept(in_flight=True, epoch=epoch,
                                        now_ns=fl.t0)
            with clock.step("wait"):
                # rtlint: sync-ok=verify-boundary the device's part, alone
                jax.block_until_ready(committed)
            # ONE transfer per verify round: committed tokens, accept
            # counts, and PRNG lanes come back together.
            with clock.step("read"):
                # rtlint: sync-ok=verify-boundary one transfer per round
                com_np = np.asarray(committed)
                # rtlint: sync-ok=verify-boundary same round-trip
                acc_np = np.asarray(n_acc)
                # rtlint: sync-ok=verify-boundary same round-trip
                rngs_np = np.asarray(rngs)
        if epoch >= 0 and epoch != self._epoch:
            return                    # stale driver: drop on the floor
        with self._phases.phase("deliver", slots_active=n_active) as dl:
            self._cache = cache
            sm = serve_metrics()
            labels = {"deployment": self.deployment}
            sm["engine_slot_occupancy"].observe(n_active / self.slots,
                                                labels=labels)
            sm["engine_dispatches"].inc(labels=labels)
            accepted_total = int(acc_np[active].sum()) if n_active else 0
            sm["engine_spec_proposed"].inc(self.draft_k * n_active,
                                           labels=labels)
            if accepted_total:
                sm["engine_spec_accepted"].inc(accepted_total, labels=labels)
            self._count(dispatches=1, occupancy_sum=n_active / self.slots,
                        spec_rounds=1, spec_proposed=self.draft_k * n_active,
                        spec_accepted=accepted_total, spec_lanes=n_active)
            if self.tp > 1:
                _driver_emit("shard.dispatch", epoch=self._epoch,
                             mesh=[("tp", self.tp)],
                             program="verify_paged")
            with self._stats_lock:
                self._stats["peak_active"] = max(self._stats["peak_active"],
                                                 n_active)
            self._advance_lanes(com_np, rngs_np, acc_np, ph, n_active, sm)
        self._note_decode_read(ph, dl, hold, n_active, 1,
                               accepted=accepted_total)
