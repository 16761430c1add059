"""The one general traffic generator: a mix file of parameters and a
seed give the requests of a run.

Stratified, so that two seeds offer the same work: a mix fixes the
MULTISET of (prompt length, answer length) pairs, of inter-arrival gaps
and of burst sizes for a window of the given length (quantiles of the
stated distributions, not draws from them); the seed chooses only the
order, the token ids, which document a question goes to and the jitter
inside a burst, and the order is itself stratified in blocks, so that
every stretch of a run offers about the same work at about the same
rate. The spread between two runs is then the system's. The price: an
open loop is less bursty over stretches longer than a block than true
Poisson arrivals are, so its tails are those of a steady stream.

A mix file (``traffic/<mix>.json``) has:

``loop``      ``open`` (a schedule, sent whether or not earlier requests
              finished), ``closed`` (``clients`` callers, each sending
              its next request when its last one ends) or ``steps``
              (training: no requests at all).
``rate_rps``  open loop: mean requests per second.
``arrivals``  open loop: ``{"kind": "poisson"}`` or ``{"kind": "bursts",
              "sizes": [...], "intra_ms": x}``.
``clients``, ``pool``  closed loop: callers, and how many distinct
              requests the multiset holds before it repeats its lengths.
``prompt``, ``answer``  ``{"dist": "lognormal", "median", "sigma",
              "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``.
``shared``    optional ``{"doc": <dist>, "asks": [3, 4, 5], "spread":
              n}``: each document is asked ``asks`` times, ``prompt`` is
              then the question after it.
``ramp_s``    seconds of the same traffic before the window opens
              (set-up: the window starts on a running system).
``fill_pages``  how many KV pages' worth of this mix's own prompts are
              sent (one token each) before the ramp, so that the LRU
              prefix cache is full, as on any server that has run for
              more than a few minutes.
``drain_s``   how long after the window the streams are still stamped:
              an open loop waits that long for its requests to end; a
              closed loop's running requests go on that long, so that
              the slice that straddles the window's end is there to be
              shared out (``perf_metrics._window_tokens``).
``block``     size of the blocks the order is stratified in: lengths
              and, in an open loop, inter-arrival gaps (16 if absent).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np

def _rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), *stream])


@dataclass
class Request:
    idx: int            # unique in the run; keys the token ids
    due_s: float        # open loop: seconds from the window's start
    prompt_len: int
    max_new: int
    doc: int = -1       # shared mixes: which document, else -1
    doc_len: int = 0
    phase: str = "window"    # "fill" | "ramp" | "window"


def quantiles(dist: dict, n: int) -> List[int]:
    """n stratified values of a length distribution, ascending: the
    quantiles at (i + 0.5) / n, clipped to [min, max]."""
    lo, hi = int(dist["min"]), int(dist["max"])
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if dist["dist"] == "uniform":
            v = lo + u * (hi - lo)
        elif dist["dist"] == "lognormal":
            v = math.exp(math.log(dist["median"])
                         + dist["sigma"] * NormalDist().inv_cdf(u))
        else:
            raise ValueError(f"unknown length distribution {dist!r}")
        out.append(int(min(max(round(v), lo), hi)))
    return out


def exp_gaps(n: int, total_s: float) -> List[float]:
    """n stratified exponential gaps that sum to total_s."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total_s / sum(raw)
    return [g * scale for g in raw]


def stratified_order(n: int, block: int, rng: np.random.Generator
                     ) -> List[int]:
    """A permutation of range(n), items taken to be sorted by size:
    every block of ``block`` consecutive positions holds one item of
    each stratum, so any stretch of the run has the same make-up; the
    seed shuffles inside a block and the order of blocks."""
    k = max(1, math.ceil(n / block))
    blocks = [list(range(j, n, k)) for j in range(k)]
    order = []
    for j in rng.permutation(k):
        b = blocks[j]
        order.extend(b[i] for i in rng.permutation(len(b)))
    return order


def _pairs(mix: dict, n: int, salt: int) -> List[tuple]:
    """The fixed multiset of (prompt, answer) lengths: prompts ascending,
    answers paired by a permutation that depends on the mix alone."""
    prompts = quantiles(mix["prompt"], n)
    answers = quantiles(mix["answer"], n)
    perm = _rng(int(mix.get("pairing_seed", 0)), salt, n).permutation(n)
    return [(prompts[i], answers[perm[i]]) for i in range(n)]


def _docs(mix: dict, n: int, seed: int, first_doc: int):
    """Shared mixes: n requests over documents asked ``asks`` times
    each. Returns per-request (doc id, doc length, position key)."""
    sh = mix["shared"]
    asks, counts = sh["asks"], []
    while sum(counts) < n:
        counts.append(asks[len(counts) % len(asks)])
    counts[-1] -= sum(counts) - n
    lens = quantiles(sh["doc"], len(counts))
    # which length goes with which count: fixed by the mix
    lperm = _rng(int(mix.get("pairing_seed", 0)), 7, len(counts)
                 ).permutation(len(counts))
    rng = _rng(seed, 11, first_doc)
    place = rng.permutation(len(counts))      # the seed orders documents
    mean_asks = n / len(counts)
    rows = []
    for d, c in enumerate(counts):
        for k in range(c):
            key = place[d] * mean_asks + k * sh.get("spread", 4) \
                + rng.random()
            rows.append((key, first_doc + d, lens[lperm[d]]))
    rows.sort()
    return [(d, dl) for _key, d, dl in rows]


def _arrivals(mix: dict, n: int, span_s: float, rng) -> List[float]:
    """n due times in [0, span_s): the seed orders a fixed multiset of
    gaps (and of burst sizes), in the same blocks as the lengths: every
    ``block`` consecutive gaps hold one of each stratum, so every such
    stretch lasts about as long. Without that, an order that puts the
    short gaps together offers half as much again in one stretch of
    the window as in the next, and a tail follows the seed."""
    arr = mix.get("arrivals", {"kind": "poisson"})
    block = int(mix.get("block", 16))
    if arr["kind"] == "poisson":
        gaps = exp_gaps(n, span_s)
        gaps = [gaps[i] for i in stratified_order(n, block, rng)]
        t, out = 0.0, []
        for g in gaps:
            out.append(t)
            t += g
        return out
    if arr["kind"] == "bursts":
        sizes, cyc = [], arr["sizes"]
        while sum(sizes) < n:
            sizes.append(cyc[len(sizes) % len(cyc)])
        sizes[-1] -= sum(sizes) - n
        sizes = sorted(sizes)
        sizes = [sizes[i] for i in
                 stratified_order(len(sizes), block, rng)]
        gaps = exp_gaps(len(sizes), span_s)
        gaps = [gaps[i] for i in
                stratified_order(len(sizes), block, rng)]
        intra = arr.get("intra_ms", 5.0) / 1e3
        t, out = 0.0, []
        for size, g in zip(sizes, gaps):
            out.extend(min(t + j * intra, span_s - 1e-6)
                       for j in range(size))
            t += g
        return out
    raise ValueError(f"unknown arrivals {arr!r}")


def _batch(mix: dict, n: int, seed: int, salt: int, first_idx: int,
           phase: str, first_doc: int = 0) -> List[Request]:
    """n requests of the mix in stratified order (no due times)."""
    if n <= 0:
        return []
    rng = _rng(seed, 3, salt)
    pairs = _pairs(mix, n, salt)
    order = stratified_order(n, int(mix.get("block", 16)), rng)
    docs = _docs(mix, n, seed, first_doc) if mix.get("shared") else None
    out = []
    for pos, i in enumerate(order):
        p, a = pairs[i]
        d, dl = docs[pos] if docs else (-1, 0)
        out.append(Request(idx=first_idx + pos, due_s=0.0,
                           prompt_len=p + dl, max_new=a, doc=d,
                           doc_len=dl, phase=phase))
    return out


def fill_requests(mix: dict, seed: int, page_size: int) -> List[Request]:
    """The mix's own prompts, one token each, until ``fill_pages`` pages
    are covered. Documents of a shared mix are other documents than the
    window's."""
    want = int(mix.get("fill_pages", 0))
    if want <= 0:
        return []
    mean_len = sum(quantiles(mix["prompt"], 64)) / 64
    if mix.get("shared"):
        mean_len += sum(quantiles(mix["shared"]["doc"], 64)) / 64
    n = max(1, math.ceil(want * page_size / mean_len))
    if mix.get("shared"):
        # one ask per document fills the cache; repeats would only hit
        mix = dict(mix, shared=dict(mix["shared"], asks=[1]))
    reqs = _batch(mix, n, seed, salt=1, first_idx=1_000_000, phase="fill",
                  first_doc=1_000_000)
    for r in reqs:
        r.max_new = 1
    return reqs


def open_schedule(mix: dict, seed: int, seconds: float) -> List[Request]:
    """Ramp and window requests of an open loop, by due time; the ramp's
    due times are negative."""
    rate = float(mix["rate_rps"])
    out = []
    for phase, span, salt, first in (
            ("ramp", float(mix.get("ramp_s", 0.0)), 2, 500_000),
            ("window", float(seconds), 0, 0)):
        n = int(round(rate * span))
        reqs = _batch(mix, n, seed, salt, first, phase,
                      first_doc=first)
        due = _arrivals(mix, n, span, _rng(seed, 5, salt)) if n else []
        shift = -span if phase == "ramp" else 0.0
        for r, t in zip(reqs, sorted(due)):
            r.due_s = t + shift
        out.extend(reqs)
    return sorted(out, key=lambda r: r.due_s)


def closed_pool(mix: dict, seed: int, cycle: int = 0) -> List[Request]:
    """One pass of the closed loop's multiset, in seeded order; callers
    take the next one. ``cycle`` numbers the passes, so that no prompt
    is ever sent twice."""
    n = int(mix["pool"])
    return _batch(mix, n, seed, salt=100 + cycle,
                  first_idx=cycle * n, phase="window",
                  first_doc=cycle * n)


def tokens_for(req: Request, seed: int, vocab: int) -> np.ndarray:
    """The request's prompt: the document's tokens (the same for every
    ask of it) and then the question's, all from the seed."""
    q = req.prompt_len - req.doc_len
    parts = []
    if req.doc_len:
        parts.append(_rng(seed, 21, req.doc).integers(
            0, vocab, (req.doc_len,)))
    parts.append(_rng(seed, 23, req.idx).integers(0, vocab, (q,)))
    return np.concatenate(parts).astype(np.int32)
