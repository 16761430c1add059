"""A launch's tokens reach the lanes behind the NEXT enqueue (ISSUE 43):
the state pass keeps what each lane is owed, ``_flush_kept`` hands it
over once the next device program is in flight, or at once where there
is none to ride behind. Nothing of the result may change: every lane
receives exactly the messages of the un-deferred walk, in order.

CPU, ``nano``: messages, orders and counts, never a speed.
"""
import queue
import sys
import threading
import time

import pytest

from ray_tpu.serve.batching import _STREAM_END
from test_engine_phases import (KINDS, _delta, _prompt, make, nano,  # noqa: F401
                                nano_params)

#: a hang fails its own test, within this many seconds, and does not
#: stall the suite
PATIENCE_S = 60.0


def at_once(eng):
    """The un-deferred walk, for comparison: the same state pass, its
    kept messages handed over before anything else happens, as the
    walk's own ``q.put``s were before ISSUE 43."""
    inner = eng._advance_lanes

    def walk(*a, **kw):
        inner(*a, **kw)
        eng._flush_kept(in_flight=False)

    eng._advance_lanes = walk
    return eng


def messages(lane, closes_after=None):
    """Everything the engine puts on ``lane`` up to its end or error,
    as ``(kind, tokens | error type name | None)``. ``closes_after=n``:
    the consumer walks away after ``n`` messages."""
    got = []
    while True:
        kind, val = lane.q.get(timeout=PATIENCE_S)
        if kind is _STREAM_END:
            got.append(("end", None))
            return got
        if kind == "err":
            got.append(("err", type(val).__name__))
            return got
        got.append(("item", [int(t) for t in val]))
        if closes_after is not None and len(got) >= closes_after:
            lane.closed = True
            return got


def tokens_of(msgs):
    return [t for kind, val in msgs if kind == "item" for t in val]


def drain_all(lanes, **kw):
    """One consumer thread a lane, as the replica has; a hang fails."""
    out = {}

    def run(name, lane):
        out[name] = messages(lane, **kw.get(name, {}))

    threads = [threading.Thread(target=run, args=item, daemon=True)
               for item in lanes.items()]
    _join_all(threads)
    return out


def _join_all(threads):
    """Start and join within PATIENCE_S together; a hang fails."""
    t_end = time.monotonic() + PATIENCE_S
    for t in threads:
        t.start()
    for t in threads:
        t.join(max(t_end - time.monotonic(), 0.0))
    assert not [t for t in threads if t.is_alive()], "a thread hung"


def _wait_quiet(eng):
    """Until the driver has freed every slot and gone idle."""
    t_end = time.monotonic() + PATIENCE_S
    while time.monotonic() < t_end:
        st = eng.stats()
        if not st["active_slots"] and not st["queued"]:
            time.sleep(0.12)
            return eng.stats()
        time.sleep(0.01)
    raise AssertionError("the engine did not come to rest")


def check_deferred_against_at_once(engine, oracle, prompts):
    """For the other model modules' files: four requests together (the
    last a replay from token 3), then one alone, on ``engine`` against
    the same engine handing over at once (``oracle``, shut down here):
    the same messages a lane, the ends last, the lone request's end
    waiting for nobody, every walked message counted."""
    def serve(eng):
        return drain_all({i: eng.submit(p, 9 + 2 * i,
                                        resume_from=3 * (i == 3))
                          for i, p in enumerate(prompts)})

    try:
        want = serve(at_once(oracle))
    finally:
        oracle.shutdown()
    a = engine.stats()
    got = serve(engine)
    assert got == want
    assert [len(tokens_of(got[i])) for i in range(4)] == [9, 11, 13, 12]
    assert all(m[-1] == ("end", None) for m in got.values())
    lone = drain_all({"lone": engine.submit(prompts[0], 11)})["lone"]
    assert lone[-1] == ("end", None) and len(tokens_of(lone)) == 11
    assert tokens_of(lone)[:9] == tokens_of(got[0])
    d = _delta(a, _wait_quiet(engine))
    # all but the three fresh requests' and the lone one's first tokens
    assert d["deliver_puts"] == \
        sum(len(m) for m in got.values()) - 3 + len(lone) - 1
    assert 0 < d["deliver_puts_overlapped"] < d["deliver_puts"]
    assert d["driver_ns_decode_flush"] > 0 and not engine._kept


# the deterministic part of the traffic: name -> (prompt length, prompt
# seed, max_new, resume_from)
PLAIN = {
    "whole_chunks": (5, 1, 9, 0),       # 1 + 4 + 4
    "inside_a_chunk": (6, 2, 11, 0),    # finishes 2 into a launch
    "eos": (7, 3, 24, 0),               # cut at the engine's EOS
    "replay": (6, 2, 11, 3),            # inside_a_chunk's, from token 3
    "replay_far": (7, 3, 24, 9),        # eos's, from past a whole launch
}


def _serve_plain(eng, nano):
    lanes = {name: eng.submit(_prompt(nano, n, seed), max_new,
                              resume_from=skip)
             for name, (n, seed, max_new, skip) in PLAIN.items()}
    return drain_all(lanes)


@pytest.mark.parametrize("kind", list(KINDS))
def test_every_lane_receives_the_undeferred_walks_messages(make, nano,
                                                           kind):
    """Finishes inside a launch, an EOS cut and two replays' ``skip``
    on a deferring engine and on one that hands over at once: the same
    messages a lane, slice for slice, the end last. (A lane's slices
    are its own: its first token, then what each launch advanced it
    by, whoever shares the pool.)"""
    # the stream the EOS is taken from, with no EOS set
    free = _serve_plain(at_once(make(kind, slots=3)), nano)
    stream = tokens_of(free["eos"])
    assert len(stream) == 24
    eos = stream[13]
    cut = stream.index(eos) + 1
    assert cut > 1

    want = _serve_plain(
        at_once(make(kind, eos_token=eos, slots=3)), nano)
    eng = make(kind, eos_token=eos, slots=3)
    a = eng.stats()
    got = _serve_plain(eng, nano)
    assert got == want
    for name, msgs in got.items():
        assert msgs[-1] == ("end", None), name
        assert [k for k, _ in msgs[:-1]] == ["item"] * (len(msgs) - 1)
    assert tokens_of(got["eos"]) == stream[:cut]
    whole = tokens_of(got["inside_a_chunk"])
    assert tokens_of(got["replay"]) == whole[3:]
    assert tokens_of(got["replay_far"]) == stream[9:cut]
    if kind != "spec":      # a chunk engine's slices: 1, then 4 a launch
        assert [len(v) for k, v in got["inside_a_chunk"][:-1]] == \
            [1, 4, 4, 2]
    # every message but a prefill's own first token (and the end of a
    # stream that ends there) went through the kept list, and some of
    # them rode behind a launch
    d = _delta(a, _wait_quiet(eng))
    walked = sum(len(m) for m in got.values()) - 3   # 3 first tokens
    assert d["deliver_puts"] == walked
    assert 0 < d["deliver_puts_overlapped"] <= d["deliver_puts"]
    assert d["completed"] == len(PLAIN) and not eng._kept


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_deadline_and_a_closed_lane_among_deferred_slices(make, nano,
                                                            kind):
    """A lane whose deadline passes mid-generation receives a prefix of
    its stream and then the deadline error, LAST (it is kept behind the
    slices before it, not put ahead of them); a consumer that closed
    its lane is freed at the next walk and receives nothing more; the
    lane beside them is whole."""
    ref = tokens_of(messages(at_once(make(kind)).submit(
        _prompt(nano, 5, 4), 40)))
    eng = make(kind, slots=3)
    messages(eng.submit(_prompt(nano, 5, 9), 6))        # compiled
    eng.inject_fault("driver_slow", wedge_s=0.02)
    a = eng.stats()
    lanes = {
        "late": eng.submit(_prompt(nano, 5, 4), 40,
                           deadline_s=time.time() + 0.15),
        "left": eng.submit(_prompt(nano, 5, 4), 40),
        "whole": eng.submit(_prompt(nano, 5, 4), 40),
    }
    got = drain_all(lanes, left={"closes_after": 2})
    d = _delta(a, _wait_quiet(eng))

    late = got["late"]
    assert late[-1] == ("err", "RequestDeadlineExceeded")
    assert [k for k, _ in late[:-1]] == ["item"] * (len(late) - 1)
    n = len(tokens_of(late))
    assert 1 <= n < 40 and tokens_of(late) == ref[:n]
    assert lanes["late"].q.empty()          # nothing behind the error
    assert d["expired"] == 1

    left = got["left"]
    assert len(left) == 2 and tokens_of(left) == ref[:len(tokens_of(left))]
    assert d["abandoned"] == 1
    # what was kept for it before it left may still arrive; no end does
    rest = []
    while True:
        try:
            rest.append(lanes["left"].q.get_nowait())
        except queue.Empty:
            break
    assert all(k == "item" for k, _ in rest)
    assert tokens_of(left) + [int(t) for _, v in rest for t in v] == \
        ref[:len(tokens_of(left)) + sum(len(v) for _, v in rest)]

    assert got["whole"][-1] == ("end", None)
    assert tokens_of(got["whole"]) == ref
    assert d["completed"] == 1 and not eng._kept


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_lone_requests_last_slice_does_not_wait_for_an_arrival(
        make, nano, kind):
    """With nobody else in the pool the last slice and the end have no
    launch to ride behind: they are handed over at once, when the loop
    finds no lane left, and counted as not overlapped."""
    eng = make(kind)
    messages(eng.submit(_prompt(nano, 5, 9), 6))        # compiled
    a = _wait_quiet(eng)
    t0 = time.monotonic()
    got = drain_all({"lone": eng.submit(_prompt(nano, 5), 11)})["lone"]
    took = time.monotonic() - t0
    assert got[-1] == ("end", None) and len(tokens_of(got)) == 11
    # nothing else was queued, and nothing else arrived: the engine is
    # at rest with the whole stream out, well inside a consumer's
    # patience (a hang would have failed in drain_all)
    assert took < PATIENCE_S / 2
    d = _delta(a, _wait_quiet(eng))
    assert d["admitted"] == 1 and d["completed"] == 1
    assert d["deliver_puts"] == len(got) - 1          # less its first token
    # the last launch's slice and the end: not behind any program
    assert d["deliver_puts"] - d["deliver_puts_overlapped"] == 2
    assert not eng._kept


@pytest.mark.parametrize("kind", list(KINDS))
def test_shutdown_hands_the_kept_slices_over_before_its_error(make, nano,
                                                              kind):
    """A lane of an engine that is shut down mid-stream has received
    every slice the state pass counted as delivered, then the error:
    what the client counted is what a replay must skip."""
    ref = tokens_of(messages(at_once(make(kind)).submit(
        _prompt(nano, 5, 4), 40)))
    eng = make(kind)
    messages(eng.submit(_prompt(nano, 5, 9), 6))        # compiled
    eng.inject_fault("driver_slow", wedge_s=0.02)
    a = _wait_quiet(eng)
    lane = eng.submit(_prompt(nano, 5, 4), 40)
    head = []
    for _ in range(2):          # its first token, and it is decoding
        kind_, val = lane.q.get(timeout=PATIENCE_S)
        assert kind_ == "item"
        head += [int(t) for t in val]
    eng.shutdown()
    rest = messages(lane)
    assert rest[-1] == ("err", "EngineShutdownError")
    assert [k for k, _ in rest[:-1]] == ["item"] * (len(rest) - 1)
    got = head + tokens_of(rest)
    assert len(got) < 40 and got == ref[:len(got)]
    assert _delta(a, eng.stats())["tokens"] == len(got)
    assert not eng._kept and lane.q.empty()


def test_racing_flushes_hand_every_message_over_once_in_order(make):
    """The kept messages are shared by the driver, which appends and
    flushes, and a thread that fails the lanes, which flushes before
    its error. Under more flushers than cores and a switch interval of
    10 us: every message reaches its lane once, in the order it was
    kept (two flushers at once would cross a lane's messages; a flush
    that swapped the list away would lose what the driver appends
    beside it)."""
    from ray_tpu.serve.batching import _StreamLane

    eng = make("paged")         # its idle driver flushes too
    lanes = [_StreamLane() for _ in range(8)]
    rounds, stop = 400, threading.Event()

    def driver():
        try:
            for r in range(rounds):
                for lane in lanes:
                    eng._kept.append((lane, ("item", r)))
                eng._flush_kept(in_flight=True)
        finally:
            stop.set()

    def failing():
        while not stop.is_set():
            eng._flush_kept(in_flight=False)

    a = eng.stats()
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        _join_all([threading.Thread(target=driver, daemon=True)] + [
            threading.Thread(target=failing, daemon=True)
            for _ in range(2 * (len(lanes) + 4))])
    finally:
        stop.set()
        sys.setswitchinterval(was)
    eng._flush_kept(in_flight=False)
    for lane in lanes:
        got = []
        while not lane.q.empty():
            got.append(lane.q.get_nowait()[1])
        assert got == list(range(rounds))
    b = eng.stats()
    assert b["deliver_puts"] - a["deliver_puts"] == rounds * len(lanes)
    assert not eng._kept
