"""One run of a serving cell: ``rt.init`` → ``serve.run`` → the mix's
traffic through the handle → the result. This process is the driver and
the load generator; it never imports jax (the replica holds the chip).

A traced run (``--trace 1``) traces a slice of the window inside the
replica. The slice begins ``trace_after_s`` into the window and is
counted in LAUNCHES where the mix states ``trace_launches``: it ends
when the engine has dispatched that many chunks, or after ``trace_s``
seconds, whichever comes first (``perf_deployment.Tracer``); a mix
without the key is traced for ``trace_s``. The replica hands out what
only it knows of the slice and reads no file; once the window has
closed and the system is shut down, the trace is reduced in a child of
this process (``trace_reduce.reduce_in_child``). A slice that was not
brought home ends the run with a ``BenchError`` that says so
(``lost_trace``), before any per-layer reader runs.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

import perf_harness as H
import perf_loadgen
import perf_metrics
import perf_traffic


def _send_fn(handle, timeout_s: float):
    stream = handle.options(stream=True, timeout_s=timeout_s)

    def send(req, prompt):
        return stream.remote({"prompt": prompt, "max_new": req.max_new,
                              "rid": req.idx})

    return send


def run(found: dict, seed: int, seconds: float, trace: int,
        describe: bool = False, require_tpu: bool = True,
        overrides: dict = None) -> dict:
    """Returns the parts of the result line (and, for ``--sweep`` and
    ``--soak``, what those modes report)."""
    cell = found["cell"]
    conf = H.load_config(found["config"])
    mix = dict(H.load_mix(cell["traffic"]))
    mix.update(overrides or {})
    out = H.out_dir(cell["name"], seed, trace)
    faillog = H.FailureLog(out)
    H.worker_env()

    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu._private import chip

    import perf_deployment

    chip.ensure_compile_cache()
    had_jax = "jax" in sys.modules
    marks = {}

    def mark(name):
        marks[name] = round(time.monotonic() - H.PROCESS_START, 3)

    mark("imports")
    core = rt.init(num_cpus=8, num_tpus=None if require_tpu else 0)
    mark("rt_init")
    stamps = perf_loadgen.Stamps()
    vocab, rows_held = H.load_architecture(conf).vocab(conf)
    ps = conf["engine"]["page_size"]
    pid0 = None
    try:
        # no call through the handle carries a deadline of the
        # benchmark's making, set-up calls included: a reference pass
        # that compiles takes a minute, the handle's default is 60 s
        no_deadline = float(mix.get("timeout_s", 86400.0))
        handle = serve.run(perf_deployment.make_deployment(
            conf, seed, require_tpu,
            os.path.join(out, "trace")).bind(), _proxy=False).options(
                timeout_s=no_deadline)
        first = handle.report.remote().result()
        pid0 = first["pid"]
        mark("replica_ready")

        closed = {"t": None}

        def on_fail(row, exc):
            if closed["t"] is not None:
                return      # the run is over: shutdown ends what is left
            faillog.add(kind="request", error=row["error"],
                        cls=type(exc).__name__, idx=row["idx"],
                        phase=row["phase"], t=row["error_t"],
                        since_due_s=row["error_t"] - row["due"],
                        prompt_len=row["prompt_len"],
                        max_new=row["max_new"],
                        tokens_before=perf_metrics.n_tokens(row))

        send = _send_fn(handle, no_deadline)

        # ---- set-up traffic: the check request, the cache fill, the
        # reference. All of it warms the served path end to end.
        ck = conf["correct"]
        rep = perf_traffic.Request(idx=2_000_000, due_s=0,
                                   prompt_len=ck["repeat_prompt"],
                                   max_new=ck["repeat_answer"],
                                   phase="check")
        served, counters = [], [first["stats"]]

        def send_check():
            # the same idx gives the same prompt: one request, sent
            # again, at temperature 0
            perf_loadgen.run_batch(send, [rep], seed, vocab, stamps,
                                   on_fail, 1, served)
            counters.append(handle.report.remote().result()["stats"])

        send_check()            # into fresh pages
        send_check()            # a hit on the pages it left
        mark("repeat")
        fill = perf_traffic.fill_requests(mix, seed, ps)
        perf_loadgen.run_batch(send, fill, seed, vocab, stamps, on_fail,
                               int(mix.get("fill_concurrency", 4)))
        mark("fill")
        send_check()            # after the fill's evictions
        send_check()            # a hit on pages that were reused
        engine_ck = _served_check(conf, mix, served, counters)
        answers = engine_ck.pop("answers")
        ref = handle.reference_check.remote(
            ck["prompt_tokens"], ck["decode_steps"],
            ([int(t) for t in perf_traffic.tokens_for(rep, seed, vocab)],
             answers) if answers else None).result()
        engine_ck["reference"] = ref.get("served") or {"ok": False}
        engine_ck["ok"] = bool(engine_ck.pop("engine_ok")
                               and engine_ck["reference"]["ok"])
        if not engine_ck["ok"]:
            print("SERVED-CHECK " + json.dumps(engine_ck), flush=True)
        mark("reference")

        # ---- the window
        ramp = float(mix.get("ramp_s", 0.0))
        tracer = {"red": None}
        if mix["loop"] == "open":
            schedule = perf_traffic.open_schedule(mix, seed, seconds)
        before = handle.report.remote().result()
        t0 = time.monotonic() + ramp + 0.25
        t1 = t0 + seconds
        setup_s = t0 - H.PROCESS_START
        side = threading.Thread(
            target=_window_side, daemon=True,
            args=(handle, t0, t1, trace, mix, tracer))
        side.start()
        if mix["loop"] == "open":
            t_close = perf_loadgen.run_open(
                send, schedule, seed, vocab, stamps, on_fail, t0,
                float(mix.get("drain_s", 60.0)))
        elif mix["loop"] == "closed":
            t_close = perf_loadgen.run_closed(
                send, lambda c: perf_traffic.closed_pool(mix, seed, c),
                seed, vocab, stamps, on_fail, int(mix["clients"]),
                t0 - ramp, t1, float(mix.get("drain_s", 0.0)))
        else:
            raise H.BenchError(f"loop {mix['loop']!r} is not a serving "
                               f"loop")
        closed["t"] = t_close
        side.join(120.0)
        if side.is_alive():
            tracer["error"] = (f"the window's side thread was still at "
                               f"{tracer.get('at')!r} 120 s after the "
                               f"load had ended")
        after = tracer.get("at_close") or handle.report.remote().result()
        final = handle.report.remote().result()
        arrivals = handle.arrivals_log.remote().result()
    except BaseException:
        _log_tails(core.session_dir)
        raise
    finally:
        try:
            serve.shutdown()
        finally:
            rt.shutdown()
    assert had_jax or "jax" not in sys.modules, \
        "the benchmark's driver process imported jax"

    rows = stamps.rows
    if mix["loop"] == "open":
        e2e = perf_metrics.open_loop(rows, t0, t1, t_close)
    else:
        e2e = perf_metrics.closed_loop(rows, t0, t1)
    e2e["setup_s"] = setup_s
    st0, st1, st2 = before["stats"], after["stats"], final["stats"]
    if trace and not tracer.get("error"):
        import trace_reduce

        try:
            tracer["red"] = trace_reduce.reduce_in_child(
                dict(tracer["handoff"], describe=describe), out)
        except Exception as e:  # noqa: BLE001 - the run fails below
            tracer["error"] = _one_line(e)
    delta = {k: st1[k] - st0[k] for k in st1
             if isinstance(st1.get(k), (int, float))
             and isinstance(st0.get(k), (int, float))
             and not isinstance(st1[k], bool)}
    health = {"driver_restarts": st2["driver_restarts"],
              "preempted": st2["preempted"], "resumed": st2["resumed"],
              "expired": st2["expired"], "abandoned": st2["abandoned"],
              "pid_before": pid0, "pid_after": final["pid"]}
    faults = perf_metrics.stream_faults(rows, rows_held)
    window_fail = [r for r in rows if r.get("error")
                   and r["error_t"] <= t_close]
    correct = bool(ref["ok"] and engine_ck["ok"] and not faults
                   and final["pid"] == pid0
                   and st2["driver_restarts"] == 0)
    for f in faults[:20]:
        faillog.add(kind="stream", error=f)
    if window_fail or not correct:
        faillog.add(kind="health", **health)
    run_data = {
        "cell": cell["name"], "mix": mix, "conf": conf, "seed": seed,
        "seconds": seconds, "t0": t0, "t1": t1, "rows": rows,
        "e2e": e2e, "stats_before": st0, "stats_after": st1,
        "stats_delta": delta, "arrivals": arrivals,
        "trace": tracer["red"], "trace_mid": tracer.get("mid"),
        "trace_slice": tracer.get("slice"),
        "trace_error": tracer.get("error"),
        "polls": tracer.get("polls"), "device": final["device"],
        "memory_peak_bytes": final["memory_peak_bytes"],
        "peaks": H.peaks(final["device"]["kind"]) if require_tpu
        else None,
        "timing": first["timing"], "marks": marks, "reference": ref,
        "served_check": engine_ck, "health": health,
    }
    with open(os.path.join(out, "run.json"), "w") as f:
        json.dump({k: v for k, v in run_data.items()
                   if k not in ("rows", "arrivals")}, f, indent=1,
                  default=str)
    with open(os.path.join(out, "stamps.jsonl"), "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print("SETUP " + json.dumps({
        "setup_s": setup_s, "marks": marks, "replica": first["timing"],
        "reference_s": ref["seconds"], "reference": ref["checks"],
        "reference_vectors": ref.get("vectors"),
        "served_check": engine_ck,
        "fill_requests": len(fill), "health": health,
        "trace_cost": (tracer["red"] or {}).get("cost"),
        "memory_stats": final.get("memory_stats")}), flush=True)
    if trace:
        lost = lost_trace(tracer, health)
        if lost:
            raise H.BenchError(lost)
    return {"run": run_data, "correct": correct,
            "attempted": e2e["attempted"], "failed": e2e["failed"],
            "device": H.device_entry(final["device"],
                                     final["memory_peak_bytes"],
                                     tracer["red"]),
            "breakdown": H.breakdown_entry(tracer["red"])}


def _served_check(conf: dict, mix: dict, served: list,
                  counters: list) -> dict:
    """What the engine did with the check request, which went through
    the handle four times: into fresh pages, as a hit on them, after
    the cache fill had evicted, and as a hit on pages that were used
    before. All four answers have to be whole (``repeat_answer``
    tokens); they need not be the same tokens, because a whole prefill
    and a prefix-cache hit are two arithmetic paths and may part at a
    near-tie: the reference judges every distinct one (``answers``, in
    the order they first came: ``perf_reference_check.
    served_verdicts``). ``counters`` are ``engine.stats()`` before the
    first and after each send; where the fill is larger than the pool
    they have to show the evictions and the later hit, or the check did
    not see what it is for and the run is not ``correct``."""
    want = conf["correct"]["repeat_answer"]

    def moved(key, a, b):
        return counters[b].get(key, 0) - counters[a].get(key, 0)

    complete = len(served) == 4 and len(counters) == 5 and all(
        len(t) == want for t in served)
    answers = []
    for t in served if complete else []:
        t = [int(x) for x in t]
        if t not in answers:
            answers.append(t)
    evictions = moved("prefix_evictions", 0, 3) if complete else 0
    out = {"complete": complete, "answers": answers,
           "identical": len(answers) == 1,
           "evictions_before_resend": evictions,
           "hit_fresh": bool(complete
                             and moved("prefix_tokens_reused", 1, 2) > 0),
           "hit_after_eviction": bool(
               complete and evictions > 0
               and moved("prefix_tokens_reused", 3, 4) > 0),
           "expected_hit_after_eviction": bool(
               conf["engine"].get("prefix_cache")
               and mix.get("fill_pages", 0) >= conf["engine"]["n_pages"])}
    out["engine_ok"] = bool(complete and (
        out["hit_after_eviction"]
        or not out["expected_hit_after_eviction"]))
    return out


def lost_trace(tracer: dict, health: dict):
    """Why a traced run has no trace to read, in one line, or None:
    the slice was not brought home (whatever the window's side or the
    reduction raised), or it was and the replica that answers at the
    end is not the one the run began with, so that the counters'
    readings are two engines'. Each with what is known of the slice."""
    pids = f"pid {health['pid_before']} -> {health['pid_after']}"
    replaced = health["pid_before"] != health["pid_after"]
    known = dict(tracer.get("slice") or {})
    known.update((tracer.get("red") or {}).get("cost") or {})
    known["driver_restarts"] = health["driver_restarts"]
    facts = ", ".join(
        f"{k} {known[k]}" for k in ("trace_stop_s", "slice_s", "launches",
                                    "ended_by", "device_events",
                                    "driver_restarts")
        if known.get(k) is not None)
    if tracer.get("error"):
        return (f"the traced slice was lost: {tracer['error']} ({facts}"
                + (f"; the replica was replaced, {pids}" if replaced
                   else "") + ")")
    if replaced:
        return (f"the replica was replaced during the run ({pids}); "
                f"{facts}")
    return None


def _window_side(handle, t0, t1, trace, mix, tracer):
    """Beside the load: the traced slice of the window, the engine's
    counters every two seconds and as the window closes. The slice ends
    by the mix's ``trace_launches`` (the replica watches its engine's
    counter) or after ``trace_s``, whichever comes first; ``mid`` is
    its middle as it was."""
    polls = tracer.setdefault("polls", [])

    def poll_until(t):
        while time.monotonic() < t - 2.0:
            time.sleep(2.0)
            st = handle.report.remote().result()
            polls.append({"t": st["t"] - t0, **{
                k: st["stats"].get(k) for k in (
                    "queued", "active_slots", "pages_used", "tokens",
                    "admitted", "completed")}})
        _sleep_until(t)

    try:
        if trace:
            a = t0 + float(mix.get("trace_after_s", 5.0))
            b = min(a + float(mix.get("trace_s", 4.0)), t1 - 0.5)
            launches = mix.get("trace_launches")
            poll_until(a)
            tracer["at"] = "trace_start"
            handle.trace_start.remote(launches, b - a).result()
            if not launches:
                _sleep_until(b)
            tracer["at"] = "trace_stop"
            tracer["slice"] = handle.trace_stop.remote().result()
            tracer["mid"] = tracer["slice"]["mid_s"]
        tracer["at"] = "the window's close"
        poll_until(t1)
        tracer["at_close"] = handle.report.remote().result()
        if trace:
            tracer["at"] = "trace_result"
            tracer["handoff"] = handle.trace_result.remote().result()
    except Exception as e:  # noqa: BLE001 - reported, and the run fails
        tracer["error"] = f"{tracer.get('at')}: {_one_line(e)}"
        print(f"WINDOW-SIDE ERROR {e!r}", flush=True)


def _one_line(exc: Exception, chars: int = 400) -> str:
    """An exception as ``Class(message)`` on one line: an error that
    came through the handle carries the replica's traceback."""
    return f"{type(exc).__name__}({' '.join(str(exc).split())[:chars]})"


def _sleep_until(t):
    d = t - time.monotonic()
    if d > 0:
        time.sleep(d)


def _log_tails(session_dir: str, lines: int = 60):
    import glob

    for path in sorted(glob.glob(os.path.join(session_dir, "logs",
                                              "worker-*.log"))):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        print(f"----- {path}\n{''.join(tail)}", flush=True)
