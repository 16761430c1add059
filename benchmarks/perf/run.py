"""The benchmark's one command.

    python benchmarks/perf/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` when traced). With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

A cell's files are found by name (``perf_harness.py``): its
configuration, its traffic mix, its per-layer readers, and through the
configuration's ``"architecture"`` the one file that knows its model
and reference (``architectures/<name>.py``; absent: ``gpt2``).

``correct`` holds a serving run to the plain reference: the served
arithmetic's logits within the configuration's tolerance, and every
distinct answer the engine gave the check request (sent four times:
fresh pages, a prefix-cache hit, after eviction, a hit on reused pages)
within twice that tolerance of the reference's best logit at each
token. Answers to one request may differ at a near-tie of the
reference; a control (the answer against another prompt's logits) has
to fail. Every number compared is printed beside its limit (``SETUP``).

This process never imports jax: the chip belongs to the replica
(serving) or the training child. No TPU, or fewer chips than the cell
asks for: a non-zero exit and no result line.

What cannot run as asked ends with exit 2 and its cause as the last
line of standard error (``perfbench: ...``). A traced run has two
causes of its own, told apart: the traced slice was not brought home
(``the traced slice was lost: ...``, ``the replica was replaced during
the run ...``: ``perf_serve_cell.lost_trace``, checked before any
reader runs), or it was and a listed per-layer metric's reader finds
nothing in it (``... no longer matches what the program or the trace
offers``: the reader is stale).

Two more modes help whoever defines a cell (they print tables, not a
result line): ``--sweep r1,r2,...`` runs an open-loop mix at each rate
and reports queue growth and lateness, to find the knee; ``--soak``
runs a cell for ``--seconds`` in one process and reports every failure
with the engine's health counters.
"""
from __future__ import annotations

import perf_harness as H  # noqa: I001 - first: stamps the process start

import argparse
import json
import os
import sys


def _cell_kind(found: dict) -> str:
    return H.load_mix(found["cell"]["traffic"])["loop"]


def _metrics(found: dict, res: dict, trace: int, strict: bool) -> dict:
    """The result line's metrics. A reader that finds nothing to read
    returns nothing; in a rehearsal the metric is then left out, and on
    the chip (``strict``) the run fails, because there every metric the
    cell lists has something to read: a yardstick that vanishes when
    the program is renamed under it would otherwise go unseen."""
    run = res["run"]
    out = {}
    if not trace:
        for m in found["end_to_end"]:
            v = run["e2e"].get(m["name"])
            if v is None:
                raise H.BenchError(
                    f"end-to-end metric {m['name']} has no value in "
                    f"{found['cell']['name']}")
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out
    for m in found["per_layer"]:
        v = H.load_reader(m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
        elif strict:
            raise H.BenchError(
                f"per-layer metric {m['name']} found nothing to read in "
                f"{found['cell']['name']}: its reader "
                f"(layer_metrics/{m['name']}.py) no longer matches what "
                f"the program or the trace offers")
    return out


def compared_lines(run: dict) -> list:
    """Each number ``correct`` compared beside its limit, one line
    each: the run's last lines on standard error, so that the record of
    a run that is not correct keeps them."""
    ref = run.get("reference") or {}
    out = []
    for c in ref.get("checks", []):         # serving: logits
        out.append(f"logits {c['where']}: rel {c['rel']} <= tol "
                   f"{c['tol']} (max|err| {c['max_abs_err']}, max|ref| "
                   f"{c['max_abs_ref']}; compared {c['compared']}, "
                   f"left out {c['left_out']})")
    if "vectors" in ref:
        out.append("logit vectors: compared {compared} >= needed "
                   "{needed} (left out {left_out})".format(
                       **ref["vectors"]))
    ck = run.get("served_check")
    if ck:                                  # serving: the engine's answers
        v = ck.get("reference", {})
        out.append(f"answers: complete {ck.get('complete')}, hit after "
                   f"eviction {ck.get('hit_after_eviction')} (expected "
                   f"{ck.get('expected_hit_after_eviction')})")
        if "margin" in v:
            out.append(
                f"served tokens: max gap {v['max_gap']} <= margin "
                f"{v['margin']} over {v.get('distinct')} answer(s); "
                f"compared {v.get('compared')} of {v.get('tokens')} "
                f"(share needed {v.get('min_compared')}); control max "
                f"gap {v.get('control_max_gap')} > margin "
                f"{v.get('control_margin')}")
    if "abs_err" in ref:                    # training
        tol = run["conf"]["correct"]["loss_abs_tol"]
        out.append(f"loss: program {ref['program_loss']} reference "
                   f"{ref['reference_loss']}: |err| {ref['abs_err']} <= "
                   f"{tol}; first {run['losses'][0]} > last "
                   f"{run['losses'][-1]}")
    return out


def run_cell(found: dict, seed: int, seconds: float, trace: int,
             require_tpu: bool = True, overrides: dict = None,
             describe: bool = False) -> dict:
    if _cell_kind(found) == "steps":
        import perf_train_cell

        return perf_train_cell.run(found, seed, seconds, trace,
                                   require_tpu, describe)
    import perf_serve_cell

    return perf_serve_cell.run(found, seed, seconds, trace,
                               describe=describe,
                               require_tpu=require_tpu,
                               overrides=overrides)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="",
                    help="comma-separated rates (open loop) or client "
                         "counts (closed loop) to try, one run each")
    ap.add_argument("--soak", action="store_true")
    ap.add_argument("--describe-trace", action="store_true",
                    help="with --trace 1: also write what the trace "
                         "holds (planes, lines, first events) to "
                         "run.json")
    ap.add_argument("--rehearsal", action="store_true",
                    help="allow a CPU: for the tests; the result is "
                         "marked a rehearsal and is no measurement")
    args = ap.parse_args(argv)
    try:
        found = H.find_cell(H.load_benchmark(), args.workload)
        if args.sweep:
            return _sweep(found, args)
        # only the builder's modes may bend a mix; a plain run never
        over = json.loads(os.environ.get("PERF_MIX_OVERRIDE", "{}")) \
            if args.soak else None
        res = run_cell(found, args.seed, args.seconds, args.trace,
                       require_tpu=not args.rehearsal, overrides=over,
                       describe=args.describe_trace)
        dev = res["device"]
        if not args.rehearsal and (
                dev["platform"] != "tpu"
                or dev["count"] != found["cell"]["chips"]):
            raise H.BenchError(
                f"the cell needs {found['cell']['chips']} TPU chip(s); "
                f"the run saw {dev}")
        metrics = _metrics(found, res, args.trace,
                           strict=not args.rehearsal)
        if args.soak:
            print("SOAK " + json.dumps({
                "health": res["run"].get("health"),
                "attempted": res["attempted"], "failed": res["failed"],
                "e2e": res["run"]["e2e"],
                "queue": [[round(p["t"], 1), p["queued"],
                           p["active_slots"]]
                          for p in res["run"].get("polls") or []]}),
                  flush=True)
        extra = {"rehearsal": True} if args.rehearsal else None
        for ln in compared_lines(res["run"]):
            print("perfbench compared: " + ln, file=sys.stderr)
        print(f"perfbench correct: {bool(res['correct'])}",
              file=sys.stderr, flush=True)
        print(H.result_line(
            correct=res["correct"], attempted=res["attempted"],
            failed=res["failed"], metrics=metrics, device=dev,
            breakdown=res.get("breakdown") if args.trace else None,
            extra=extra), flush=True)
        return 0
    except H.BenchError as e:
        print(f"perfbench: {e}", file=sys.stderr, flush=True)
        return 2


def _sweep(found: dict, args) -> int:
    """One run per value, each a fresh system in a child process (a
    chip belongs to one process at a time); the table goes to the
    output directory and to stdout."""
    import subprocess

    mix = H.load_mix(found["cell"]["traffic"])
    key = "rate_rps" if mix["loop"] == "open" else "clients"
    table = []
    for val in [float(x) for x in args.sweep.split(",")]:
        env = dict(os.environ, PERF_MIX_OVERRIDE=json.dumps(
            {key: val if key == "rate_rps" else int(val)}))
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--seed", str(args.seed), "--seconds",
             str(args.seconds), "--trace", "0", "--soak"],
            env=env, capture_output=True, text=True)
        soak = next((json.loads(ln[5:]) for ln in
                     proc.stdout.splitlines() if ln.startswith("SOAK ")),
                    None)
        table.append({key: val, "rc": proc.returncode, "soak": soak})
        print("SWEEP " + json.dumps(table[-1]), flush=True)
        if proc.returncode:
            print(proc.stdout[-3000:], proc.stderr[-3000:], flush=True)
    out = H.out_dir(args.workload, args.seed, 0)
    with open(os.path.join(out, "sweep.json"), "w") as f:
        json.dump(table, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
